"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", ICLR 2025) — the recurrent mixer of linear-attention layers.

Per head, a state ``S`` in R^{dk x dv}, ``S_0 = 0``::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t`` in (0, 1) (given as its log) and ``beta_t`` in (0, 2).
Three formulations, all float32 at ``Precision.HIGHEST``:

* :func:`gated_delta_step` — one token for a batch of states (decode);
  :func:`gated_delta_step_flat` is the same update on states stored
  with the heads along the lanes, ``(dk, H dv)``, and what the decode
  step calls. Where ``dk`` is a multiple of 8 and ``H dv`` of 128 (the
  published 96 and 30 x 192) it runs as one Pallas kernel,
  ``gdn_step``, that reads each state tile once and writes it over
  itself; any other shape takes :func:`gated_delta_step_flat_xla`, the
  same arithmetic in plain XLA, which stays as the fallback and as
  what the kernel is tested against. Nothing but the shape chooses.
* :func:`gated_delta_chunked` — a whole sequence in chunks of ``chunk``
  tokens (prefill): inside a chunk the rule is solved in its WY form
  (one unit-lower-triangular system a chunk and head), and only the
  state passes from chunk to chunk. Where a chunk is 128 tokens and
  ``dk`` and ``dv`` are multiples of 8 (:func:`chunk_kernel_fits`; the
  published 128, 96, 192) the call is one Pallas kernel, ``gdn_chunk``: grid
  (groups of heads, chunks in order), a head's state in VMEM from the
  first chunk to the last, read from ``S0`` once and written once, and
  ``(I + L)^-1`` built by blocks (the 16-row diagonal blocks by their
  finite Neumann product, then three merges of pairs of blocks:
  twelve products of (128, 128) matrices and no loop over rows). Any
  other shape takes :func:`gated_delta_chunked_xla`, the same form in
  plain XLA (one ``triangular_solve`` for all chunks, then a scan),
  which stays as the fallback and as what the kernel is tested
  against. Nothing but the shape chooses.
* :func:`gated_delta_recurrent` — the token-by-token scan the other two
  are tested against.

A token with ``beta = 0`` and ``log_alpha = 0`` leaves the state as it
is: that is how a padded chunk's tail is masked.

The chunked form, for one chunk that starts from ``S_0``. Write
``G_i = prod_{j<=i} alpha_j`` and ``u_i = beta_i (v_i - alpha_i k_i^T
S_{i-1})``, so that ``S_i = alpha_i S_{i-1} + k_i u_i^T`` and therefore
``S_i = G_i S_0 + sum_{j<=i} (G_i / G_j) k_j u_j^T``. Substituting,

    (I + L) U = diag(beta) (V - diag(G) K S_0),
    L_ij = beta_i (G_i / G_j) (k_i . k_j)  for j < i, else 0

and, ``S_0`` entering linearly, ``U = U~ - W S_0`` with ``U~`` and ``W``
the solutions for the right-hand sides ``diag(beta) V`` and
``diag(beta G) K``. Then ``O = diag(G) Q S_0 + ((Q K^T) * D) U`` with
``D_ij = G_i / G_j`` for ``j <= i``, and ``S_C = G_C S_0 + (diag(G_C /
G) K)^T U``. The kernel has the chunk's ``S_0`` at hand when it solves,
so it solves the first equation as it stands, for ``U`` alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
LANES = 128
# a tile of (96, 1920) float32 is 737 kB: read and written, each
# double-buffered, 2.9 MB of a core's 16 MiB of scoped VMEM
MAX_BLOCK_LANES = 2048


def gated_delta_step(S, q, k, v, log_alpha, beta):
    """One token. ``S`` (B, H, dk, dv) float32; ``q``, ``k`` (B, H, dk);
    ``v`` (B, H, dv); ``log_alpha``, ``beta`` (B, H). Returns
    ``(S', o)`` with ``o`` (B, H, dv)."""
    Sd = S * jnp.exp(log_alpha)[..., None, None]
    kS = jnp.einsum("bhk,bhkv->bhv", k, Sd, precision=_HI)
    u = beta[..., None] * (v - kS)
    S2 = Sd + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, S2, precision=_HI)
    return S2, o


def step_kernel_fits(dk: int, lanes: int) -> bool:
    """Whether :func:`gated_delta_step_flat` runs its kernel on states
    ``(dk, lanes)``: whole (8, 128) float32 tiles, nothing padded."""
    return dk % 8 == 0 and lanes % LANES == 0


def gated_delta_step_flat(S, q, k, v, log_alpha, beta):
    """One token on states stored ``(B, dk, H dv)``: the layout in
    which a float32 state tiles without padding on a TPU (``dv`` need
    not be a multiple of the 128 lanes, ``H dv`` is), and in which the
    whole update is elementwise work and reductions over ``dk`` — no
    per-head matrix is ever cut out of the tile. ``q``, ``k`` (B, H,
    dk); ``v`` (B, H, dv); ``log_alpha``, ``beta`` (B, H). Returns
    ``(S', o)`` with ``o`` (B, H, dv); the arithmetic of
    :func:`gated_delta_step` up to the order of the sums over ``dk``.

    Where the shape fits (:func:`step_kernel_fits`) the update is one
    kernel that reads each state tile once and writes it over itself;
    any other shape takes :func:`gated_delta_step_flat_xla`."""
    if step_kernel_fits(S.shape[1], S.shape[2]):
        return _gated_delta_step_kernel(S, q, k, v, log_alpha, beta)
    return gated_delta_step_flat_xla(S, q, k, v, log_alpha, beta)


def gated_delta_step_flat_xla(S, q, k, v, log_alpha, beta):
    """:func:`gated_delta_step_flat` in plain XLA: the form for shapes
    the kernel does not take, and what the kernel is tested against.

    A head's value is spread over its ``dv`` lanes by a product with
    the 0/1 matrix ``E[h, h dv + j] = 1`` at ``Precision.HIGHEST``
    (exact: a float32 is the sum of its three bfloat16 pieces, each
    times 1): one operation that lands in the state's own tiling,
    where a broadcast and a reshape would be re-laid as several."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    E = jnp.repeat(jnp.eye(h, dtype=jnp.float32), dv, axis=1)   # (H, H dv)

    def lanes(a):          # (B, n, H) -> (B, n, H dv)
        return jnp.einsum("bnh,hl->bnl", a, E, precision=_HI)

    k_x, q_x = jnp.split(jnp.einsum("bhk,hl->bkl",
                                    jnp.concatenate([k, q], axis=-1), E,
                                    precision=_HI), 2, axis=1)
    gates = lanes(jnp.stack([jnp.exp(log_alpha), beta], axis=1))
    Sd = S * gates[:, :1]
    kS = jnp.sum(k_x * Sd, axis=1, keepdims=True)          # (B, 1, H dv)
    u = gates[:, 1:] * (v.reshape(b, 1, h * dv) - kS)
    S2 = Sd + k_x * u
    o = jnp.sum(q_x * S2, axis=1)
    return S2, o.reshape(b, h, dv)


def _bf16_pieces(x):
    """A float32 array as three bfloat16 arrays that sum to it
    exactly: its mantissa's 24 bits cut into three runs of eight by
    masking, never by rounding, so each piece and each remainder is
    exact (and a compiler that keeps excess precision across a
    convert has nothing to keep)."""
    def top(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = top(x)
    mid = top(x - hi)
    lo = x - hi - mid
    return [p.astype(jnp.bfloat16) for p in (hi, mid, lo)]


def _gated_delta_step_kernel(S, q, k, v, log_alpha, beta):
    """The update as one Pallas kernel (``gdn_step`` in a device
    trace) over tiles ``(1 slot, dk, lane block)`` of the state, which
    it reads once and writes in place.

    What a tile needs of ``k``, ``q``, ``alpha`` and ``beta`` is spread
    over the tile's lanes in VMEM: the small operand ``x`` (rows: ``k``
    by ``dk``, ``q`` by ``dk``, ``alpha``, ``beta``; columns: the three
    bfloat16 pieces of each head's value) times the 0/1 matrix
    ``E[piece * H + h, h dv + j] = 1`` in ONE bfloat16 pass with
    float32 accumulation — exact, since the pieces sum to the value and
    every other term is a zero. The arithmetic on the tile is float32
    on the vector unit, a column of 128 lanes at a time."""
    from jax.experimental import pallas as pl

    from netsdb_tpu.ops.common import pallas_interpret

    b, h, dk = k.shape
    dv = v.shape[-1]
    lanes = h * dv
    block = _lane_block(lanes)
    rows = 2 * dk + 16                   # k, q, alpha, beta, 14 of zeros
    depth = -(-3 * h // 16) * 16         # the pieces, to bfloat16 tiles

    gates = jnp.stack([jnp.exp(log_alpha), beta], axis=1)       # (B, 2, H)
    x = jnp.concatenate(
        [jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2), gates], axis=1)
    x = jnp.pad(jnp.concatenate(_bf16_pieces(x), axis=2),
                ((0, 0), (0, rows - x.shape[1]), (0, depth - 3 * h)))
    E = np.zeros((depth, lanes), np.float32)
    E[:3 * h] = np.tile(np.repeat(np.eye(h), dv, axis=1), (3, 1))
    E = jnp.asarray(E, jnp.bfloat16)

    def kernel(x_ref, e_ref, v_ref, s_ref, s_out_ref, o_ref):
        xs = x_ref[0]
        for c in range(block // LANES):
            at = slice(c * LANES, (c + 1) * LANES)
            spread = jnp.dot(xs, e_ref[:, at],
                             preferred_element_type=jnp.float32)
            k_x, q_x = spread[:dk], spread[dk:2 * dk]
            alpha = spread[2 * dk:2 * dk + 1]
            beta_x = spread[2 * dk + 1:2 * dk + 2]
            Sd = s_ref[0, :, at] * alpha
            kS = jnp.sum(k_x * Sd, axis=0, keepdims=True)
            u = beta_x * (v_ref[0, :, at] - kS)
            S2 = Sd + k_x * u
            s_out_ref[0, :, at] = S2
            o_ref[0, :, at] = jnp.sum(q_x * S2, axis=0, keepdims=True)

    tile = pl.BlockSpec((1, dk, block), lambda j, i: (i, 0, j))
    row = pl.BlockSpec((1, 1, block), lambda j, i: (i, 0, j))
    # lane block outermost: E's block stays put while the slots pass
    S2, o = pl.pallas_call(
        kernel, grid=(lanes // block, b),
        in_specs=[pl.BlockSpec((1, rows, depth), lambda j, i: (i, 0, 0)),
                  pl.BlockSpec((depth, block), lambda j, i: (0, j)),
                  row, tile],
        out_specs=[tile, row],
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, lanes), jnp.float32)],
        input_output_aliases={3: 0}, name="gdn_step",
        interpret=pallas_interpret())(
            x, E, v.reshape(b, 1, lanes), S)
    return S2, o.reshape(b, h, dv)


def _lane_block(lanes: int) -> int:
    """Lanes of a state tile: the widest run of whole 128-lane columns
    that divides ``lanes`` and stays within ``MAX_BLOCK_LANES``."""
    cols = lanes // LANES
    return LANES * max(d for d in range(1, cols + 1)
                       if cols % d == 0 and d * LANES <= MAX_BLOCK_LANES)


def heads_first(S_flat, heads: int):
    """(dk, H dv) -> (H, dk, dv)."""
    dk = S_flat.shape[0]
    return jnp.moveaxis(S_flat.reshape(dk, heads, -1), 1, 0)


def heads_on_lanes(S):
    """(H, dk, dv) -> (dk, H dv), the inverse of :func:`heads_first`."""
    h, dk, dv = S.shape
    return jnp.moveaxis(S, 0, 1).reshape(dk, h * dv)


def gated_delta_recurrent(S0, q, k, v, log_alpha, beta):
    """Token-by-token scan over one sequence. ``S0`` (H, dk, dv);
    ``q``, ``k`` (L, H, dk); ``v`` (L, H, dv); ``log_alpha``, ``beta``
    (L, H). Returns ``(S_L, O)`` with ``O`` (L, H, dv)."""

    def body(S, t):
        qt, kt, vt, gt, bt = t
        S2, o = gated_delta_step(S[None], qt[None], kt[None], vt[None],
                                 gt[None], bt[None])
        return S2[0], o[0]

    return jax.lax.scan(body, S0, (q, k, v, log_alpha, beta))


def chunk_kernel_fits(chunk: int, dk: int, dv: int) -> bool:
    """Whether :func:`gated_delta_chunked` runs its kernel: a delta-chunk
    whose ``(chunk, chunk)`` matrices are one lane tile wide, and states
    ``(dk, dv)`` of whole float32 sublane tiles."""
    return chunk == LANES and dk % 8 == 0 and dv % 8 == 0


def gated_delta_chunked(S0, q, k, v, log_alpha, beta, chunk: int = 64):
    """One sequence in chunks (shapes as :func:`gated_delta_recurrent`;
    ``L`` a multiple of ``chunk``). Returns ``(S_L, O)``.

    Where the shape fits (:func:`chunk_kernel_fits`) the whole call is
    one kernel that keeps a head's state in VMEM from the first chunk
    to the last; any other shape takes
    :func:`gated_delta_chunked_xla`."""
    if chunk_kernel_fits(chunk, q.shape[-1], v.shape[-1]):
        return _gated_delta_chunk_kernel(S0, q, k, v, log_alpha, beta, chunk)
    return gated_delta_chunked_xla(S0, q, k, v, log_alpha, beta, chunk)


def gated_delta_chunked_xla(S0, q, k, v, log_alpha, beta, chunk: int = 64):
    """:func:`gated_delta_chunked` in plain XLA: the form for shapes the
    kernel does not take, and what the kernel is tested against. One
    unit-lower-triangular solve for all chunks at once, then a scan
    that passes the state from chunk to chunk."""
    L, H, dk = q.shape
    dv = v.shape[-1]
    if L % chunk:
        raise ValueError(f"sequence length {L} is not a multiple of "
                         f"the chunk {chunk}")
    nc = L // chunk

    def chunks(a):  # (L, H, ...) -> (nc, H, chunk, ...)
        a = a.reshape((nc, chunk) + a.shape[1:])
        return jnp.moveaxis(a, 2, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta)                                  # (nc, H, C)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)         # log G_i
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # D_ij = G_i / G_j on and below the diagonal, 0 above: the exponent
    # is masked before exp so that the upper triangle cannot overflow
    D = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                          -jnp.inf))
    kk = jnp.einsum("nhik,nhjk->nhij", kc, kc, precision=_HI)
    strict = idx[:, None] > idx[None, :]
    A = jnp.where(strict, bc[..., :, None] * D * kk, 0.0) \
        + jnp.eye(chunk, dtype=jnp.float32)
    rhs = jnp.concatenate(
        [(bc * jnp.exp(g))[..., None] * kc, bc[..., None] * vc], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)
    W, Ut = sol[..., :dk], sol[..., dk:]
    qk = jnp.einsum("nhik,nhjk->nhij", qc, kc, precision=_HI) * D
    to_end = jnp.exp(g[..., -1:] - g)                  # G_C / G_i

    def body(S, t):
        q_c, k_c, W_c, Ut_c, qk_c, g_c, e_c = t
        U = Ut_c - jnp.einsum("hik,hkv->hiv", W_c, S, precision=_HI)
        O = jnp.exp(g_c)[..., None] * jnp.einsum(
            "hik,hkv->hiv", q_c, S, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_c, U, precision=_HI)
        S2 = jnp.exp(g_c[:, -1])[:, None, None] * S + jnp.einsum(
            "hik,hiv->hkv", k_c * e_c[..., None], U, precision=_HI)
        return S2, O

    S_L, O = jax.lax.scan(body, S0, (qc, kc, W, Ut, qk, g, to_end))
    # (nc, H, C, dv) -> (L, H, dv)
    return S_L, jnp.moveaxis(O, 1, 2).reshape(L, H, dv)


# heads a grid step of the chunked form's kernel, so that a step's 0.35
# microseconds are shared: on the v5e groups of 2 to 10 take the same
# time (the products bound it), one head a step 40% more, and the
# compile time grows with the group
MAX_CHUNK_HEADS = 3
# rows of the diagonal blocks of ``I + L`` that are inverted by their
# finite Neumann product before the blocks are merged upward
INVERSE_BLOCK = 16


def _mm(a, b):
    """``a @ b`` a head, (G, m, k) x (G, k, n), in float32 at six
    bfloat16 passes."""
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(L):
    """``(I + L)^-1`` for strictly lower triangular ``L`` (G, n, n), by
    blocks: the diagonal blocks of ``INVERSE_BLOCK`` rows by the Neumann
    product ``(I + N)(I + N^2)(I + N^4)...`` with ``N = -L`` there
    (finite, ``N`` being nilpotent), then pairs of blocks merged,
    ``[[T11, 0], [-T22 A21 T11, T22]]``, until one block is left. Every
    step is a product of whole (n, n) matrices under a mask: no loop
    over rows."""
    n = L.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 2)
    apart = rows ^ cols                 # < b: in the same block of b
    width = INVERSE_BLOCK
    N = jnp.where(apart < width, -L, 0.0)
    T = jnp.where(rows == cols, 1.0, 0.0) + N
    power = N
    for _ in range(width.bit_length() - 2):
        power = _mm(power, power)
        T = T + _mm(T, power)
    while width < n:
        below = jnp.where((apart >= width) & (apart < 2 * width), L, 0.0)
        T = T - _mm(T, _mm(below, T))
        width *= 2
    return T


def _chunk_heads(heads: int) -> int:
    return max(g for g in range(1, MAX_CHUNK_HEADS + 1) if heads % g == 0)


def _gated_delta_chunk_kernel(S0, q, k, v, log_alpha, beta, chunk):
    """The chunked form as one Pallas kernel (``gdn_chunk`` in a device
    trace). Grid: groups of heads, then the sequence's chunks in order;
    a group's states live in the output block, which stays in VMEM
    while the chunks pass, is filled from ``S0`` before the first and
    written back after the last.

    A grid step solves one chunk of each of its heads from the state as
    it stands, ``(I + L) U = diag(beta) (V - diag(G) K S)`` (the module
    docstring's system with ``U~ - W S`` taken together, the state being
    at hand), with the inverse of ``I + L`` built by blocks
    (:func:`_unit_lower_inverse`). ``k`` comes in both layouts, rows of
    tokens and rows of ``dk``, so that no product transposes an operand;
    the gates come as a row a head (``g_i`` along the lanes) and as
    columns (a group's ``g`` and ``beta`` side by side, tokens along the
    sublanes, one 128-lane tile a group), prepared outside: 3 MB a
    512-token chunk of the published shape beside the 41 MB of q, k,
    ``k^T``, v and o."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from netsdb_tpu.ops.common import pallas_interpret

    L, H, dk = q.shape
    dv = v.shape[-1]
    nc, G = L // chunk, _chunk_heads(H)

    def chunks(a):  # (L, H, ...) -> (nc, H, chunk, ...)
        a = a.reshape((nc, chunk) + a.shape[1:])
        return jnp.moveaxis(a, 2, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    kt = jnp.swapaxes(kc, 2, 3)                        # (nc, H, dk, C)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)         # log G_i, (nc, H, C)
    g_rows = g[:, :, None, :]

    def columns(a):  # (nc, H, C) -> (nc, H / G, C, G)
        return jnp.moveaxis(a.reshape(nc, H // G, G, chunk), 2, 3)

    # (nc, H / G, C, LANES): lane j is g of the group's head j, lane
    # G + j its beta
    gates = jnp.concatenate([columns(g), columns(chunks(beta))], axis=-1)
    gates = jnp.pad(gates, ((0, 0),) * 3 + ((0, LANES - 2 * G),))

    def kernel(s0_ref, q_ref, k_ref, kt_ref, v_ref, g_ref, gates_ref,
               s_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = s0_ref[...]

        rows = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2)
        S = s_ref[...]                                   # (G, dk, dv)
        kt_g, g_row = kt_ref[0], g_ref[0]                # g_row (G, 1, C)
        cols_of = gates_ref[0, 0]
        g_col = jnp.stack([cols_of[:, h:h + 1] for h in range(G)])
        b_col = jnp.stack([cols_of[:, G + h:G + h + 1] for h in range(G)])
        # D_ij = G_i / G_j on and below the diagonal, 0 above: the
        # exponent is masked before exp
        D = jnp.exp(jnp.where(rows >= cols, g_col - g_row, -jnp.inf))
        kq = jnp.concatenate([k_ref[0], q_ref[0]], axis=1)   # (G, 2C, dk)
        kq_kt = _mm(kq, kt_g)                            # k k^T over q k^T
        kq_S = _mm(kq, S)                                # K S over Q S
        T = _unit_lower_inverse(
            jnp.where(rows > cols, b_col * D * kq_kt[:, :chunk], 0.0))
        decay = jnp.exp(g_col)
        U = _mm(T, b_col * (v_ref[0] - decay * kq_S[:, :chunk]))
        o_ref[0] = decay * kq_S[:, chunk:] + _mm(kq_kt[:, chunk:] * D, U)
        g_end = g_row[:, :, chunk - 1:]                  # (G, 1, 1)
        s_ref[...] = jnp.exp(g_end) * S + _mm(
            kt_g * jnp.exp(g_end - g_row), U)

    def per_chunk(*block):
        return pl.BlockSpec((1, G) + block, lambda i, c: (c, i, 0, 0))

    state = pl.BlockSpec((G, dk, dv), lambda i, c: (i, 0, 0))

    def lanes(n):
        return -(-n // LANES) * LANES

    # a group's blocks (q, k, k^T, v, o, the state in and out), each
    # double-buffered, and room for the body: some forty (chunk, chunk)
    # matrices a head live at once
    vmem = 8 * G * (chunk * (2 * lanes(dk) + 2 * lanes(dv)) + dk * chunk
                    + 2 * dk * lanes(dv)) + G * (3 << 20) + (4 << 20)
    S_L, O = pl.pallas_call(
        kernel, grid=(H // G, nc),
        in_specs=[state, per_chunk(chunk, dk), per_chunk(chunk, dk),
                  per_chunk(dk, chunk), per_chunk(chunk, dv),
                  per_chunk(1, chunk),
                  pl.BlockSpec((1, 1, chunk, LANES),
                               lambda i, c: (c, i, 0, 0))],
        out_specs=[state, per_chunk(chunk, dv)],
        out_shape=[jax.ShapeDtypeStruct((H, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((nc, H, chunk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        name="gdn_chunk", interpret=pallas_interpret())(
            S0, qc, kc, kt, vc, g_rows, gates)
    # (nc, H, C, dv) -> (L, H, dv)
    return S_L, jnp.moveaxis(O, 1, 2).reshape(L, H, dv)
