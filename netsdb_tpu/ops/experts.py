"""Sparse experts of a served language model: routing over ALL experts,
the products of the experts HELD here.

A chip that shares an expert layer with others holds a contiguous range
of its routed experts (``first`` .. ``first + held``). Every token is
routed over the layer's whole width (:func:`route`), and this chip
computes, for the (token, expert) pairs whose expert it holds, the part
of the layer's result that those experts give (:func:`held_experts_ffn`).
What the absent experts would add is left out; nothing stands in for
them. No pair is dropped and there is no capacity: the pairs are sorted
by expert into tiles of ``tile`` rows, every held expert's rows padded to
whole tiles, and one grouped product walks the tiles
(:func:`grouped_ffn`), each tile under its own expert's matrices. An
expert that no token chose has no tile, and its matrices are not read.

Experts are gated SiLU feed-forward blocks stored ``[out, in]`` like
every other matrix of the model: ``w_gate_up`` (held, 2, F, D) holds an
expert's gate over its up projection, ``w_down`` (held, D, F).
Operands take the matrices' dtype, sums are float32. A token's experts
are chosen by sigmoid scores with a selection bias (:func:`route`) or by
a softmax over the router's logits (:func:`route_softmax`).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128
#: columns of an expert's hidden width that one grid step of the kernel
#: works on: gate, up and down blocks of (512, 3072) bfloat16 are 3.1 MB
#: each, 18.9 MB double-buffered
TILE_F = 512
#: a width of whole lanes that is not whole ``TILE_F`` blocks is one
#: block, while its three matrices double-buffered take at most this many
#: bytes of VMEM: (896, 2304) bfloat16 blocks are 24.8 MB so
WHOLE_BLOCK_VMEM = 32 << 20


def route(u: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scale: float) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing with a selection bias. ``u`` (T, D) float32,
    ``w_router`` (E, D), ``bias`` (1, E) or (E,) float32: ``s =
    sigmoid(W_r u)`` in float32 (the router's product takes float32
    operands at the highest precision: a choice is a step function of
    it); the ``top_k`` experts with the largest ``s + bias``; weights
    ``scale * s_e / (sum of the chosen s + 1e-20)``: the bias chooses
    and does not weigh. Returns (experts (T, k) int32, weights (T, k)
    float32)."""
    logits = lax.dot_general(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + bias.reshape(-1), top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights


def route_softmax(u: jax.Array, w_router: jax.Array,
                  top_k: int) -> Tuple[jax.Array, jax.Array]:
    """Softmax routing, the chosen weights renormalised. ``u`` (T, D)
    float32, ``w_router`` (E, D): ``p = softmax(W_r u)`` in float32 (the
    router's product at the highest precision, as in :func:`route`);
    the ``top_k`` experts with the largest ``p``; weights ``p_e / (sum
    of the chosen p)``. No bias, no scale. Returns (experts (T, k) int32,
    weights (T, k) float32)."""
    logits = lax.dot_general(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    picked, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return idx.astype(jnp.int32), picked / picked.sum(-1, keepdims=True)


def n_tiles(pairs: int, held: int, tile: int) -> int:
    """Tiles that hold ``pairs`` rows whatever their split over ``held``
    experts: each expert's last tile may be partly empty."""
    return -(-pairs // tile) + held


def plan_tiles(idx: jax.Array, valid: jax.Array, first: int, held: int,
               tile: int):
    """Lay the pairs of held experts out in tiles by expert.

    ``idx`` (T, k) the experts each token chose, ``valid`` (T,) which
    tokens count. Returns a dict: ``row_token`` (rows,) the token whose
    activations each row of the tiled layout carries (0 for an empty
    row), ``pair_row`` (T, k) the row that answers each pair, ``here``
    (T, k) whether the pair's expert is held (and its token counts),
    ``tile_expert`` (tiles,) the held expert each tile belongs to (the
    last used tile's beyond ``used``), ``used`` the tiles that carry
    rows, ``sizes`` (held,) pairs by held expert."""
    t, k = idx.shape
    pairs = t * k
    local = idx - first
    here = (local >= 0) & (local < held) & valid[:, None]
    e = jnp.where(here, local, held).reshape(pairs)   # not here: sorts last
    order = jnp.argsort(e, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)       # a pair's place in it
    sizes = jnp.sum(e[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    start = jnp.cumsum(sizes) - sizes                 # in the sorted order
    tiles_e = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles_e)
    tile_start = tile_end - tiles_e
    used = tile_end[-1]
    nt = n_tiles(pairs, held, tile)
    at = jnp.minimum(jnp.arange(nt, dtype=jnp.int32),
                     jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, at, side="right", method="compare_all"),
        held - 1
    ).astype(jnp.int32)
    row = jnp.arange(nt * tile, dtype=jnp.int32)
    er = tile_expert[row // tile]
    in_e = row - tile_start[er] * tile
    full = (row // tile < used) & (in_e < sizes[er])
    src = order[jnp.clip(start[er] + in_e, 0, pairs - 1)]
    row_token = jnp.where(full, src // k, 0)
    ep = jnp.minimum(e, held - 1)
    pair_row = (tile_start[ep] * tile + rank - start[ep]).reshape(t, k)
    return {"row_token": row_token, "pair_row": jnp.where(here, pair_row, 0),
            "here": here, "tile_expert": tile_expert, "used": used,
            "sizes": sizes}


def expert_block(d: int, f: int, dtype):
    """Columns of an expert's hidden width ``f`` (over a model width
    ``d``) that one grid step of :func:`grouped_ffn` takes, or None where
    the kernel does not take the shape: ``TILE_F`` where ``f`` is whole
    ``TILE_F`` blocks, else the whole width while it is whole lanes and
    its three blocks double-buffered fit ``WHOLE_BLOCK_VMEM``. With one
    block an expert, consecutive tiles of one expert ask for the same
    blocks, and the pipeline fetches an expert's matrices once."""
    if d % LANES or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    if f % TILE_F == 0:
        return TILE_F
    fits = 6 * f * d * jnp.dtype(dtype).itemsize <= WHOLE_BLOCK_VMEM
    return f if f % LANES == 0 and (f < TILE_F or fits) else None


def grouped_ffn_fits(d: int, f: int, dtype) -> bool:
    """Whether :func:`grouped_ffn`'s kernel takes experts of hidden width
    ``f`` over a model width ``d`` (:func:`expert_block`)."""
    return expert_block(d, f, dtype) is not None


def grouped_ffn_xla(xs, tile_expert, used, w_gate_up, w_down, tile: int):
    """:func:`grouped_ffn` in plain XLA: every tile's expert gathered,
    one batched product. For shapes the kernel does not take (small
    ones: it copies an expert a tile)."""
    nt = tile_expert.shape[0]
    x = xs.reshape(nt, tile, xs.shape[-1]).astype(w_gate_up.dtype)
    gu = w_gate_up[tile_expert]                       # (tiles, 2, F, D)
    g = jnp.einsum("tmd,tfd->tmf", x, gu[:, 0],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tmd,tfd->tmf", x, gu[:, 1],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(w_down.dtype)
    y = jnp.einsum("tmf,tdf->tmd", h, w_down[tile_expert],
                   preferred_element_type=jnp.float32)
    y = jnp.where((jnp.arange(nt) < used)[:, None, None], y, 0.0)
    return y.reshape(nt * tile, -1)


def grouped_ffn(xs: jax.Array, tile_expert: jax.Array, used: jax.Array,
                w_gate_up: jax.Array, w_down: jax.Array,
                tile: int) -> jax.Array:
    """Each tile of ``tile`` rows of ``xs`` (tiles x tile, D) through the
    gated SiLU block of its own expert, one Pallas kernel
    (``grouped_ffn`` in a device trace). ``tile_expert`` (tiles,) and
    ``used`` are scalar-prefetched: the grid is (tiles, F / block), the
    block :func:`expert_block`'s; a tile's step ``j`` fetches block ``j``
    of its expert's gate, up and down matrices and adds ``(silu(x W_g^T)
    * x W_u^T) W_d^T`` of that block to the tile's float32 sum. Tiles
    from ``used`` on repeat the last used tile's last blocks, so they
    fetch nothing, and write zeros. Returns float32 (tiles x tile, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from netsdb_tpu.ops.common import pallas_interpret

    rows, d = xs.shape
    held, _, f, _ = w_gate_up.shape
    dt = w_gate_up.dtype
    tf = expert_block(d, f, dt)
    if tf is None:
        raise ValueError(f"experts of width {f} over {d} in {dt} are not "
                         f"whole blocks of ({TILE_F}, {LANES}) nor one "
                         f"block of whole lanes that fits VMEM")
    nj = f // tf
    nt = rows // tile

    def kernel(te_ref, used_ref, x_ref, g_ref, u_ref, d_ref, o_ref, acc_ref):
        t, j = pl.program_id(0), pl.program_id(1)
        live = t < used_ref[0]

        @pl.when(live & (j == 0))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(live)
        def _():
            x = x_ref[...]
            nt_dims = (((1,), (1,)), ((), ()))
            g = lax.dot_general(x, g_ref[0, 0], nt_dims,
                                preferred_element_type=jnp.float32)
            u = lax.dot_general(x, u_ref[0, 0], nt_dims,
                                preferred_element_type=jnp.float32)
            h = (g * jax.nn.sigmoid(g) * u).astype(dt)
            acc_ref[...] += lax.dot_general(
                h, d_ref[0], nt_dims, preferred_element_type=jnp.float32)

        @pl.when(j == nj - 1)
        def _():
            o_ref[...] = jnp.where(live, acc_ref[...], 0.0)

    def last_live(t, used_ref):
        return jnp.minimum(t, jnp.maximum(used_ref[0] - 1, 0))

    def block_j(t, j, used_ref):
        return jnp.where(t < used_ref[0], j, nj - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nt, nj),
        in_specs=[
            pl.BlockSpec((tile, d),
                         lambda t, j, te, us: (last_live(t, us), 0)),
            pl.BlockSpec((1, 1, tf, d),
                         lambda t, j, te, us: (te[t], 0, block_j(t, j, us), 0)),
            pl.BlockSpec((1, 1, tf, d),
                         lambda t, j, te, us: (te[t], 1, block_j(t, j, us), 0)),
            pl.BlockSpec((1, d, tf),
                         lambda t, j, te, us: (te[t], 0, block_j(t, j, us)))],
        out_specs=pl.BlockSpec((tile, d), lambda t, j, te, us: (t, 0)),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)])
    # three weight blocks, double-buffered, the tile's rows in and out
    # and its sum, and room for the body
    vmem = (6 * tf * d * jnp.dtype(dt).itemsize + 5 * tile * d * 4
            + (16 << 20))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name="grouped_ffn", interpret=pallas_interpret())(
            tile_expert.astype(jnp.int32),
            jnp.reshape(used, (1,)).astype(jnp.int32),
            xs.astype(dt), w_gate_up, w_gate_up, w_down)


def held_experts_ffn(u: jax.Array, idx: jax.Array, weights: jax.Array,
                     valid: jax.Array, w_gate_up: jax.Array,
                     w_down: jax.Array, first: int, tile: int):
    """The held experts' part of an expert layer's result. ``u`` (T, D)
    float32 the layer's input, ``idx``/``weights`` (T, k) from
    :func:`route`, ``valid`` (T,) the tokens that count, ``w_gate_up``
    (held, 2, F, D), ``w_down`` (held, D, F), ``first`` the first held
    expert's number. Returns (``sum over the chosen, held e of w_e
    E_e(u)`` float32 (T, D), counts int32 (3,): pairs routed here,
    distinct held experts touched, the largest load of one expert)."""
    held, _, f, d = w_gate_up.shape
    plan = plan_tiles(idx, valid, first, held, tile)
    xs = u[plan["row_token"]]
    product = (grouped_ffn if grouped_ffn_fits(d, f, w_gate_up.dtype)
               else grouped_ffn_xla)
    ys = product(xs, plan["tile_expert"], plan["used"], w_gate_up, w_down,
                 tile)
    picked = jnp.where(plan["here"][..., None], ys[plan["pair_row"]], 0.0)
    out = jnp.sum(picked * weights[..., None], axis=1)
    sizes = plan["sizes"]
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    return out, counts.astype(jnp.int32)
