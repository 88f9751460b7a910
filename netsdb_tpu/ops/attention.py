"""Attention ops — the long-context compute core.

The reference has no attention anywhere (SURVEY §5: no sequence
dimension exists in netsDB), but this framework treats long-context as
first-class: serving modern models through the same set/computation API
requires attention plus sequence parallelism. This module provides the
single-device formulations; :mod:`netsdb_tpu.parallel.ring` distributes
them over the mesh.

Layouts: q/k/v are (batch, heads, seq, head_dim) — B H S D.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True,
              scale: Optional[float] = None) -> jax.Array:
    """Plain softmax attention (the reference formulation everything
    else must match numerically)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                      precision=jax.lax.Precision.HIGHEST)


def _block_attn(q, k, v, carry_num, carry_den, carry_max, mask):
    """One online-softmax accumulation step (the flash-attention update
    rule): combine the running (num, den, max) with a new k/v block."""
    scale_logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                              precision=jax.lax.Precision.HIGHEST)
    scale_logits = jnp.where(mask, scale_logits, NEG_INF)
    block_max = jnp.max(scale_logits, axis=-1, keepdims=True)
    new_max = jnp.maximum(carry_max, block_max)
    correction = jnp.exp(carry_max - new_max)
    p = jnp.exp(scale_logits - new_max)
    new_den = carry_den * correction + p.sum(-1, keepdims=True)
    new_num = carry_num * correction + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, precision=jax.lax.Precision.HIGHEST)
    return new_num, new_den, new_max


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int, causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """Attention with k/v processed in blocks via online softmax —
    O(block) memory in the sequence dim, the single-device form of ring
    attention. Numerically identical to :func:`attention`."""
    b, h, s, d = q.shape
    if s % block_size != 0:
        raise ValueError(f"seq {s} not divisible by block {block_size}")
    scale = scale if scale is not None else d ** -0.5
    q = q * scale
    n_blocks = s // block_size
    kb = k.reshape(b, h, n_blocks, block_size, d)
    vb = v.reshape(b, h, n_blocks, block_size, d)
    q_pos = jnp.arange(s)[:, None]

    def body(i, carry):
        num, den, mx = carry
        k_i = kb[:, :, i]
        v_i = vb[:, :, i]
        if causal:
            k_pos = i * block_size + jnp.arange(block_size)[None, :]
            mask = q_pos >= k_pos
        else:
            mask = jnp.ones((s, block_size), jnp.bool_)
        return _block_attn(q, k_i, v_i, num, den, mx, mask)

    num0 = jnp.zeros_like(q)
    den0 = jnp.zeros((b, h, s, 1), q.dtype)
    max0 = jnp.full((b, h, s, 1), NEG_INF, q.dtype)
    num, den, _ = jax.lax.fori_loop(0, n_blocks, body, (num0, den0, max0))
    return num / jnp.maximum(den, 1e-30)


def cached_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     q_pos: jax.Array, block_size: Optional[int] = None,
                     scale: Optional[float] = None, row0=None,
                     k_pos: Optional[jax.Array] = None,
                     window: Optional[int] = None) -> jax.Array:
    """Attention of new queries over a per-row cache — the
    chunked-prefill path (one row, a chunk of queries) of a model that
    keeps keys and values in slot-indexed caches, and its decode path
    (one query a row) where the caches' shape does not fit
    :func:`decode_attention`, which is tested against this.

    ``q`` (B, Lq, H, D). ``k_cache`` and ``v_cache`` are (rows, Hkv, T,
    D): tokens and head_dim are the two minor axes, so a bfloat16 cache
    tiles without padding. ``H`` is a multiple of ``Hkv`` (grouped
    queries: ``H / Hkv`` consecutive query heads share a key/value head,
    and are folded into the query axis, so the products are the same
    ones). With ``row0=None`` every row is attended
    (``B`` = rows) and the caches are the products' operands as they
    stand, never sliced or copied; with a (possibly traced) ``row0``
    the ``B`` rows from there are. ``q_pos`` (B, Lq) int32 is the
    position of each query: key ``j`` of row ``b`` is visible to query
    ``i`` when ``j <= q_pos[b, i]``. A query with ``q_pos < 0`` sees
    nothing and yields zeros. A cache that is a ring gives ``k_pos``
    (T,) or (B, T) int32, the position of the token each cache row holds
    (below 0: none yet), in place of the row's own index, and every
    block is walked; with ``window`` key ``j`` is visible only while
    ``j > q_pos - window``.

    ``block_size=None`` reads a row's whole cache in one pass (masked
    beyond each query's position): the fewest operations, and what a
    batch of rows near their cache's end costs anyway. With a
    ``block_size`` (a divisor of ``T``) the cache is walked in blocks
    with the online softmax and the walk stops after the block that
    holds the largest position: what lies beyond the longest row's
    length is never read. Products take the cache's dtype as operands
    and accumulate in float32; returns float32 (B, Lq, H, D)."""
    b, lq, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    block_size = block_size or t
    if t % block_size:
        raise ValueError(f"cache length {t} is not a multiple of the "
                         f"block {block_size}")
    scale = scale if scale is not None else d ** -0.5
    qs = jnp.moveaxis((q.astype(jnp.float32) * scale).astype(k_cache.dtype),
                      1, 2)                                # (B, H, Lq, D)
    group = h // hkv
    if group > 1:
        qs = qs.reshape(b, hkv, group * lq, d)
        q_pos = jnp.tile(q_pos, (1, group))
    lf = group * lq
    whole = row0 is None and block_size == t

    def block(cache, i):
        if whole:
            return cache
        start = (jnp.asarray(0 if row0 is None else row0, jnp.int32),
                 jnp.int32(0), jnp.asarray(i * block_size, jnp.int32),
                 jnp.int32(0))
        return jax.lax.dynamic_slice(cache, start, (b, hkv, block_size, d))

    def body(i, carry):
        num, den, mx = carry
        logits = jnp.einsum("bhqd,bhkd->bhqk", qs, block(k_cache, i),
                            preferred_element_type=jnp.float32)
        if k_pos is None:
            at = i * block_size + jnp.arange(block_size)
        else:
            at = jax.lax.dynamic_slice_in_dim(k_pos, i * block_size,
                                              block_size, axis=-1)
        at = at.reshape((-1, 1, 1, block_size))     # one ring, or one a row
        qp = q_pos[:, None, :, None]
        mask = at <= qp
        if k_pos is not None:
            mask &= at >= 0
        if window is not None:
            mask &= at > qp - window
        logits = jnp.where(mask, logits, NEG_INF)
        new_max = jnp.maximum(mx, logits.max(-1, keepdims=True))
        corr = jnp.exp(mx - new_max)
        p = jnp.where(mask, jnp.exp(logits - new_max), 0.0)
        den = den * corr + p.sum(-1, keepdims=True)
        num = num * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_cache.dtype), block(v_cache, i),
            preferred_element_type=jnp.float32)
        return num, den, new_max

    carry = (jnp.zeros((b, hkv, lf, d), jnp.float32),
             jnp.zeros((b, hkv, lf, 1), jnp.float32),
             jnp.full((b, hkv, lf, 1), NEG_INF, jnp.float32))
    if block_size == t:
        num, den, _ = body(0, carry)
    elif k_pos is not None:
        num, den, _ = jax.lax.fori_loop(0, t // block_size, body, carry)
    else:
        n_blocks = jnp.minimum(
            (jnp.max(q_pos) + block_size) // block_size, t // block_size)
        num, den, _ = jax.lax.fori_loop(0, n_blocks, body, carry)
    out = num / jnp.maximum(den, 1e-30)
    return jnp.moveaxis(out.reshape(b, h, lq, d), 1, 2)


def cache_write_rows(cache: jax.Array, new: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """Write one token's row into every row of a cache, in place:
    ``cache[b, :, pos[b], :] = new[b]`` for ``cache`` (B, H, T, D),
    ``new`` (B, H, D) and ``pos`` (B,) int32 — a decode step's append
    for a whole batch in ONE kernel (``cache_write_rows`` in a device
    trace), where a scatter makes XLA re-lay the cache and a
    ``dynamic_update_slice`` a row is B operations.

    The kernel walks the batch; for row ``b`` the block of ``ROWS``
    tokens that holds ``pos[b]`` (found from the scalar-prefetched
    ``pos``) is read, the one token replaced, and the block written
    back to the aliased cache. ``T`` must be a multiple of 16."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from netsdb_tpu.ops.common import pallas_interpret

    bsz, h, t, d = cache.shape
    rows = 16
    if t % rows:
        raise ValueError(f"cache length {t} is not a multiple of {rows}")

    def kernel(pos_ref, new_ref, cache_ref, out_ref):
        at = pos_ref[pl.program_id(0)] % rows
        blk = cache_ref[...]                               # (1, H, rows, D)
        token = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 2)
        fresh = jnp.broadcast_to(new_ref[...][:, :, None, :], blk.shape)
        out_ref[...] = jnp.where(token == at, fresh, blk)

    def block_of(b, pos_ref):
        return b, 0, pos_ref[b] // rows, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bsz,),
        in_specs=[pl.BlockSpec((1, h, d), lambda b, pos_ref: (b, 0, 0)),
                  pl.BlockSpec((1, h, rows, d), block_of)],
        out_specs=pl.BlockSpec((1, h, rows, d), block_of))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0}, name="cache_write_rows",
        interpret=pallas_interpret())(
            pos.astype(jnp.int32), new.astype(cache.dtype), cache)


# tokens of a cache block the decode kernel fetches at a time: thirty
# heads of (256, 128) bfloat16 are 1.97 MB, keys and values each
# double-buffered 7.9 MB of a core's 16 MiB of scoped VMEM. On the chip
# 128 and 512 rows were no faster (PERF.md section 6, PR 30)
DECODE_BLOCK = 256
LANES = 128


def decode_attention_fits(t: int, d: int, dtype) -> bool:
    """Whether :func:`decode_attention` takes caches of ``t`` tokens and
    ``head_dim`` ``d``: whole blocks of ``DECODE_BLOCK`` tokens (so
    whole (16, 128) tiles of a bfloat16 cache) and whole lanes."""
    return (t % DECODE_BLOCK == 0 and d % LANES == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, scale: Optional[float] = None,
                     window: Optional[int] = None) -> jax.Array:
    """The decode case of :func:`cached_attention` (one query a row,
    every row a slot) as one Pallas kernel, ``decode_attention`` in a
    device trace, that fetches of each row's cache only the blocks its
    own length reaches. ``q`` (B, 1, H, D); the caches (B, Hkv, T, D)
    as they stand in the slab, ``H`` a multiple of ``Hkv`` (grouped
    queries: the ``H / Hkv`` query heads of a key/value head are the
    rows of one query tile); ``pos`` (B,) int32: key ``j`` of row ``b``
    is visible when ``j <= pos[b]``, and a row with ``pos[b] < 0`` sees
    nothing and yields zeros. Returns float32 (B, 1, H, D): the
    arithmetic of ``cached_attention(..., block_size=DECODE_BLOCK)``,
    operands in the cache's dtype, every sum and the softmax float32.

    With ``window`` the cache is a ring of ``T`` rows (``T >= window +
    DECODE_BLOCK``): the token at position ``j`` lies in row ``j % T``
    and is visible while ``pos[b] - window < j <= pos[b]``.

    The grid is (rows, blocks a row can need) with ``pos`` prefetched.
    A row that needs ``n`` blocks spends its first grid steps on its
    first block with the body skipped and then walks its ``n`` blocks
    (0 to ``n - 1``; in a ring from the block that holds its oldest
    visible key, around the ring's end): consecutive steps on one block
    fetch nothing, so what a row does not see never leaves HBM, and the
    row's first block is copied under the row before's last product.
    (Idle steps at a row's end would leave that copy exposed, a block a
    row.) A row that sees nothing still has one block fetched, once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from netsdb_tpu.ops.common import pallas_interpret

    b, _, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    dt = k_cache.dtype
    block = DECODE_BLOCK
    if not decode_attention_fits(t, d, dt):
        raise ValueError(f"a {dt} cache of {t} tokens by {d} is not whole "
                         f"blocks of ({block}, {LANES})")
    if window is not None and t < window + block:
        raise ValueError(f"a ring of {t} rows cannot hold a window of "
                         f"{window} and a block of {block}")
    n_all = t // block
    n_walk = n_all if window is None else -(-window // block) + 1
    group = h // hkv
    tile = 32 // jnp.dtype(dt).itemsize      # rows of one query tile
    rows = -(-group // tile) * tile
    scale = scale if scale is not None else d ** -0.5
    qs = (q.astype(jnp.float32) * scale).astype(dt)
    if group == 1:
        qs = qs.reshape(b, h, d)     # broadcast to a tile in the kernel
    else:
        qs = jnp.pad(qs.reshape(b, hkv, group, d),
                     ((0, 0), (0, 0), (0, rows - group), (0, 0)))

    def walk(last):
        """(first block, blocks) that a row whose last visible key is
        ``last`` reads."""
        if window is None:
            return 0, (jnp.maximum(last, -1) + block) // block
        seen = jnp.clip(last + 1, 0, window)
        oldest = jnp.maximum(last - window + 1, 0) % t
        used = jnp.where(seen > 0,
                         (oldest % block + seen + block - 1) // block, 0)
        return oldest // block, used

    def step_at(j, last):
        """(which of its blocks a row reads at grid step ``j``, below 0
        while it idles; that block's number in the cache)."""
        first, used = walk(last)
        at = j - (n_walk - used)
        if window is None:
            return at, jnp.maximum(at, 0)
        return at, (first + jnp.maximum(at, 0)) % n_all

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, num_ref, den_ref,
               max_ref):
        row, j = pl.program_id(0), pl.program_id(1)
        last = pos_ref[row]
        at, blk = step_at(j, last)

        @pl.when(j == 0)
        def _():
            num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)
            den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
            max_ref[...] = jnp.full(max_ref.shape, NEG_INF, jnp.float32)

        @pl.when(at >= 0)
        def _():
            # the query heads of a key/value head as the rows of one
            # tile (one head: its query in every row): the products are
            # batched over the key/value heads, (rows, D) x (block, D)^T
            if group == 1:
                qr = jnp.broadcast_to(q_ref[0][:, None, :], (hkv, rows, d))
            else:
                qr = q_ref[0]
            logits = jax.lax.dot_general(
                qr, k_ref[0], (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # (Hkv, rows, block)
            # (not a ring: a row's block ``at`` is the cache's, as it is)
            k_row = (at if window is None else blk) * block \
                + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block), 2)
            # one key of the block at least is visible, so the new max
            # is finite and a masked key's weight is exactly 0
            if window is None:
                seen = k_row <= last
            else:
                age = last % t - k_row          # back from the newest key
                age = jnp.where(age < 0, age + t, age)
                seen = age < jnp.minimum(last + 1, window)
            logits = jnp.where(seen, logits, NEG_INF)
            mx = max_ref[...]
            new_max = jnp.maximum(mx, logits.max(-1, keepdims=True))
            corr = jnp.exp(mx - new_max)
            p = jnp.exp(logits - new_max)
            den_ref[...] = den_ref[...] * corr + p.sum(-1, keepdims=True)
            num_ref[...] = num_ref[...] * corr + jax.lax.dot_general(
                p.astype(dt), v_ref[0], (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # (Hkv, rows, D)
            max_ref[...] = new_max

        @pl.when(j == n_walk - 1)
        def _():
            out = num_ref[...] / jnp.maximum(den_ref[...], 1e-30)
            if group == 1:
                o_ref[0] = out.max(1)    # a head's rows are all the same
            else:
                o_ref[0] = out

    def cache_block(row, j, pos_ref):
        return row, 0, step_at(j, pos_ref[row])[1], 0

    def whole_row(row, j, pos_ref):
        return (row, 0, 0) if group == 1 else (row, 0, 0, 0)

    q_block = (1, h, d) if group == 1 else (1, hkv, rows, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, n_walk),
        in_specs=[pl.BlockSpec(q_block, whole_row),
                  pl.BlockSpec((1, hkv, block, d), cache_block),
                  pl.BlockSpec((1, hkv, block, d), cache_block)],
        out_specs=pl.BlockSpec(q_block, whole_row),
        scratch_shapes=[pltpu.VMEM((hkv, rows, d), jnp.float32),
                        pltpu.VMEM((hkv, rows, 1), jnp.float32),
                        pltpu.VMEM((hkv, rows, 1), jnp.float32)])
    # keys and values, each double-buffered, and room for the body
    vmem = 4 * hkv * block * d * jnp.dtype(dt).itemsize + (8 << 20)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + q_block[1:], jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name="decode_attention", interpret=pallas_interpret())(
            pos.astype(jnp.int32), qs, k_cache, v_cache)
    if group > 1:
        out = out[:, :, :group].reshape(b, h, d)
    return out[:, None]


# a prompt chunk's attention kernel: the most rows (a key/value head's
# query heads x tokens) of one query tile, and the key blocks it tries,
# first to last. On the chip what a step costs beside its two products
# grows with the tile's rows, so long blocks pay: 512 keys took twice
# 1,024's time, 1,536 two thirds of 512's; past that a tile computes
# more keys than its queries see (PERF.md section 6, PR 36)
PREFILL_TILE_ROWS = 1536
PREFILL_BLOCKS = (1536, 1024, 512, DECODE_BLOCK)


def _query_tile(dtype) -> int:
    """Rows of one (sublanes, 128) tile of a query in ``dtype``."""
    return 32 // jnp.dtype(dtype).itemsize


def prefill_attention_fits(chunk: int, t: int, d: int, dtype) -> bool:
    """Whether :func:`prefill_attention` takes a chunk of ``chunk``
    queries over caches of ``t`` tokens and ``head_dim`` ``d``: what
    :func:`decode_attention` takes (whole lanes, whole key blocks), and
    query tiles of whole (16, 128) tiles of a bfloat16 query ((8, 128)
    of a float32 one)."""
    return (decode_attention_fits(t, d, dtype)
            and chunk % _query_tile(dtype) == 0)


def prefill_tiles(chunk: int, t: int, group: int, dtype) -> tuple:
    """(tokens of a query tile, keys of a block) that
    :func:`prefill_attention` takes for such a chunk and cache: the
    whole chunk a tile, halved while its ``group`` query heads make
    more than ``PREFILL_TILE_ROWS`` rows; the first of
    ``PREFILL_BLOCKS`` that divides the cache."""
    tq, least = chunk, 2 * _query_tile(dtype)
    while group * tq > PREFILL_TILE_ROWS and tq % least == 0:
        tq //= 2
    return tq, next(b for b in PREFILL_BLOCKS if t % b == 0)


def prefill_walk(xp, qi, pos0, n_valid, tq: int, bk: int, t: int,
                 window: Optional[int]):
    """(first block, blocks) of the walk of query tile ``qi`` (``tq``
    tokens) of :func:`prefill_attention` around a cache of ``t`` rows in
    blocks of ``bk``: a full cache from its first block to the block of
    the tile's last query that counts, a ring from the block of the
    oldest key the tile's first query sees, and no block where none of
    its queries counts. ``xp`` is ``jax.numpy`` in the kernel and its
    block map, and ``numpy`` where the host counts what a chunk reads
    (``models/hybrid_lm.py::prefill_blocks_read``): one arithmetic."""
    i0 = qi * tq
    live = xp.clip(n_valid - i0, 0, tq)          # its queries that count
    newest = pos0 + i0 + live - 1
    if window is None:
        first, used = 0, newest // bk + 1
    else:
        oldest = xp.maximum(pos0 + i0 - window + 1, 0)
        first = oldest % t // bk
        used = (oldest % t % bk + newest - oldest + bk) // bk
    return first, xp.where(live > 0, xp.minimum(used, t // bk), 0)


def prefill_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      slot, pos0, n_valid, scale: Optional[float] = None,
                      window: Optional[int] = None) -> jax.Array:
    """The chunked-prefill case of :func:`cached_attention` (a chunk of
    queries over ONE slot's cache) as one Pallas kernel,
    ``prefill_attention`` in a device trace, that keeps the online
    softmax in VMEM: no score of a (query, key) pair reaches HBM.

    ``q`` (C, H, D) float32, the chunk's queries at positions ``pos0``
    to ``pos0 + C - 1``, of which the first ``n_valid`` count; the
    caches (slots, Hkv, T, D) as they stand in the slab with the chunk's
    keys and values already written, ``H`` a multiple of ``Hkv``; ``slot``,
    ``pos0`` and ``n_valid`` traced scalars. Key ``j`` is visible to
    query ``i < n_valid`` while ``j <= pos0 + i``; a query past
    ``n_valid`` yields zeros. With ``window`` the cache is a ring of
    ``T`` rows (``T >= window + C``) written up to position ``pos0 + C -
    1``: the token at position ``j`` lies in row ``j % T`` and is
    visible while ``pos0 + i - window < j <= pos0 + i``. Returns float32
    (C, H, D): the arithmetic of ``cached_attention(..., block_size=bk)``
    block for block in the order of the cache's rows (``q`` scaled in
    float32 and cast to the cache's dtype, operands in that dtype, every
    sum and the softmax float32), ``bk`` by :func:`prefill_tiles`.

    The grid is (key/value heads, query tiles, blocks a tile can need)
    with ``slot``, ``pos0`` and ``n_valid`` prefetched. The ``H / Hkv``
    query heads of a key/value head are the rows of one query tile
    (``tq`` tokens each), held against one ``(bk, D)`` block of that
    head's keys at a time. Queries and result keep a token's heads side
    by side, ``(C, H x D)``: a key/value head's query heads are whole
    lanes of a ``(tq, H / Hkv x D)`` block, stacked to the tile's rows
    in VMEM, so nothing is re-laid in HBM on either side of the call.
    A tile that needs ``n`` blocks (a full cache: up to the block of its
    last query that counts; a ring: from the block of the oldest key its
    first query sees) spends its first grid steps on its first block
    with the body skipped and then walks its ``n`` blocks; a tile wholly
    past ``n_valid`` stays on the block before it and does no product.
    Consecutive steps on one block fetch nothing, so what no query of a
    tile sees never leaves HBM. A block that every query of the tile
    sees whole takes no mask."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from netsdb_tpu.ops.common import pallas_interpret

    chunk, h, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    dt = k_cache.dtype
    if not prefill_attention_fits(chunk, t, d, dt):
        raise ValueError(f"a chunk of {chunk} queries over a {dt} cache of "
                         f"{t} tokens by {d} is not whole tiles and blocks "
                         f"of ({DECODE_BLOCK}, {LANES})")
    if window is not None and t < window + chunk:
        raise ValueError(f"a ring of {t} rows cannot hold a window of "
                         f"{window} and a chunk of {chunk}")
    group = h // hkv
    tq, bk = prefill_tiles(chunk, t, group, dt)
    rows, n_q, n_all = group * tq, chunk // tq, t // bk
    n_walk = n_all if window is None else min(
        n_all, -(-(window + tq - 1) // bk) + 1)
    scale = scale if scale is not None else d ** -0.5
    # a token's heads side by side: a key/value head's query heads are
    # whole lanes of a (tokens, group x D) block, as the result's are
    qs = (q.astype(jnp.float32) * scale).astype(dt).reshape(chunk, h * d)

    def step_at(qi, j, pos0, n_valid):
        """(which of its blocks the tile reads at grid step ``j``, below
        0 while it idles or where no query of it counts; that block's
        number in the cache). The blocks are taken in the order of the
        cache's rows: a ring's walk that passes the ring's end starts
        at row 0."""
        last_live = jnp.maximum(n_valid - 1, 0) // tq
        first, used = prefill_walk(jnp, jnp.minimum(qi, last_live), pos0,
                                   n_valid, tq, bk, t, window)
        at = j - (n_walk - used)
        # a tile past n_valid stays where the tile before it ended
        held = jnp.maximum(jnp.where(qi > last_live, used - 1, at), 0)
        low = jnp.maximum(first + used - n_all, 0)
        return (jnp.where(qi > last_live, -1, at),
                jnp.where(held < low, held, first + held - low))

    def kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, q_rows, num_ref,
               den_ref, max_ref):
        qi, j = pl.program_id(1), pl.program_id(2)
        pos0, n_valid = meta_ref[1], meta_ref[2]
        at, blk = step_at(qi, j, pos0, n_valid)
        i0, row0 = qi * tq, blk * bk
        newest = pos0 + chunk - 1          # the ring is written up to it
        ring_at = newest % t

        def query_rows():
            i = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            return i0 + (i % tq if group > 1 else i)

        def fold(seen):
            logits = jax.lax.dot_general(
                q_rows[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (rows, bk)
            if seen is not None:
                logits = jnp.where(seen, logits, NEG_INF)
            mx = max_ref[...]
            new_max = jnp.maximum(mx, logits.max(-1, keepdims=True))
            corr = jnp.exp(mx - new_max)
            p = jnp.exp(logits - new_max)
            if seen is not None:
                p = jnp.where(seen, p, 0.0)
            den_ref[...] = den_ref[...] * corr + p.sum(-1, keepdims=True)
            num_ref[...] = num_ref[...] * corr + jax.lax.dot_general(
                p.astype(dt), v_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (rows, D)
            max_ref[...] = new_max

        # whether every query of the tile sees every key of the block
        # (queries past n_valid see what they like: zeroed at the end)
        if window is None:
            clear = row0 + bk - 1 <= pos0 + i0
        else:
            # a ring row's age: how far its token lies behind the newest
            age0 = jnp.where(ring_at < row0, ring_at - row0 + t,
                             ring_at - row0)
            ages_fall = (ring_at < row0) | (ring_at >= row0 + bk - 1)
            clear = (ages_fall & (age0 - (bk - 1) >= chunk - 1 - i0)
                     & (age0 < chunk - i0 - tq + window) & (age0 <= newest))

        @pl.when(j == 0)
        def _():
            # the tile's rows: query head after query head
            for g in range(group):
                q_rows[g * tq:(g + 1) * tq] = q_ref[:, g * d:(g + 1) * d]
            num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)
            den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
            max_ref[...] = jnp.full(max_ref.shape, NEG_INF, jnp.float32)

        @pl.when((at >= 0) & clear)
        def _():
            fold(None)

        @pl.when((at >= 0) & jnp.logical_not(clear))
        def _():
            key_row = row0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            if window is None:
                seen = key_row <= pos0 + query_rows()
            else:
                age = ring_at - key_row
                age = jnp.where(age < 0, age + t, age)
                # a row that holds no token yet is older than any window
                age = jnp.where(age <= newest, age, 1 << 30)
                behind = chunk - 1 - query_rows()    # the query's own age
                seen = (age >= behind) & (age < behind + window)
            fold(seen)

        @pl.when(j == n_walk - 1)
        def _():
            out = num_ref[...] / jnp.maximum(den_ref[...], 1e-30)
            out = jnp.where(query_rows() < n_valid, out, 0.0)
            for g in range(group):
                o_ref[:, g * d:(g + 1) * d] = out[g * tq:(g + 1) * tq]

    def cache_block(hd, qi, j, meta_ref):
        return (meta_ref[0], hd,
                step_at(qi, j, meta_ref[1], meta_ref[2])[1], 0)

    def tile(hd, qi, j, meta_ref):
        return qi, hd

    item = jnp.dtype(dt).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(hkv, n_q, n_walk),
        in_specs=[pl.BlockSpec((tq, group * d), tile),
                  pl.BlockSpec((None, None, bk, d), cache_block),
                  pl.BlockSpec((None, None, bk, d), cache_block)],
        out_specs=pl.BlockSpec((tq, group * d), tile),
        scratch_shapes=[pltpu.VMEM((rows, d), dt),
                        pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32)])
    # keys and values, queries and the result, each double-buffered, the
    # sums, and the body: a tile's scores, their exponent, its cast and
    # the mask
    vmem = (4 * bk * d * item + 2 * rows * d * (item + 4) + rows * d * item
            + 4 * rows * (d + 2 * LANES) + 16 * rows * bk + (4 << 20))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((chunk, h * d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="prefill_attention", interpret=pallas_interpret())(
            jnp.stack([jnp.asarray(slot, jnp.int32),
                       jnp.asarray(pos0, jnp.int32),
                       jnp.asarray(n_valid, jnp.int32)]),
            qs, k_cache, v_cache)
    return out.reshape(chunk, h, d)


def split_qkv_heads(qkv: jax.Array, num_heads: int):
    """Packed (B,S,3E) projection → q/k/v (B,H,S,D) — THE layout
    convention (split into thirds, then head reshape/transpose); every
    consumer (fused forward, staged DAGs) must share it or the paths
    silently diverge."""
    b, s, f = qkv.shape
    e = f // 3
    d = e // num_heads
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

    return heads(q), heads(k), heads(v)


def merge_heads(out: jax.Array) -> jax.Array:
    """(B,H,S,D) attention output → (B,S,E), the inverse of
    :func:`split_qkv_heads`'s layout."""
    b, h, s, d = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def qkv_project(x: jax.Array, w_qkv: jax.Array, num_heads: int):
    """x (B,S,E) → q/k/v (B,H,S,D) — shared by local and
    sequence-parallel layers."""
    qkv = jnp.einsum("bse,ef->bsf", x, w_qkv,
                     precision=jax.lax.Precision.HIGHEST)
    return split_qkv_heads(qkv, num_heads)


def merge_project(out: jax.Array, w_out: jax.Array) -> jax.Array:
    """(B,H,S,D) attention output → (B,S,E) through the out projection."""
    return jnp.einsum("bse,ef->bsf", merge_heads(out), w_out,
                      precision=jax.lax.Precision.HIGHEST)


def attention_dispatch(q: jax.Array, k: jax.Array, v: jax.Array,
                       causal: bool = True, scale: Optional[float] = None,
                       impl: Optional[str] = None,
                       block_size: Optional[int] = None,
                       out_vma=None) -> jax.Array:
    """Pick the attention implementation: 'full', 'blockwise', or
    'flash' (pallas kernel). ``impl=None`` auto-selects: flash on TPU
    when the sequence divides its blocks, else blockwise when a
    block_size is given, else full."""
    from netsdb_tpu.ops.common import on_tpu

    s = q.shape[2]
    if impl is None:
        # flash only when the sequence is a whole number of pallas blocks
        # AND the block the kernel would use is lane-aligned — an explicit
        # caller block_size that Mosaic can't tile (not a multiple of 128)
        # must keep the exact blockwise path, not be silently overridden
        blk = block_size or min(256, s)
        if on_tpu() and s % 256 == 0 and blk % 128 == 0 and s % blk == 0:
            impl = "flash"
        elif block_size:
            impl = "blockwise"
        else:
            impl = "full"
    if impl == "flash":
        from netsdb_tpu.ops.pallas_kernels import flash_attention

        if block_size:
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=block_size, block_k=block_size,
                                   out_vma=out_vma)
        # no explicit block: use the kernel's tuned defaults (1024^2,
        # ~3x the throughput of 256^2 at long seq — see flash_attention)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               out_vma=out_vma)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, block_size or min(256, s),
                                   causal, scale)
    if impl == "full":
        return attention(q, k, v, causal, scale)
    raise ValueError(f"unknown attention impl {impl!r}")


def mha_forward(x: jax.Array, w_qkv: jax.Array, w_out: jax.Array,
                num_heads: int, causal: bool = True,
                block_size: Optional[int] = None,
                impl: Optional[str] = None) -> jax.Array:
    """Full multi-head attention layer: x (B, S, E), w_qkv (E, 3E),
    w_out (E, E) — the flagship long-context layer the parallel plans
    shard."""
    q, k, v = qkv_project(x, w_qkv, num_heads)
    out = attention_dispatch(q, k, v, causal=causal, impl=impl,
                             block_size=block_size)
    return merge_project(out, w_out)
