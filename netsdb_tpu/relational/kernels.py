"""Vectorized relational kernels over device arrays.

Each kernel is the TPU-native form of one of the reference's executor /
processor families (``src/queryExecution``):

- group-by + aggregate → masked scatter-add segments
  (reference: CombinerProcessor / AggregationProcessor hash maps,
  ``src/queryExecution/headers/CombinerProcessor.h:20``);
- equi-join → sort the build side once, ``searchsorted`` probes, gather
  (reference: JoinMap build + probe,
  ``src/builtInPDBObjects/headers/JoinPairArray.h:122``);
- semi/anti-join → membership probe with a sentinel for masked rows;
- top-k → ``lax.top_k`` over masked scores
  (reference: TopK aggregation, ``src/sharedLibraries/headers/TopKTest.h``).

All kernels take/return fixed-shape arrays and are jit-safe; dynamic
cardinalities (number of groups, join fan-out) are bounded by host-side
static metadata (key-space size), which the caller reads off table
shapes/dictionaries before tracing.

Masked rows are handled with identity elements (0 for sum/count,
±inf for min/max) or key sentinels that can never match — never with
shape-changing compaction.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# a HOST scalar: a jnp value here would create a device array at import
# time, i.e. importing this package (as every client process does for
# ColumnTable) would initialise a jax backend and claim the chip
_I32_SENTINEL = np.int32(-2147483648)


def _masked(values: jnp.ndarray, mask: Optional[jnp.ndarray],
            identity) -> jnp.ndarray:
    if mask is None:
        return values
    return jnp.where(mask, values, jnp.asarray(identity, values.dtype))


# --- group-by aggregates ---------------------------------------------

def _in_range(segment_ids: jnp.ndarray, num_segments: int,
              mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Rows whose segment id is outside [0, num_segments) are dropped,
    not clipped — an orphan key (e.g. an order whose customer was not
    loaded) must not be credited to the last segment."""
    ok = (segment_ids >= 0) & (segment_ids < num_segments)
    return ok if mask is None else (ok & mask)


def _use_dense(num_segments: int, method: Optional[str]) -> bool:
    """Dense (broadcast-compare + column reduce) vs scatter dispatch.

    Below the crossover a dense pass beats the scatter-add: TPU
    scatters with millions of colliding updates serialize, while the
    dense form is one fused streaming pass (measured on Q01 @ SF1, 12
    groups: 52.6 ms scatter → ~2 ms dense). Above it the O(N*G) dense
    work loses; large-G queries (Q13's per-customer counts) keep the
    scatter. The crossover is measured per device kind
    (:mod:`netsdb_tpu.relational.tuning`), not frozen; ``method``
    ("dense"/"scatter") forces a strategy (tests, autotune probes).
    """
    if method is not None:
        return method == "dense"
    from netsdb_tpu.relational import planner

    return planner.segment_method(num_segments) == "dense"


def _dense_segment_reduce(v: jnp.ndarray, segment_ids: jnp.ndarray,
                          num_segments: int, identity, reduce_axis0):
    """(N,) → (G,) via broadcast-compare + column reduce; ``v`` must
    already carry ``identity`` in masked rows."""
    eq = segment_ids[:, None] == jnp.arange(num_segments,
                                            dtype=segment_ids.dtype)
    return reduce_axis0(jnp.where(eq, v[:, None],
                                  jnp.asarray(identity, v.dtype)))


def segment_sum(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int,
                mask: Optional[jnp.ndarray] = None,
                method: Optional[str] = None) -> jnp.ndarray:
    """Per-segment sum; masked and out-of-range rows contribute 0."""
    v = _masked(values, _in_range(segment_ids, num_segments, mask), 0)
    if _use_dense(num_segments, method):
        return _dense_segment_reduce(v, segment_ids, num_segments, 0,
                                     lambda m: m.sum(axis=0))
    ids = jnp.clip(segment_ids, 0, num_segments - 1)
    return jnp.zeros((num_segments,), v.dtype).at[ids].add(v)


def segment_count(segment_ids: jnp.ndarray, num_segments: int,
                  mask: Optional[jnp.ndarray] = None,
                  method: Optional[str] = None) -> jnp.ndarray:
    """Per-segment counts. Three strategies, chosen by the planner's
    measured thresholds when ``method`` is None: "dense" (tiny G),
    "grid" (mid-range G — one-hot int8 MXU matmuls, measured 0.67 ms vs
    6.9 ms scatter at G=50k/1M rows on v5e; linear in G/128, losing to
    scatter again near G~590k — `tuning` key ``count_grid_limit``),
    "scatter" (large G)."""
    if method is None:
        from netsdb_tpu.relational import planner

        method = planner.count_method(num_segments)
    if method == "grid":
        return count_grid(segment_ids, num_segments, mask)
    ones = jnp.ones(segment_ids.shape, jnp.int32)
    return segment_sum(ones, segment_ids, num_segments, mask, method)


def _grid_reduce(folded_ids: jnp.ndarray, key_space: int,
                 block: int, chunk: int) -> jnp.ndarray:
    """Shared core of the grid kernels: per-key occurrence counts of
    ``folded_ids`` (already masked: dropped rows hold -1) as one-hot
    int8 matmuls over an (H, block) key grid — the MXU accumulates, no
    scatter. ``folded_ids`` must already be padded to a multiple of
    ``chunk``. Returns the (H, block) int32 count grid."""
    H = (key_space + block - 1) // block
    hi, lo = folded_ids // block, folded_ids % block

    def step(acc, xs):
        h, l = xs
        m2 = (h[None, :] == jnp.arange(H, dtype=jnp.int32)[:, None]
              ).astype(jnp.int8)
        m1 = (l[:, None] == jnp.arange(block, dtype=jnp.int32)[None, :]
              ).astype(jnp.int8)
        return acc + jax.lax.dot(m2, m1,
                                 preferred_element_type=jnp.int32), None

    # carry init derives from the data so it inherits its varying manual
    # axes under shard_map (a plain zeros const is unvarying and fails
    # the scan carry typecheck there; no-op elsewhere)
    init = jnp.zeros((H, block), jnp.int32) + folded_ids.sum() * 0
    grid, _ = jax.lax.scan(step, init,
                           (hi.reshape(-1, chunk), lo.reshape(-1, chunk)))
    return grid


def _pad_to(x: jnp.ndarray, chunk: int, fill) -> jnp.ndarray:
    pad = (-x.shape[0]) % chunk
    if not pad:
        return x
    return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])


def count_grid(segment_ids: jnp.ndarray, num_segments: int,
               mask: Optional[jnp.ndarray] = None,
               block: int = 128, chunk: int = 4096) -> jnp.ndarray:
    """Exact per-segment counts on the grid path (`_grid_reduce`).
    Masked and out-of-range rows fold into the index (-1 matches no
    cell)."""
    a = _pad_to(segment_ids, chunk, -1)
    ok = (a >= 0) & (a < num_segments)
    if mask is not None:
        ok = ok & _pad_to(mask, chunk, False)
    am = jnp.where(ok, a, jnp.int32(-1))
    grid = _grid_reduce(am, num_segments, block, chunk)
    return grid.reshape(-1)[:num_segments]


def segment_min(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int,
                mask: Optional[jnp.ndarray] = None,
                method: Optional[str] = None) -> jnp.ndarray:
    """Per-segment min; empty segments hold +inf (f32) / max (i32)."""
    big = jnp.inf if values.dtype.kind == "f" else jnp.iinfo(values.dtype).max
    v = _masked(values, _in_range(segment_ids, num_segments, mask), big)
    if _use_dense(num_segments, method):
        return _dense_segment_reduce(v, segment_ids, num_segments, big,
                                     lambda m: m.min(axis=0))
    ids = jnp.clip(segment_ids, 0, num_segments - 1)
    init = jnp.full((num_segments,), big, values.dtype)
    return init.at[ids].min(v)


def segment_max(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int,
                mask: Optional[jnp.ndarray] = None,
                method: Optional[str] = None) -> jnp.ndarray:
    small = (-jnp.inf if values.dtype.kind == "f"
             else jnp.iinfo(values.dtype).min)
    v = _masked(values, _in_range(segment_ids, num_segments, mask), small)
    if _use_dense(num_segments, method):
        return _dense_segment_reduce(v, segment_ids, num_segments, small,
                                     lambda m: m.max(axis=0))
    ids = jnp.clip(segment_ids, 0, num_segments - 1)
    init = jnp.full((num_segments,), small, values.dtype)
    return init.at[ids].max(v)


def segment_mean(values: jnp.ndarray, segment_ids: jnp.ndarray,
                 num_segments: int,
                 mask: Optional[jnp.ndarray] = None,
                 method: Optional[str] = None) -> jnp.ndarray:
    """Per-segment mean; empty segments yield 0."""
    s = segment_sum(values.astype(jnp.float32), segment_ids, num_segments,
                    mask, method)
    c = segment_count(segment_ids, num_segments, mask, method)
    return s / jnp.maximum(c, 1).astype(jnp.float32)


def bincount_masked(values: jnp.ndarray, length: int,
                    mask: Optional[jnp.ndarray] = None,
                    method: Optional[str] = None) -> jnp.ndarray:
    """Histogram of small non-negative ints (Q13's count-of-counts)."""
    return segment_count(values, length, mask, method)


# --- joins ------------------------------------------------------------

def _sentineled(keys: jnp.ndarray, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    if mask is None:
        return keys
    return jnp.where(mask, keys, _I32_SENTINEL)


def pk_fk_join(pk_keys: jnp.ndarray, fk_keys: jnp.ndarray,
               pk_mask: Optional[jnp.ndarray] = None,
               fk_mask: Optional[jnp.ndarray] = None,
               key_space: Optional[int] = None,
               plan=None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Equi-join a unique-key (primary) side into a foreign-key side.

    Returns ``(gather_idx, match_mask)`` both shaped like ``fk_keys``:
    row i of the probe side matches row ``gather_idx[i]`` of the build
    side iff ``match_mask[i]``. Columns of the build side are then
    brought over with ``jnp.take(col, gather_idx)`` — the vectorized
    JoinMap probe.

    ``plan`` (a :class:`netsdb_tpu.relational.planner.JoinPlan`,
    produced from ingest-time column statistics) selects the physical
    strategy; it is the stats-driven replacement for the round-1
    caller-supplied ``key_space=`` (still accepted: it forces the LUT
    path, which the autotune probes and legacy callers use).

    LUT strategy — dense lookup table over [0, key_space): one scatter
    to build, one gather to probe. Measured ~19x faster than
    sort+binary-search at SF-1 TPC-H scale (49 ms vs 947 ms for 6M
    probes into 1.5M build rows) — TPU binary search serializes,
    gathers stream. Sort strategy — argsort +
    ``searchsorted(method="sort")``; wins when the key space is sparse
    enough that the LUT is mostly padding (TPU's while-loop "scan"
    searchsorted is another ~8x slower, so "sort" here always means the
    vectorized sort-based probe).
    """
    if plan is not None:
        key_space = plan.key_space if plan.strategy == "lut" else None
    if key_space is not None:
        p = pk_keys.shape[0]
        valid_pk = (pk_keys >= 0) & (pk_keys < key_space)
        if pk_mask is not None:
            valid_pk = valid_pk & pk_mask
        # invalid build rows route to an extra trash slot
        slot = jnp.where(valid_pk, pk_keys, jnp.int32(key_space))
        lut = jnp.full((key_space + 1,), jnp.int32(-1)).at[slot].set(
            jnp.arange(p, dtype=jnp.int32), mode="drop")
        fk_in = (fk_keys >= 0) & (fk_keys < key_space)
        pos = jnp.take(lut, jnp.clip(fk_keys, 0, key_space - 1))
        hit = fk_in & (pos >= 0)
        if fk_mask is not None:
            hit = hit & fk_mask
        return jnp.maximum(pos, 0), hit
    pk = _sentineled(pk_keys, pk_mask)
    order = jnp.argsort(pk)
    pk_sorted = pk[order]
    pos = jnp.searchsorted(pk_sorted, fk_keys, method="sort")
    pos_c = jnp.clip(pos, 0, pk.shape[0] - 1)
    hit = pk_sorted[pos_c] == fk_keys
    if fk_mask is not None:
        hit = hit & fk_mask
    # masked build rows carry the sentinel key; a probe key equal to the
    # sentinel would false-match, so exclude it explicitly
    hit = hit & (fk_keys != _I32_SENTINEL)
    return order[pos_c], hit


def member(build_keys: jnp.ndarray, probe_keys: jnp.ndarray,
           build_mask: Optional[jnp.ndarray] = None,
           probe_mask: Optional[jnp.ndarray] = None,
           key_space: Optional[int] = None,
           plan=None) -> jnp.ndarray:
    """Semi-join membership: for each probe row, does any valid build
    row share its key? (Q04 EXISTS, Q22 NOT EXISTS.) Build keys need
    not be unique."""
    _, hit = pk_fk_join(
        # duplicates are fine for membership: any representative row
        # (leftmost via searchsorted, last-writer via the LUT) works
        build_keys, probe_keys, build_mask, probe_mask, key_space, plan)
    return hit


def any_by_key(keys: jnp.ndarray, flag: jnp.ndarray, key_space: int,
               block: int = 128, chunk: int = 4096) -> jnp.ndarray:
    """Per row: does ANY row sharing its key have ``flag`` set?
    (Self-semi-join — reddit label propagation,
    ref ``src/reddit/headers/RedditCommentLabelJoin.h``.)

    Scatter-free formulation, measured on v5e at 1M rows / 50k keys
    (2026-07, netsdb bench harness):

    - the naive scatter-max + flat gather costs 13.6 ms — colliding
      scatter updates serialize on TPU (see ``_use_dense``), and a flat
      1M-row gather from a 50k table alone costs 6.7 ms;
    - this kernel reshapes the key space into an (H, block) grid.
      REDUCE: flagged keys become (hi, lo) one-hot int8 matrices whose
      product accumulates the mark grid on the MXU (~0.7 ms — flag
      folded into the index, so unflagged rows match no grid cell).
      GATHER: per-row lookup = a row gather on ``hi`` (vectorized,
      lane-wide) + a one-hot lane select on ``lo`` (~2.7 ms vs 6.7 for
      the flat gather).
    - total 3.45 ms = 3.9× over the scatter form. ``block=128`` (one
      lane register) measured best; larger blocks only move cost from
      rows to lanes.

    Out-of-range keys return 0 and contribute nothing (orphan-key rule
    of `_in_range`). Rows are padded to ``chunk`` internally.
    """
    n = keys.shape[0]
    a = _pad_to(keys, chunk, -1)
    f = _pad_to(flag, chunk, 0)
    # flag folds into the index: unflagged rows match no grid cell
    am = jnp.where((f != 0) & (a >= 0) & (a < key_space), a, jnp.int32(-1))
    grid = _grid_reduce(am, key_space, block, chunk)
    gridb = (grid > 0).astype(jnp.int8)  # marks, not counts
    # gather phase chunked too: the (rows, block) select intermediate
    # must stay VMEM-sized — unchunked it is N*block bytes (25 GB at
    # 50M rows, an HBM OOM)
    kin = (a >= 0) & (a < key_space)
    kc = jnp.clip(a, 0, key_space - 1)
    gchunk = 65536
    gpad = (-kc.shape[0]) % gchunk
    if gpad:
        kc = jnp.concatenate([kc, jnp.zeros((gpad,), jnp.int32)])
        kin = jnp.concatenate([kin, jnp.zeros((gpad,), jnp.bool_)])

    def gstep(carry, xs):
        k, k_ok = xs
        rows = jnp.take(gridb, k // block, axis=0)
        oneh = ((k % block)[:, None]
                == jnp.arange(block, dtype=jnp.int32)[None, :])
        got = jnp.where(oneh, rows, 0).sum(axis=1)
        return carry, ((got > 0) & k_ok).astype(jnp.int32)

    _, out = jax.lax.scan(gstep, jnp.zeros((), jnp.int32) + am.sum() * 0,
                          (kc.reshape(-1, gchunk),
                           kin.reshape(-1, gchunk)))
    return out.reshape(-1)[:n]


def top_k_masked(scores: jnp.ndarray, k: int,
                 mask: Optional[jnp.ndarray] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Indices of the k largest valid scores. Returns ``(idx, valid)``;
    ``valid[j]`` is False when fewer than j+1 rows were valid."""
    neg = jnp.asarray(-jnp.inf, jnp.float32)
    s = scores.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, neg)
    vals, idx = jax.lax.top_k(s, k)
    return idx, vals > neg
