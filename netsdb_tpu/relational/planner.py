"""Statistics-driven physical planning for the columnar engine.

The reference's ``TCAPAnalyzer`` greedily picks sources and stage cuts
from runtime set statistics and re-plans after every stage
(``src/queryPlanning/headers/TCAPAnalyzer.h:20-40``,
``src/serverFunctionalities/source/QuerySchedulerServer.cc:1332-1420``).
On a single-controller JAX stack the stage-cutting half is absorbed by
XLA (stages = jit boundaries), but three physical choices remain that
XLA cannot make because they change the *algorithm*, not the schedule:

- **LUT vs sort equi-join** (:func:`plan_join`) — a dense lookup table
  is ~19x faster when keys are dense surrogate ints, but is mostly
  padding (and eventually HBM-prohibitive) for sparse key ranges;
- **dense vs scatter segment reduction** (:func:`segment_method`) —
  broadcast-compare wins for small group counts where TPU scatter-adds
  serialize, loses O(N*G) above the crossover;
- **broadcast vs repartition distribution** (:func:`plan_distribution`)
  — replicate the small join side to every shard, or all-to-all both
  sides by key hash.

Each chooser reads column statistics collected at ingest
(:mod:`netsdb_tpu.relational.stats`) and thresholds measured per device
kind (:mod:`netsdb_tpu.relational.tuning`), so the decisions follow the
data and the hardware instead of the round-1 hand-tuned call sites.

A :class:`JoinPlan` is a hashable NamedTuple so it rides through
``jax.jit`` static arguments — the physical choice is fixed at trace
time, exactly like the reference fixing a stage's algorithm before
shipping it to workers.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

from netsdb_tpu.relational import tuning
from netsdb_tpu.relational.stats import ColumnStats, column_stats
from netsdb_tpu.relational.table import ColumnTable


class JoinPlan(NamedTuple):
    """Physical equi-join choice.

    ``key_space`` is always the stats-derived dense bound (segment
    reductions keyed on the same column reuse it); ``strategy`` selects
    the join implementation: ``"lut"`` (scatter build / gather probe)
    or ``"sort"`` (argsort + searchsorted).
    """

    strategy: str
    key_space: int

    @property
    def is_lut(self) -> bool:
        return self.strategy == "lut"


def plan_join_from_stats(build: ColumnStats,
                         n_probe: int,
                         kind: Optional[str] = None) -> JoinPlan:
    """Cost-model core, exposed for tests: LUT wins while the key space
    is within ``join_lut_factor`` of the touched rows AND the LUT fits
    the byte cap; otherwise sort."""
    ks = build.key_space
    factor = tuning.get("join_lut_factor", kind)
    max_bytes = tuning.get("join_lut_max_bytes", kind)
    touched = build.n_rows + n_probe
    if ks <= factor * max(touched, 1) and ks * 4 <= max_bytes:
        return JoinPlan("lut", ks)
    return JoinPlan("sort", ks)


def plan_join(build: ColumnTable, build_col: str,
              probe: ColumnTable, probe_col: Optional[str] = None,
              kind: Optional[str] = None) -> JoinPlan:
    """Choose the physical join of ``build[build_col]`` (unique or
    representative keys) probed by ``probe[probe_col]``.

    The plan's ``key_space`` bounds BOTH columns (with ``probe_col``
    given), so a query reusing it as a segment-reduction cardinality
    over the foreign-key column stays in range even when the data has
    orphan foreign keys.
    """
    bs = column_stats(build, build_col)
    ks = bs.key_space
    if probe_col is not None:
        ks = max(ks, column_stats(probe, probe_col).key_space)
    merged = ColumnStats(bs.n_rows, bs.min_val, max(bs.max_val, ks - 1),
                         bs.n_distinct)
    return plan_join_from_stats(merged, probe.num_rows, kind)


def segment_method(num_segments: int, kind: Optional[str] = None) -> str:
    """``"dense"`` (broadcast-compare + column reduce) or ``"scatter"``
    (indexed add) for a ``num_segments``-group reduction."""
    limit = tuning.get("segment_dense_limit", kind)
    return "dense" if num_segments <= limit else "scatter"


def count_method(num_segments: int, kind: Optional[str] = None) -> str:
    """Strategy for a pure COUNT reduction, which has a third option:
    the grid one-hot int8 MXU formulation (`kernels.count_grid`) — exact
    for counts, and measured faster than the scatter-add through
    mid-range cardinalities (`count_grid_limit`, autotuned)."""
    if num_segments <= tuning.get("segment_dense_limit", kind):
        return "dense"
    if num_segments <= tuning.get("count_grid_limit", kind):
        return "grid"
    return "scatter"


class DistPlan(NamedTuple):
    """Distributed join-side placement: replicate the build side to all
    shards (``"broadcast"``) or hash-repartition both sides
    (``"partition"``)."""

    strategy: str


# Broadcast while the replicated build side stays under this fraction of
# per-device HBM (the reference's analogue: BroadcastJoinBuildHTJobStage
# is chosen for sides that fit one SharedHashSet,
# src/serverFunctionalities/source/HermesExecutionServer.cc:172-369).
_BROADCAST_HBM_FRACTION = 0.10


def device_memory_bytes() -> int:
    """Per-device memory for distribution planning: the accelerator's
    own ``bytes_limit``. The CPU backend reports none, so tests plan
    against the cpu tuning table's host share; an accelerator that
    reports none is an error rather than an assumed HBM size."""
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return int(tuning.get("device_hbm_bytes", "cpu"))
    stats = dev.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit']; "
            f"cannot size broadcast-vs-repartition plans")
    return int(stats["bytes_limit"])


def plan_distribution(build_bytes: int, n_devices: int,
                      device_bytes: Optional[int] = None,
                      ) -> DistPlan:
    """Broadcast-vs-repartition: replicating costs ``build_bytes`` on
    EVERY device plus one all-gather; repartitioning moves each row once
    but needs the all-to-all machinery. Broadcast wins while the build
    side is small relative to HBM (dimension tables); repartition when
    both sides are fact-scale."""
    if device_bytes is None:
        device_bytes = device_memory_bytes()
    if build_bytes <= _BROADCAST_HBM_FRACTION * device_bytes:
        return DistPlan("broadcast")
    return DistPlan("partition")
