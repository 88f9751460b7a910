"""Statistics-driven physical planning (VERDICT round-1 item 2).

The engine must choose LUT-vs-sort joins and dense-vs-scatter segment
reductions from ingest-time column statistics — and both strategies
must agree bit-for-bit so the choice is purely physical
(reference analogue: TCAPAnalyzer's cost-based source/algorithm picks,
``src/queryPlanning/headers/TCAPAnalyzer.h:20-40``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu.relational import kernels as K
from netsdb_tpu.relational import planner as P
from netsdb_tpu.relational import tuning
from netsdb_tpu.relational.stats import (analyze_array, analyze_table,
                                         column_stats, key_space)
from netsdb_tpu.relational.table import ColumnTable


def _table(**cols):
    return ColumnTable({k: jnp.asarray(np.asarray(v)) for k, v in
                        cols.items()})


# ------------------------------------------------------------- stats
def test_column_stats_basic():
    s = analyze_array(np.array([3, 1, 4, 1, 5], np.int32))
    assert (s.n_rows, s.min_val, s.max_val) == (5, 1, 5)
    assert s.key_space == 6
    # distinct count is lazy (an O(N log N) sort nothing at ingest needs)
    assert s.n_distinct == -1
    with pytest.raises(ValueError):
        _ = s.density
    s2 = analyze_array(np.array([3, 1, 4, 1, 5], np.int32), distinct=True)
    assert s2.n_distinct == 4
    assert s2.density == pytest.approx(4 / 6)


def test_column_stats_cached_on_table():
    t = _table(k=np.arange(10, dtype=np.int32))
    s1 = column_stats(t, "k")
    s2 = column_stats(t, "k")
    assert s1 is s2
    assert key_space(t, "k") == 10


def test_analyze_table_skips_floats():
    t = _table(k=np.arange(4, dtype=np.int32),
               v=np.ones(4, np.float32))
    stats = analyze_table(t)
    assert "k" in stats and "v" not in stats


# ----------------------------------------------------------- planning
def test_dense_keys_pick_lut():
    build = _table(k=np.arange(1000, dtype=np.int32))
    probe = _table(fk=np.random.default_rng(0).integers(
        0, 1000, 5000).astype(np.int32))
    jp = P.plan_join(build, "k", probe, "fk")
    assert jp.strategy == "lut"
    assert jp.key_space == 1000


def test_sparse_keys_pick_sort():
    # 1000 rows spread over a 500M key space: LUT would be ~2GB of
    # padding — the cost model must fall back to sort.
    keys = np.linspace(0, 500_000_000, 1000).astype(np.int32)
    build = _table(k=keys)
    probe = _table(fk=keys[:500])
    jp = P.plan_join(build, "k", probe, "fk")
    assert jp.strategy == "sort"


def test_crossover_tracks_measured_factor():
    """The choice flips exactly at the tuned join_lut_factor boundary."""
    from netsdb_tpu.relational.stats import ColumnStats

    kind = tuning.device_kind()
    factor = tuning.get("join_lut_factor", kind)
    n_build, n_probe = 1000, 1000
    touched = n_build + n_probe
    below = ColumnStats(n_build, 0, int(factor * touched) - 1, n_build)
    above = ColumnStats(n_build, 0, int(factor * touched) + touched,
                        n_build)
    assert P.plan_join_from_stats(below, n_probe, kind).strategy == "lut"
    assert P.plan_join_from_stats(above, n_probe, kind).strategy == "sort"


def test_lut_byte_cap_forces_sort():
    from netsdb_tpu.relational.stats import ColumnStats

    kind = tuning.device_kind()
    cap = int(tuning.get("join_lut_max_bytes", kind))
    huge = ColumnStats(10**9, 0, cap // 4 + 10, 10**9)  # dense but giant
    assert P.plan_join_from_stats(huge, 10**9, kind).strategy == "sort"


def test_join_key_space_covers_probe_column():
    # orphan FK beyond the build max: plan must still bound it so the
    # key space can serve as a segment cardinality over the FK column
    build = _table(k=np.arange(10, dtype=np.int32))
    probe = _table(fk=np.array([3, 99], np.int32))
    jp = P.plan_join(build, "k", probe, "fk")
    assert jp.key_space == 100


# ----------------------------------- strategy equivalence (both forced)
def test_join_strategies_agree():
    rng = np.random.default_rng(7)
    pk = jnp.asarray(rng.permutation(4000)[:1500].astype(np.int32))
    fk = jnp.asarray(rng.integers(0, 4200, 10_000).astype(np.int32))
    pk_mask = jnp.asarray(rng.random(1500) > 0.3)
    ks = 4200
    il, hl = K.pk_fk_join(pk, fk, pk_mask, plan=P.JoinPlan("lut", ks))
    isrt, hs = K.pk_fk_join(pk, fk, pk_mask, plan=P.JoinPlan("sort", ks))
    np.testing.assert_array_equal(np.asarray(hl), np.asarray(hs))
    # gather rows must agree wherever there is a hit (pk is unique)
    np.testing.assert_array_equal(np.asarray(il)[np.asarray(hl)],
                                  np.asarray(isrt)[np.asarray(hs)])


def test_segment_methods_agree():
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.standard_normal(5000).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, 48, 5000).astype(np.int32))
    mask = jnp.asarray(rng.random(5000) > 0.5)
    for fn in (K.segment_sum, K.segment_min, K.segment_max):
        d = np.asarray(fn(v, seg, 48, mask, method="dense"))
        s = np.asarray(fn(v, seg, 48, mask, method="scatter"))
        # sums differ only by accumulation order between strategies
        np.testing.assert_allclose(d, s, rtol=1e-4, atol=1e-5)


def test_segment_method_auto_uses_tuned_limit():
    limit = int(tuning.get("segment_dense_limit"))
    assert P.segment_method(limit) == "dense"
    assert P.segment_method(limit + 1) == "scatter"


def test_tuning_override_and_device_table():
    tuning.clear_overrides()
    kind = tuning.device_kind()
    base = tuning.get("segment_dense_limit", kind)
    tuning.set_override("segment_dense_limit", 7, kind)
    assert tuning.get("segment_dense_limit", kind) == 7
    tuning.clear_overrides()
    assert tuning.get("segment_dense_limit", kind) == base
    # an unknown device kind is an error, never another chip's numbers
    with pytest.raises(LookupError, match="weird-accelerator"):
        tuning.get("join_lut_factor", "weird-accelerator")


# ------------------------------------------------- distribution choice
def test_distribution_broadcast_vs_partition():
    assert P.plan_distribution(10 * 2**20, 8).strategy == "broadcast"
    assert P.plan_distribution(4 * 2**30, 8).strategy == "partition"


# ------------------------------------- queries run on planner choices
def test_queries_agree_under_forced_sort(monkeypatch):
    """Force the planner to 'sort' everywhere and re-run the columnar
    suite against the row-engine oracle — results must not change."""
    from netsdb_tpu.relational.queries import (COLUMNAR_QUERIES,
                                               tables_from_rows)
    from netsdb_tpu.workloads import tpch

    data = tpch.generate(scale=2, seed=11)
    tables = tables_from_rows(data)
    baseline = {n: q(tables) for n, q in COLUMNAR_QUERIES.items()}

    monkeypatch.setattr(
        P, "plan_join_from_stats",
        lambda bs, n_probe, kind=None: P.JoinPlan("sort", bs.key_space))
    t2 = tables_from_rows(data)
    for name, q in COLUMNAR_QUERIES.items():
        assert q(t2) == baseline[name], name


def test_stats_never_alias_across_equal_schema_tables():
    """Regression (r3 review): jax reuses output treedefs across
    equal-schema tables, so a stats cache keyed on shared schema
    objects would let one table's key_space apply to another's data.
    Stats must be per-instance."""
    import jax
    import jax.numpy as jnp

    from netsdb_tpu.relational.table import ColumnTable

    f = jax.jit(lambda t: t.filter(t["k"] >= 0))
    a = f(ColumnTable({"k": jnp.arange(10, dtype=jnp.int32)}))
    b = f(ColumnTable({"k": jnp.arange(0, 9010, 10, dtype=jnp.int32)}))
    assert key_space(a, "k") == 10
    assert key_space(b, "k") == 9001  # NOT a's 10


def test_inject_stats_seeds_trace_visible_cache():
    import jax.numpy as jnp

    from netsdb_tpu.relational.stats import ColumnStats, inject_stats
    from netsdb_tpu.relational.table import ColumnTable

    t = ColumnTable({"k": jnp.arange(5, dtype=jnp.int32)})
    inject_stats(t, {"k": ColumnStats(5, 0, 99)})
    assert key_space(t, "k") == 100  # injected, not recomputed
