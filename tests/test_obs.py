"""Unit tests for the observability subsystem (netsdb_tpu/obs/):
registry instruments, bounded histograms, query traces + ring, and the
bounded StageTimer.

The serve-side integration (GET_TRACE over the wire, COLLECT_STATS
"metrics", leader/follower merge) lives in tests/test_obs_serve.py.
"""

import threading
import time

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from netsdb_tpu.obs.trace import QueryTrace, TraceRing
from netsdb_tpu.utils.profiling import StageTimer


# ----------------------------------------------------------- instruments
def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge()
    g.set(2.5)
    g.add(0.5)
    assert g.value == 3.0


def test_histogram_bounded_with_exact_totals():
    h = Histogram(max_samples=64)
    for i in range(1000):
        h.observe(float(i))
    # exact aggregates survive the bound...
    assert h.count == 1000
    assert h.total == sum(range(1000))
    s = h.summary()
    assert s["min"] == 0.0 and s["max"] == 999.0
    assert s["mean"] == pytest.approx(499.5)
    # ...while per-sample state stays bounded (the ring holds the most
    # RECENT window, so quantiles track current behavior)
    assert s["samples"] == 64
    assert h.sample_count == 64
    assert s["p50"] >= 900  # recent window = the last 64 values
    assert h.quantile(0.0) is not None


def test_histogram_quantiles_small():
    h = Histogram(max_samples=128)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 4.0
    assert h.summary()["p50"] in (2.0, 3.0)


def test_registry_get_or_create_and_snapshot():
    r = MetricsRegistry()
    r.counter("x.hits").inc(2)
    assert r.counter("x.hits") is r.counter("x.hits")
    r.gauge("x.live").set(7)
    r.histogram("x.lat").observe(0.5)
    r.register_collector("sub", lambda: {"a": 1})
    snap = r.snapshot()
    assert snap["counters"]["x.hits"] == 2
    assert snap["gauges"]["x.live"] == 7.0
    assert snap["histograms"]["x.lat"]["count"] == 1
    assert snap["sub"] == {"a": 1}


def test_registry_collector_errors_are_typed_not_fatal():
    r = MetricsRegistry()

    def boom():
        raise RuntimeError("nope")

    r.register_collector("bad", boom)
    snap = r.snapshot()
    assert "RuntimeError" in snap["bad"]["error"]


def test_process_registry_absorbs_existing_stat_surfaces():
    """compile_stats / staging leak registry / GLOBAL_TIMER report into
    the ONE process registry under their own sections, same numbers as
    their original accessors."""
    from netsdb_tpu.plan import staging
    from netsdb_tpu.plan.executor import compile_stats

    snap = obs.REGISTRY.snapshot()
    assert snap["compile"] == compile_stats()
    assert snap["staging"]["active_stagers"] == staging.active_count()
    assert "stages" in snap


# ----------------------------------------------------------------- traces
def test_trace_spans_nesting_counters_and_ring():
    ring = TraceRing(capacity=8)
    with obs.trace("q-abc", origin="client", ring=ring) as tr:
        assert obs.current_trace() is tr
        with obs.span("outer", "x"):
            time.sleep(0.002)
            with obs.span("inner", "y") as sp:
                sp.counters["n"] = 3
        obs.add("bytes", 100)
        obs.add("bytes", 28)
    assert obs.current_trace() is None
    (prof,) = ring.last()
    assert prof["qid"] == "q-abc" and prof["origin"] == "client"
    assert prof["total_s"] >= 0.002
    names = {s["name"]: s for s in prof["spans"]}
    assert names["outer"]["depth"] == 0 and names["inner"]["depth"] == 1
    assert names["inner"]["counters"] == {"n": 3}
    assert names["outer"]["duration_s"] >= names["inner"]["duration_s"]
    assert prof["counters"] == {"bytes": 128}


def test_span_and_add_are_noops_without_a_trace():
    with obs.span("free", "x") as sp:
        assert sp is None
    obs.add("nothing")  # must not raise


def test_nested_trace_joins_outer():
    ring = TraceRing()
    with obs.trace("outer-q", ring=ring) as tr:
        with obs.trace("inner-q", ring=ring) as inner:
            assert inner is None  # no shadowing
            with obs.span("work", "x"):
                pass
        assert obs.current_trace() is tr
    profs = ring.last()
    assert len(profs) == 1 and profs[0]["qid"] == "outer-q"
    assert any(s["name"] == "work" for s in profs[0]["spans"])


def test_trace_ring_capacity_and_find():
    ring = TraceRing(capacity=3)
    for i in range(7):
        ring.push({"qid": f"q{i}"})
    assert len(ring) == 3
    assert [p["qid"] for p in ring.last()] == ["q4", "q5", "q6"]
    assert [p["qid"] for p in ring.last(2)] == ["q5", "q6"]
    assert ring.find("q6") and not ring.find("q0")


def test_disable_switch_stops_trace_creation():
    ring = TraceRing()
    obs.set_enabled(False)
    try:
        with obs.trace("q-off", ring=ring) as tr:
            assert tr is None
            with obs.span("x") as sp:
                assert sp is None
    finally:
        obs.set_enabled(True)
    assert len(ring) == 0


def test_trace_record_and_cross_thread_counters():
    tr = QueryTrace("qt", "server")
    tr.record("decode", 0.005, "serve", start_s=0.0)

    def worker():
        tr.add("stage.chunks", 2)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    prof = tr.finish()
    assert prof["spans"][0]["name"] == "decode"
    assert prof["spans"][0]["duration_s"] == pytest.approx(0.005)
    assert prof["counters"]["stage.chunks"] == 2


# ------------------------------------------------------ bounded StageTimer
def test_stage_timer_bounded_samples_exact_count():
    t = StageTimer(max_samples=16)
    for _ in range(200):
        with t.span("hot"):
            pass
    s = t.summary()
    # exact aggregates, bounded retention — the long-lived-daemon fix
    assert s["hot"]["count"] == 200
    assert t.sample_count("hot") <= 16
    assert s["hot"]["total_s"] >= 0
    assert {"count", "total_s", "mean_s", "max_s"} <= set(s["hot"])
    assert "p99_s" in s["hot"]
    t.reset()
    assert t.summary() == {}


def test_stage_timer_summary_shape_backward_compatible():
    t = StageTimer()
    with t.span("plan"):
        time.sleep(0.01)
    with t.span("plan"):
        time.sleep(0.01)
    s = t.summary()
    assert s["plan"]["count"] == 2
    assert s["plan"]["total_s"] >= 0.02
    assert s["plan"]["mean_s"] == pytest.approx(
        s["plan"]["total_s"] / 2)


# ------------------------------------------------- staging/devcache ticks
def test_staged_stream_reports_into_active_trace(tmp_path):
    """A staged fold under a trace accounts chunks + bytes; the same
    stream untraced pays only the one-check fast path."""
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.relational.outofcore import PagedColumns
    from netsdb_tpu.storage.paged import PagedTensorStore

    cfg = Configuration(root_dir=str(tmp_path))
    store = PagedTensorStore(cfg, pool_bytes=8 << 20)
    try:
        rng = np.random.default_rng(0)
        pc = PagedColumns.ingest(
            store, "t", {"k": rng.integers(0, 8, 5000, dtype=np.int32),
                         "v": rng.standard_normal(5000).astype(np.float32)},
            row_block=1024)
        ring = TraceRing()
        import contextlib

        with obs.trace("q-staged", ring=ring):
            with contextlib.closing(pc.stream()) as chunks:
                n = sum(1 for _ in chunks)
        (prof,) = ring.last()
        assert prof["counters"]["stage.chunks"] == n
        assert prof["counters"]["stage.bytes"] > 0
    finally:
        store.close()
