"""EXPLAIN ANALYZE: per-operator plan profiling (ISSUE 7 tentpole 1).

Acceptance shape: EXECUTE(explain=True) on a multi-node plan returns a
per-operator tree whose node times sum to within the profile's
executor span, with devcache/compile counters per node; the tree is
SHAPE-IDENTICAL between a cold run and a devcache-warm re-run (cache
counters differing), survives the mirror hop (leader + follower
sections under one qid), and rides GET_TRACE.
"""

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
from netsdb_tpu.obs.operators import (
    OperatorLedger,
    OperatorRecorder,
    render_tree,
)
from netsdb_tpu.relational import dag as rdag
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
from netsdb_tpu.serve.server import ServeController


def _remote(addr, **kw):
    kw.setdefault("retry", RetryPolicy(max_attempts=1))
    return RemoteClient(addr, **kw)


def _li_cols(n, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "l_shipdate": rng.integers(19940101, 19950101, n, dtype=np.int32),
        "l_discount": np.full(n, 0.06, np.float32),
        "l_quantity": np.full(n, 10.0, np.float32),
        "l_extendedprice": rng.uniform(1000, 2000, n).astype(np.float32),
    }


def _paged_client(tmp_path, n=20_000):
    c = Client(Configuration(root_dir=str(tmp_path / "ex"),
                             page_size_bytes=1 << 16,
                             page_pool_bytes=1 << 20))
    c.create_database("d")
    c.create_set("d", "lineitem", type_name="table", storage="paged")
    c.send_table("d", "lineitem", ColumnTable(_li_cols(n), {}))
    return c


def _shape(tree):
    return [(n["id"], n["kind"], n["label"], tuple(n["inputs"]))
            for n in tree["nodes"]]


# ------------------------------------------------------- local client
def test_local_explain_returns_tree_with_per_node_counters(tmp_path):
    # cold by construction: the compiled-program cache is the process's,
    # and under ``-n 6 --dist loadfile`` whichever file ran on this
    # worker before may have compiled this very fold (the same key),
    # leaving the "cold" run here without a trace to count
    from netsdb_tpu.plan import executor
    executor.clear_compiled_cache()
    c = _paged_client(tmp_path)
    results, tree = c.execute_computations(rdag.q06_sink("d"),
                                           job_name="q06", explain=True)
    assert results  # normal results still come back
    kinds = [n["kind"] for n in tree["nodes"]]
    assert "Scan" in kinds and "Apply" in kinds and "Write" in kinds
    assert tree["mode"] == "streamed"
    apply_ = next(n for n in tree["nodes"] if n["kind"] == "Apply")
    # the fold-bearing node carries the work: chunks, a device
    # estimate, staged bytes and (cold) a devcache miss + a compile
    assert apply_["counters"]["chunks"] >= 1
    assert apply_["device_est_s"] > 0
    assert apply_["counters"]["stage.chunks"] >= 1
    assert apply_["counters"]["stage.bytes"] > 0
    assert apply_["counters"]["devcache.misses"] >= 1
    assert apply_["counters"]["traces"] >= 1
    assert apply_["rows_in"] == 20_000
    scan = next(n for n in tree["nodes"] if n["kind"] == "Scan")
    assert scan["label"] == "d:lineitem"
    assert scan["rows_out"] == 20_000


def test_explain_shape_stable_cold_vs_warm_counters_differ(tmp_path):
    """The satellite stability contract: identical tree shape across a
    cold run and a devcache-warm re-run of the same plan — only the
    cache counters move."""
    c = _paged_client(tmp_path)
    _, cold = c.execute_computations(rdag.q06_sink("d"),
                                     job_name="q06", explain=True)
    _, warm = c.execute_computations(rdag.q06_sink("d"),
                                     job_name="q06", explain=True)
    assert _shape(cold) == _shape(warm)
    cold_apply = next(n for n in cold["nodes"] if n["kind"] == "Apply")
    warm_apply = next(n for n in warm["nodes"] if n["kind"] == "Apply")
    assert cold_apply["counters"].get("devcache.misses", 0) >= 1
    assert warm_apply["counters"].get("devcache.hits", 0) >= 1
    assert warm_apply["counters"].get("devcache.misses", 0) == 0
    # warm run rode the cached device run: zero staged chunks
    assert warm_apply["counters"].get("stage.chunks", 0) == 0
    assert warm_apply["counters"].get("stage.cached_runs", 0) >= 1


def test_node_times_sum_to_within_the_executor_span(tmp_path):
    """The acceptance invariant: nodes evaluate sequentially in the
    topo loop, so their inclusive walls sum to within the executor
    span of the same query's trace profile."""
    c = _paged_client(tmp_path)
    with obs.trace(origin="local") as tr:
        c.execute_computations(rdag.q06_sink("d"), job_name="q06")
    prof = tr.profile()
    tree = prof.get("operators")
    assert tree, "a traced execution must record the operator tree"
    node_sum = sum(n["wall_s"] for n in tree["nodes"])
    exec_spans = [s for s in prof["spans"]
                  if s["name"] in ("executor.streamed",
                                   "executor.eager",
                                   "executor.whole_plan_jit")]
    assert exec_spans
    span_total = sum(s["duration_s"] for s in exec_spans)
    assert node_sum <= span_total * 1.05, (node_sum, span_total)
    # and the tree accounts for the bulk of the executor span (the
    # loop does little besides dispatching nodes)
    assert node_sum >= span_total * 0.5, (node_sum, span_total)


def test_eager_host_object_plan_records_tree(tmp_path):
    """The eager interpreter path (host-object Filter/Aggregate)
    records per-node walls too."""
    from netsdb_tpu.plan.computations import (Aggregate, Filter,
                                              ScanSet, WriteSet)

    c = Client(Configuration(root_dir=str(tmp_path / "eager")))
    c.create_database("o")
    c.create_set("o", "recs")
    c.send_data("o", "recs", [{"k": i % 3, "v": i} for i in range(50)])
    scan = ScanSet("o", "recs")
    flt = Filter(scan, lambda r: r["v"] % 2 == 0, label="even")
    agg = Aggregate(flt, key=lambda r: r["k"], value=lambda r: r["v"],
                    combine=lambda a, b: a + b, label="sum_by_k")
    sink = WriteSet(agg, "o", "out")
    _, tree = c.execute_computations(sink, job_name="eager-job",
                                     explain=True)
    assert tree["mode"] == "eager"
    labels = {n["label"] for n in tree["nodes"]}
    assert {"even", "sum_by_k"} <= labels
    flt_node = next(n for n in tree["nodes"] if n["label"] == "even")
    assert flt_node["rows_in"] == 50
    assert flt_node["rows_out"] == 25


def test_whole_plan_jit_marks_fused(tmp_path):
    """A pure-resident tensor job fuses into one XLA program — the
    tree keeps the plan's shape with nodes marked fused and a
    synthetic root carrying the program's time."""
    from netsdb_tpu.core.blocked import BlockedTensor
    from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet

    c = Client(Configuration(root_dir=str(tmp_path / "fused")))
    c.create_database("t")
    c.create_set("t", "x")
    c.send_matrix("t", "x", np.ones((16, 16), np.float32), (8, 8))
    scan = ScanSet("t", "x")
    ap = Apply(scan, lambda t: t.with_data(t.data * 2.0),
               label="double")
    sink = WriteSet(ap, "t", "y")
    _, tree = c.execute_computations(sink, job_name="fused-job",
                                     explain=True)
    assert tree["mode"] == "whole_plan_jit"
    fused = [n for n in tree["nodes"] if n.get("fused")]
    assert len(fused) == 3  # scan, apply, write — shape preserved
    root = next(n for n in tree["nodes"]
                if n["kind"] == "WholePlanJit")
    assert root["wall_s"] > 0


def test_render_tree_classic_explain_output(tmp_path):
    c = _paged_client(tmp_path, n=2_000)
    _, tree = c.execute_computations(rdag.q06_sink("d"),
                                     job_name="q06", explain=True)
    text = render_tree(tree)
    assert "EXPLAIN ANALYZE" in text
    assert "Scan[d:lineitem]" in text
    assert "%" in text and "wall=" in text
    # sinks render at the root, scans indented below
    lines = text.splitlines()
    write_at = next(i for i, l in enumerate(lines) if "Write[" in l)
    scan_at = next(i for i, l in enumerate(lines) if "Scan[" in l)
    assert write_at < scan_at
    assert lines[scan_at].startswith("    ")


def test_operator_ledger_aggregates_and_bounds():
    led = OperatorLedger(max_keys=2)
    node = {"wall_s": 0.5, "device_est_s": 0.1,
            "counters": {"chunks": 3}}
    led.add("j1", "Apply:a", node)
    led.add("j1", "Apply:a", node)
    led.add("j1", "Apply:b", node)   # second key fits
    led.add("j2", "Apply:c", node)   # beyond max_keys -> overflow
    snap = led.snapshot()
    assert snap["j1"]["Apply:a"]["count"] == 2
    assert snap["j1"]["Apply:a"]["wall_s"] == pytest.approx(1.0)
    assert snap["j1"]["Apply:a"]["chunks"] == 6
    assert "overflow" in snap and "*" in snap["overflow"]


def test_recorder_noop_without_trace_or_capture(tmp_path):
    """obs_explain gates TRACED recording; an untraced, uncaptured
    execution records nothing and op_add is a cheap no-op."""
    c = _paged_client(tmp_path, n=2_000)
    before = len(obs.operators.LEDGER.snapshot().get("plain-job", {}))
    c.execute_computations(rdag.q06_sink("d"), job_name="plain-job")
    after = obs.operators.LEDGER.snapshot().get("plain-job", {})
    assert len(after) == before == 0
    obs.operators.op_add("anything")  # no current op: must not raise


def test_obs_explain_config_off_skips_traced_recording(tmp_path):
    c = Client(Configuration(root_dir=str(tmp_path / "off"),
                             page_size_bytes=1 << 16,
                             page_pool_bytes=1 << 20,
                             obs_explain=False))
    c.create_database("d")
    c.create_set("d", "lineitem", type_name="table", storage="paged")
    c.send_table("d", "lineitem", ColumnTable(_li_cols(2_000), {}))
    with obs.trace(origin="local") as tr:
        c.execute_computations(rdag.q06_sink("d"), job_name="q06")
    assert "operators" not in tr.profile()
    # explicit explain still records — the operator asked
    _, tree = c.execute_computations(rdag.q06_sink("d"),
                                     job_name="q06", explain=True)
    assert tree and tree["nodes"]


# ------------------------------------------------------- serve layer
def test_execute_explain_round_trip_and_get_trace(tmp_path):
    """EXECUTE(explain=True) round-trips the annotated tree in the
    reply; the same tree rides the qid's GET_TRACE profile."""
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / "srv"),
                      page_size_bytes=1 << 16,
                      page_pool_bytes=1 << 20), port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table",
                     storage="paged")
        c.send_table("d", "lineitem", ColumnTable(_li_cols(8_000), {}))
        _, tree = c.execute_computations(
            rdag.q06_sink("d"), job_name="q06", fetch_results=False,
            explain=True)
        assert tree and any(n["kind"] == "Apply"
                            for n in tree["nodes"])
        reply = c.get_trace(last=3)
        withops = [p for p in reply["profiles"]
                   if p.get("operators")]
        assert withops, "traced EXECUTE must carry the tree in its " \
                        "GET_TRACE profile"
        assert _shape(withops[-1]["operators"]) == _shape(tree)
        c.close()
    finally:
        ctl.shutdown()


def test_explain_tree_survives_the_mirror_hop(tmp_path):
    """Satellite: leader + follower sections under ONE qid each carry
    an operator tree of the same shape (the mirrored EXECUTE runs the
    same plan on both daemons)."""
    fctl = ServeController(
        Configuration(root_dir=str(tmp_path / "f"),
                      page_size_bytes=1 << 16,
                      page_pool_bytes=1 << 20), port=0)
    faddr = f"127.0.0.1:{fctl.start()}"
    mctl = ServeController(
        Configuration(root_dir=str(tmp_path / "m"),
                      page_size_bytes=1 << 16,
                      page_pool_bytes=1 << 20),
        port=0, followers=[faddr])
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table",
                     storage="paged")
        c.send_table("d", "lineitem", ColumnTable(_li_cols(800), {}))
        c.execute_computations(rdag.q06_sink("d"), job_name="q06",
                               fetch_results=False)
        reply = c.get_trace(last=1)
        (prof,) = reply["profiles"]
        assert prof.get("operators"), "leader profile lacks the tree"
        fsections = prof.get("followers") or {}
        assert faddr in fsections
        fprofs = [fp for fp in fsections[faddr]
                  if fp.get("operators")]
        assert fprofs, "follower section lacks the tree"
        assert all(fp["qid"] == prof["qid"] for fp in fprofs)
        assert _shape(fprofs[-1]["operators"]) == \
            _shape(prof["operators"])
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


def test_cli_obs_explain_renders(tmp_path, capsys):
    """`cli obs --explain <qid>` fetches the qid's profile and renders
    the classic tree."""
    from netsdb_tpu import cli

    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / "cli"),
                      page_size_bytes=1 << 16,
                      page_pool_bytes=1 << 20), port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table",
                     storage="paged")
        c.send_table("d", "lineitem", ColumnTable(_li_cols(4_000), {}))
        c.execute_computations(rdag.q06_sink("d"), job_name="q06",
                               fetch_results=False)
        qid = c.get_trace(last=1)["profiles"][-1]["qid"]
        c.close()
        rc = cli.main(["obs", "--addr", addr, "--explain", qid])
        out = capsys.readouterr().out
        assert rc == 0
        assert "EXPLAIN ANALYZE" in out
        assert "Scan[d:lineitem]" in out
        rc = cli.main(["obs", "--addr", addr, "--explain", "nope"])
        assert rc == 1
    finally:
        ctl.shutdown()


class _Rec:
    """Tiny node stand-in for recorder unit tests."""
    op_kind = "Apply"

    def __init__(self, label):
        self.label = label

    def plan_atom(self):
        return f"x <= APPLY(y, '{self.label}')"


def test_recorder_reserve_gives_collision_free_components():
    rec = OperatorRecorder("job")
    b1 = rec.reserve(3)
    b2 = rec.reserve(2)
    assert b1 == 0 and b2 == 3
    with rec.op(b1, _Rec("a"), []):
        obs.operators.op_add("chunks", 2)
    with rec.op(b2, _Rec("b"), []):
        obs.operators.op_add("chunks", 5)
    tree = rec.tree()
    by_id = {n["id"]: n for n in tree["nodes"]}
    assert by_id[0]["counters"]["chunks"] == 2
    assert by_id[3]["counters"]["chunks"] == 5
