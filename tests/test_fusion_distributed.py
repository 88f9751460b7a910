"""Whole-plan distributed compilation (ISSUE 18).

Pins the acceptance properties of the optimal fusion mapper and the
scatter-boundary compilation:

* a 4-daemon scatter q01 executes with exactly ONE compiled program
  per shard (the partial-fold region — one ``fold::`` key, shared
  in-process because every shard ships the identical subplan) plus
  ONE coordinator merge+finalize program (``region::…::merge``);
* ``plan_fusion=off`` and ``fusion_mapper=greedy`` are byte-for-byte
  rollbacks: same results, same jit-key shapes as the pre-region
  path, no ``region::`` scatter keys minted;
* a multi-sink fan over one scan ships as ONE subplan per shard and
  each sink's result is byte-equal to running it separately;
* a region whose static staged-bytes estimate exceeds
  ``fusion_stage_budget_bytes`` SPLITS at the cheapest edges
  (``fusion.splits``-proven) instead of falling back per-node;
* EXPLAIN renders the distributed region tree — per-shard forests
  with the same ``┆rN`` / ``region=rN*`` markers the coordinator tree
  gets, shape-identical cold vs warm.
"""

import contextlib

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
from netsdb_tpu.plan import executor, fusion, scatter
from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet
from netsdb_tpu.plan.planner import plan_from_sinks
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.serve.client import RemoteClient
from netsdb_tpu.serve.server import ServeController
from netsdb_tpu.storage.store import SetIdentifier
from netsdb_tpu.workloads.scaleout import (
    scale_rows,
    scaleout_q01_sink,
    scaleout_table,
)

_STORAGE = {"page_size_bytes": 64 * 1024}
_CUTS = (19950101, 19970101, 19980902)


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


@contextlib.contextmanager
def pool4(tmp_path, **cfg_extra):
    """Leader + 3 shard workers (the acceptance pool size), all
    in-process; yields (leader, leader_address)."""
    storage = dict(_STORAGE, **cfg_extra)
    daemons = []
    try:
        workers = []
        for i in range(3):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}"),
                              **storage), port=0)
            w.start()
            daemons.append(w)
            workers.append(w)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"), **storage),
            port=0, workers=[f"127.0.0.1:{w.port}" for w in workers])
        leader.start()
        daemons.append(leader)
        yield leader, f"127.0.0.1:{leader.port}"
    finally:
        for d in daemons:
            d.shutdown()


def _load_q01(client, rows=12000):
    client.create_database("d")
    client.create_set("d", "lineitem", type_name="table",
                      storage="paged", placement="range")
    client.send_table("d", "lineitem", scaleout_table(rows))


# ------------------------------------ one program per shard + one merge
def test_scatter_q01_one_program_per_shard_plus_one_merge(tmp_path):
    with pool4(tmp_path) as (_leader, addr):
        c = RemoteClient(addr)
        _load_q01(c)
        keys0 = set(executor.compiled_cache_keys())
        sp0 = _counter("shard.subplans")
        dr0 = _counter("fusion.distributed_regions")
        fb0 = _counter("fusion.fallbacks")
        c.execute_computations(scaleout_q01_sink("d"), job_name="dq01",
                               fetch_results=False)
        new = set(executor.compiled_cache_keys()) - keys0
        fold_keys = {k for k in new if k.startswith("fold::dq01@shard")}
        merge_keys = {k for k in new
                      if k.startswith("region::dq01::scatter::")
                      and "::merge::k4::" in k}
        # ONE program per shard: every daemon ships the identical
        # subplan, so in-process the 4 legs share one fold:: entry
        assert len(fold_keys) == 1, sorted(new)
        # ONE coordinator merge+finalize program
        assert len(merge_keys) == 1, sorted(new)
        assert new == fold_keys | merge_keys, sorted(new)
        assert _counter("shard.subplans") - sp0 == 4
        # 4 shard anchor regions + the coordinator merge region
        assert _counter("fusion.distributed_regions") - dr0 == 5
        assert _counter("fusion.fallbacks") - fb0 == 0
        rows = scale_rows(c, "d", "scale_q01_out")
        assert len(rows) == 6
        c.close()


# ------------------------------------------------- rollback parity arms
def test_rollback_off_and_greedy_byte_equal_and_same_keys(tmp_path):
    """``plan_fusion=off`` and ``fusion_mapper=greedy`` must behave
    byte-for-byte like the pre-region scatter path: identical results,
    ONLY the original per-shard ``fold::`` jit key minted, no scatter
    ``region::`` programs anywhere."""
    def run(tag, **cfg_extra):
        with pool4(tmp_path / tag, **cfg_extra) as (_leader, addr):
            c = RemoteClient(addr)
            _load_q01(c)
            keys0 = set(executor.compiled_cache_keys())
            c.execute_computations(scaleout_q01_sink("d"),
                                   job_name=f"rb-{tag}",
                                   fetch_results=False)
            new = set(executor.compiled_cache_keys()) - keys0
            rows = scale_rows(c, "d", "scale_q01_out")
            c.close()
            return rows, new

    rows_opt, _ = run("opt")
    rows_off, new_off = run("off", plan_fusion=False)
    rows_greedy, new_greedy = run("greedy", fusion_mapper="greedy")
    assert rows_opt == rows_off == rows_greedy
    for new in (new_off, new_greedy):
        assert len(new) == 1 and all(k.startswith("fold::")
                                     for k in new), sorted(new)


# ----------------------------------------------------- multi-sink plans
def test_multi_sink_fan_one_subplan_per_shard_byte_equal(tmp_path):
    """A dashboard-style fan of 3 q01 queries over ONE scan compiles
    and ships as one distributed program per shard with 3 sinks, and
    every sink's result is byte-equal to running it separately."""
    with pool4(tmp_path) as (_leader, addr):
        c = RemoteClient(addr)
        _load_q01(c)
        sinks = [scaleout_q01_sink("d", cutoff=ct,
                                   output_set=f"fan_out_{i}")
                 for i, ct in enumerate(_CUTS)]
        sp0 = _counter("shard.subplans")
        sq0 = _counter("shard.scatter_queries")
        keys0 = set(executor.compiled_cache_keys())
        c.execute_computations(*sinks, job_name="fan",
                               fetch_results=False)
        # the whole fan: ONE scatter query, ONE subplan per daemon
        assert _counter("shard.scatter_queries") - sq0 == 1
        assert _counter("shard.subplans") - sp0 == 4
        new = set(executor.compiled_cache_keys()) - keys0
        assert {k for k in new if k.startswith("fold::fan@shard")
                and "multi::" in k}, sorted(new)
        assert {k for k in new if k.startswith("region::fan::scatter::")
                and "::merge::k4::" in k}, sorted(new)
        fan = [scale_rows(c, "d", f"fan_out_{i}")
               for i in range(len(_CUTS))]
        for i, ct in enumerate(_CUTS):
            c.execute_computations(
                scaleout_q01_sink("d", cutoff=ct,
                                  output_set=f"solo_out_{i}"),
                job_name=f"fan-solo{i}", fetch_results=False)
            assert fan[i] == scale_rows(c, "d", f"solo_out_{i}")
        c.close()


def test_analyze_multi_sinks_units():
    sharded = lambda db, s: s == "lineitem"  # noqa: E731
    fan = [scaleout_q01_sink("d", cutoff=ct, output_set=f"o{i}")
           for i, ct in enumerate(_CUTS)]
    mspec = scatter.analyze_sinks(fan, sharded)
    assert isinstance(mspec, scatter.MultiScatterSpec)
    assert mspec.kind == "multi_fold"
    assert len(mspec.components) == 3
    assert mspec.scan_sets == (("d", "lineitem"),)
    # the combined subplan: ONE fresh scan, one tuple-state fold
    sink = scatter.multi_partial_sink(mspec)
    partial = sink.inputs[0]
    assert getattr(partial, "scatter_partial", False)
    assert isinstance(partial.inputs[0], ScanSet)
    # a sink scatter-gather cannot push poisons the whole fan
    bad = Apply(ScanSet("d", "lineitem"),
                lambda t: ColumnTable({"x": t["l_price"]}, t.dicts,
                                      t.valid), label="nofold")
    assert scatter.analyze_sinks(
        fan + [WriteSet(bad, "d", "bad_out")], sharded) is None


# --------------------------------------------- staged-bytes budget split
def _spined_q06(spine):
    import jax.numpy as jnp

    from netsdb_tpu.plan.computations import Join
    from netsdb_tpu.relational import dag as rdag

    node = ScanSet("d", "dim")
    for i in range(spine):
        node = Apply(node, lambda t, _i=i: ColumnTable(
            {"x": t["x"] * (1.0 + 1e-6 * _i)}, t.dicts, t.valid),
            label=f"sp{i}")
    z = Apply(node, lambda t: jnp.sum(t["x"]) * 1e-9, label="zsum")
    q06 = rdag.q06_sink("d")
    j = Join(q06.inputs[0], z, fn=lambda rev, v: ColumnTable(
        {"revenue": rev["revenue"] + v}, rev.dicts, rev.valid),
        label="combine")
    return WriteSet(j, "d", "out")


def _mixed_client(tmp_path, name, **cfg_extra):
    rng = np.random.default_rng(2)
    c = Client(Configuration(root_dir=str(tmp_path / name),
                             fusion_cost_source="static", **cfg_extra))
    c.create_database("d")
    c.create_set("d", "lineitem", type_name="table", storage="paged")
    n = 900
    c.send_table("d", "lineitem", ColumnTable({
        "l_shipdate": rng.integers(19940101, 19950101, n,
                                   dtype=np.int32),
        "l_discount": np.full(n, 0.06, np.float32),
        "l_quantity": np.full(n, 10.0, np.float32),
        "l_extendedprice": rng.uniform(1000, 2000, n
                                       ).astype(np.float32)}, {}))
    c.create_set("d", "dim", type_name="table")
    c.send_table("d", "dim", ColumnTable(
        {"x": np.random.default_rng(0).standard_normal(512)
         .astype(np.float32)}, {}))
    return c


def test_budget_splits_region_at_cheapest_edge_not_per_node(tmp_path):
    """With a staged-bytes budget of 2 nodes (static estimate 4MiB per
    cold node), the 8-node admissible run splits into 2-node regions —
    counted by ``fusion.splits`` — instead of abandoning fusion."""
    budget = 2 * fusion.STATIC_STAGED_BYTES
    c = _mixed_client(tmp_path, "budget",
                      fusion_stage_budget_bytes=budget)
    sink = _spined_q06(spine=6)  # sp0..sp5 + zsum + combine = 8 nodes
    plan = plan_from_sinks([sink])
    scan_values = {
        n.node_id: c.store.get_items(
            SetIdentifier(n.db, n.set_name))[0]
        for n in plan.topo if isinstance(n, ScanSet)}
    sp0 = _counter("fusion.splits")
    rmap = fusion.map_regions(plan, scan_values, c.store.config,
                              "budget-unit",
                              traceable=executor._is_traceable)
    spines = [r for r in rmap.regions if r.kind == "spine"]
    assert len(spines) == 4  # 8 admissible nodes / 2-node budget
    assert all(len(r.node_ids) == 2 for r in spines)
    assert _counter("fusion.splits") - sp0 == 3  # 3 cut edges

    # end to end: the split regions execute and match the unbudgeted
    # single-region run exactly
    out_b = c.execute_computations(_spined_q06(spine=6),
                                   job_name="budget-run")
    v_b = np.asarray(next(iter(out_b.values()))["revenue"])
    c2 = _mixed_client(tmp_path, "nobudget")
    out_u = c2.execute_computations(_spined_q06(spine=6),
                                    job_name="nobudget-run")
    v_u = np.asarray(next(iter(out_u.values()))["revenue"])
    np.testing.assert_array_equal(v_b, v_u)


def test_optimal_mapper_matches_greedy_without_budget_pressure(tmp_path):
    """The DP must reproduce greedy whole-run fusion when no budget
    binds — the tie-break prefers the fully fused segmentation, so
    default-config region maps are identical to PR 10's."""
    c = _mixed_client(tmp_path, "parity")
    sink = _spined_q06(spine=4)
    plan = plan_from_sinks([sink])
    scan_values = {
        n.node_id: c.store.get_items(
            SetIdentifier(n.db, n.set_name))[0]
        for n in plan.topo if isinstance(n, ScanSet)}

    def regions_for(mapper):
        c.store.config.fusion_mapper = mapper
        rmap = fusion.map_regions(plan, scan_values, c.store.config,
                                  f"parity-{mapper}",
                                  traceable=executor._is_traceable)
        return [(r.kind, r.node_ids) for r in rmap.regions]

    assert regions_for("optimal") == regions_for("greedy")


# -------------------------------------------- ledger staged-bytes feed
def test_cost_model_staged_bytes_ledger_and_static_fallback():
    ledger = obs.operators.LEDGER
    ledger.add("sb-job", "Apply:warm", {
        "wall_s": 0.5, "device_est_s": 0.1,
        "counters": {"stage.bytes": 3000.0, "bytes_in": 1000.0}})
    cm = fusion.CostModel("sb-job", source="ledger")

    class _N:
        op_kind = "Apply"

    warm, cold = _N(), _N()
    warm.label, cold.label = "warm", "cold"
    assert cm.staged_bytes(warm) == 4000.0
    assert cm.staged_bytes(cold) == float(fusion.STATIC_STAGED_BYTES)
    # static source mirrors fusion_cost_source=static: never consults
    # the ledger
    cm_static = fusion.CostModel("sb-job", source="static")
    assert cm_static.staged_bytes(warm) == \
        float(fusion.STATIC_STAGED_BYTES)


# --------------------------------------------------- EXPLAIN forest
def test_explain_distributed_region_tree_cold_warm_identical(tmp_path):
    with pool4(tmp_path) as (_leader, addr):
        c = RemoteClient(addr)
        _load_q01(c)

        def tree_once():
            _res, tree = c.execute_computations(
                scaleout_q01_sink("d"), job_name="dx01",
                fetch_results=False, explain=True)
            return tree

        cold = tree_once()
        warm = tree_once()
        c.close()
    forest = cold.get("shard_operators")
    assert forest is not None and len(forest) == 4
    for addr_, tree in forest.items():
        # every node carries its executing daemon (the _annotate_shard
        # fix: trees hold flat "nodes" lists, not "children")
        assert all(n.get("shard") == addr_ for n in tree["nodes"])
    rendered = obs.operators.render_shard_forest(forest)
    # the per-shard forest carries the SAME region markers as the
    # coordinator tree: region boundary + streaming-anchor annotation
    assert rendered.count("-- shard ") == 4
    assert "┆r0" in rendered  # ┆r0 boundary marker
    assert "region=r0*" in rendered  # anchor-only graft region

    def shape(f):
        return [(a, [(n["kind"], n.get("label"), n.get("region"),
                      bool(n.get("fused"))) for n in f[a]["nodes"]])
                for a in sorted(f)]

    assert shape(forest) == shape(warm["shard_operators"])
    assert obs.operators.render_shard_forest(None) \
        == "(no shard operator forest)"


# --------------------------------------------- compiled merge fallback
def test_merge_fold_states_compiled_falls_back_eager():
    class _F:
        state_merge = staticmethod(lambda a, b: a + b)
        finalize = staticmethod(lambda st, src: st)

    fb0 = _counter("fusion.fallbacks")
    # non-jit-safe states (host objects) never reach the compiler
    out = scatter.merge_fold_states_compiled(
        _F(), [{"k": object()}], {}, 0, "fb-job", "fb")
    assert isinstance(out["k"], object)
    # untraceable folds skip the compiled path without a fallback tick
    out2 = scatter.merge_fold_states_compiled(
        _F(), [np.ones(3), np.ones(3)], {}, 0, "fb-job", "fb",
        traceable=False)
    np.testing.assert_array_equal(np.asarray(out2), 2 * np.ones(3))
    assert _counter("fusion.fallbacks") == fb0


# ------------------------------------------------- advisor mapper arms
def test_mapper_candidates_are_advisor_arms():
    from netsdb_tpu.learning.advisor import (PlacementAdvisor,
                                             mapper_candidates)
    from netsdb_tpu.learning.history import HistoryDB

    cands = list(mapper_candidates())
    assert {c.specs["fusion_mapper"] for c in cands} \
        == {"optimal", "greedy"}
    adv = PlacementAdvisor(cands, HistoryDB(":memory:"))
    adv.record("map-ab", cands[0], 0.4)
    adv.record("map-ab", cands[1], 0.2)
    assert adv.choose("map-ab").label == cands[1].label


@pytest.mark.slow
def test_mapper_ab_harness_live_loop():
    from netsdb_tpu.learning.ab_bench import bench_mapper_ab

    out = bench_mapper_ab(rows=20_000, spine=3, rounds=2, reps=1,
                          shape="mixed")
    assert {r[0] for r in out["rounds"]} \
        <= {"mapper_optimal", "mapper_greedy"}
    assert out["winner"] in ("mapper_optimal", "mapper_greedy")


# ------------------------------------------------------- config knobs
def test_config_rejects_bad_mapper_and_budget(tmp_path):
    with pytest.raises(ValueError):
        Configuration(root_dir=str(tmp_path / "x"),
                      fusion_mapper="eager")
    with pytest.raises(ValueError):
        Configuration(root_dir=str(tmp_path / "y"),
                      fusion_stage_budget_bytes=-1)
