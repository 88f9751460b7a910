"""Distribution through the database API (round-3 item 1).

In the reference, distribution is the default path: ``createSet``
chooses a PartitionPolicy, ingest partitions every set across workers
(``src/dispatcher/headers/PartitionPolicy.h:27-50``), and each
scheduled stage runs distributed against local partitions
(``src/serverFunctionalities/source/QuerySchedulerServer.cc:216-330``).
These tests assert the TPU-native equivalent end to end on the virtual
8-device mesh: ``create_set(placement=...)`` → mesh-sharded stored
values → the SAME Computation DAG executes distributed with results
identical to single-device — both in-process and through the serve
daemon.
"""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from netsdb_tpu.parallel.placement import Placement
from netsdb_tpu.relational import dag as rdag
from netsdb_tpu.relational.queries import cq01, tables_from_rows
from netsdb_tpu.workloads import tpch


def _num_shards(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


# --------------------------------------------------------- Placement unit
def test_placement_meta_roundtrip():
    p = Placement((("data", 4), ("model", 2)), ("data", None))
    q = Placement.from_meta(p.to_meta())
    assert q == p
    assert q.mesh() is p.mesh()  # cached: equal axes → same Mesh object
    assert "data=4" in p.label()


def test_placement_degrades_to_available_devices():
    # 64 devices declared, 8 available → collapses to the trivial mesh
    # (the dispatcher's DEFAULT-policy fallback); data stays correct.
    p = Placement((("data", 64),), ("data",))
    assert p.resolved_axes() == (("data", 1),)
    x = p.apply(jax.numpy.arange(16, dtype=jax.numpy.float32))
    assert _num_shards(x) == 1


def test_placement_short_of_devices_raises_off_cpu(monkeypatch):
    # on an accelerator a set declared over more chips than the process
    # holds must not quietly compute on one
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = Placement((("data", 64),), ("data",))
    with pytest.raises(ValueError, match="needs 64 devices"):
        p.resolved_axes()
    full = len(jax.devices())
    assert Placement.data_parallel(ndim=1).resolved_axes() == (
        ("data", full),)


def test_placement_zero_means_all_devices():
    p = Placement.data_parallel(ndim=2)
    assert dict(p.resolved_axes())["data"] == len(jax.devices())


def test_placement_two_free_axes_raises():
    # "all remaining devices" on two axes has no canonical split — the
    # old behavior silently pinned both to 1 (round-3 VERDICT weak #7);
    # now it errors like a dispatcher with no applicable policy.
    p = Placement((("data", 0), ("model", 0)), ("data", None))
    with pytest.raises(ValueError, match="at most one axis"):
        p.resolved_axes()
    with pytest.raises(ValueError, match="at most one axis"):
        p.mesh()


# --------------------------------------------------- sharded tensor sets
def test_create_set_shards_tensor_ingest(client):
    client.create_database("d")
    client.create_set("d", "m", placement=Placement.data_parallel(ndim=2))
    dense = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    client.send_matrix("d", "m", dense, block_shape=(8, 8))
    t = client.get_tensor("d", "m")
    assert _num_shards(t.data) == 8
    np.testing.assert_allclose(np.asarray(t.to_dense()), dense)
    # the client's mesh is wired to the placement's mesh (weak #1)
    assert client.mesh is Placement.data_parallel(ndim=2).mesh()
    assert client.store.set_stats(
        client.store.list_sets()[0])["placement"].startswith("mesh[")


def test_placement_history_row_records_sharding(client):
    from netsdb_tpu.learning.history import get_history_db

    client.create_database("d")
    pl = Placement((("data", 8),), ("data", None))
    client.create_set("d", "m", placement=pl)
    runs = get_history_db().runs("d.m:placement")
    assert runs and runs[-1]["config"] == pl.label()


# ------------------------------------------------------ FF via the set API
def _ff_setup(client, placements):
    from netsdb_tpu.models.ff import FFModel

    model = FFModel(db="ffp", block=(8, 8))
    model.setup(client, placements=placements)
    model.load_random_weights(client, features=16, hidden=32, labels=8,
                              seed=3)
    rng = np.random.default_rng(7)
    x = rng.standard_normal((32, 16)).astype(np.float32)
    model.load_inputs(client, x)
    return model


def test_ff_inference_distributed_matches_single(client, config):
    from netsdb_tpu.client import Client

    axes = (("data", 4), ("model", 2))
    placements = {
        "inputs": Placement(axes, ("data", None)),
        "w1": Placement(axes, ("model", None)),
        "b1": Placement(axes, (None, None)),
        "wo": Placement(axes, (None, "model")),
        "bo": Placement(axes, (None, None)),
        "output": Placement(axes, (None, "data")),  # (labels x batch)
    }
    dist = _ff_setup(client, placements)
    out_dist = dist.inference(client)
    # distributed materialization: stored weights and inputs are sharded
    assert _num_shards(client.get_tensor("ffp", "inputs").data) > 1
    assert _num_shards(client.get_tensor("ffp", "w1").data) > 1

    solo_client = Client(config)
    solo = _ff_setup(solo_client, None)
    out_solo = solo.inference(solo_client)
    np.testing.assert_allclose(np.asarray(out_dist.to_dense()),
                               np.asarray(out_solo.to_dense()),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- TPC-H via the set API
@pytest.fixture(scope="module")
def tpch_rows():
    return tpch.generate(scale=1, seed=11)


def test_q01_distributed_via_set_api_matches_columnar(client, tpch_rows):
    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    table = client.send_table("tpch", "lineitem",
                              tpch_rows["lineitem"])
    # ingest sharded the rows over all 8 devices (padding rides the mask)
    stored = client.get_table("tpch", "lineitem")
    assert _num_shards(next(iter(stored.cols.values()))) == 8
    assert stored.num_rows % 8 == 0

    result = rdag.run_query(client, rdag.q01_sink("tpch"))
    got = {(r["l_returnflag"], r["l_linestatus"]):
           {k: v for k, v in r.items() if k not in
            ("l_returnflag", "l_linestatus")}
           for r in result.to_rows()}

    want = dict(cq01(tables_from_rows(tpch_rows)))
    assert set(got) == set(want)
    for key, exp in want.items():
        for name, val in exp.items():
            np.testing.assert_allclose(got[key][name], val, rtol=1e-4,
                                       err_msg=f"{key}/{name}")
    # result is materialized into the output set as a relation
    out = client.get_table("tpch", "q01_out")
    assert "sum_qty" in out.cols


def test_q01_set_api_single_device_identical(client, config, tpch_rows):
    """Same DAG, no placement → same numbers (shard-count invariance
    through the database API)."""
    from netsdb_tpu.client import Client

    c2 = Client(config)
    c2.create_database("tpch")
    c2.create_set("tpch", "lineitem", type_name="table")
    c2.send_table("tpch", "lineitem", tpch_rows["lineitem"])
    r_solo = rdag.run_query(c2, rdag.q01_sink("tpch")).to_rows()

    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    client.send_table("tpch", "lineitem", tpch_rows["lineitem"])
    r_dist = rdag.run_query(client, rdag.q01_sink("tpch")).to_rows()

    assert len(r_solo) == len(r_dist)
    for a, b in zip(r_solo, r_dist):
        assert a.keys() == b.keys()
        for k in a:
            if isinstance(a[k], str):
                assert a[k] == b[k]
            else:
                np.testing.assert_allclose(a[k], b[k], rtol=1e-4)


def test_q06_distributed_via_set_api(client, tpch_rows):
    from netsdb_tpu.relational.queries import cq06

    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    client.send_table("tpch", "lineitem", tpch_rows["lineitem"])
    result = rdag.run_query(client, rdag.q06_sink("tpch"))
    want = dict(cq06(tables_from_rows(tpch_rows)))["revenue"]
    np.testing.assert_allclose(float(result["revenue"][0]), want, rtol=1e-4)


def test_q03_three_table_join_distributed_via_set_api(client, tpch_rows):
    """Broadcast-join plan by placement: fact table sharded over the
    mesh, dimension tables replicated — the three-table q03 DAG runs
    distributed through the set API and matches the columnar engine."""
    from netsdb_tpu.relational.queries import cq03

    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    client.create_set("tpch", "orders", type_name="table",
                      placement=Placement.replicated(ndim=1))
    client.create_set("tpch", "customer", type_name="table",
                      placement=Placement.replicated(ndim=1))
    for name in ("lineitem", "orders", "customer"):
        client.send_table("tpch", name, tpch_rows[name])
    assert _num_shards(
        client.get_table("tpch", "lineitem")["l_orderkey"]) == 8

    sink = rdag.q03_sink_for(client, "tpch")
    result = rdag.run_query(client, sink)
    got = rdag.q03_rows(result)
    want = cq03(tables_from_rows(tpch_rows))
    assert [r["okey"] for r in got] == [r["okey"] for r in want]
    assert [r["odate"] for r in got] == [r["odate"] for r in want]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g["revenue"], w["revenue"], rtol=1e-5)


# --------------------------------------------- review-finding regressions
def test_direct_columnar_path_ignores_placement_padding(client, tpch_rows):
    """cq01 on a table read back from a placed set (rows padded with
    valid=False) must equal cq01 on the raw rows — the direct path
    compacts masks away."""
    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    client.send_table("tpch", "lineitem", tpch_rows["lineitem"])
    stored = client.get_table("tpch", "lineitem")
    assert stored.num_rows % 8 == 0  # padded
    got = cq01({"lineitem": stored})
    want = cq01(tables_from_rows(tpch_rows))
    assert len(got) == len(want)
    for (gk, gv), (wk, wv) in zip(got, want):
        assert gk == wk and gv["count"] == wv["count"]
        np.testing.assert_allclose(gv["sum_qty"], wv["sum_qty"], rtol=1e-5)


def test_placement_survives_eviction_roundtrip(config):
    from netsdb_tpu.client import Client
    from netsdb_tpu.storage.store import SetIdentifier

    c = Client(config)
    c.store.max_host_bytes = 1 << 14  # force eviction
    c.create_database("d")
    c.create_set("d", "a", placement=Placement.data_parallel(ndim=2))
    c.create_set("d", "b")
    c.send_matrix("d", "a", np.ones((64, 16), np.float32), (8, 8))
    # ingest into b evicts a (a is LRU-oldest)
    c.send_matrix("d", "b", np.ones((64, 64), np.float32), (8, 8))
    sa = c.store._sets[SetIdentifier("d", "a")]
    assert sa.items is None, "test setup: 'a' should have spilled"
    t = c.get_tensor("d", "a")  # reload from spill
    assert _num_shards(t.data) == 8, "placement lost across eviction"


def test_recreate_set_replaces_existing_data(client):
    client.create_database("d")
    client.create_set("d", "m")
    client.send_matrix("d", "m", np.ones((64, 16), np.float32), (8, 8))
    assert _num_shards(client.get_tensor("d", "m").data) == 1
    client.create_set("d", "m", placement=Placement.data_parallel(ndim=2))
    assert _num_shards(client.get_tensor("d", "m").data) == 8


def test_table_aux_key_cached_across_flattens(tpch_rows):
    import jax

    from netsdb_tpu.relational.table import ColumnTable

    t = ColumnTable.from_rows(tpch_rows["lineitem"])
    _, aux1 = t.tree_flatten()
    _, aux2 = t.tree_flatten()
    assert aux1 is aux2  # built once, not per flatten
    leaves, treedef = jax.tree_util.tree_flatten(t)
    t2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert t2.tree_flatten()[1] is aux1


# ------------------------------------------------------ through the daemon
def test_distributed_job_through_serve_daemon(config, tpch_rows):
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    ctl = ServeController(config, port=0)
    port = ctl.start()
    try:
        rc = RemoteClient(f"127.0.0.1:{port}")
        rc.create_database("tpch")
        rc.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
        reply = rc.send_table("tpch", "lineitem", tpch_rows["lineitem"])
        assert reply.num_rows == len(tpch_rows["lineitem"])
        # daemon-side set is mesh-sharded
        ident = ctl.library.store.list_sets()[0]
        held = ctl.library.get_table("tpch", "lineitem")
        assert _num_shards(next(iter(held.cols.values()))) == 8

        rc.execute_computations(rdag.q01_sink("tpch"),
                                job_name="served-q01",
                                fetch_results=False)
        result = rc.get_table("tpch", "q01_out")
        got = {(r["l_returnflag"], r["l_linestatus"]): r["count"]
               for r in result.to_rows()}
        want = {k: v["count"]
                for k, v in dict(cq01(tables_from_rows(tpch_rows))).items()}
        assert got == want

        # sharded FF through the daemon: placement-carrying weight sets
        axes = (("data", 4), ("model", 2))
        from netsdb_tpu.models.ff import FFModel

        model = FFModel(db="ffs", block=(8, 8))
        model.setup(rc, placements={
            "inputs": Placement(axes, ("data", None)),
            "w1": Placement(axes, ("model", None)),
        })
        model.load_random_weights(rc, features=16, hidden=32, labels=8,
                                  seed=5)
        rng = np.random.default_rng(9)
        x = rng.standard_normal((32, 16)).astype(np.float32)
        model.load_inputs(rc, x)
        assert _num_shards(
            ctl.library.get_tensor("ffs", "w1").data) > 1
        rc.execute_computations(model.build_inference_dag(),
                                job_name="served-ff", fetch_results=False)
        out = rc.get_tensor("ffs", "output")
        probs = np.asarray(out.to_dense())
        np.testing.assert_allclose(probs.sum(axis=0), 1.0, rtol=1e-4)
    finally:
        ctl.shutdown()


def test_whole_suite_distributed_via_set_api(client, tpch_rows):
    """ALL TEN TPC-H query cores run as DAGs over placement-sharded
    stored sets (facts sharded, dims replicated) with raw outputs
    matching the single-device cores — the full columnar suite
    distributed through the database API."""
    import jax

    from netsdb_tpu.relational.queries import _SUITE_CORES

    client.create_database("tpch")
    for name in tpch_rows:
        pl = (Placement.data_parallel(ndim=1)
              if name in rdag.FACT_TABLES else Placement.replicated(ndim=1))
        client.create_set("tpch", name, type_name="table", placement=pl)
        client.send_table("tpch", name, tpch_rows[name])

    solo_tables = tables_from_rows(tpch_rows)
    for qname, (core, args_fn) in _SUITE_CORES.items():
        got = rdag.run_query(client,
                             rdag.suite_sink_for(client, "tpch", qname),
                             job_name=f"suite-{qname}")
        want = core(*args_fn(solo_tables))
        g_leaves = jax.tree_util.tree_leaves(got)
        w_leaves = jax.tree_util.tree_leaves(want)
        assert len(g_leaves) == len(w_leaves), qname
        for a, b in zip(g_leaves, w_leaves):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-3,
                                       err_msg=qname)


def test_suite_sink_reingest_does_not_reuse_stale_stats(client):
    """Regression (r3 review): the suite DAG closes over build-time
    planner stats; re-ingesting data with a LARGER key space must not
    hit the old compiled closure (whose smaller LUT would silently
    drop join rows). The stats fingerprint in the node label forces a
    fresh compile."""
    import jax

    from netsdb_tpu.relational.queries import _SUITE_CORES

    def load(c, stride, n_orders):
        rows = tpch.generate(scale=1, seed=21)
        # remap orderkeys onto a stride so the key SPACE genuinely
        # changes between ingests (scale-1 keys are 0..~150; a plain
        # modulo above that would be a no-op)
        for r in rows["orders"]:
            r["o_orderkey"] = (r["o_orderkey"] * stride) % n_orders
        for r in rows["lineitem"]:
            r["l_orderkey"] = (r["l_orderkey"] * stride) % n_orders
        for name in ("customer", "orders", "lineitem"):
            if not c.set_exists("tpch", name):
                c.create_set("tpch", name, type_name="table",
                             placement=(Placement.data_parallel(ndim=1)
                                        if name in rdag.FACT_TABLES else
                                        Placement.replicated(ndim=1)))
            c.send_table("tpch", name, rows[name])
        return rows

    client.create_database("tpch")
    core, args_fn = _SUITE_CORES["q03"]

    load(client, stride=1, n_orders=128)  # small key space first
    rdag.run_query(client, rdag.suite_sink_for(client, "tpch", "q03"))

    # stride-31 remap: max key ~ 150*31 % 4096 → key space ~32× larger
    rows2 = load(client, stride=31, n_orders=4096)
    got = rdag.run_query(client,
                         rdag.suite_sink_for(client, "tpch", "q03"))
    want = core(*args_fn(tables_from_rows(rows2)))
    g_leaves = jax.tree_util.tree_leaves(got)
    w_leaves = jax.tree_util.tree_leaves(want)
    assert len(g_leaves) == len(w_leaves)
    for a, b in zip(g_leaves, w_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-3)


def test_kmeans_on_placed_set_matches_single_device(client, config):
    """The classic ML workloads distribute through the set API too:
    kmeans over a placed points set (rows sharded over the mesh) runs
    the same jitted Lloyd's loop with XLA inserting the psums, matching
    the single-device result."""
    from netsdb_tpu.client import Client
    from netsdb_tpu.workloads.kmeans import kmeans_on_set

    rng = np.random.default_rng(11)
    pts = (rng.standard_normal((512, 16)) +
           (rng.integers(0, 4, (512, 1)) * 8)).astype(np.float32)

    def run(c, placement):
        c.create_database("ml")
        c.create_set("ml", "points", placement=placement)
        c.send_matrix("ml", "points", pts, (8, 8))
        cents, assign = kmeans_on_set(c, "ml", "points", k=4, iters=8,
                                      seed=3)
        return np.asarray(cents), np.asarray(assign)

    dist_c, dist_a = run(client, Placement.data_parallel(ndim=2))
    t = client.get_tensor("ml", "points")
    assert _num_shards(t.data) == 8
    solo_c, solo_a = run(Client(config), None)
    np.testing.assert_allclose(dist_c, solo_c, rtol=1e-4, atol=1e-4)
    # distributed float-reduce ordering can flip points on decision
    # boundaries: admit a handful of tie flips over the 512 points
    assert (dist_a == solo_a).mean() >= 0.99
