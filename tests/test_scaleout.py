"""Horizontal scale-out: partitioned placement, routed ingest,
scatter-gather execution, the distributed shuffle, and the
epoch/handoff fault story (PR 13).

In-process pools (a leader ServeController + N worker controllers on
loopback, like the follower-concurrency tests) — correctness, not
throughput.
"""

import contextlib
import threading

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.serve import placement as PL
from netsdb_tpu.serve.client import (
    PlacementStaleError,
    RemoteClient,
    RetryPolicy,
    ShardUnavailableError,
)
from netsdb_tpu.serve.errors import RemoteError
from netsdb_tpu.serve.protocol import (
    CODEC_PICKLE,
    IDEMPOTENCY_KEY,
    PLACEMENT_EPOCH_KEY,
    SHARD_SLOT_KEY,
    MsgType,
)
from netsdb_tpu.serve.server import ServeController
from netsdb_tpu.storage.store import SetIdentifier
from netsdb_tpu.workloads.scaleout import (
    scale_rows,
    scaleout_join_sink,
    scaleout_q01_sink,
    scaleout_table,
)


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


@contextlib.contextmanager
def pool(tmp_path, n_workers=2, leader_kwargs=None, worker_kwargs=None,
         storage_kwargs=None):
    """Leader + N shard workers, all in-process; yields
    (leader, workers, leader_address)."""
    daemons = []
    try:
        workers = []
        for i in range(n_workers):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}"),
                              **(storage_kwargs or {})),
                port=0, **(worker_kwargs or {}))
            w.start()
            daemons.append(w)
            workers.append(w)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"),
                          **(storage_kwargs or {})),
            port=0,
            workers=[f"127.0.0.1:{w.port}" for w in workers],
            **(leader_kwargs or {}))
        leader.start()
        daemons.append(leader)
        yield leader, workers, f"127.0.0.1:{leader.port}"
    finally:
        for d in daemons:
            d.shutdown()


@contextlib.contextmanager
def solo(tmp_path, name="solo", storage_kwargs=None):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / name),
                      **(storage_kwargs or {})), port=0)
    ctl.start()
    try:
        yield ctl, f"127.0.0.1:{ctl.port}"
    finally:
        ctl.shutdown()


def _local_rows(ctl, db, set_name) -> int:
    items = ctl.library.store.get_items(SetIdentifier(db, set_name))
    total = 0
    for it in items:
        total += int(getattr(it, "num_rows", 0) or 0)
    return total


_result_rows = scale_rows


# --- placement map / routing units -----------------------------------

def test_placement_map_basics():
    m = PL.PlacementMap()
    e = m.create("d", "t", ["a:1", "b:2", "c:3"], mode="hash", key="k")
    assert e["epoch"] == 1 and len(e["slots"]) == 3
    assert m.entry("d", "t")["mode"] == "hash"
    changed = m.degrade_addr("b:2")
    assert changed == [("d", "t")]
    e2 = m.entry("d", "t")
    assert e2["epoch"] == 2
    assert e2["slots"][1]["state"] == PL.HANDOFF
    assert e2["slots"][0]["state"] == PL.LIVE
    m.readmit_addr("b:2")
    e3 = m.entry("d", "t")
    assert e3["epoch"] == 3
    assert all(s["state"] == PL.LIVE for s in e3["slots"])
    wire = m.to_wire()
    assert PL.PlacementMap.entry_from_wire(wire, "d", "t")["epoch"] == 3


def test_routing_deterministic_and_complete():
    # range: contiguous, covering, deterministic
    assert PL.range_slices(10, 4) == [(0, 2), (2, 5), (5, 7), (7, 10)]
    # hash: stable slot ids, every key to exactly one slot
    keys = np.arange(1000, dtype=np.int32)
    a = PL.hash_slot_ids(keys, 4)
    b = PL.hash_slot_ids(keys, 4)
    assert np.array_equal(a, b)
    assert set(np.unique(a)) <= {0, 1, 2, 3}
    entry = {"mode": "hash", "key": "k",
             "slots": [{"addr": "x", "state": "live"}] * 3}
    t = ColumnTable({"k": keys, "v": keys * 2}, {}, None)
    parts = PL.split_table(t, entry)
    assert sum(p.num_rows for _, p in parts) == 1000
    # co-partitioning: one key never splits across slots
    seen = {}
    for slot, p in parts:
        for k in np.asarray(p["k"]):
            assert seen.setdefault(int(k), slot) == slot


# --- handshake + routed ingest ---------------------------------------

def test_handshake_ships_placement_only_when_sharded(tmp_path):
    with pool(tmp_path, n_workers=1) as (leader, _ws, addr):
        c0 = RemoteClient(addr)
        assert c0.placement_map() is None  # no sharded sets yet
        c0.create_database("d")
        c0.create_set("d", "plain", type_name="table")
        assert c0.placement_map() is None
        c0.create_set("d", "t", type_name="table", placement="range")
        # a FRESH client learns the map in the handshake
        c1 = RemoteClient(addr)
        wire = c1.placement_map()
        assert wire is not None and "d:t" in wire["sets"]
        assert len(wire["sets"]["d:t"]["slots"]) == 2
        c0.close()
        c1.close()


def test_routed_table_ingest_spreads_and_scans_back(tmp_path):
    rows = 9000
    table = scaleout_table(rows)
    with pool(tmp_path, n_workers=2) as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        info = c.send_table("d", "t", table)
        assert info.num_rows == rows
        # every slot holds its contiguous third
        assert _local_rows(leader, "d", "t") == 3000
        for w in workers:
            assert _local_rows(w, "d", "t") == 3000
        # scan-back (leader fans in every slot) covers all rows exactly
        back = c.get_table_streamed("d", "t")
        assert back.num_rows == rows
        assert (sorted(np.asarray(back["l_price"]).tolist())
                == sorted(np.asarray(table["l_price"]).tolist()))
        assert _counter("serve.client.routed_ingests") >= 1
        c.close()


def test_hash_ingest_copartitions_keys(tmp_path):
    rng = np.random.default_rng(3)
    t = ColumnTable({"k": rng.integers(0, 40, 2000, dtype=np.int32),
                     "v": rng.integers(0, 9, 2000, dtype=np.int32)},
                    {}, None)
    with pool(tmp_path, n_workers=2) as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table",
                     placement={"shard": "hash", "key": "k"})
        c.send_table("d", "t", t)
        daemons = [leader] + workers
        owner = {}
        for i, d in enumerate(daemons):
            items = d.library.store.get_items(SetIdentifier("d", "t"))
            for it in items:
                if hasattr(it, "to_host_table"):
                    it = it.to_host_table()
                if not hasattr(it, "cols"):
                    continue
                for k in np.asarray(it["k"]):
                    assert owner.setdefault(int(k), i) == i
        assert sum(_local_rows(d, "d", "t") for d in daemons) == 2000
        c.close()


# --- scatter-gather execution ----------------------------------------

def _load_q01(client, rows=12000, sharded=True):
    client.create_database("d")
    kw = {"placement": "range"} if sharded else {}
    client.create_set("d", "lineitem", type_name="table",
                      storage="paged", **kw)
    client.send_table("d", "lineitem", scaleout_table(rows))


def test_scatter_fold_state_byte_equal(tmp_path):
    """The q01-style int fold over a sharded PAGED set: 3-daemon
    scatter-gather result must be byte-equal to the single-node run
    (integer accumulators — no reassociation slack)."""
    storage = {"page_size_bytes": 64 * 1024}
    with pool(tmp_path, n_workers=2, storage_kwargs=storage) \
            as (leader, _ws, addr):
        c = RemoteClient(addr)
        _load_q01(c, sharded=True)
        before = _counter("shard.scatter_queries")
        c.execute_computations(scaleout_q01_sink("d"),
                               job_name="sq01", fetch_results=False)
        assert _counter("shard.scatter_queries") == before + 1
        sharded_rows = _result_rows(c, "d", "scale_q01_out")
        c.close()
    with solo(tmp_path, storage_kwargs=storage) as (_ctl, saddr):
        sc = RemoteClient(saddr)
        _load_q01(sc, sharded=False)
        sc.execute_computations(scaleout_q01_sink("d"),
                                job_name="sq01-solo",
                                fetch_results=False)
        solo_rows = _result_rows(sc, "d", "scale_q01_out")
        sc.close()
    assert sharded_rows == solo_rows
    assert len(sharded_rows) == 6


def test_real_q01_scatter_matches_allclose(tmp_path):
    """The shipped float q01 sink scatters too (its fold declares
    state_merge); float sums reassociate across the merge, so the
    contract is allclose, int columns exact."""
    from netsdb_tpu.relational import dag as rdag

    rows = 8000
    rng = np.random.default_rng(0)
    cols = {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows,
                                   dtype=np.int32).astype(np.float32),
        "l_extendedprice": rng.uniform(1000, 100000,
                                       rows).astype(np.float32),
        "l_discount": rng.uniform(0, 0.1, rows).astype(np.float32),
        "l_tax": rng.uniform(0, 0.08, rows).astype(np.float32),
    }
    table = ColumnTable(cols, {"l_returnflag": ["A", "N", "R"],
                               "l_linestatus": ["F", "O"]})

    def run(ctx_addr, sharded):
        c = RemoteClient(ctx_addr)
        c.create_database("d")
        kw = {"placement": "range"} if sharded else {}
        c.create_set("d", "lineitem", type_name="table", **kw)
        c.send_table("d", "lineitem", table)
        c.execute_computations(rdag.q01_sink("d"), job_name="q01f",
                               fetch_results=False)
        out = c.get_table("d", "q01_out")
        c.close()
        return out

    with pool(tmp_path, n_workers=2) as (_l, _w, addr):
        got = run(addr, True)
    with solo(tmp_path) as (_ctl, saddr):
        want = run(saddr, False)
    for name in want.cols:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        assert np.allclose(a, b, rtol=1e-5), name


def test_group_partial_aggregate_equality(tmp_path):
    from netsdb_tpu.plan.computations import (Aggregate, Filter,
                                              ScanSet, WriteSet)

    items = [{"k": i % 7, "v": i % 11} for i in range(600)]

    def sink():
        node = Aggregate(
            Filter(ScanSet("d", "objs"), lambda r: r["v"] > 2,
                   label="v>2"),
            key=lambda r: r["k"], value=lambda r: r["v"],
            combine=lambda a, b: a + b, label="sumv")
        return WriteSet(node, "d", "g_out")

    def run(addr, sharded):
        c = RemoteClient(addr)
        c.create_database("d")
        kw = {"placement": "hash"} if sharded else {}
        c.create_set("d", "objs", type_name="object", **kw)
        c.send_data("d", "objs", items)
        res = c.execute_computations(sink(), job_name="grp")
        c.close()
        return next(iter(res.values()))

    with pool(tmp_path, n_workers=2) as (_l, _w, addr):
        got = run(addr, True)
    with solo(tmp_path) as (_ctl, saddr):
        want = run(saddr, False)
    assert dict(got) == dict(want)


def test_shuffle_join_byte_equal(tmp_path):
    key_space = 300
    rng = np.random.default_rng(1)
    li = ColumnTable(
        {"l_orderkey": rng.integers(0, key_space, 8000, dtype=np.int32),
         "l_price": rng.integers(1, 100, 8000, dtype=np.int32)},
        {}, None)
    orders = ColumnTable(
        {"o_orderkey": np.arange(key_space, dtype=np.int32)}, {}, None)

    def run(addr, sharded):
        c = RemoteClient(addr)
        c.create_database("d")
        kw = {"placement": "hash"} if sharded else {}
        c.create_set("d", "lineitem", type_name="table", **kw)
        c.create_set("d", "orders", type_name="table", **kw)
        c.send_table("d", "lineitem", li)
        c.send_table("d", "orders", orders)
        c.execute_computations(scaleout_join_sink("d", key_space),
                               job_name="sjoin", fetch_results=False)
        rows = _result_rows(c, "d", "scale_join_out")
        c.close()
        return rows

    parts_before = _counter("shard.shuffle_parts")
    with pool(tmp_path, n_workers=2) as (_l, _w, addr):
        got = run(addr, True)
    # 3 slots x 2 sides x 2 peers = 12 buckets crossed the wire
    assert _counter("shard.shuffle_parts") == parts_before + 12
    with solo(tmp_path) as (_ctl, saddr):
        want = run(saddr, False)
    assert got == want and len(got) == key_space


def test_unsupported_shape_refused_typed(tmp_path):
    from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet

    with pool(tmp_path, n_workers=1) as (_l, _w, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(200))
        # a whole-table Apply (no fold, no rowwise) cannot be pushed
        sink = WriteSet(Apply(ScanSet("d", "t"), fn=lambda t: t,
                              label="whole"), "d", "out")
        with pytest.raises(RemoteError) as ei:
            c.execute_computations(sink, job_name="bad",
                                   fetch_results=False)
        assert not ei.value.retryable
        assert "scatter-gather cannot push" in str(ei.value)
        c.close()


def test_scatter_explain_annotates_shards(tmp_path):
    with pool(tmp_path, n_workers=1) as (leader, _w, addr):
        c = RemoteClient(addr)
        _load_q01(c, rows=2000, sharded=True)
        results, shard_ops = leader.shards.scatter_execute(
            [scaleout_q01_sink("d")], "explain-job", explain=True)
        assert results
        assert set(shard_ops) == {leader.advertise_addr,
                                  f"127.0.0.1:{_w[0].port}"}
        for addr_key, tree in shard_ops.items():
            assert tree["shard"] == addr_key
        c.close()


# --- epochs, eviction, handoff, readmit ------------------------------

def test_stale_epoch_rejected_typed(tmp_path):
    with pool(tmp_path, n_workers=1) as (leader, _w, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        before = _counter("shard.epoch_rejects")
        with pytest.raises(PlacementStaleError) as ei:
            c._request(MsgType.SEND_DATA,
                       {"db": "d", "set": "t",
                        "items": ColumnTable(
                            {"x": np.arange(4, dtype=np.int32)}, {},
                            None),
                        "as_table": True, "date_cols": [],
                        "append": True,
                        PLACEMENT_EPOCH_KEY: 999, SHARD_SLOT_KEY: 0,
                        IDEMPOTENCY_KEY: "tok-stale"},
                       codec=CODEC_PICKLE)
        assert ei.value.retryable
        assert ei.value.epoch == 1  # the receiver's current epoch rides
        assert _counter("shard.epoch_rejects") > before
        # unrouted ingest into a partitioned set rejects typed too
        with pytest.raises(PlacementStaleError):
            c._request(MsgType.SEND_DATA,
                       {"db": "d", "set": "t", "items": [1],
                        IDEMPOTENCY_KEY: "tok-unrouted"},
                       codec=CODEC_PICKLE)
        c.close()


def test_stale_client_reroutes_after_eviction(tmp_path):
    """A client holding an epoch-1 map keeps working after the leader
    evicts a shard: stale-routed slots reject typed (placement-epoch
    rejected), the retry refreshes the map and re-routes — and with a
    CURRENT map, the degraded slot's partition lands in the leader's
    handoff buffer and drains (only its own pages) at readmit."""
    with pool(tmp_path, n_workers=2,
              leader_kwargs={"heartbeat_interval_s": 60.0}) \
            as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(3000))
        w0_addr = f"127.0.0.1:{workers[0].port}"
        assert c.placement_map()["sets"]["d:t"]["epoch"] == 1
        leader._evict_shard(w0_addr, "test eviction")
        assert leader.placement.entry("d", "t")["epoch"] == 2
        # the surviving worker learned the new epoch via the push
        assert workers[1].shard_registration("d", "t")["epoch"] == 2
        rejects = _counter("shard.epoch_rejects")
        refreshes = _counter("serve.client.placement_refreshes")
        # STALE map (epoch 1): the leader + surviving-worker slots
        # reject, the retry refreshes + re-routes, and the batch lands
        # whole. (The evicted worker still registers epoch 1 and
        # accepts its slot directly — a benign net-split shape: each
        # batch still lands exactly once.)
        c.send_table("d", "t", scaleout_table(3000, seed=1),
                     append=True)
        assert _counter("shard.epoch_rejects") > rejects
        assert _counter("serve.client.placement_refreshes") > refreshes
        total = sum(_local_rows(d, "d", "t")
                    for d in [leader] + workers)
        assert total == 6000
        # CURRENT map: the degraded slot's partition goes to the
        # leader's handoff buffer, not the shard
        handoffs = _counter("shard.handoff_batches")
        w0_rows = _local_rows(workers[0], "d", "t")
        c.send_table("d", "t", scaleout_table(3000, seed=2),
                     append=True)
        assert _counter("shard.handoff_batches") == handoffs + 1
        assert leader.shards.handoff_pending(w0_addr) == 1
        assert _local_rows(workers[0], "d", "t") == w0_rows
        # readmit: the drain ships ONLY the buffered slot batch
        drained = _counter("shard.handoff_drained")
        assert leader._try_readmit_shard(w0_addr)
        assert _counter("shard.handoff_drained") == drained + 1
        assert leader.shards.handoff_pending(w0_addr) == 0
        assert _local_rows(workers[0], "d", "t") == w0_rows + 1000
        # full pool coverage, no loss, no doubles
        total = sum(_local_rows(d, "d", "t")
                    for d in [leader] + workers)
        assert total == 9000
        c.close()


def test_scatter_refused_while_slot_degraded_then_recovers(tmp_path):
    with pool(tmp_path, n_workers=1,
              leader_kwargs={"heartbeat_interval_s": 60.0}) \
            as (leader, workers, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        _load_q01(c, rows=3000, sharded=True)
        sink = scaleout_q01_sink("d")
        c.execute_computations(sink, job_name="pre",
                               fetch_results=False)
        want = _result_rows(c, "d", "scale_q01_out")
        w_addr = f"127.0.0.1:{workers[0].port}"
        leader._evict_shard(w_addr, "test eviction")
        with pytest.raises(ShardUnavailableError) as ei:
            c.execute_computations(sink, job_name="during",
                                   fetch_results=False)
        assert ei.value.retryable
        assert leader._try_readmit_shard(w_addr)
        c.execute_computations(sink, job_name="after",
                               fetch_results=False)
        assert _result_rows(c, "d", "scale_q01_out") == want
        c.close()


def test_shard_death_mid_scatter_never_partial(tmp_path):
    """A shard dying mid scatter-gather: the client sees ONE typed
    retryable error, partials are discarded (the output set keeps its
    previous content — never a partial merge), the shard is evicted
    (epoch bump) and a post-readmit retry returns the full result."""
    with pool(tmp_path, n_workers=2,
              leader_kwargs={"heartbeat_interval_s": 60.0,
                             "mirror_ack_timeout_s": 15.0}) \
            as (leader, workers, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        _load_q01(c, rows=3000, sharded=True)
        sink = scaleout_q01_sink("d")
        c.execute_computations(sink, job_name="pre",
                               fetch_results=False)
        want = _result_rows(c, "d", "scale_q01_out")
        # kill worker 0's subplan leg: the handler path drops the
        # connection without a reply (the wire-level death shape)
        w0 = workers[0]
        original = w0.handlers[MsgType.SUBPLAN]

        def dying(p):
            raise BrokenPipeError("injected shard death")

        w0.handlers[MsgType.SUBPLAN] = dying
        epoch_before = leader.placement.entry("d", "lineitem")["epoch"]
        with pytest.raises(ShardUnavailableError) as ei:
            c.execute_computations(sink, job_name="mid",
                                   fetch_results=False)
        assert ei.value.retryable
        assert "partials discarded" in str(ei.value)
        # the output set was NOT overwritten by a partial merge
        assert _result_rows(c, "d", "scale_q01_out") == want
        assert leader.placement.entry("d", "lineitem")["epoch"] \
            > epoch_before
        # heal: restore the handler, readmit, retry succeeds whole
        w0.handlers[MsgType.SUBPLAN] = original
        w0_addr = f"127.0.0.1:{w0.port}"
        assert leader._try_readmit_shard(w0_addr)
        c.execute_computations(sink, job_name="post",
                               fetch_results=False)
        assert _result_rows(c, "d", "scale_q01_out") == want
        c.close()


def test_subplan_epoch_guard_rejects_cross_epoch_merge(tmp_path):
    """A SUBPLAN carrying a stale epoch is refused by the shard — the
    guard that makes a mid-query membership change abort the whole
    query instead of merging partials computed against two maps."""
    from netsdb_tpu.serve import shard as SH
    from netsdb_tpu.serve.errors import PlacementStale

    with pool(tmp_path, n_workers=1) as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(200))
        with pytest.raises(PlacementStale):
            SH.check_epochs(workers[0], {"d:t": 999})
        c.close()


# --- the default paths stay byte-for-byte ----------------------------

def test_plain_daemon_paths_untouched(tmp_path):
    with solo(tmp_path) as (ctl, addr):
        c = RemoteClient(addr)
        assert c.placement_map() is None  # handshake carried no map
        assert len(ctl.placement) == 0
        c.create_database("d")
        c.create_set("d", "t", type_name="table")
        c.send_table("d", "t", scaleout_table(500))
        assert not ctl.is_sharded("d", "t")
        assert _local_rows(ctl, "d", "t") == 500
        # EXECUTE takes the local path (no scatter counters move)
        before = _counter("shard.scatter_queries")
        c.execute_computations(scaleout_q01_sink("d", lineitem_set="t"),
                               job_name="plain", fetch_results=False)
        assert _counter("shard.scatter_queries") == before
        c.close()


def test_hash_split_missing_key_refused():
    entry = {"mode": "hash", "key": "k",
             "slots": [{"addr": "x", "state": "live"}] * 2}
    t = ColumnTable({"other": np.arange(10, dtype=np.int32)}, {}, None)
    with pytest.raises(ValueError, match="declares key"):
        PL.split_table(t, entry)


def test_ddl_refused_while_slot_degraded_and_purge_on_remove(tmp_path):
    """CLEAR/REMOVE over a sharded set are all-or-nothing like the
    merges: a degraded slot refuses typed (a clear that skipped the
    absent shard would diverge it at readmit), and REMOVE purges the
    set's buffered handoff so the shared byte budget cannot leak."""
    with pool(tmp_path, n_workers=1,
              leader_kwargs={"heartbeat_interval_s": 60.0}) \
            as (leader, workers, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(1000))
        w_addr = f"127.0.0.1:{workers[0].port}"
        leader._evict_shard(w_addr, "test eviction")
        with pytest.raises(ShardUnavailableError):
            c.clear_set("d", "t")
        with pytest.raises(ShardUnavailableError):
            c.send_table("d", "t", scaleout_table(100))  # replace=clear
        # append lands (degraded slot buffers), then REMOVE after
        # readmit purges nothing — and REMOVE with buffered handoff
        # gives the bytes back. (max_attempts=1 client: refresh the
        # map explicitly instead of riding the stale-retry loop.)
        c._refresh_placement()
        c.send_table("d", "t", scaleout_table(1000, seed=1),
                     append=True)
        assert leader.shards.handoff_pending(w_addr) == 1
        assert leader.shards._handoff_bytes > 0
        assert leader._try_readmit_shard(w_addr)
        c.remove_set("d", "t")
        assert leader.shards._handoff_bytes == 0
        assert not leader.is_sharded("d", "t")
        c.close()


def test_placement_mirror_alias_is_default(tmp_path):
    """``placement="mirror"`` — the explicit spelling of the default
    replication mode — creates a plain (un-sharded) set even on a
    pool leader."""
    with pool(tmp_path, n_workers=1) as (leader, _w, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "m", type_name="table", placement="mirror")
        assert not leader.is_sharded("d", "m")
        c.send_table("d", "m", scaleout_table(300))
        assert _local_rows(leader, "d", "m") == 300  # nothing routed
        c.close()


def test_concurrent_scatter_queries(tmp_path):
    """Two concurrent scatter-gather queries through one pool share
    the per-worker control connections without deadlock or
    cross-talk."""
    with pool(tmp_path, n_workers=1) as (_l, _w, addr):
        c = RemoteClient(addr)
        _load_q01(c, rows=2000, sharded=True)
        sink_a = scaleout_q01_sink("d", cutoff=19960101,
                                   output_set="out_a")
        sink_b = scaleout_q01_sink("d", cutoff=19990101,
                                   output_set="out_b")
        errs = []

        def run(sink, name):
            cc = RemoteClient(addr)
            try:
                cc.execute_computations(sink, job_name=name,
                                        fetch_results=False)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)
            finally:
                cc.close()

        threads = [threading.Thread(target=run, args=(s, n))
                   for s, n in ((sink_a, "qa"), (sink_b, "qb"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs
        a = _result_rows(c, "d", "out_a")
        b = _result_rows(c, "d", "out_b")
        assert a != b  # different cutoffs, different sums
        c.close()
