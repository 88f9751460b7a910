"""Cross-query device-resident set cache (storage/devcache.py) +
overlapped grace-hash pairs — the PR 4 acceptance surface.

What these tests pin:

* the cache itself: LRU eviction under the byte budget, counters,
  invalidation, resize/disable;
* the warm path: a second execution over an unchanged paged set serves
  every block from device memory — the MISS COUNTER STAYS FLAT (the
  zero-host→device-transfers assertion) and results are identical;
* no stale reads, through every write path: direct ingest/replace/
  append, a mirrored write through a leader, a resync-restored
  follower, and a mid-BULK fault (where the version must NOT advance);
* grace-hash partition pairs overlap: pair *i+1*'s build upload begins
  before pair *i*'s probe stream finishes (staging event order), and
  sequential mode (stage_depth=0) provably does not — plus the leak
  registry stays clean when a grace join dies mid-pair;
* the PR 2 leftover: a paged MATRIX resyncs page by page instead of
  arriving empty;
* cached blocks are never donation targets: with fold-buffer donation
  forced on, cached device blocks survive repeated folds bit-identical.
"""

import contextlib
import time

import numpy as np
import pytest

from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
from netsdb_tpu.plan import staging
from netsdb_tpu.relational import dag as rdag
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.storage.devcache import DeviceBlockCache
from netsdb_tpu.storage.store import SetIdentifier


def _li_cols(n, seed=0, disc=0.06):
    rng = np.random.default_rng(seed)
    return {
        "l_shipdate": rng.integers(19940101, 19950101, n, dtype=np.int32),
        "l_discount": np.full(n, disc, np.float32),
        "l_quantity": np.full(n, 10.0, np.float32),
        "l_extendedprice": rng.uniform(1000, 2000, n).astype(np.float32),
    }


def _q06_ref(cols):
    return float((cols["l_extendedprice"]
                  * cols["l_discount"]).sum(dtype=np.float64))


def _paged_lineitem(client, cols):
    if client.set_exists("d", "lineitem"):
        client.remove_set("d", "lineitem")
    client.create_set("d", "lineitem", type_name="table", storage="paged")
    client.send_table("d", "lineitem", ColumnTable(cols, {}))


def _run_q06(client):
    out = rdag.run_query(client, rdag.q06_sink("d"))
    return float(np.asarray(out["revenue"])[0])


# ------------------------------------------------------------- unit: cache
def test_cache_lru_budget_counters_and_invalidation():
    c = DeviceBlockCache(budget_bytes=4096)
    blk = lambda: [np.zeros(256, np.uint8)]  # 256-byte runs

    assert c.get(("a:s", 1, "tables")) is None  # miss counted
    assert c.install(("a:s", 1, "tables"), blk())
    assert c.install(("b:s", 1, "tables"), blk())
    assert c.get(("a:s", 1, "tables")) is not None
    st = c.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["installs"] == 2
    assert st["bytes"] == 512 and st["entries"] == 2

    # budget pressure evicts LRU-first ("b:s" is older than the
    # just-refreshed "a:s")
    for i in range(16):
        assert c.install(("c:s", i, "tables"), blk())
    st = c.stats()
    assert st["bytes"] <= 4096
    assert st["evictions"] > 0
    assert c.get(("b:s", 1, "tables")) is None

    # an entry bigger than the whole budget is rejected, not installed
    assert not c.install(("huge", 1, "x"), [np.zeros(8192, np.uint8)])
    assert c.stats()["rejected"] == 1

    # scope invalidation drops every entry of one set
    n = c.invalidate("c:s")
    assert n > 0 and all(c.get(("c:s", i, "tables")) is None
                         for i in range(16))

    # resize(0) disables: gets return None silently, installs refuse
    c.resize(0)
    assert not c.enabled
    assert c.get(("a:s", 1, "tables")) is None
    assert not c.install(("a:s", 2, "tables"), blk())


def test_cache_value_nbytes_counts_tables():
    from netsdb_tpu.storage.devcache import _value_nbytes

    t = ColumnTable({"a": np.zeros(10, np.int32),
                     "b": np.zeros(10, np.float32)}, {},
                    np.ones(10, np.bool_))
    assert _value_nbytes([t]) == 40 + 40 + 10
    assert _value_nbytes([(3, np.zeros(4, np.float32))]) == 64 + 16


# ------------------------------------------------------ unit: bucket ladder
def test_bucket_density_four_ladder():
    b2 = staging.bucket_rows
    # density 4 inserts the 1.25x/1.75x rungs
    assert b2(100, 4) == 112  # 64*1.75
    assert b2(113, 4) == 128
    assert b2(129, 4) == 160  # 128*1.25
    assert b2(8, 4) == 8      # floor shared
    prev = 0
    for n in range(1, 4000):
        b = b2(n, 4)
        assert b >= n
        # worst-case pad factor strictly tighter than density 2
        assert b <= max(8, (5 * n) // 4 + 2)
        assert b >= prev
        prev = b
    assert staging.pad_rows_target(129, True, density=4) == 160
    assert staging.pad_rows_target(129, True, density=2) == 192


# ------------------------------------------------- warm path, local client
def test_warm_query_miss_counter_flat_and_exact(config):
    c = Client(config)
    c.create_database("d")
    cols = _li_cols(1100)
    _paged_lineitem(c, cols)
    ref = _q06_ref(cols)

    got1 = _run_q06(c)
    np.testing.assert_allclose(got1, ref, rtol=1e-4)
    cache = c.store.device_cache()
    st1 = cache.stats()
    assert st1["installs"] >= 1

    got2 = _run_q06(c)  # WARM: zero host->device transfers
    st2 = cache.stats()
    assert st2["misses"] == st1["misses"], (st1, st2)
    assert st2["hits"] > st1["hits"]
    np.testing.assert_allclose(got2, got1, rtol=0, atol=0)

    # a DIFFERENT query over the same set reuses the SAME cached chunk
    # run (the cache holds set content, not query results)
    out = rdag.run_query(c, rdag.q06_sink("d", d0="1994-03-01",
                                          d1="1994-09-01"))
    assert float(np.asarray(out["revenue"])[0]) != got1
    st3 = cache.stats()
    assert st3["misses"] == st2["misses"]


def test_direct_write_invalidates_replace_and_append(config):
    c = Client(config)
    c.create_database("d")
    cols = _li_cols(900)
    _paged_lineitem(c, cols)
    _run_q06(c)
    _run_q06(c)  # warm

    # REPLACE: a fresh send_table must never serve the old blocks
    cols2 = _li_cols(900, seed=9)
    c.send_table("d", "lineitem", ColumnTable(cols2, {}))
    np.testing.assert_allclose(_run_q06(c), _q06_ref(cols2), rtol=1e-4)

    # APPEND through the store: version bumps, result covers both
    extra = _li_cols(137, seed=3)
    c.send_table("d", "lineitem", ColumnTable(extra, {}), append=True)
    merged = {k: np.concatenate([cols2[k], extra[k]]) for k in cols2}
    np.testing.assert_allclose(_run_q06(c), _q06_ref(merged), rtol=1e-4)

    # DIRECT pc.append (bypassing the store's version bump): the
    # handle's own mutation counter still unkeys the cached run
    pc = c.store.get_items(SetIdentifier("d", "lineitem"))[0]
    _run_q06(c)  # warm again
    extra2 = _li_cols(41, seed=5)
    pc.append({k: np.asarray(v) for k, v in extra2.items()})
    merged2 = {k: np.concatenate([merged[k], extra2[k]]) for k in merged}
    np.testing.assert_allclose(_run_q06(c), _q06_ref(merged2), rtol=1e-4)


def test_tiny_budget_streams_every_time_correctly(config):
    config.device_cache_bytes = 512  # smaller than any run
    c = Client(config)
    c.create_database("d")
    cols = _li_cols(700)
    _paged_lineitem(c, cols)
    for _ in range(2):
        np.testing.assert_allclose(_run_q06(c), _q06_ref(cols), rtol=1e-4)
    st = c.store.device_cache().stats()
    assert st["entries"] == 0 and st["hits"] == 0
    assert st["rejected"] >= 1  # runs refused, never thrash


def test_cached_blocks_survive_donated_folds(config):
    """Donation applies only to fold-carried accumulators, never to
    cache-owned blocks: with donation forced ON, repeated folds over
    the cached run leave its arrays bit-identical."""
    config.donate_fold_buffers = True
    c = Client(config)
    c.create_database("d")
    cols = _li_cols(600)
    _paged_lineitem(c, cols)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU warns donation unimplemented
        got1 = _run_q06(c)
        cache = c.store.device_cache()
        with cache._mu:
            (blocks, _), = [v for v in cache._entries.values()]
        before = np.asarray(blocks[0]["l_extendedprice"]).copy()
        got2 = _run_q06(c)
        got3 = _run_q06(c)
    np.testing.assert_array_equal(
        np.asarray(blocks[0]["l_extendedprice"]), before)
    assert got1 == got2 == got3


# --------------------------------------------- grace-hash pair overlap
def _grace_client(tmp_path, scale=6):
    from netsdb_tpu.workloads import tpch
    from netsdb_tpu.relational.queries import tables_from_rows

    tables = tables_from_rows(tpch.generate(scale=scale, seed=3))
    cfg = Configuration(root_dir=str(tmp_path / "grace"),
                        page_size_bytes=1024, page_pool_bytes=16384)
    c = Client(cfg)
    c.create_database("d")
    for name, t in tables.items():
        c.create_set("d", name, type_name="table",
                     storage="paged" if name == "lineitem" else "memory")
        c.send_table("d", name, t)
    cust = c.analyze_set("d", "customer")
    orders = c.analyze_set("d", "orders")
    c.create_set("d", "q03_build", type_name="table", storage="paged")
    c.execute_computations(rdag.q03_build_sink(
        "d", n_customers=cust["stats"]["c_custkey"].key_space,
        segment_code=cust["dicts"]["c_mktsegment"].index("BUILDING")))
    bpc = c.store.get_items(SetIdentifier("d", "q03_build"))[0]
    assert bpc.num_pages() > 1  # real partition pairs
    return c, orders["stats"]["o_orderkey"].key_space


def _grace_events(c, n_orders):
    staging.trace_events(True)
    try:
        rdag.run_query(c, rdag.q03_probe_sink("d", n_orders=n_orders))
        return staging.events()
    finally:
        staging.trace_events(False)


def _overlap_indices(evs):
    """(index of pair 1's build upload, index of pair 0's probe-stream
    finish) in the event log; None when absent."""
    build1 = next((i for i, (k, n, s) in enumerate(evs)
                   if k == "place" and n.startswith("grace-build:")
                   and s == 1), None)
    probe0_done = next((i for i, (k, n, _s) in enumerate(evs)
                        if k == "close" and n.startswith("tables:")
                        and "#gr" in n), None)
    return build1, probe0_done


def test_grace_pairs_overlap_and_sequential_does_not(tmp_path):
    c, n_orders = _grace_client(tmp_path)

    # warm the jit caches first: the assertion is about STEADY-STATE
    # overlap, and on a 2-core box the cold run's compilation can
    # starve the build staging worker long enough to blur the margin
    rdag.run_query(c, rdag.q03_probe_sink("d", n_orders=n_orders))

    evs = _grace_events(c, n_orders)
    build1, probe0_done = _overlap_indices(evs)
    assert build1 is not None and probe0_done is not None, evs[:20]
    # OVERLAP: pair 1's build upload began BEFORE pair 0's probe
    # stream finished (the acceptance criterion, via staging counters)
    assert build1 < probe0_done, (build1, probe0_done)
    assert staging.active_count() == 0  # no leaked stagers

    # counter-factual: stage_depth=0 degrades to the sequential loop
    c.store.page_store().config.stage_depth = 0
    evs = _grace_events(c, n_orders)
    build1, probe0_done = _overlap_indices(evs)
    assert build1 is not None and probe0_done is not None
    assert build1 > probe0_done, (build1, probe0_done)
    assert staging.active_count() == 0


def test_grace_death_mid_pair_leaves_no_leaks(tmp_path, monkeypatch):
    """A grace join dying mid-pair must join its build stager (leak
    registry clean) and reclaim every spill partition."""
    from netsdb_tpu.plan import executor

    c, n_orders = _grace_client(tmp_path, scale=4)
    calls = {"n": 0}
    real = executor._part_chunks

    def dying(ppc, placement):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("injected mid-pair death")
        return real(ppc, placement)

    monkeypatch.setattr(executor, "_part_chunks", dying)
    with pytest.raises(RuntimeError, match="mid-pair death"):
        rdag.run_query(c, rdag.q03_probe_sink("d", n_orders=n_orders))
    deadline = time.monotonic() + 10
    while staging.active_count() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert staging.active_count() == 0
    # spill partitions were dropped: only the two stored relations'
    # arena sets remain referenced
    ps = c.store.page_store()
    assert not any("#gr" in name for name in ps._ids)


# ------------------------------------------------------- serve-path tests
@pytest.fixture()
def daemon(tmp_path):
    from netsdb_tpu.serve.server import ServeController

    ctl = ServeController(Configuration(root_dir=str(tmp_path / "srv")),
                          port=0)
    port = ctl.start()
    yield ctl, f"127.0.0.1:{port}"
    ctl.shutdown()


def _remote(addr, **kw):
    from netsdb_tpu.serve.client import RemoteClient, RetryPolicy

    kw.setdefault("retry", RetryPolicy(max_attempts=1))
    return RemoteClient(addr, **kw)


def _serve_q06(ctl, client):
    client.execute_computations(rdag.q06_sink("d"), job_name="q06",
                                fetch_results=False)
    out = ctl.library.get_table("d", "q06_out")
    return float(np.asarray(out["revenue"])[0])


def test_serve_warm_execute_then_direct_write_never_stale(daemon):
    ctl, addr = daemon
    c = _remote(addr)
    c.create_database("d")
    c.create_set("d", "lineitem", type_name="table", storage="paged")
    cols = _li_cols(1000)
    c.send_table("d", "lineitem", ColumnTable(cols, {}))

    np.testing.assert_allclose(_serve_q06(ctl, c), _q06_ref(cols),
                               rtol=1e-4)
    cache = ctl.library.store.device_cache()
    m0 = cache.stats()["misses"]
    _serve_q06(ctl, c)  # warm EXECUTE over the serve path
    st = cache.stats()
    assert st["misses"] == m0 and st["hits"] > 0

    # stats surface through the serve STATUS path
    wire = c.collect_stats()
    assert "device_cache" in wire and wire["device_cache"]["hits"] > 0

    # direct write through the serve path: next EXECUTE sees new data
    cols2 = _li_cols(1000, seed=7)
    c.send_table("d", "lineitem", ColumnTable(cols2, {}))
    np.testing.assert_allclose(_serve_q06(ctl, c), _q06_ref(cols2),
                               rtol=1e-4)
    c.close()


@pytest.mark.chaos
def test_mid_bulk_fault_freezes_version_and_cache(daemon, tmp_path):
    """A BULK conversation faulted before COMMIT must not advance the
    set version — the warm cache keeps serving the LAST COMMITTED
    content (which is correct: the torn ingest never applied)."""
    from netsdb_tpu.serve.chaos import ChaosInjector
    from netsdb_tpu.serve.server import ServeController

    chaos = ChaosInjector()
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "cs")),
                          port=0, chaos=chaos, frame_timeout_s=5.0)
    addr = f"127.0.0.1:{ctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table", storage="paged")
        cols = _li_cols(1200)
        c.send_table("d", "lineitem", ColumnTable(cols, {}))
        ref = _q06_ref(cols)
        np.testing.assert_allclose(_serve_q06(ctl, c), ref, rtol=1e-4)
        _serve_q06(ctl, c)  # warm
        # drain c's async PUT_TRACE shipper BEFORE arming: a background
        # ship landing after arm() would consume the fault sequence
        # meant for the bulk conversation — and the shipper swallows
        # the injected error by design (best-effort)
        assert c.flush_traces(10.0)
        ident = SetIdentifier("d", "lineitem")
        v0 = ctl.library.store.version_of(ident)

        # fault the NEXT bulk conversation mid-stream: let BEGIN and
        # chunk 1 through (delays), kill the connection on chunk 2
        chaos.arm("delay", "delay", "kill", where="recv", delay_s=0.0)
        killer = _remote(addr, ship_traces=False)
        with pytest.raises(Exception):
            killer.send_table("d", "lineitem",
                              ColumnTable(_li_cols(1200, seed=8), {}),
                              pipeline=True, chunk_bytes=1 << 10)
        killer.close()
        assert any(f[0] == "kill" for f in chaos.faults)

        # the version did NOT advance and the warm path still serves
        # the committed content
        assert ctl.library.store.version_of(ident) == v0
        m0 = ctl.library.store.device_cache().stats()["misses"]
        np.testing.assert_allclose(_serve_q06(ctl, c), ref, rtol=1e-4)
        assert ctl.library.store.device_cache().stats()["misses"] == m0
        c.close()
    finally:
        ctl.shutdown()


def test_mirrored_write_invalidates_follower_cache(tmp_path):
    """Leader + follower: a mirrored SEND_DATA bumps the FOLLOWER's set
    version too, so its warm cache never serves the pre-write blocks."""
    from netsdb_tpu.serve.server import ServeController

    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                           port=0)
    fport = fctl.start()
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m")),
                           port=0, followers=[f"127.0.0.1:{fport}"])
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table", storage="paged")
        cols = _li_cols(800)
        c.send_table("d", "lineitem", ColumnTable(cols, {}))
        # mirrored EXECUTE warms BOTH daemons' caches
        np.testing.assert_allclose(_serve_q06(mctl, c), _q06_ref(cols),
                                   rtol=1e-4)
        _serve_q06(mctl, c)
        assert fctl.library.store.device_cache().stats()["installs"] >= 1

        cols2 = _li_cols(800, seed=11)
        c.send_table("d", "lineitem", ColumnTable(cols2, {}))  # mirrored
        _serve_q06(mctl, c)  # mirrored EXECUTE re-runs on the follower
        out = fctl.library.get_table("d", "q06_out")
        np.testing.assert_allclose(float(np.asarray(out["revenue"])[0]),
                                   _q06_ref(cols2), rtol=1e-4)
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


def test_resync_restore_clears_cache_and_serves_fresh(tmp_path):
    """A follower restored from a leader snapshot must drop every
    cached block: its next query serves the LEADER's data."""
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu.storage import checkpoint

    leader = ServeController(Configuration(root_dir=str(tmp_path / "l")),
                             port=0)
    follower = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                               port=0)
    try:
        lcols = _li_cols(500, seed=1)
        leader.library.create_database("d")
        leader.library.create_set("d", "lineitem", type_name="table",
                                  storage="paged")
        leader.library.send_table("d", "lineitem", ColumnTable(lcols, {}))

        fcols = _li_cols(500, seed=2)
        follower.library.create_database("d")
        follower.library.create_set("d", "lineitem", type_name="table",
                                    storage="paged")
        follower.library.send_table("d", "lineitem",
                                    ColumnTable(fcols, {}))
        # warm the follower's cache on ITS pre-resync data
        _run_q06(follower.library)
        _run_q06(follower.library)
        assert follower.library.store.device_cache().stats()["hits"] > 0

        blob = checkpoint.dumps_store(leader._snapshot_state())
        typ, reply = follower._on_resync_follower({"snapshot_blob": blob})
        assert reply["restored_sets"] >= 1
        assert follower.last_resync_mode == "wire"
        assert follower.library.store.device_cache().stats()["entries"] == 0
        np.testing.assert_allclose(_run_q06(follower.library),
                                   _q06_ref(lcols), rtol=1e-4)
    finally:
        leader.shutdown()
        follower.shutdown()


def test_paged_matrix_resyncs_page_by_page(tmp_path):
    """PR 2 leftover regression: a paged MATRIX must survive
    RESYNC_FOLLOWER with its content (it used to arrive empty)."""
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu.storage import checkpoint

    leader = ServeController(Configuration(root_dir=str(tmp_path / "l"),
                                           page_size_bytes=1024),
                             port=0)
    follower = ServeController(Configuration(root_dir=str(tmp_path / "f"),
                                             page_size_bytes=1024),
                               port=0)
    try:
        rng = np.random.default_rng(0)
        m = rng.standard_normal((96, 16)).astype(np.float32)
        rhs = rng.standard_normal((16, 4)).astype(np.float32)
        leader.library.create_database("d")
        leader.library.create_set("d", "w", storage="paged")
        leader.library.send_matrix("d", "w", m)
        assert leader.library.store.page_store().num_blocks(
            [i for i in leader.library.store.get_items(
                SetIdentifier("d", "w"))][0].ident + ".mat") > 1

        blob = checkpoint.dumps_store(leader._snapshot_state())
        follower._on_resync_follower({"snapshot_blob": blob})
        got = follower.library.paged_matmul("d", "w", rhs)
        np.testing.assert_allclose(got, m @ rhs, rtol=1e-4, atol=1e-4)
    finally:
        leader.shutdown()
        follower.shutdown()
