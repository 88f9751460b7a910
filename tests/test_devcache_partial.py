"""Block-granular partial-run device caching (PR 14).

What these tests pin:

* **dirty-range invalidation** — an append to a cached paged set
  drops NOTHING (pre-append blocks stay resident, the counters prove
  it) and a warm re-query re-stages ONLY the appended tail;
* **range stitching** — cold, warm and mixed (partially evicted)
  streams produce byte-identical results to an uncached execution,
  including a grace-hash build side and a sharded 4-daemon scatter
  query;
* **partial consumption** — an early-exited stream keeps the
  consumed prefix cached instead of discarding everything;
* **pinning** — head blocks under ``device_cache_pin_bytes`` survive
  LRU pressure in install order; invalidation still drops them;
* **off mode** — ``device_cache_partial=False`` restores the PR 4
  whole-run behavior byte-for-byte (key shapes, counters, stats
  surface);
* **serve paths** — mirrored appends keep the follower's pre-append
  blocks, resync-restore clears everything, a shard handoff drain
  lands as an append-tail dirty range on the readmitted shard;
* the satellites: the remainder-keyed AffinityGate, the derived
  ``rowwise`` registry + shadow lint rule, and the pinned SLO
  load-shedding formula.
"""

import contextlib
import threading

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
from netsdb_tpu.plan import staging
from netsdb_tpu.relational import dag as rdag
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.storage.devcache import DeviceBlockCache
from netsdb_tpu.storage.store import SetIdentifier


def _li_cols(n, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "l_shipdate": rng.integers(19940101, 19950101, n, dtype=np.int32),
        "l_discount": np.full(n, 0.06, np.float32),
        "l_quantity": np.full(n, 10.0, np.float32),
        "l_extendedprice": rng.uniform(1000, 2000, n).astype(np.float32),
    }


def _client(tmp_path, name="p", **cfg):
    cfg.setdefault("page_size_bytes", 4096)
    c = Client(Configuration(root_dir=str(tmp_path / name), **cfg))
    c.create_database("d")
    return c


def _load(c, cols, set_name="lineitem"):
    if c.set_exists("d", set_name):
        c.remove_set("d", set_name)
    c.create_set("d", set_name, type_name="table", storage="paged")
    c.send_table("d", set_name, ColumnTable(cols, {}))


def _q06(c):
    out = rdag.run_query(c, rdag.q06_sink("d"))
    return float(np.asarray(out["revenue"])[0])


# ------------------------------------------------- the tentpole proof
def test_append_invalidates_only_tail_range(tmp_path):
    """The partial-invalidation acceptance shape at test scale: a
    small append to a warm multi-block cached set leaves EVERY
    pre-append block resident (zero evictions, zero dropped entries)
    and the warm re-query serves them from HBM (partial_hits) while
    staging only the appended tail."""
    c = _client(tmp_path)
    cache = c.store.device_cache()
    assert cache.partial
    cols = _li_cols(6000)
    _load(c, cols)

    got = _q06(c)          # cold: installs per block
    st0 = cache.stats()
    blocks_before = st0["entries"]
    assert blocks_before > 4  # genuinely multi-block

    _q06(c)                # warm: full coverage
    st1 = cache.stats()
    assert st1["hits"] == st0["hits"] + 1
    assert st1["partial_hits"] >= blocks_before

    epoch0 = cache.scope_epoch("d:lineitem")
    extra = _li_cols(300, seed=3)
    c.send_table("d", "lineitem", ColumnTable(extra, {}), append=True)
    # ONE epoch bump per store append (pc.append owns the range
    # invalidation; _touch only logs — a double bump would refuse
    # installs of streams planned between the two)
    assert cache.scope_epoch("d:lineitem") == epoch0 + 1
    # the last-planned total is stale after a growing write: coverage
    # must NOT report "fully resident" (the affinity gate would admit
    # every warm re-query to race the cold-tail install)
    _cov, total = cache.coverage("d:lineitem")
    assert total is None
    st2 = cache.stats()
    # the append dropped NOTHING: the dirty tail range intersects no
    # pre-append block
    assert st2["entries"] == blocks_before
    assert st2["evictions"] == 0
    assert st2["invalidations"] == 0
    assert st2["dirty_invalidations"] == 0

    staged0 = obs.REGISTRY.counter("staging.chunks").value
    merged = {k: np.concatenate([cols[k], extra[k]]) for k in cols}
    got2 = _q06(c)
    ref = float((merged["l_extendedprice"]
                 * merged["l_discount"]).sum(dtype=np.float64))
    np.testing.assert_allclose(got2, ref, rtol=1e-4)
    st3 = cache.stats()
    new_blocks = st3["entries"] - blocks_before
    assert new_blocks >= 1
    # ONLY the tail staged; every pre-append block rode partial hits
    staged = obs.REGISTRY.counter("staging.chunks").value - staged0
    assert staged == new_blocks, (staged, new_blocks)
    assert st3["partial_hits"] >= st1["partial_hits"] + blocks_before
    assert st3["evictions"] == 0
    # the set's dirty log recorded the tail range, not whole-scope
    stats = c.store.set_stats(SetIdentifier("d", "lineitem"))
    assert stats["dirty_ranges"][-1] == (6000, 6300)
    assert staging.active_count() == 0


def test_stitched_mixed_stream_byte_equal_uncached(tmp_path):
    """Cold, warm and MIXED (middle range invalidated) stitched
    streams must be byte-equal to an uncached execution — stitching
    preserves chunk order and content exactly."""
    cols = _li_cols(5000, seed=7)
    cu = _client(tmp_path, "uncached", device_cache_bytes=0)
    _load(cu, cols)
    want = _q06(cu)

    c = _client(tmp_path, "cached")
    cache = c.store.device_cache()
    _load(c, cols)
    assert _q06(c) == want            # cold (installing)
    assert _q06(c) == want            # warm (fully stitched)

    # mixed: punch a hole in the MIDDLE of the cached range
    pc = c.store.get_items(SetIdentifier("d", "lineitem"))[0]
    ranges = pc.block_ranges()
    assert len(ranges) > 3
    mid = ranges[len(ranges) // 2]
    dropped = cache.invalidate_range("d:lineitem", mid[0], mid[1])
    assert dropped >= 1
    st = cache.stats()
    assert st["dirty_invalidations"] >= 1
    assert _q06(c) == want            # stitched around the hole
    assert cache.stats()["stitched_ranges"] > st["stitched_ranges"]
    assert staging.active_count() == 0


def test_partial_consumption_caches_consumed_prefix(tmp_path):
    """An early-exited stream keeps what it paid for: the consumed
    prefix (plus at most the staging depth ahead) is resident, and the
    next full stream serves it as partial hits."""
    c = _client(tmp_path)
    cache = c.store.device_cache()
    cols = _li_cols(6000, seed=5)
    _load(c, cols)
    pc = c.store.get_items(SetIdentifier("d", "lineitem"))[0]
    nblocks = len(pc.block_ranges())
    assert nblocks > 4

    consumed = 2
    with contextlib.closing(pc.stream_tables()) as chunks:
        for i, _chunk in enumerate(chunks):
            if i + 1 >= consumed:
                break
    st = cache.stats()
    # the whole-run design installed NOTHING on early exit; partial
    # mode keeps the consumed prefix (bounded by consumed + depth)
    assert st["entries"] >= consumed
    assert st["entries"] < nblocks
    assert st["installs"] == 0  # run-level install = full run only

    before = st["entries"]
    _q06(c)  # full stream: prefix stitched, remainder installed
    st2 = cache.stats()
    assert st2["partial_hits"] >= before
    assert st2["entries"] == nblocks
    assert st2["installs"] == 1
    assert staging.active_count() == 0


# -------------------------------------------------------- unit: pinning
def _blk(nbytes=256):
    return np.zeros(nbytes, np.uint8)


def test_pin_budget_keeps_head_blocks_under_pressure():
    c = DeviceBlockCache(budget_bytes=2048, partial=True,
                         pin_bytes=1024)
    base = ("a:s", "tables", 8, None)
    ranges = [(i * 100, (i + 1) * 100) for i in range(8)]
    epoch, covered = c.plan_ranges(base, ranges)
    assert covered == {}
    for rng in ranges:
        assert c.install_block(base, rng, _blk(), epoch)
    st = c.stats()
    assert st["entries"] == 8
    # head blocks pinned in install order until the budget ran out
    assert st["pinned_bytes"] == 1024  # 4 x 256-byte head blocks

    # pressure from another scope: unpinned entries evict LRU-first,
    # pinned head blocks NEVER do
    bbase = ("b:s", "tables", 8, None)
    bepoch, _ = c.plan_ranges(bbase, ranges)
    for rng in ranges:
        assert c.install_block(bbase, rng, _blk(), bepoch)
    _, covered = c.plan_ranges(base, ranges)
    kept = sorted(covered)
    assert [r for r in ranges[:4]] == kept[:4]  # the pinned head
    assert c.stats()["evictions"] >= 4

    # a cache full of pinned+fresh entries refuses, never thrashes pins
    st = c.stats()
    assert st["pinned_bytes"] == 1024

    # dirty-range invalidation outranks pinning
    c.invalidate_range("a:s", 0, 100)
    st = c.stats()
    assert st["pinned_bytes"] == 1024 - 256
    _, covered = c.plan_ranges(base, ranges)
    assert (0, 100) not in covered

    # whole-scope invalidation drops the rest and zeroes the pins
    c.invalidate("a:s")
    assert c.stats()["pinned_bytes"] == 0


def test_install_epoch_gate_refuses_racing_writes():
    c = DeviceBlockCache(budget_bytes=4096, partial=True)
    base = ("a:s", "tables", 8, None)
    epoch, _ = c.plan_ranges(base, [(0, 100), (100, 200)])
    assert c.install_block(base, (0, 100), _blk(), epoch)
    # a write lands mid-stream: the epoch moves, in-flight installs
    # are refused (a stale block must never squat on the budget)
    c.invalidate_range("a:s", 100, None)
    assert not c.install_block(base, (100, 200), _blk(), epoch)
    epoch2, covered = c.plan_ranges(base, [(0, 100), (100, 200)])
    assert epoch2 == epoch + 1
    assert (100, 200) not in covered
    assert c.install_block(base, (100, 200), _blk(), epoch2)


def test_dirty_log_bounded_folds_to_whole_scope(tmp_path):
    c = _client(tmp_path, device_cache_dirty_log=4)
    _load(c, _li_cols(1200))
    ident = SetIdentifier("d", "lineitem")
    for i in range(6):
        c.send_table("d", "lineitem",
                     ColumnTable(_li_cols(50, seed=i + 1), {}),
                     append=True)
    log = c.store.set_stats(ident)["dirty_ranges"]
    assert len(log) <= 5  # bound + the post-fold entry
    assert (0, None) in log  # overflow folded to whole-scope


# ------------------------------------------------------------ off mode
def test_off_mode_restores_whole_run_behavior(tmp_path):
    """``device_cache_partial=off`` is the PR 4 cache byte-for-byte:
    whole-run entries under version-keyed 6-tuples, run-level counters
    only (no partial keys on the stats surface), one entry per run,
    append unkeys the whole run."""
    c = _client(tmp_path, device_cache_partial=False)
    cache = c.store.device_cache()
    assert not cache.partial
    cols = _li_cols(3000)
    _load(c, cols)
    _q06(c)
    st = cache.stats()
    # the PR 4 stats surface exactly — no partial-mode keys
    assert sorted(st) == ["budget_bytes", "bytes", "entries",
                          "evictions", "hits", "installs",
                          "invalidations", "misses", "rejected"]
    assert st["entries"] == 1  # ONE whole-run entry
    with cache._mu:
        (key,) = list(cache._entries)
    # the PR 4 key: (scope, version, mutations, kind, bucket, sharding)
    assert key[0] == "d:lineitem" and key[3] == "tables"
    assert len(key) == 6

    _q06(c)
    st2 = cache.stats()
    assert st2["hits"] == st["hits"] + 1
    assert st2["misses"] == st["misses"]

    # an append invalidates the WHOLE run (the behavior partial mode
    # exists to fix — off mode must keep it)
    c.send_table("d", "lineitem", ColumnTable(_li_cols(50, seed=2), {}),
                 append=True)
    assert cache.stats()["entries"] == 0


def test_partial_lookups_feed_run_level_slo_counters(tmp_path):
    """The devcache hit-rate SLO feed keeps its meaning in partial
    mode: one lookup per stream consult, full coverage = hit."""
    c = _client(tmp_path)
    lk0 = obs.REGISTRY.counter("devcache.lookups").value
    h0 = obs.REGISTRY.counter("devcache.hits").value
    _load(c, _li_cols(2000))
    _q06(c)
    _q06(c)
    assert obs.REGISTRY.counter("devcache.lookups").value == lk0 + 2
    assert obs.REGISTRY.counter("devcache.hits").value == h0 + 1


# ------------------------------------------------- grace-hash build side
def test_grace_hash_q03_byte_equal_with_partial_cache(tmp_path):
    """The one-pass grace-hash join (paged build side) under partial
    caching: result byte-equal to the devcache-off run — spill
    partitions stay uncached, the fact stream's cached blocks stitch
    correctly into the partition pass."""
    from netsdb_tpu.relational.queries import tables_from_rows
    from netsdb_tpu.workloads import tpch

    tables = tables_from_rows(tpch.generate(scale=5, seed=3))

    def build(name, **cfg):
        cfg.setdefault("page_size_bytes", 1024)
        cfg.setdefault("page_pool_bytes", 16384)
        c = _client(tmp_path, name, **cfg)
        for tname, t in tables.items():
            c.create_set("d", tname, type_name="table",
                         storage="paged" if tname == "lineitem"
                         else "memory")
            c.send_table("d", tname, t)
        cust = c.analyze_set("d", "customer")
        c.create_set("d", "q03_build", type_name="table",
                     storage="paged")
        c.execute_computations(rdag.q03_build_sink(
            "d", n_customers=cust["stats"]["c_custkey"].key_space,
            segment_code=cust["dicts"]["c_mktsegment"].index(
                "BUILDING")))
        orders = c.analyze_set("d", "orders")
        return c, orders["stats"]["o_orderkey"].key_space

    def q03_rows(c, n_orders):
        out = rdag.run_query(c, rdag.q03_probe_sink(
            "d", n_orders=n_orders))
        return rdag.q03_rows(out)

    c0, n_orders = build("q03-off", device_cache_bytes=0)
    want = q03_rows(c0, n_orders)
    c1, n_orders1 = build("q03-on")
    assert n_orders1 == n_orders
    assert c1.store.device_cache().partial
    got_cold = q03_rows(c1, n_orders)
    got_warm = q03_rows(c1, n_orders)
    assert got_cold == want
    assert got_warm == want
    assert want  # non-trivial result
    # spill partitions never entered the cache (unbound temporaries)
    cache1 = c1.store.device_cache()
    with cache1._mu:
        assert not any("#gr" in str(k[0]) for k in cache1._entries)
    assert staging.active_count() == 0


# ------------------------------------------------------- serve paths
def _remote(addr, **kw):
    from netsdb_tpu.serve.client import RemoteClient, RetryPolicy

    kw.setdefault("retry", RetryPolicy(max_attempts=1))
    return RemoteClient(addr, **kw)


def _serve_q06(ctl, client):
    client.execute_computations(rdag.q06_sink("d"), job_name="q06",
                                fetch_results=False)
    out = ctl.library.get_table("d", "q06_out")
    return float(np.asarray(out["revenue"])[0])


def test_mirrored_append_keeps_follower_blocks(tmp_path):
    """A mirrored APPEND lands on the follower through the same
    ranged ``_touch``: the follower's pre-append cached blocks stay
    resident and its mirrored re-EXECUTE stitches them."""
    from netsdb_tpu.serve.server import ServeController

    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f"),
                                         page_size_bytes=4096), port=0)
    fport = fctl.start()
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m"),
                                         page_size_bytes=4096),
                           port=0, followers=[f"127.0.0.1:{fport}"])
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table", storage="paged")
        cols = _li_cols(4000)
        c.send_table("d", "lineitem", ColumnTable(cols, {}))
        _serve_q06(mctl, c)  # mirrored EXECUTE warms BOTH caches
        fcache = fctl.library.store.device_cache()
        blocks = fcache.stats()["entries"]
        assert blocks > 2

        extra = _li_cols(200, seed=9)
        c.send_table("d", "lineitem", ColumnTable(extra, {}),
                     append=True)  # mirrored append
        st = fcache.stats()
        assert st["entries"] == blocks      # nothing dropped
        assert st["evictions"] == 0
        _serve_q06(mctl, c)  # mirrored re-EXECUTE stitches on follower
        assert fcache.stats()["partial_hits"] >= blocks
        merged = {k: np.concatenate([cols[k], extra[k]]) for k in cols}
        out = fctl.library.get_table("d", "q06_out")
        ref = float((merged["l_extendedprice"]
                     * merged["l_discount"]).sum(dtype=np.float64))
        np.testing.assert_allclose(float(np.asarray(out["revenue"])[0]),
                                   ref, rtol=1e-4)
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


def test_resync_restore_clears_partial_cache(tmp_path):
    """A snapshot-restored follower drops every block entry — the
    whole store was replaced, there is no range to keep."""
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu.storage import checkpoint

    leader = ServeController(Configuration(root_dir=str(tmp_path / "l"),
                                           page_size_bytes=4096), port=0)
    follower = ServeController(
        Configuration(root_dir=str(tmp_path / "fw"),
                      page_size_bytes=4096), port=0)
    try:
        lcols = _li_cols(1500, seed=1)
        leader.library.create_database("d")
        leader.library.create_set("d", "lineitem", type_name="table",
                                  storage="paged")
        leader.library.send_table("d", "lineitem",
                                  ColumnTable(lcols, {}))
        follower.library.create_database("d")
        follower.library.create_set("d", "lineitem", type_name="table",
                                    storage="paged")
        follower.library.send_table("d", "lineitem",
                                    ColumnTable(_li_cols(1500, seed=2),
                                                {}))
        _q06(follower.library)
        fcache = follower.library.store.device_cache()
        assert fcache.stats()["entries"] > 0
        blob = checkpoint.dumps_store(leader._snapshot_state())
        follower._on_resync_follower({"snapshot_blob": blob})
        assert fcache.stats()["entries"] == 0
        ref = float((lcols["l_extendedprice"]
                     * lcols["l_discount"]).sum(dtype=np.float64))
        np.testing.assert_allclose(_q06(follower.library), ref,
                                   rtol=1e-4)
    finally:
        leader.shutdown()
        follower.shutdown()


def test_handoff_drain_lands_as_tail_range_on_shard(tmp_path):
    """The shard-scoped resync: a readmitted shard's drained handoff
    batch applies as an APPEND — its pre-buffered cached blocks stay
    resident (dirty-range coherence across the pool)."""
    from tests.test_scaleout import _load_q01, pool
    from netsdb_tpu.workloads.scaleout import (scale_rows,
                                               scaleout_q01_sink,
                                               scaleout_table)

    with pool(tmp_path, n_workers=2,
              leader_kwargs={"heartbeat_interval_s": 60.0},
              storage_kwargs={"page_size_bytes": 64 * 1024}) \
            as (leader, workers, addr):
        from netsdb_tpu.serve.client import RemoteClient

        # default retry policy: the post-eviction stale-epoch reject
        # must refresh the placement map and re-route
        c = RemoteClient(addr)
        _load_q01(c, rows=9000, sharded=True)
        sink = scaleout_q01_sink("d")
        c.execute_computations(sink, job_name="warm1",
                               fetch_results=False)
        want = scale_rows(c, "d", "scale_q01_out")
        w0 = workers[0]
        w0_addr = f"127.0.0.1:{w0.port}"
        w0_cache = w0.library.store.device_cache()
        blocks = w0_cache.stats()["entries"]
        assert blocks > 0  # the scatter subplan warmed the shard

        leader._evict_shard(w0_addr, "test eviction")
        # first append: the client's stale map rejects + refreshes
        # (the evicted worker may still accept its slot directly —
        # the benign net-split shape test_scaleout pins)
        c.send_table("d", "lineitem", scaleout_table(3000, seed=4),
                     append=True)
        # second append rides the CURRENT map: the degraded slot's
        # partition buffers at the leader (>= 1 — whether the FIRST
        # append landed directly or buffered depends on when the
        # eviction's epoch push reached the evicted worker)
        c.send_table("d", "lineitem", scaleout_table(3000, seed=5),
                     append=True)
        assert leader.shards.handoff_pending(w0_addr) >= 1
        assert leader._try_readmit_shard(w0_addr)
        st = w0_cache.stats()
        assert st["entries"] >= blocks   # pre-buffered blocks resident
        assert st["evictions"] == 0

        # post-drain scatter query equals a fresh full computation
        c.execute_computations(sink, job_name="warm2",
                               fetch_results=False)
        got = scale_rows(c, "d", "scale_q01_out")
        assert got != want  # the append changed the answer
        assert w0_cache.stats()["partial_hits"] > 0
        c.close()


def test_scatter_4daemon_partial_cache_byte_equal(tmp_path):
    """The sharded 4-daemon (leader + 3 workers) scatter query under
    partial caching: cold and warm scatter runs byte-equal to the
    single-node run; every shard serves its second run from resident
    blocks."""
    from tests.test_scaleout import _load_q01, pool, solo
    from netsdb_tpu.workloads.scaleout import (scale_rows,
                                               scaleout_q01_sink)

    storage = {"page_size_bytes": 64 * 1024}
    with pool(tmp_path, n_workers=3, storage_kwargs=storage) \
            as (leader, workers, addr):
        c = _remote(addr)
        _load_q01(c, rows=12000, sharded=True)
        sink = scaleout_q01_sink("d")
        c.execute_computations(sink, job_name="cold",
                               fetch_results=False)
        cold = scale_rows(c, "d", "scale_q01_out")
        c.execute_computations(sink, job_name="warm",
                               fetch_results=False)
        warm = scale_rows(c, "d", "scale_q01_out")
        hits = sum(d.library.store.device_cache().stats()["hits"]
                   for d in [leader] + workers)
        assert hits >= 4  # every daemon's slot re-served resident
        c.close()
    with solo(tmp_path, storage_kwargs=storage) as (_ctl, saddr):
        sc = _remote(saddr)
        _load_q01(sc, rows=12000, sharded=False)
        sc.execute_computations(scaleout_q01_sink("d"),
                                job_name="solo", fetch_results=False)
        want = scale_rows(sc, "d", "scale_q01_out")
        sc.close()
    assert cold == want and warm == want


# ----------------------------------------- satellite: affinity ranges
def test_affinity_gate_remainder_keyed():
    """The range-aware gate: fully-covered scopes admit immediately,
    a partial remainder serializes exactly one gap installer, and the
    remainder start is recorded."""
    from netsdb_tpu.serve.sched.policy import AffinityGate

    state = {"s": 500}  # covered prefix: partial

    def probe(scope):
        return state[scope]

    gate = AffinityGate(probe, wait_s=5.0)
    started = threading.Event()
    release = threading.Event()
    order = []

    def installer():
        with gate.admit(["s"]):
            order.append("install-start")
            started.set()
            release.wait(5.0)
            order.append("install-end")

    t = threading.Thread(target=installer, daemon=True)
    t.start()
    assert started.wait(5.0)
    assert gate._remainder.get("s") == 500  # the cold remainder start

    # a sibling over the same partial scope waits for the installer
    def sibling():
        with gate.admit(["s"]):
            order.append("sibling")

    t2 = threading.Thread(target=sibling, daemon=True)
    t2.start()
    t2.join(0.3)
    assert t2.is_alive()  # parked behind the gap installer

    # a query arriving after coverage completed admits immediately,
    # without touching the gate
    state["s"] = True
    done = threading.Event()

    def warm_query():
        with gate.admit(["s"]):
            done.set()

    threading.Thread(target=warm_query, daemon=True).start()
    assert done.wait(2.0)  # admitted while the installer still runs

    release.set()
    t.join(5.0)
    t2.join(5.0)
    assert not t2.is_alive()
    assert order[0] == "install-start"
    assert "sibling" in order and "install-end" in order
    assert order.index("install-end") < order.index("sibling")
    assert "s" not in gate._remainder


# -------------------------------------------- satellite: rowwise derive
def test_rowwise_derived_from_registry():
    from netsdb_tpu.plan.computations import (Apply, ScanSet,
                                              rowwise_safe)

    scan = ScanSet("d", "s")
    assert rowwise_safe("pre:affine")
    assert not rowwise_safe("pre")          # no namespace match
    assert not rowwise_safe("suite:q01")
    a = Apply(scan, lambda t: t, label="pre:affine")
    assert a.rowwise and not a.rowwise_declared
    b = Apply(scan, lambda t: t, label="myfn")
    assert not b.rowwise
    # an explicit declaration ALWAYS wins — both directions
    c = Apply(scan, lambda t: t, label="pre:affine", rowwise=False)
    assert not c.rowwise and c.rowwise_declared
    d = Apply(scan, lambda t: t, label="custom", rowwise=True)
    assert d.rowwise and d.rowwise_declared


def test_rowwise_shadow_rule_flags_redundant_declaration(tmp_path):
    from netsdb_tpu.analysis import run_lint

    bad = tmp_path / "bad_rw.py"
    bad.write_text(
        "from netsdb_tpu.plan.computations import Apply\n"
        "n = Apply(x, lambda t: t, label='pre:affine', rowwise=True)\n")
    good = tmp_path / "good_rw.py"
    good.write_text(
        "from netsdb_tpu.plan.computations import Apply\n"
        "n = Apply(x, lambda t: t, label='pre:affine')\n"
        "m = Apply(x, lambda t: t, label='custom', rowwise=True)\n")
    diags = run_lint(paths=[str(bad)], rules=["rowwise-shadow"],
                     select_all=True)
    assert len(diags) == 1 and diags[0].rule == "rowwise-shadow"
    assert run_lint(paths=[str(good)], rules=["rowwise-shadow"],
                    select_all=True) == []


def test_fused_prechain_still_grafts_with_derived_rowwise(tmp_path):
    """The fusion graft path reads the DERIVED declaration: a
    ``pre:affine`` chain over a paged fact fuses into the fold's chunk
    step without a per-node rowwise argument, result exact."""
    import jax.numpy as jnp

    from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet
    from netsdb_tpu.plan.fold import single_pass

    c = _client(tmp_path, "fz")
    c.create_set("d", "fact", type_name="table", storage="paged")
    rng = np.random.default_rng(0)
    k = rng.integers(0, 8, 4000, dtype=np.int32)
    v = rng.uniform(0.0, 10.0, 4000).astype(np.float32)
    c.send_table("d", "fact", ColumnTable({"k": k, "v": v}, {}))

    def sink():
        s = ScanSet("d", "fact")
        pre = Apply(s, lambda t: ColumnTable(
            {"k": t["k"], "v": t["v"] * 1.5 + 0.25},
            t.dicts, t.valid), label="pre:affine")
        assert pre.rowwise  # derived, not declared

        def init(prev, src):
            return jnp.zeros((8,), jnp.float32)

        def step(state, chunk):
            seg = jnp.where(chunk.mask(), chunk["k"], 0)
            vals = jnp.where(chunk.mask(), chunk["v"], 0.0)
            import jax

            return state + jax.ops.segment_sum(vals, seg,
                                               num_segments=8)

        agg = Apply(pre, fold=single_pass(init, step,
                                          lambda st, src: st),
                    label="segsum")
        return WriteSet(agg, "d", "out")

    res = c.execute_computations(sink(), job_name="derived-graft",
                                 materialize=False)
    got = np.asarray(next(iter(res.values())))
    oracle = np.zeros(8, np.float64)
    np.add.at(oracle, k, v.astype(np.float64) * 1.5 + 0.25)
    np.testing.assert_allclose(got, oracle, rtol=1e-4)


# ---------------------------------------------- satellite: SLO shedding
def test_slo_shed_pinned_formula_and_recovery():
    from netsdb_tpu.serve.sched import QueryScheduler
    from netsdb_tpu.serve.sched import feedback as fb

    assert fb.SHED_FACTOR == 0.5 and fb.SHED_MIN_QUOTA == 1  # pinned

    breaches = ["availability"]
    qs = QueryScheduler(slots=2, quota=8, lanes={"vip": 4.0},
                        slo_source=lambda: breaches)
    for lane, n in (("heavy", 5), ("light", 2), ("vip", 9)):
        for _ in range(n):
            qs.release(qs.acquire(lane, 1.0))
    shed0 = obs.REGISTRY.counter("sched.shed_events").value
    # heaviest NON-RESERVED lane halves: vip (reserved) is immune
    assert qs.refresh_shed() == "heavy"
    snap = qs.lanes.snapshot()
    assert snap["lane_quotas"]["heavy"] == 4      # 8 × 0.5
    assert snap["shed_lanes"] == ["heavy"]
    assert obs.REGISTRY.counter("sched.shed_events").value == shed0 + 1
    # one shed at a time while the breach persists
    assert qs.refresh_shed() is None

    # a reseed mid-shed updates the REMEMBERED quota, not the override
    qs.lanes.reseed({}, {"heavy": 6})
    assert qs.lanes.snapshot()["lane_quotas"]["heavy"] == 4

    # recovery restores (the reseeded value, not a stale one)
    breaches.clear()
    assert qs.refresh_shed() is None
    snap = qs.lanes.snapshot()
    assert snap["shed_lanes"] == []
    assert snap["lane_quotas"]["heavy"] == 6


def test_shed_floor_and_unbounded_lanes():
    from netsdb_tpu.serve.sched.queue import LaneScheduler

    ls = LaneScheduler(2, quota=0)        # unbounded: nothing to shed
    ls.acquire("a", 1.0)
    assert ls.shed("a", 0.5) is None

    ls2 = LaneScheduler(2, quota=2)
    ls2.acquire("a", 1.0)
    assert ls2.shed("a", 0.5) == 1        # floored at SHED_MIN_QUOTA
    assert ls2.shed("a", 0.5) is None     # already shed
    ls3 = LaneScheduler(2, quota=1)
    ls3.acquire("a", 1.0)
    assert ls3.shed("a", 0.5) is None     # already at the floor
