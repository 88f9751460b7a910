"""Serve-side observability integration: GET_TRACE profiles over the
wire, the COLLECT_STATS "metrics" section, query ids across the mirror
hop, merged leader/follower stats, and the histogram-backed hedge
estimator.

Acceptance shape (ISSUE 5): one warm serve EXECUTE of a q01-style
query yields a GET_TRACE profile whose spans cover client send →
server decode → executor chunk loop → devcache hit, with span
durations summing to within 20% of the measured wall time; existing
stats accessors keep their shapes.
"""

import time

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
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


def _load_lineitem(c, n=20_000, seed=0):
    c.create_database("d")
    c.create_set("d", "lineitem", type_name="table", storage="paged")
    c.send_table("d", "lineitem", ColumnTable(_li_cols(n, seed), {}))


def _execute_q06(c):
    c.execute_computations(rdag.q06_sink("d"), job_name="q06",
                           fetch_results=False)


@pytest.fixture()
def daemon(tmp_path):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / "obs"),
                      page_size_bytes=1 << 16, page_pool_bytes=1 << 20),
        port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    yield ctl, addr
    ctl.shutdown()


def test_warm_execute_trace_covers_the_whole_path(daemon):
    """The tentpole acceptance: client send → server decode → executor
    chunk loop → devcache hit in ONE query's profile, span sums within
    20% of the measured wall."""
    ctl, addr = daemon
    c = _remote(addr)
    _load_lineitem(c)
    _execute_q06(c)  # cold: compiles, installs into the device cache

    seen = {p["qid"] for p in obs.DEFAULT_RING.last()}
    t0 = time.perf_counter()
    _execute_q06(c)  # WARM: the profile under test
    wall = time.perf_counter() - t0

    # client-side profile: send + wait spans covering the request
    client_profs = [p for p in obs.DEFAULT_RING.last()
                    if p["origin"] == "client" and p["qid"] not in seen]
    assert len(client_profs) == 1
    cp = client_profs[0]
    cnames = {s["name"] for s in cp["spans"]}
    assert {"client.send", "client.wait"} <= cnames
    span_sum = sum(s["duration_s"] for s in cp["spans"]
                   if s["depth"] == 0)
    assert span_sum <= wall * 1.05
    assert span_sum >= 0.8 * wall, (span_sum, wall)

    # server-side profile under the SAME qid, fetched over the wire
    reply = c.get_trace(qid=cp["qid"])
    assert reply["enabled"]
    (sp,) = reply["profiles"]
    assert sp["origin"] == "server"
    names = {s["name"]: s for s in sp["spans"]}
    assert "server.decode" in names
    assert "server.dispatch:EXECUTE_COMPUTATIONS" in names
    fold = names["executor.fold_stream"]
    assert fold["counters"]["chunks"] >= 1
    # warm == served from the device cache, visible on the profile
    assert sp["counters"]["devcache.hits"] >= 1
    assert sp["counters"].get("stage.cached_runs", 0) >= 1
    # server spans at depth 0 decompose the server's own total
    server_sum = sum(s["duration_s"] for s in sp["spans"]
                     if s["depth"] == 0)
    assert server_sum <= sp["total_s"] * 1.05
    c.close()


def test_get_trace_last_n_and_ring_bound(daemon):
    ctl, addr = daemon
    c = _remote(addr)
    _load_lineitem(c, n=2_000)
    for _ in range(3):
        _execute_q06(c)
    reply = c.get_trace(last=2)
    assert len(reply["profiles"]) == 2
    assert all(p["origin"] == "server" for p in reply["profiles"])
    # the ring is the controller's, bounded by config.obs_trace_ring
    assert len(ctl.trace_ring) <= ctl.library.config.obs_trace_ring
    c.close()


def test_collect_stats_metrics_section_and_stable_shapes(daemon):
    ctl, addr = daemon
    c = _remote(addr)
    _load_lineitem(c, n=2_000)
    _execute_q06(c)
    _execute_q06(c)
    st = c.collect_stats()
    # pre-existing sections keep their exact shapes
    assert set(st["cache"]) == {"hits", "misses", "evictions", "spills",
                                "loads"}
    assert {"hits", "misses", "installs", "evictions", "invalidations",
            "rejected", "bytes", "entries",
            "budget_bytes"} <= set(st["device_cache"])
    from netsdb_tpu.plan.executor import compile_stats

    assert set(compile_stats()) == {"hits", "misses", "traces",
                                    "region_traces"}
    # the new metrics section: registry + absorbed collectors
    m = st["metrics"]
    assert {"counters", "gauges", "histograms", "compile", "staging",
            "stages"} <= set(m)
    assert m["compile"] == compile_stats()
    assert m["counters"]["devcache.hits"] >= 1
    assert m["counters"]["staging.chunks"] >= 1
    c.close()


def test_obs_disable_switch(tmp_path):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / "off"), obs_enabled=False,
                      page_size_bytes=1 << 16, page_pool_bytes=1 << 20),
        port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    try:
        c = _remote(addr)
        _load_lineitem(c, n=2_000)
        _execute_q06(c)
        reply = c.get_trace()
        assert reply["enabled"] is False
        assert reply["profiles"] == []
        c.close()
    finally:
        ctl.shutdown()


# ---------------------------------------------- mirrored leader/follower
def test_mirrored_pair_merged_stats_and_qid_across_the_hop(tmp_path):
    """Satellite: COLLECT_STATS over a leader/follower pair merges the
    follower's sections (a mirrored write's devcache invalidation on
    the FOLLOWER is visible through the leader), and the query id
    survives the mirror hop (the leader's GET_TRACE profile carries
    the follower's section under the same qid)."""
    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                           port=0)
    fport = fctl.start()
    faddr = f"127.0.0.1:{fport}"
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m")),
                           port=0, followers=[faddr])
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr)
        _load_lineitem(c, n=800)
        # mirrored EXECUTEs warm BOTH daemons' device caches
        _execute_q06(c)
        _execute_q06(c)
        assert fctl.library.store.device_cache().stats()["installs"] >= 1

        # qid across the hop: the leader's newest EXECUTE profile and
        # the follower's, joined by one query id
        reply = c.get_trace(last=1)
        (prof,) = reply["profiles"]
        assert prof["origin"] == "server"
        assert faddr in reply["followers"]
        fsections = prof.get("followers") or {}
        assert faddr in fsections, prof
        assert all(fp["qid"] == prof["qid"] for fp in fsections[faddr])
        assert fctl.trace_ring.find(prof["qid"])

        # a mirrored write invalidates the FOLLOWER's warm cache; the
        # merged COLLECT_STATS shows it from the leader alone
        c.send_table("d", "lineitem", ColumnTable(_li_cols(800, 7), {}))
        st = c.collect_stats()
        assert faddr in st["followers"]
        fdc = st["followers"][faddr]["device_cache"]
        assert fdc["invalidations"] >= 1
        assert fdc == fctl.library.store.device_cache().stats()
        assert "metrics" in st["followers"][faddr]
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


# --------------------------------------------------- hedge estimator
def test_hedge_estimator_backed_by_shared_histogram(daemon):
    """Satellite: hedge_delay_s quantiles over the client's bounded
    latency histogram, whose every observation also lands in the
    registry histogram COLLECT_STATS ships — one set of numbers."""
    ctl, addr = daemon
    before = obs.REGISTRY.histogram("serve.client.read_latency_s").count
    c = _remote(addr, replicas=[addr])
    # cold start: no samples yet → the documented 50 ms default
    assert c.hedge_delay_s() == pytest.approx(0.05)
    for i in range(20):
        c._observe_read_latency(0.001 * (i + 1))
    assert c.read_latency_stats()["count"] == 20
    assert c.hedge_delay_s() == c._read_hist.quantile(0.99)
    assert 0.015 <= c.hedge_delay_s() <= 0.020
    shared = obs.REGISTRY.histogram("serve.client.read_latency_s")
    assert shared.count - before == 20
    # the explicit knob still wins
    c._hedge_delay_s = 0.3
    assert c.hedge_delay_s() == 0.3
    c.close()


def test_hedged_read_observes_latency_through_histogram(daemon):
    """A real hedged read lands its latency in the SAME histogram the
    trigger reads — the introspection loop closes end-to-end."""
    ctl, addr = daemon
    c = _remote(addr, replicas=[addr], hedge_delay_s=5.0)
    _load_lineitem(c, n=500)
    assert c.set_exists("d", "lineitem")  # an idempotent, hedgeable read
    assert c._read_hist.count >= 1
    assert c.read_latency_stats()["count"] == c._read_hist.count
    c.close()


# ============================================================ ISSUE 6:
# the ACTIVE observability layer — client-shipped traces, HEALTH/SLO,
# per-(client, set) attribution, slow-query log, sampled qids.

def test_put_trace_merges_client_section_on_one_clock(daemon):
    """Tentpole acceptance: GET_TRACE for a traced qid returns ONE
    merged profile — client send/wait spans (shipped via PUT_TRACE
    after the reply) and leader dispatch/job spans, each half with a
    wall-clock anchor that puts the daemon's work inside the client's
    wait."""
    ctl, addr = daemon
    c = _remote(addr, client_id="tenant-a")
    _load_lineitem(c)
    _execute_q06(c)  # cold
    _execute_q06(c)  # warm: the profile under test

    (cp,) = [p for p in obs.DEFAULT_RING.last(3)
             if p["origin"] == "client"][-1:]
    # shipping is async (off the request critical path): drain the
    # shipper before asserting the merge landed
    assert c.flush_traces(10.0)
    reply = c.get_trace(qid=cp["qid"])
    (sp,) = reply["profiles"]
    assert sp["origin"] == "server"
    # the client section arrived over PUT_TRACE and merged by qid
    client_sec = sp.get("client")
    assert client_sec is not None, sp
    assert client_sec["qid"] == sp["qid"]
    cnames = {s["name"] for s in client_sec["spans"]}
    assert {"client.send", "client.wait"} <= cnames
    # the frame carried the identity; the trace recorded it
    assert sp["meta"]["client"] == "tenant-a"
    # one clock: the daemon's receive starts after the client began to
    # send and its reply ends before the client stopped waiting
    def at(prof, span, end=False):
        return (prof["t0_unix_ns"] + 1e9 * (
            span["start_s"] + (span["duration_s"] if end else 0.0)))

    cspans = {s["name"]: s for s in client_sec["spans"]}
    sspans = {s["name"]: s for s in sp["spans"]}
    assert at(client_sec, cspans["client.send"]) \
        <= at(sp, sspans["server.recv"]) + 1e6
    assert at(sp, sspans["server.reply"], end=True) \
        <= at(client_sec, cspans["client.wait"], end=True) + 1e6
    assert cspans["client.encode"]["parent"] == cspans["client.send"]["id"]
    # nothing presents a host clock as device time any more
    assert "host_device" not in sp
    assert "device.est_s" not in sp["counters"]
    # shipping was counted, not silent
    assert obs.REGISTRY.counter(
        "serve.client.traces_shipped").value >= 1
    c.close()


def test_put_trace_unmatched_qid_is_counted_not_an_error(daemon):
    ctl, addr = daemon
    c = _remote(addr)
    out = c._request_once(
        __import__("netsdb_tpu.serve.protocol",
                   fromlist=["MsgType"]).MsgType.PUT_TRACE,
        {"qid": "nope", "profile": {"qid": "nope", "spans": []}}, 1)
    assert out["merged"] is False
    c.close()


def test_obs_frames_do_not_feed_request_slis(daemon):
    """Monitoring must not move the SLOs it reads: PING/HEALTH/
    GET_TRACE/COLLECT_STATS frames stay out of serve.requests/_ok and
    the request_s histogram; and workload frames count total alongside
    ok at OUTCOME time, so an in-flight request can never read as a
    window of failed availability."""
    ctl, addr = daemon
    c = _remote(addr)
    _load_lineitem(c, n=500)

    def settled():
        # counters tick at OUTCOME time, after the reply send — the
        # in-process dispatch thread may still be a few instructions
        # behind the client's receipt; read once stable
        deadline, prev = time.perf_counter() + 5.0, None
        while True:
            cur = (obs.REGISTRY.counter("serve.requests").value,
                   obs.REGISTRY.counter("serve.requests_ok").value,
                   obs.REGISTRY.histogram("serve.request_s").count)
            if cur == prev or time.perf_counter() > deadline:
                return cur
            prev = cur
            time.sleep(0.05)

    req0, ok0, h0 = settled()
    c.ping()
    c.health()
    c.collect_stats()
    c.get_trace(last=1)
    assert settled() == (req0, ok0, h0)  # monitoring moved nothing
    _execute_q06(c)
    req1, ok1, _ = settled()
    dreq, dok = req1 - req0, ok1 - ok0
    assert dreq >= 1 and dreq == dok  # outcome-time: no in-flight skew
    c.close()


def test_trace_sampling_mints_one_in_n(daemon):
    """config.obs_trace_sample / RemoteClient(trace_sample=N): exactly
    1 in N query-shaped requests mints a qid (deterministic
    round-robin), so high-QPS traffic pays tracing at bounded cost."""
    ctl, addr = daemon
    c = _remote(addr, trace_sample=4)
    _load_lineitem(c, n=2_000)
    before = {p["qid"] for p in ctl.trace_ring.last()}
    for _ in range(8):
        _execute_q06(c)
    # the server's trace closes (and lands in the ring) AFTER the
    # reply is sent — when the sampled hit is the last call, give the
    # dispatch thread a moment to finish closing it
    deadline = time.perf_counter() + 5.0
    while True:
        new = [p for p in ctl.trace_ring.last()
               if p["qid"] not in before and p["origin"] == "server"]
        if len(new) >= 2 or time.perf_counter() > deadline:
            break
        time.sleep(0.01)
    # phase-independent: any 8 consecutive calls at 1-in-4 mint 2
    assert len(new) == 2, [p["qid"] for p in new]
    assert obs.REGISTRY.counter("obs.qid_sampled_out").value >= 6
    c.close()


def test_health_frame_objectives_events_and_slowlog_summary(daemon):
    """obs --health acceptance: at least 3 evaluated SLOs with
    multi-window burn rates, plus breach events and the slowlog
    summary, over one live daemon."""
    ctl, addr = daemon
    c = _remote(addr)
    _load_lineitem(c, n=2_000)
    _execute_q06(c)
    h = c.health()
    objs = {o["name"]: o for o in h["objectives"]}
    assert len(objs) >= 3
    assert {"availability", "request_p99_s",
            "devcache_hit_rate"} <= set(objs)
    # the registry is process-global (other tests' ERR frames count),
    # so assert the ratio is evaluated and sane, not an exact value
    avail = objs["availability"]
    assert avail["value"] is not None
    assert 0.0 < avail["value"] <= 1.0
    for o in objs.values():
        assert "windows" in o and o["windows"], o
        for w in o["windows"].values():
            assert {"value", "burn_rate", "scope"} <= set(w)
    assert isinstance(h["events"], list)
    assert h["slowlog"]["entries"] >= 0
    assert h["followers_status"] is None  # no followers configured
    c.close()


def test_slow_query_log_persists_across_daemon_restart(tmp_path):
    """Satellite/tentpole: a query over config.obs_slow_query_s lands
    its FULL profile in <root>/slowlog/, readable via GET_TRACE
    slow=True, surviving a daemon restart."""
    root = str(tmp_path / "slow")
    cfg = Configuration(root_dir=root, obs_slow_query_s=1e-6,
                        page_size_bytes=1 << 16,
                        page_pool_bytes=1 << 20)
    ctl = ServeController(cfg, port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    try:
        c = _remote(addr)
        _load_lineitem(c, n=2_000)
        _execute_q06(c)  # any traced query exceeds 1µs
        reply = c.get_trace(slow=True)
        profs = reply["profiles"]
        assert profs, reply
        qid = profs[-1]["qid"]
        assert profs[-1]["spans"]  # the FULL profile, not a summary
        assert reply["slowlog"]["entries"] >= 1
        # the entry persisted when the trace closed — BEFORE the
        # client's spans could ship; PUT_TRACE rewrites it so the
        # on-disk profile is end-to-end too
        assert c.flush_traces(10.0)
        slow = c.get_trace(slow=True, qid=qid)["profiles"]
        assert slow and slow[-1].get("client"), slow
        c.close()
    finally:
        ctl.shutdown()

    # restart over the same root: the on-disk ring survived
    ctl2 = ServeController(Configuration(
        root_dir=root, obs_slow_query_s=1e-6,
        page_size_bytes=1 << 16, page_pool_bytes=1 << 20), port=0)
    addr2 = f"127.0.0.1:{ctl2.start()}"
    try:
        c = _remote(addr2)
        reply = c.get_trace(slow=True, qid=qid)
        assert [p["qid"] for p in reply["profiles"]] == [qid]
        c.close()
    finally:
        ctl2.shutdown()


def test_attribution_survives_collect_stats_round_trip(daemon):
    """Acceptance: per-(client, db:set) staged bytes / devcache /
    executor-chunk counters aggregate in the registry's "attribution"
    section and survive the COLLECT_STATS wire round-trip."""
    ctl, addr = daemon
    obs.attrib.LEDGER.reset()
    c = _remote(addr, client_id="tenant-b")
    _load_lineitem(c)
    _execute_q06(c)
    _execute_q06(c)
    st = c.collect_stats()
    attr = st["metrics"]["attribution"]
    assert "tenant-b" in attr, attr
    mine = attr["tenant-b"]
    assert mine.get("d:lineitem"), mine
    per_set = mine["d:lineitem"]
    assert per_set["staged_bytes"] > 0
    assert per_set["staged_chunks"] >= 1
    assert per_set["executor.chunks"] >= 1
    # warm run rode the cache under the SAME identity
    assert per_set.get("devcache.hits", 0) >= 1
    # the ingest/requests ticks carry the identity too
    req_scopes = {s for s, m in mine.items() if m.get("requests")}
    assert "d:lineitem" in req_scopes
    c.close()


def test_anonymous_traffic_stays_complete_under_anon(daemon):
    ctl, addr = daemon
    obs.attrib.LEDGER.reset()
    c = _remote(addr)  # no client_id
    _load_lineitem(c, n=2_000)
    _execute_q06(c)
    snap = obs.attrib.LEDGER.snapshot()
    assert "anon" in snap
    assert snap["anon"].get("d:lineitem", {}).get("requests", 0) >= 1
    c.close()


def test_health_and_attribution_merge_across_leader_follower(tmp_path):
    """Acceptance: a real leader+follower pair — HEALTH merges the
    follower's evaluated objectives; mirrored frames carry the client
    identity so the follower books the same tenant."""
    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                           port=0)
    faddr = f"127.0.0.1:{fctl.start()}"
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m")),
                           port=0, followers=[faddr])
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr, client_id="tenant-c")
        _load_lineitem(c, n=800)
        _execute_q06(c)
        h = c.health()
        assert faddr in (h.get("followers") or {}), h
        fh = h["followers"][faddr]
        fobjs = {o["name"] for o in fh["objectives"]}
        assert {"availability", "request_p99_s"} <= fobjs
        assert "slowlog" in fh
        # follower stats carry the attribution section over the merge
        st = c.collect_stats()
        fattr = st["followers"][faddr]["metrics"]["attribution"]
        assert "tenant-c" in fattr
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


def test_health_fanout_best_effort_never_evicts_degraded_follower(
        tmp_path):
    """Satellite: a follower that stops answering makes the leader's
    HEALTH (and stats) reads report an error entry for it — the reads
    stay best-effort and NEVER evict the follower (liveness is the
    heartbeat loop's job, here configured away)."""
    from netsdb_tpu.serve.protocol import MsgType

    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                           port=0)
    faddr = f"127.0.0.1:{fctl.start()}"
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m")),
                           port=0, followers=[faddr],
                           heartbeat_interval_s=3600.0,
                           frame_timeout_s=1.0)
    addr = f"127.0.0.1:{mctl.start()}"
    try:
        c = _remote(addr)
        c.create_database("d")  # dials the follower link
        assert faddr in mctl.follower_status()["active"]

        # the follower wedges: its health/stats handlers hang past the
        # leader's fan-out deadline (the link stays up — this is a
        # SLOW follower, the case eviction must not punish)
        def wedged(p):
            time.sleep(5.0)
            return MsgType.OK, {}

        fctl.handlers[MsgType.HEALTH] = wedged
        fctl.handlers[MsgType.COLLECT_STATS] = wedged

        h = c.health()  # must still answer, with an error entry
        assert faddr in h["followers"], h
        assert "error" in h["followers"][faddr]
        st = c.collect_stats()
        assert "error" in st["followers"][faddr]
        # best-effort reads did NOT evict it
        status = mctl.follower_status()
        assert faddr in status["active"], status
        assert faddr not in status["degraded"]
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()
