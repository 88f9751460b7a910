"""Unit tests for the ACTIVE observability layer (ISSUE 6): the
SLO/health engine (obs/slo.py), the bounded on-disk slow-query log
(obs/slowlog.py), the per-(client, set) resource ledger
(obs/attrib.py), sampled qid minting (obs.sample_qid), and the
host-vs-device split on trace profiles.

The serve-side integration (PUT_TRACE merge, HEALTH frames over a real
leader+follower pair, attribution through COLLECT_STATS) lives in
tests/test_obs_serve.py.
"""

import json
import os
import threading

import pytest

from netsdb_tpu import obs
from netsdb_tpu.obs.attrib import ResourceLedger, client_context, current_client
from netsdb_tpu.obs.metrics import MetricsRegistry
from netsdb_tpu.obs.slo import Objective, SLOEngine, default_objectives
from netsdb_tpu.obs.slowlog import SlowQueryLog
from netsdb_tpu.obs.trace import QueryTrace


# ------------------------------------------------------------ SLO engine
class _Clock:
    """Deterministic monotonic clock the engine's windows step over."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _ratio_engine(reg, clock, target=0.9, windows=(60.0, 600.0)):
    return SLOEngine(
        registry=reg, clock=clock, windows=windows,
        objectives=[Objective(name="avail", kind="ratio_min",
                              target=target, good="ok", total="all")])


def test_objective_validation():
    with pytest.raises(ValueError):
        Objective(name="x", kind="nonsense", target=1.0)
    with pytest.raises(ValueError):
        Objective(name="x", kind="ratio_min", target=0.9, good="a")
    with pytest.raises(ValueError):
        Objective(name="x", kind="quantile_max", target=0.9)


def test_ratio_min_all_time_fallback_then_windowed():
    reg = MetricsRegistry()
    clock = _Clock()
    eng = _ratio_engine(reg, clock)
    # no traffic at all: value is None, nothing breached
    (res,) = eng.evaluate()
    assert res["value"] is None and not res["breached"]

    # all-time fallback: traffic exists but no window history yet
    reg.counter("ok").inc(99)
    reg.counter("all").inc(100)
    clock.advance(1.0)
    (res,) = eng.evaluate()
    assert res["value"] == pytest.approx(0.99)
    assert not res["breached"]

    # a fast burn INSIDE the short window: 50 requests, 25 fail
    clock.advance(30.0)
    reg.counter("ok").inc(25)
    reg.counter("all").inc(50)
    clock.advance(1.0)
    (res,) = eng.evaluate()
    # short window sees the burn (ratio 0.5 < 0.9 target)
    w60 = res["windows"]["60s"]
    assert w60["scope"] == "window"
    assert w60["value"] < 0.9
    assert res["breached"]
    # burn rate = (1 - ratio) / (1 - target): error budget burning 5x
    assert w60["burn_rate"] == pytest.approx(
        (1 - w60["value"]) / 0.1, rel=1e-6)
    assert res["worst_burn_rate"] >= w60["burn_rate"] - 1e-9


def test_breach_events_fire_on_transitions_only():
    reg = MetricsRegistry()
    clock = _Clock()
    eng = _ratio_engine(reg, clock)
    reg.counter("ok").inc(1)
    reg.counter("all").inc(10)  # 10% availability, target 90%
    clock.advance(1.0)
    eng.evaluate()
    clock.advance(1.0)
    eng.evaluate()  # still breached: NO second event
    evs = eng.events()
    assert len(evs) == 1
    assert evs[0]["objective"] == "avail"
    assert evs[0]["event"] == "breach"
    # the TRANSITION ticked the engine's registry exactly once
    assert reg.counter("slo.breaches").value == 1

    # recovery: flood with successes until the windows agree again
    reg.counter("ok").inc(100_000)
    reg.counter("all").inc(100_000)
    clock.advance(700.0)  # old readings age out of both windows
    eng.evaluate()
    clock.advance(1.0)
    eng.evaluate()
    evs = eng.events()
    assert [e["event"] for e in evs] == ["breach", "recovery"]
    assert reg.counter("slo.recoveries").value == 1


def test_quantile_objective_reads_histogram_ring():
    reg = MetricsRegistry()
    eng = SLOEngine(
        registry=reg, clock=_Clock(),
        objectives=[Objective(name="p99", kind="quantile_max",
                              target=0.1, hist="lat", quantile=0.99)])
    for _ in range(100):
        reg.histogram("lat").observe(0.01)
    (res,) = eng.evaluate()
    assert res["value"] == pytest.approx(0.01)
    assert not res["breached"]
    for _ in range(100):
        reg.histogram("lat").observe(0.5)  # recent window goes bad
    (res,) = eng.evaluate()
    assert res["breached"]
    assert res["worst_burn_rate"] == pytest.approx(0.5 / 0.1)


def test_rate_objective_total_seconds_per_wall_second():
    reg = MetricsRegistry()
    clock = _Clock()
    eng = SLOEngine(
        registry=reg, clock=clock, windows=(60.0,),
        objectives=[Objective(name="waitfrac", kind="rate_max",
                              target=0.25, hist="wait")])
    (res,) = eng.evaluate()
    assert res["value"] is None  # no history yet — never breached
    # 30 seconds of wall, 3 seconds blocked => 10% wait fraction
    for _ in range(30):
        reg.histogram("wait").observe(0.1)
    clock.advance(30.0)
    (res,) = eng.evaluate()
    assert res["value"] == pytest.approx(3.0 / 30.0, rel=0.01)
    assert not res["breached"]
    # 10 more wall seconds fully blocked => the window rate breaches
    for _ in range(100):
        reg.histogram("wait").observe(0.1)
    clock.advance(10.0)
    (res,) = eng.evaluate()
    assert res["breached"]


def test_default_objectives_shape():
    objs = default_objectives()
    assert len(objs) >= 3  # the acceptance floor: >= 3 evaluated SLOs
    names = {o.name for o in objs}
    assert {"availability", "request_p99_s",
            "devcache_hit_rate"} <= names
    # every default evaluates against an empty registry without error
    out = SLOEngine(registry=MetricsRegistry(), clock=_Clock(),
                    objectives=objs).evaluate()
    assert [o["name"] for o in out] == [o.name for o in objs]
    for res in out:
        assert {"value", "windows", "worst_burn_rate", "breached",
                "kind", "target", "description"} <= set(res)
    # and the whole readout is msgpack/json-clean
    json.dumps(out)


# --------------------------------------------------------------- slowlog
def _profile(qid, total):
    return {"qid": qid, "origin": "server", "total_s": total,
            "spans": [], "counters": {}}


def test_slowlog_threshold_and_bound(tmp_path):
    log = SlowQueryLog(str(tmp_path), capacity=3, threshold_s=1.0)
    assert log.maybe_record(_profile("fast", 0.5)) is None
    assert log.maybe_record(_profile("nototal", None)) is None
    for i in range(5):
        assert log.maybe_record(_profile(f"slow{i}", 2.0 + i))
    entries = log.entries()
    assert len(entries) == 3  # pruned to capacity, oldest first out
    assert [e["qid"] for e in entries] == ["slow2", "slow3", "slow4"]
    assert all(e["slowlog_file"].startswith("slow-") for e in entries)
    assert log.summary()["entries"] == 3


def test_slowlog_survives_restart_with_continuing_seq(tmp_path):
    log = SlowQueryLog(str(tmp_path), capacity=10, threshold_s=1.0)
    log.record(_profile("a", 2.0))
    log.record(_profile("b", 2.0))
    # a NEW instance over the same root: entries visible, sequence
    # numbers continue (lexicographic order stays age order)
    log2 = SlowQueryLog(str(tmp_path), capacity=10, threshold_s=1.0)
    assert [e["qid"] for e in log2.entries()] == ["a", "b"]
    log2.record(_profile("c", 2.0))
    assert [e["qid"] for e in log2.entries()] == ["a", "b", "c"]
    names = sorted(os.listdir(log2.dir))
    seqs = [int(n.split("-")[1]) for n in names]
    assert seqs == sorted(seqs) and len(set(seqs)) == 3


def test_slowlog_disabled_and_unserializable_never_fatal(tmp_path):
    off = SlowQueryLog(str(tmp_path / "off"), capacity=4, threshold_s=None)
    assert off.maybe_record(_profile("x", 100.0)) is None
    log = SlowQueryLog(str(tmp_path / "on"), capacity=4, threshold_s=1.0)
    # default=str makes exotic values serializable; a profile that
    # still fails returns None, never raises
    prof = _profile("y", 2.0)
    prof["weird"] = object()
    assert log.record(prof) is not None  # default=str absorbed it
    # corrupt file on disk: entries() skips it
    with open(os.path.join(log.dir, "slow-999999999999-zz.json"),
              "w") as f:
        f.write("{not json")
    qids = [e["qid"] for e in log.entries()]
    assert qids == ["y"]


# ------------------------------------------------------------ attribution
def test_ledger_context_var_and_anon():
    led = ResourceLedger()
    assert current_client() is None
    with client_context("tenant-a"):
        assert current_client() == "tenant-a"
        led.add("staged_bytes", 100, scope="d:s")
        with client_context(None):  # None = keep outer identity
            assert current_client() == "tenant-a"
    assert current_client() is None
    led.add("staged_bytes", 7, scope="d:s")  # anonymous
    snap = led.snapshot()
    assert snap["tenant-a"]["d:s"]["staged_bytes"] == 100
    assert snap["anon"]["d:s"]["staged_bytes"] == 7


def test_ledger_totals_and_reset():
    led = ResourceLedger()
    led.add("chunks", 2, scope="d:a", client="t")
    led.add("chunks", 3, scope="d:b", client="t")
    led.add("chunks", 9, scope="d:a", client="other")
    assert led.totals("t") == {"chunks": 5}
    led.reset()
    assert led.snapshot() == {}


def test_ledger_bounded_overflow_bucket():
    led = ResourceLedger(max_keys=4)
    before = obs.REGISTRY.counter("attrib.overflow").value
    for i in range(10):
        led.add("m", 1, scope=f"d:s{i}", client="attacker")
    snap = led.snapshot()
    # 4 real keys + the shared overflow bucket, never more
    assert sum(len(v) for v in snap.values()) <= 5
    assert snap["overflow"]["*"]["m"] == 6
    assert obs.REGISTRY.counter("attrib.overflow").value - before == 6


def test_ledger_thread_safety_sums_exact():
    led = ResourceLedger()

    def work(cid):
        with client_context(cid):
            for _ in range(1000):
                led.add("n", 1, scope="d:s")

    ts = [threading.Thread(target=work, args=(f"c{i}",)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = led.snapshot()
    assert sum(snap[f"c{i}"]["d:s"]["n"] for i in range(4)) == 4000


# --------------------------------------------------------- sampled qids
def test_sample_qid_every_query_at_one():
    assert all(obs.sample_qid(1) for _ in range(5))
    assert all(obs.sample_qid(0) for _ in range(2))  # <=1 = always


def test_sample_qid_exact_one_in_n():
    n = 8
    got = [obs.sample_qid(n) for _ in range(4 * n)]
    minted = [q for q in got if q]
    # deterministic round-robin: exactly 1 in n, regardless of phase
    assert len(minted) == 4
    assert len(set(minted)) == 4  # fresh ids each time


def test_sample_qid_disabled_returns_none():
    obs.set_enabled(False)
    try:
        assert obs.sample_qid(1) is None
    finally:
        obs.set_enabled(True)


# ------------------------------------------- anchor, causes and meta
def test_profile_anchor_and_meta():
    import time

    before = time.time_ns()
    tr = QueryTrace("q1", origin="server")
    after = time.time_ns()
    tr.backdate(1.0)  # a 1 s query, without sleeping for one
    tr.record("step", 0.5, "executor")
    tr.add("stage.wait_s", 0.1)
    tr.annotate("device_profile", "/tmp/prof/q1")
    prof = tr.finish()
    # the wall-clock anchor is read once at open and moves with the
    # back-dated start; nothing presents a host clock as device time
    assert before - 10**9 <= prof["t0_unix_ns"] <= after - 10**9
    assert "host_device" not in prof
    assert prof["total_s"] >= 1.0
    assert prof["counters"] == {"stage.wait_s": 0.1}
    assert prof["meta"]["device_profile"] == "/tmp/prof/q1"


def test_profile_spans_name_their_cause():
    tr = QueryTrace("q2")
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            tr.record("measured", 0.0)
        with tr.span("sibling") as sibling:
            pass
    prof = tr.finish()
    by_name = {s["name"]: s for s in prof["spans"]}
    assert sorted(s["id"] for s in prof["spans"]) == [1, 2, 3, 4]
    assert by_name["outer"]["parent"] == 0
    assert by_name["inner"]["parent"] == outer.id
    assert by_name["measured"]["parent"] == inner.id
    assert by_name["sibling"]["parent"] == outer.id
    assert sibling.depth == inner.depth == outer.depth + 1


def test_trace_ring_merge_section():
    from netsdb_tpu.obs.trace import TraceRing

    ring = TraceRing(4)
    ring.push({"qid": "a", "total_s": 1.0})
    assert ring.merge_section("a", "client", {"spans": []})
    assert not ring.merge_section("missing", "client", {})
    (prof,) = ring.find("a")
    assert prof["client"] == {"spans": []}


def test_trace_ring_pending_section_survives_reply_before_push():
    """The PUT_TRACE race: the reply goes out inside the trace
    context, the ring push after — a fast client's shipped section
    can arrive FIRST. It must buffer and fold in at push, bounded."""
    from netsdb_tpu.obs.trace import TraceRing

    ring = TraceRing(8, pending_capacity=2)
    assert not ring.merge_section("early", "client", {"spans": [1]})
    ring.push({"qid": "early", "total_s": 1.0})
    (prof,) = ring.find("early")
    assert prof["client"] == {"spans": [1]}
    # kept for a later profile of the same qid: one request of several
    # frames rings one profile a frame, the last after the client shipped
    ring.push({"qid": "early", "total_s": 2.0})
    assert ring.find("early")[1]["client"] == {"spans": [1]}
    # bounded: beyond pending_capacity the OLDEST buffered qid drops
    for i in range(4):
        ring.merge_section(f"p{i}", "client", {"i": i})
    ring.push({"qid": "p0", "total_s": 1.0})
    assert "client" not in ring.find("p0")[0]  # evicted, not leaked
    ring.push({"qid": "p3", "total_s": 1.0})
    assert ring.find("p3")[0]["client"] == {"i": 3}


def test_slo_breach_requires_all_windows_to_agree():
    """Multi-window agreement (the SRE rule the module docstring
    states): a short-window burst alone must NOT breach while the
    long window is still healthy — only a sustained burn does."""
    reg = MetricsRegistry()
    clock = _Clock()
    eng = _ratio_engine(reg, clock)  # target 0.9, windows 60/600
    reg.counter("ok").inc(1000)
    reg.counter("all").inc(1000)
    clock.advance(545.0)
    eng.observe()  # a reading the short window can delta from
    reg.counter("all").inc(10)  # 10 failures in a 6 s burst
    clock.advance(6.0)
    (res,) = eng.evaluate()
    assert res["windows"]["60s"]["value"] < 0.9   # short: burning
    assert res["windows"]["600s"]["value"] > 0.9  # long: healthy
    assert not res["breached"]                    # no agreement
    assert res["value"] < 0.9  # worst window still surfaces
    assert eng.events() == []
    # sustain the failures until the long window agrees
    for _ in range(12):
        reg.counter("all").inc(100)
        clock.advance(60.0)
        out = eng.evaluate()
    (res,) = out
    assert res["windows"]["60s"]["value"] < 0.9
    assert res["windows"]["600s"]["value"] < 0.9
    assert res["breached"]
    assert [e["event"] for e in eng.events()] == ["breach"]


def test_slo_rate_breach_requires_all_windows_to_agree():
    reg = MetricsRegistry()
    clock = _Clock()
    eng = SLOEngine(
        registry=reg, clock=clock, windows=(60.0, 600.0),
        objectives=[Objective(name="waitfrac", kind="rate_max",
                              target=0.25, hist="wait")])
    # 200 blocked seconds early on, then a long quiet stretch
    for _ in range(200):
        reg.histogram("wait").observe(1.0)
    clock.advance(100.0)
    eng.observe()
    clock.advance(440.0)
    eng.observe()
    clock.advance(60.0)
    (res,) = eng.evaluate()
    # long window still over target, short window idle: no breach
    assert res["windows"]["600s"]["value"] > 0.25
    assert res["windows"]["60s"]["value"] == 0.0
    assert not res["breached"]
    # enough fresh blocking that BOTH windows exceed target
    for _ in range(200):
        reg.histogram("wait").observe(1.0)
    clock.advance(30.0)
    (res,) = eng.evaluate()
    assert res["windows"]["60s"]["value"] > 0.25
    assert res["windows"]["600s"]["value"] > 0.25
    assert res["breached"]


def test_slowlog_merge_section_rewrites_persisted_entry(tmp_path):
    """PUT_TRACE's slowlog half: the profile persists when the trace
    closes — before the client's spans exist — so the merge must
    rewrite the on-disk entry (atomically, only the matching qid)."""
    log = SlowQueryLog(str(tmp_path), capacity=4, threshold_s=1.0)
    log.record(_profile("q1", 2.0))
    log.record(_profile("q2", 3.0))
    assert log.merge_section("q1", "client", {"spans": [{"name": "s"}]})
    assert not log.merge_section("absent", "client", {})
    by_qid = {e["qid"]: e for e in log.entries()}
    assert by_qid["q1"]["client"] == {"spans": [{"name": "s"}]}
    assert "client" not in by_qid["q2"]
