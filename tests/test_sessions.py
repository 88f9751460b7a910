"""Stateful interactive serving suite (serve/sessions.py + the decode
lane + TTL'd session state + multi-model dedup residency).

The acceptance contract, straight from the structural gates:

* batched multi-session decode compiles ONE step program per
  (model-shape, batch-bucket) — trace counts are pinned, and every
  session's output is byte-equal to a solo unbatched run;
* warm decode steps never touch the host arena (zero arena reads);
* TTL expiry and LRU pressure DEMOTE state (spill to the arena, revive
  on the next step) — they never lose it, even racing a live decode;
* a leader kill mid-decode resumes from mirror-replayed state with no
  token reuse (steps stay exactly sequential);
* a session-owning shard death surfaces as the typed retryable
  SessionMoved path and the state revives from the arena spill pushed
  home before the death;
* a LIVE session move (the rebalance hook) completes under a running
  decode loop with zero failed client requests;
* two fine-tuned variants of one base model are resident in
  MEASURABLY less than 2x one model's pages, with exact attribution.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.models import decode as decode_mod
from netsdb_tpu.models.decode import deploy_decode_model
from netsdb_tpu.serve import ha as ha_mod
from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
from netsdb_tpu.serve.errors import SessionUnknownError
from netsdb_tpu.serve.protocol import (CODEC_PICKLE, IDEMPOTENCY_KEY,
                                       MsgType)
from netsdb_tpu.serve.sched.sessions import DecodeBatcher
from netsdb_tpu.serve.server import ServeController

FAILOVER = RetryPolicy(max_attempts=80, base_delay_s=0.05,
                       max_delay_s=0.25)
ELECTION_S = 0.35

_DAEMON_KW = dict(heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
                  heartbeat_misses=2, mirror_ack_timeout_s=5.0,
                  resync_grace_s=2.0)

HID = 64


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


def _gauge(name: str) -> float:
    return obs.REGISTRY.gauge(name).value


def _wait_for(pred, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _x(i: int, step: int) -> np.ndarray:
    """Deterministic per-(session, step) input row."""
    rng = np.random.default_rng(1000 * i + step)
    return rng.standard_normal(HID).astype(np.float32)


def _solo_outputs(library, db, kind, xs):
    """The unbatched reference: one fresh runtime, one session, the
    same xs — per-row byte-equality against the batched path is the
    correctness gate for coalescing."""
    rt = decode_mod.DecodeRuntime(library)
    rt.register_model(db, kind)
    solo = rt.solo_session(db)
    outs = [solo.step(x) for x in xs]
    return outs


@contextlib.contextmanager
def _daemon(tmp_path, name="d0", **cfg_kw):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / name), **cfg_kw),
        port=0, **_DAEMON_KW)
    ctl.start()
    try:
        yield ctl
    finally:
        ctl.shutdown()


@contextlib.contextmanager
def _pool(tmp_path, n_workers=0, n_followers=0, arm=False, **cfg_kw):
    daemons = []
    try:
        workers = []
        for i in range(n_workers):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}"),
                              **cfg_kw),
                port=0, **_DAEMON_KW)
            w.start()
            daemons.append(w)
            workers.append(w)
        followers = []
        for i in range(n_followers):
            f = ServeController(
                Configuration(root_dir=str(tmp_path / f"f{i}"),
                              **cfg_kw),
                port=0, **_DAEMON_KW)
            f.start()
            daemons.append(f)
            followers.append(f)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"), **cfg_kw),
            port=0,
            followers=[f.advertise_addr for f in followers],
            workers=[w.advertise_addr for w in workers],
            **_DAEMON_KW)
        leader.start()
        daemons.append(leader)
        if arm:
            peers = [leader.advertise_addr] \
                + [f.advertise_addr for f in followers]
            for d in [leader] + followers:
                d.arm_ha(peers, election_timeout_s=ELECTION_S)
        yield leader, followers, workers
    finally:
        for d in daemons:
            d.shutdown()


# --- DecodeBatcher (the lane shape, no daemon) ------------------------

def test_batcher_coalesces_concurrent_sessions():
    seen = []

    def run(db, reqs):
        seen.append(len(reqs))
        time.sleep(0.005)
        return [r * 10 for r in reqs]

    b = DecodeBatcher(run, max_batch=8, window_s=0.05)
    results = {}
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        results[i] = b.submit("m", f"s{i}", i)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert results == {i: i * 10 for i in range(4)}
    snap = b.snapshot()
    assert snap["coalesced"] == 4 and snap["pending"] == 0
    # 4 sessions arriving together coalesce into fewer dispatches
    assert snap["max_occupancy"] >= 2


def test_batcher_never_double_steps_one_session():
    """Two in-flight requests for ONE session must land in two
    different batches — a single dispatch double-advancing a session
    would corrupt its state."""
    sizes = []

    def run(db, reqs):
        sizes.append(len(reqs))
        time.sleep(0.005)
        return list(reqs)

    b = DecodeBatcher(run, max_batch=8, window_s=0.03)
    barrier = threading.Barrier(2)
    done = []

    def worker(v):
        barrier.wait()
        done.append(b.submit("m", "same-sid", v))

    ts = [threading.Thread(target=worker, args=(v,)) for v in (1, 2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert sorted(done) == [1, 2]
    assert all(s == 1 for s in sizes) and len(sizes) == 2


def test_batcher_failure_fans_out_typed():
    def run(db, reqs):
        raise RuntimeError("device fault")

    b = DecodeBatcher(run, max_batch=4, window_s=0.001)
    with pytest.raises(RuntimeError, match="device fault"):
        b.submit("m", "s1", 1)
    assert b.snapshot()["pending"] == 0


def test_batcher_leader_handoff_no_lost_wakeup():
    """A waiter enqueueing while the leader drains its last batch must
    either be batched by that leader or become the next leader —
    never park forever (the lost-wakeup regression)."""
    release = threading.Event()
    first_running = threading.Event()

    def run(db, reqs):
        first_running.set()
        release.wait(5)
        return list(reqs)

    b = DecodeBatcher(run, max_batch=1, window_s=0.001)
    out = {}

    def submit(sid):
        out[sid] = b.submit("m", sid, sid)

    t1 = threading.Thread(target=submit, args=("a",))
    t1.start()
    assert first_running.wait(5)
    t2 = threading.Thread(target=submit, args=("b",))
    t2.start()
    time.sleep(0.02)  # t2 parked while the leader is mid-batch
    release.set()
    t1.join(timeout=10)
    t2.join(timeout=10)
    assert not t1.is_alive() and not t2.is_alive()
    assert out == {"a": "a", "b": "b"}


# --- single daemon: the full open/generate/close lane -----------------

def test_open_generate_close_counters_and_solo_byte_equality(tmp_path):
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=3)
        opened0 = _counter("session.opened")
        closed0 = _counter("session.closed")
        steps0 = _counter("session.decode_steps")
        h = c.open_session("m1", kind="lstm")
        xs = [_x(0, s) for s in range(5)]
        got = [h.generate(x) for x in xs]
        assert h.steps == 5
        want = _solo_outputs(ctl.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert np.asarray(g).tobytes() == w.tobytes()
        assert _counter("session.opened") == opened0 + 1
        assert _counter("session.decode_steps") == steps0 + 5
        assert _gauge("session.resident_bytes") > 0
        assert h.close()
        assert _counter("session.closed") == closed0 + 1
        assert ctl.sessions.table.count() == 0
        with pytest.raises(SessionUnknownError):
            c._request(MsgType.GENERATE,
                       {"db": "m1", "set": h.sid, "sid": h.sid,
                        "x": xs[0]},
                       codec=CODEC_PICKLE)
        c.close()


def test_concurrent_sessions_one_program_byte_equal(tmp_path):
    """8 concurrent sessions on one model: batches coalesce (occupancy
    > 1), the whole run traces ONE step program (bucket ladder pins
    1..8 rows to the same padded program), and every session's stream
    is byte-equal to its solo unbatched twin."""
    decode_mod.clear_decode_programs()
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=5)
        n_sessions, n_steps = 8, 4
        # one client per session: a shared socket would serialize the
        # submits client-side and nothing could ever coalesce
        clients = [RemoteClient(ctl.advertise_addr)
                   for _ in range(n_sessions)]
        handles = [clients[i].open_session("m1", kind="lstm")
                   for i in range(n_sessions)]
        outs = {i: [] for i in range(n_sessions)}
        errors = []
        barrier = threading.Barrier(n_sessions)

        def drive(i):
            try:
                barrier.wait()
                for s in range(n_steps):
                    outs[i].append(np.asarray(
                        handles[i].generate(_x(i, s))))
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((i, e))

        ts = [threading.Thread(target=drive, args=(i,))
              for i in range(n_sessions)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert errors == []
        stats = decode_mod.decode_stats()
        assert stats["traces"] == 1, stats  # ONE program, pinned
        assert ctl.sessions.batcher.snapshot()["max_occupancy"] >= 2
        for i in range(n_sessions):
            want = _solo_outputs(ctl.library, "m1", "lstm",
                                 [_x(i, s) for s in range(n_steps)])
            for g, w in zip(outs[i], want):
                assert g.tobytes() == w.tobytes()
        # solo replays above reused the SAME padded program: still 1
        assert decode_mod.decode_stats()["traces"] == 1
        for h in handles:
            h.close()
        for cc in clients:
            cc.close()
        c.close()


def test_warm_decode_steps_never_read_the_arena(tmp_path):
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=7)
        h = c.open_session("m1", kind="lstm")
        for s in range(6):
            h.generate(_x(0, s))
        assert ctl.sessions.arena.stats()["reads"] == 0
        h.close()
        c.close()


def test_get_trace_decomposes_decode_spans(tmp_path):
    """GET_TRACE on a decode turn shows the turn's phases, recorded by
    the batch's leader into the frame's own trace: children of
    ``session.coalesce``, in order, inside it and tiling it
    (``session.turn.prefill`` is absent: a toy kind has no prompt)."""
    phases = ["server.sched.session_wait", "session.admit",
              "session.turn.first_token", "session.turn.decode",
              "session.retire", "session.turn.reply"]
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=9)
        h = c.open_session("m1", kind="lstm")
        h.generate(_x(0, 0))
        reply = c.get_trace(last=5)
        server = [p for p in reply["profiles"]
                  if p.get("origin") == "server"]
        names = {s["name"] for p in server for s in p["spans"]}
        assert not {"session.batch", "session.device"} & names, names
        for p in server:
            by_name = {s["name"]: s for s in p["spans"]}
            if "session.coalesce" not in by_name:
                continue
            co = by_name["session.coalesce"]
            assert set(phases) <= set(by_name), by_name
            assert "session.turn.prefill" not in by_name
            spans = [by_name[n] for n in phases]
            assert all(s["parent"] == co["id"] for s in spans)
            assert co["start_s"] <= spans[0]["start_s"] + 1e-6
            for a, b in zip(spans, spans[1:]):
                assert a["start_s"] + a["duration_s"] \
                    <= b["start_s"] + 1e-3
            last = spans[-1]
            assert last["start_s"] + last["duration_s"] \
                <= co["start_s"] + co["duration_s"] + 1e-6
            assert abs(sum(s["duration_s"] for s in spans)
                       - co["duration_s"]) <= 2e-3
            decode = by_name["session.turn.decode"]["counters"]
            assert decode == {"steps": 0, "chunk_steps": 0,
                              "chunk_step_s": 0.0}
            assert by_name["session.step"]["counters"] == {
                "rows": 1, "prefill_tokens": 0}
            break
        else:
            raise AssertionError(names)
        h.close()
        c.close()


def test_ttl_expiry_under_pressure_races_live_decode(tmp_path):
    """Shrunk TTL + a tiny device-cache budget: session state expires
    and thrashes out between steps of a LIVE decode loop. Every
    eviction spills to the arena, every next step revives — outputs
    stay byte-equal to the solo run that never lost residency."""
    with _daemon(tmp_path, session_ttl_s=0.25,
                 device_cache_bytes=4096) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=11)
        evicted0 = _counter("session.evicted")
        h = c.open_session("m1", kind="lstm")
        xs = [_x(0, s) for s in range(4)]
        got = []
        for x in xs:
            got.append(np.asarray(h.generate(x)))
            time.sleep(0.45)  # outlive the TTL between steps
        arena = ctl.sessions.arena.stats()
        assert arena["reads"] > 0, "state never revived from the arena"
        assert _counter("session.evicted") > evicted0
        want = _solo_outputs(ctl.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        assert h.steps == len(xs)
        h.close()
        c.close()


def test_dedup_two_finetuned_models_share_pages_exactly(tmp_path):
    """Two 25%-fine-tuned variants of one base model register against
    the dedup detector: unique resident page bytes land measurably
    under 2x one model, and the per-model charges sum exactly to the
    unique total (attribution stays exact under sharing)."""
    with _daemon(tmp_path, model_dedup=True) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "ma", kind="lstm", hidden=HID,
                            seed=21, base_seed=77, finetune_frac=0.25)
        deploy_decode_model(c, "mb", kind="lstm", hidden=HID,
                            seed=22, base_seed=77, finetune_frac=0.25)
        ha = c.open_session("ma", kind="lstm")
        hb = c.open_session("mb", kind="lstm")
        rep = ctl.sessions.runtime.residency_report()
        assert rep["models"] == 2
        one_model = rep["charged_by_model"]  # per-model charge
        unique = rep["unique_page_bytes"]
        undeduped = rep["total_page_bytes"]
        # >= 50% of pages shared -> measurably less than 2x one model
        assert unique < 0.8 * undeduped, rep
        # attribution exact: charges sum to the unique total
        assert abs(sum(one_model.values()) - unique) <= len(one_model)
        assert _gauge("dedup.page_bytes") == unique
        # the two variants still decode as DIFFERENT models
        ya = np.asarray(ha.generate(_x(0, 0)))
        yb = np.asarray(hb.generate(_x(0, 0)))
        assert ya.tobytes() != yb.tobytes()
        ha.close()
        hb.close()
        c.close()


# --- chaos: failover, shard death, live move --------------------------

pytestmark_chaos = pytest.mark.chaos


@pytest.mark.chaos
def test_leader_kill_mid_decode_resumes_exact_steps(tmp_path):
    """The flagship kill: the leader dies mid decode loop. GENERATE is
    mirrored, so the follower replayed every step against its own warm
    state and idempotency cache — after promotion the client's typed
    retry resumes with NO token reuse: steps stay exactly sequential
    and the full output stream is byte-equal to a solo run."""
    with _pool(tmp_path, n_followers=1, arm=True) \
            as (leader, followers, _):
        follower = followers[0]
        c = RemoteClient(leader.advertise_addr,
                         failover=[follower.advertise_addr],
                         retry=FAILOVER)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=13)
        h = c.open_session("m1", kind="lstm")
        n_steps = 10
        xs = [_x(0, s) for s in range(n_steps)]
        got, steps_seen = [], []
        done = threading.Event()

        def drive():
            for x in xs:
                got.append(np.asarray(h.generate(x, deadline_s=60.0)))
                steps_seen.append(h.steps)
            done.set()

        t = threading.Thread(target=drive)
        t.start()
        assert _wait_for(lambda: len(got) >= 2)
        leader.shutdown()  # kill mid-decode
        t.join(timeout=120)
        assert not t.is_alive() and done.is_set()
        assert _wait_for(lambda: follower._ha.role == ha_mod.LEADER)
        # no token reuse, no double-apply: strictly sequential steps
        assert steps_seen == list(range(1, n_steps + 1))
        assert follower.sessions.table.steps(h.sid) == n_steps
        want = _solo_outputs(follower.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        c.close()


@pytest.mark.chaos
def test_owner_shard_death_revives_from_pushed_spill(tmp_path):
    """A worker owns the session (sticky routing); its TTL sweep
    spills the idle state and the housekeeping push ships it home.
    Kill the worker: the next decode step bounces typed, the leader
    adopts, revives from the arena copy, and the step count continues
    exactly where the worker left off."""
    with _pool(tmp_path, n_workers=1, session_ttl_s=0.4) \
            as (leader, _, workers):
        worker = workers[0]
        c = RemoteClient(leader.advertise_addr, retry=FAILOVER)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=15)
        h = c.open_session("m1", kind="lstm")
        assert h.owner == worker.advertise_addr
        pre_steps = 3
        xs = [_x(0, s) for s in range(pre_steps + 3)]
        got = [np.asarray(h.generate(xs[s], deadline_s=60.0))
               for s in range(pre_steps)]
        # idle past the TTL: the worker spills, housekeeping pushes
        # the dirty state home to the leader's arena
        assert _wait_for(
            lambda: leader.sessions.arena.steps(h.sid, "m1")
            == pre_steps, timeout_s=20.0), \
            leader.sessions.arena.stats()
        worker.shutdown()
        for s in range(pre_steps, len(xs)):
            got.append(np.asarray(h.generate(xs[s], deadline_s=60.0)))
        assert h.steps == len(xs)
        assert h.owner == leader.advertise_addr
        assert h.moves >= 1  # at least one typed SessionMoved hop
        row = leader.sessions.table.get(h.sid)
        assert row["owner"] == leader.advertise_addr
        want = _solo_outputs(leader.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        h.close()
        c.close()


def test_oversized_state_layer_spills_to_arena_not_lost(tmp_path):
    """A state layer larger than the WHOLE device-cache budget can
    never be resident: every save is budget-rejected. The advanced
    state must fall through to the arena (counted), not be silently
    dropped — the session keeps decoding byte-equal, revived from the
    arena each step, instead of dying SessionUnknown on step 2."""
    with _daemon(tmp_path, device_cache_bytes=200) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=19)
        spills0 = _counter("session.budget_spills")
        h = c.open_session("m1", kind="lstm")
        xs = [_x(0, s) for s in range(3)]
        got = [np.asarray(h.generate(x)) for x in xs]
        assert h.steps == 3
        assert _counter("session.budget_spills") > spills0
        assert ctl.sessions.arena.steps(h.sid, "m1") == 3
        want = _solo_outputs(ctl.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        h.close()
        c.close()


def test_degrade_invalidates_shipped_weights_record(tmp_path):
    """The weights-already-shipped memo must not outlive the worker it
    describes: once the pool marks the member degraded (death or
    restart), the next session placed there ships weights again
    instead of a weight-less adopt against an empty store."""
    with _pool(tmp_path, n_workers=1) as (leader, _, workers):
        worker = workers[0]
        c = RemoteClient(leader.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=23)
        h = c.open_session("m1", kind="lstm")
        assert h.owner == worker.advertise_addr
        with leader.sessions._shipped_mu:
            assert (worker.advertise_addr, "m1") in \
                leader.sessions._shipped
        leader.shards.degrade(worker.advertise_addr, "test kill")
        with leader.sessions._shipped_mu:
            assert (worker.advertise_addr, "m1") not in \
                leader.sessions._shipped
        h.close()
        c.close()


@pytest.mark.chaos
def test_retry_same_token_after_live_move_never_double_applies(
        tmp_path):
    """The no-double-apply contract across a relocation: a step
    applied at the old owner whose reply was lost retries under the
    SAME idempotency token at the NEW owner (whose daemon-local token
    cache never saw it). The applied-token record travels with the
    handoff state, so the retry replays the recorded reply instead of
    advancing the state a second time."""
    with _pool(tmp_path, n_workers=2) as (leader, _, workers):
        c = RemoteClient(leader.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=25)
        h = c.open_session("m1", kind="lstm")
        src = h.owner
        dst = next(w.advertise_addr for w in workers
                   if w.advertise_addr != src)
        xs = [_x(0, 0), _x(0, 1)]
        tok = "step-1-token-fixed"
        step1 = {"db": "m1", "set": h.sid, "sid": h.sid, "x": xs[0],
                 IDEMPOTENCY_KEY: tok}
        cs = RemoteClient(src)
        rep1 = cs._request(MsgType.GENERATE, dict(step1),
                           codec=CODEC_PICKLE)
        assert rep1["steps"] == 1
        # the reply is "lost"; the session moves live to dst
        c._request(MsgType.SESSION_OPEN,
                   {"op": "move", "sid": h.sid, "to": dst})
        # client retry of the SAME logical step lands at the new owner
        cd = RemoteClient(dst)
        rep2 = cd._request(MsgType.GENERATE, dict(step1),
                           codec=CODEC_PICKLE)
        assert rep2["steps"] == 1, \
            "retry under one token double-advanced the state"
        assert np.asarray(rep2["y"]).tobytes() \
            == np.asarray(rep1["y"]).tobytes()
        # a FRESH token advances normally from the moved state
        rep3 = cd._request(MsgType.GENERATE,
                           {"db": "m1", "set": h.sid, "sid": h.sid,
                            "x": xs[1],
                            IDEMPOTENCY_KEY: "step-2-token-fixed"},
                           codec=CODEC_PICKLE)
        assert rep3["steps"] == 2
        want = _solo_outputs(leader.library, "m1", "lstm", xs)
        assert np.asarray(rep1["y"]).tobytes() == want[0].tobytes()
        assert np.asarray(rep3["y"]).tobytes() == want[1].tobytes()
        for cc in (cs, cd):
            cc.close()
        h.close()
        c.close()


@pytest.mark.chaos
def test_promotion_never_rewinds_worker_owned_session(tmp_path):
    """The stale-resident rewind: a mirror follower replays op=open
    owning the session itself, installing step-0 init state — but a
    WORKER-owned session's decode steps are never mirrored; its
    durability reaches the follower only as mirrored op=spill merges
    into the arena. After the worker AND leader die, the promoted
    follower must revive from its arena copy (newest wins), not
    assemble the consistent-looking step-0 residents and silently
    rewind."""
    with _pool(tmp_path, n_workers=1, n_followers=1, arm=True) \
            as (leader, followers, workers):
        worker, follower = workers[0], followers[0]
        c = RemoteClient(leader.advertise_addr,
                         failover=[follower.advertise_addr],
                         retry=FAILOVER)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=27)
        h = c.open_session("m1", kind="lstm")
        assert h.owner == worker.advertise_addr
        pre_steps = 3
        xs = [_x(0, s) for s in range(pre_steps + 3)]
        got = [np.asarray(h.generate(xs[s], deadline_s=60.0))
               for s in range(pre_steps)]
        # force the worker's TTL expiry NOW (the default TTL keeps the
        # follower's stale step-0 residents alive — the bug's window);
        # the worker spills, pushes home, and the leader MIRRORS the
        # merge — wait until the follower holds it
        worker.library.store.device_cache().session_sweep(
            now=time.monotonic() + 1e9)
        assert _wait_for(
            lambda: follower.sessions.arena.steps(h.sid, "m1")
            == pre_steps, timeout_s=20.0), \
            follower.sessions.arena.stats()
        # the follower still holds its replayed step-0 resident state
        assert follower.library.store.device_cache() \
            .session_entries() > 0
        worker.shutdown()
        leader.shutdown()
        assert _wait_for(
            lambda: follower._ha.role == ha_mod.LEADER, timeout_s=30.0)
        for s in range(pre_steps, len(xs)):
            got.append(np.asarray(h.generate(xs[s], deadline_s=60.0)))
        # the gate: steps CONTINUE from the pushed spill — a rewind
        # would answer steps 1..3 again
        assert h.steps == len(xs)
        assert follower.sessions.table.steps(h.sid) == len(xs)
        want = _solo_outputs(follower.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        c.close()


@pytest.mark.chaos
def test_live_session_move_zero_failed_requests(tmp_path):
    """The rebalance hook: relocate a live session between pool
    members while a decode loop hammers it. In-flight steps bounce
    with the typed retryable SessionMoved and land at the target —
    zero failed client requests, steps exactly sequential, outputs
    byte-equal."""
    with _pool(tmp_path, n_workers=2) as (leader, _, workers):
        c = RemoteClient(leader.advertise_addr, retry=FAILOVER)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=17)
        h = c.open_session("m1", kind="lstm")
        src = h.owner
        dst = next(w.advertise_addr for w in workers
                   if w.advertise_addr != src)
        n_steps = 12
        xs = [_x(0, s) for s in range(n_steps)]
        got, errors = [], []
        moved = threading.Event()

        def drive():
            try:
                for s, x in enumerate(xs):
                    got.append(np.asarray(
                        h.generate(x, deadline_s=60.0)))
                    if s == 3:
                        moved.set()
            except Exception as e:  # noqa: BLE001 — the gate: none
                errors.append(e)

        t = threading.Thread(target=drive)
        t.start()
        assert moved.wait(30)
        c._request(MsgType.SESSION_OPEN,
                   {"op": "move", "sid": h.sid, "to": dst})
        t.join(timeout=120)
        assert not t.is_alive()
        assert errors == [], errors
        assert len(got) == n_steps and h.steps == n_steps
        assert h.owner == dst
        want = _solo_outputs(leader.library, "m1", "lstm", xs)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        h.close()
        c.close()


# --- every kind on the one state path (PR 27) --------------------------

@pytest.mark.parametrize("kind", ["lstm", "transformer_layer"])
def test_every_kind_steps_on_the_slab_and_moves_no_state(tmp_path, kind):
    """Both toy kinds declare their state as a layout and step on the
    model's slab: 3 sessions batched equal their solo twins byte for
    byte (the transformer layer's ring cache wraps: 70 steps over 64
    entries), a warm step copies no state across the host, and a
    closed session's slot is the next session's."""
    decode_mod.clear_decode_programs()
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind=kind, hidden=HID, seed=31)
        clients = [RemoteClient(ctl.advertise_addr) for _ in range(3)]
        handles = [cc.open_session("m1", kind=kind) for cc in clients]
        n_steps = 70 if kind == "transformer_layer" else 6
        host0 = _counter("session.state_host_bytes")
        outs = {i: [] for i in range(3)}
        errors = []

        def drive(i):
            try:
                for s in range(n_steps):
                    outs[i].append(np.asarray(
                        handles[i].generate(_x(i, s))))
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((i, e))

        ts = [threading.Thread(target=drive, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert errors == []
        assert _counter("session.state_host_bytes") == host0
        assert decode_mod.decode_stats()["traces"] == 1
        cache = ctl.library.store.device_cache()
        slots = sorted(cache.session_get(h.sid, "m1", "slot",
                                         touch=False)["slot"]
                       for h in handles)
        assert slots == [0, 1, 2]
        for i in range(3):
            want = _solo_outputs(ctl.library, "m1", kind,
                                 [_x(i, s) for s in range(n_steps)])
            for g, w in zip(outs[i], want):
                assert g.tobytes() == w.tobytes()
        handles[1].close()
        again = clients[1].open_session("m1", kind=kind)
        assert cache.session_get(again.sid, "m1", "slot",
                                 touch=False)["slot"] == 1
        assert cache.slab("m1").live() == 3
        for h in (handles[0], handles[2], again):
            h.close()
        assert cache.slab("m1").live() == 0
        for cc in clients + [c]:
            cc.close()


def test_more_sessions_than_slots_spill_and_revive(tmp_path):
    """Nine sessions on a slab of eight slots: the least recently used
    lease is spilled to make room, and comes back when its session
    steps again — every stream still equals its solo twin."""
    with _daemon(tmp_path) as ctl:
        c = RemoteClient(ctl.advertise_addr)
        deploy_decode_model(c, "m1", kind="lstm", hidden=HID, seed=33)
        handles = [c.open_session("m1", kind="lstm") for _ in range(9)]
        assert ctl.library.store.device_cache().slab("m1").slots == 8
        spills0 = _counter("session.slab.spills")
        outs = {i: [] for i in range(9)}
        for s in range(3):
            for i, h in enumerate(handles):
                outs[i].append(np.asarray(h.generate(_x(i, s))))
        assert _counter("session.slab.spills") > spills0
        assert _counter("session.slab.revives") > 0
        for i in range(9):
            want = _solo_outputs(ctl.library, "m1", "lstm",
                                 [_x(i, s) for s in range(3)])
            for g, w in zip(outs[i], want):
                assert g.tobytes() == w.tobytes()
        for h in handles:
            h.close()
        c.close()
