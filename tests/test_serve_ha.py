"""True multi-host HA suite (serve/ha.py + storage/mutlog.py).

Chaos-style, deterministic where the protocol allows it: leader kills
are real daemon shutdowns mid-ingest, elections run the real probe
loop at shrunk timings, and the straggler/fencing scenarios script the
promotion instead of racing for it. The acceptance contract: a leader
kill on an armed pool promotes a follower within the election window
with ZERO lost and ZERO doubled writes, a deposed leader's straggler
frames are rejected typed (naming the stale term), the handoff buffer
drains from the durable log even across a leader restart, and a
coalesce waiter's idempotency token survives the failover hop
(TOKEN_ALIAS) so its retry replays instead of re-executing.
"""

import contextlib
import threading
import time

import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.serve import ha as ha_mod
from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
from netsdb_tpu.serve.errors import (
    NotLeaderError,
    RetryableRemoteError,
)
from netsdb_tpu.serve.protocol import (
    CODEC_PICKLE,
    IDEMPOTENCY_KEY,
    MsgType,
)
from netsdb_tpu.serve.server import ServeController, _FollowerLink
from netsdb_tpu.storage.store import SetIdentifier
from netsdb_tpu.workloads.scaleout import scaleout_table

pytestmark = pytest.mark.chaos

FAST = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.1)
#: generous enough to ride out a full election window (0.35 s) plus
#: the NotLeader switch-back ping-pong against the dead leader
FAILOVER = RetryPolicy(max_attempts=80, base_delay_s=0.05,
                       max_delay_s=0.25)
ELECTION_S = 0.35

_DAEMON_KW = dict(heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
                  heartbeat_misses=2, mirror_ack_timeout_s=5.0,
                  resync_grace_s=2.0)


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


def _wait_for(pred, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _content(ctl, db, s):
    return sorted(r["i"] for r in ctl.library.get_set_iterator(db, s))


def _local_rows(ctl, db, set_name) -> int:
    items = ctl.library.store.get_items(SetIdentifier(db, set_name))
    return sum(int(getattr(it, "num_rows", 0) or 0) for it in items)


@contextlib.contextmanager
def ha_pool(tmp_path, n_followers=1, n_workers=0, arm=True,
            storage_kwargs=None, leader_kwargs=None):
    """An armed succession pool: a leader mirroring to ``n_followers``
    HA followers, optionally over ``n_workers`` shard workers. Yields
    ``(leader, followers, workers)``; addresses via
    ``d.advertise_addr``. Daemons killed by a test must be removed
    from teardown by the test setting ``d.port = None``... instead we
    just tolerate double-shutdown (it is idempotent)."""
    daemons = []
    try:
        workers = []
        for i in range(n_workers):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}"),
                              **(storage_kwargs or {})),
                port=0, **_DAEMON_KW)
            w.start()
            daemons.append(w)
            workers.append(w)
        followers = []
        for i in range(n_followers):
            f = ServeController(
                Configuration(root_dir=str(tmp_path / f"f{i}"),
                              **(storage_kwargs or {})),
                port=0, **_DAEMON_KW)
            f.start()
            daemons.append(f)
            followers.append(f)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"),
                          **(storage_kwargs or {})),
            port=0,
            followers=[f.advertise_addr for f in followers],
            workers=[w.advertise_addr for w in workers],
            **dict(_DAEMON_KW, **(leader_kwargs or {})))
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


# --- satellite 2: abort-closed links count dropped mirror frames ------

def test_abort_closed_link_counts_dropped_frames():
    """close(abort=True) with frames still queued: each undelivered
    frame fails fast AND ticks serve.mirror_dropped — previously they
    were silently swallowed, so operators could not see the
    divergence depth a resync had to close."""
    class _Gate:
        def __init__(self):
            self.release = threading.Event()
            self.calls = 0

        def _request(self, typ, payload, codec):
            self.calls += 1
            self.release.wait(10)
            return {"ok": True}

        def _force_close(self):
            self.release.set()

    gate = _Gate()
    link = _FollowerLink("gate:1", gate)
    r1 = link.submit(MsgType.SEND_DATA, {"i": 1}, CODEC_PICKLE)
    assert _wait_for(lambda: gate.calls == 1)  # r1 in flight, blocked
    r2 = link.submit(MsgType.SEND_DATA, {"i": 2}, CODEC_PICKLE)
    r3 = link.submit(MsgType.SEND_DATA, {"i": 3}, CODEC_PICKLE)
    dropped0 = _counter("serve.mirror_dropped")
    link.close(abort=True)
    assert r1["done"].wait(5) and "reply" in r1  # released, acked
    assert r2["done"].wait(5) and r3["done"].wait(5)
    assert _counter("serve.mirror_dropped") == dropped0 + 2
    assert "not forwarded" in r2["error"]
    assert "not forwarded" in r3["error"]
    # post-close submits refuse without counting (never enqueued, the
    # caller sees the error synchronously)
    r4 = link.submit(MsgType.SEND_DATA, {"i": 4}, CODEC_PICKLE)
    assert r4["done"].is_set() and "closed" in r4["error"]
    assert _counter("serve.mirror_dropped") == dropped0 + 2


def test_mirror_dropped_surfaces_in_collect_stats(tmp_path):
    with ha_pool(tmp_path, arm=False) as (leader, followers, _):
        c = RemoteClient(leader.advertise_addr, retry=FAST)
        stats = c.collect_stats()
        mirror = stats.get("mirror")
        assert isinstance(mirror, dict)
        assert mirror["mirror_dropped"] == _counter(
            "serve.mirror_dropped")
        assert leader.follower_status()["mirror_dropped"] \
            == _counter("serve.mirror_dropped")
        c.close()


# --- tentpole: promotion under kill, exact totals ---------------------

def test_leader_kill_mid_ingest_promotes_with_exact_totals(tmp_path):
    """The flagship kill: the leader dies while a client is streaming
    BULK ingest batches. The follower promotes within the election
    window (term 2), the client fails over via the typed NotLeader /
    connection-lost rotation, and every batch lands EXACTLY once —
    zero lost, zero doubled writes."""
    with ha_pool(tmp_path) as (leader, followers, _):
        follower = followers[0]
        c = RemoteClient(leader.advertise_addr,
                         failover=[follower.advertise_addr],
                         retry=FAILOVER)
        c.create_database("d")
        c.create_set("d", "t", type_name="table")
        batches, rows_each = 6, 1000
        done, failed = [], []

        def ingest():
            for i in range(batches):
                deadline = time.monotonic() + 30.0
                while True:
                    try:
                        c.send_table("d", "t",
                                     scaleout_table(rows_each, seed=i),
                                     append=True)
                        done.append(i)
                        break
                    except RetryableRemoteError:
                        if time.monotonic() > deadline:
                            failed.append(i)
                            break
                        time.sleep(0.05)

        promos0 = _counter("ha.promotions")
        t = threading.Thread(target=ingest)
        t.start()
        assert _wait_for(lambda: len(done) >= 2)
        leader.shutdown()  # kill mid-stream
        t.join(timeout=90)
        assert not t.is_alive()
        assert failed == [] and len(done) == batches
        assert _wait_for(
            lambda: follower._ha.role == ha_mod.LEADER), \
            "follower never promoted"
        assert follower._ha.term == 2
        assert _counter("ha.promotions") == promos0 + 1
        assert _local_rows(follower, "d", "t") == batches * rows_each
        # the promoted leader serves the client directly now
        assert c.ping()["ha"]["role"] == ha_mod.LEADER
        assert c.failovers >= 1
        c.close()


def test_double_failover_climbs_the_succession_ladder(tmp_path):
    """peers = [L, F1, F2]: killing L promotes F1 (term 2) while F2
    stays a follower (its earlier peer F1 answers probes); killing F1
    then promotes F2 (term 3). Writes land exactly once at every
    rung — succession order makes the double election deterministic."""
    with ha_pool(tmp_path, n_followers=2) as (leader, followers, _):
        f1, f2 = followers
        c = RemoteClient(leader.advertise_addr,
                         failover=[f1.advertise_addr,
                                   f2.advertise_addr],
                         retry=FAILOVER)
        c.create_database("d")
        c.create_set("d", "s", type_name="object")

        def send_batch(base):
            deadline = time.monotonic() + 30.0
            while True:
                try:
                    c.send_data("d", "s",
                                [{"i": base + k} for k in range(10)])
                    return
                except RetryableRemoteError:
                    assert time.monotonic() < deadline
                    time.sleep(0.05)

        send_batch(0)
        leader.shutdown()
        assert _wait_for(lambda: f1._ha.role == ha_mod.LEADER)
        assert f1._ha.term == 2
        # F2 adopted the new leader instead of promoting itself
        assert f2._ha.role == ha_mod.FOLLOWER
        send_batch(100)
        assert _wait_for(
            lambda: f2._ha.leader_addr == f1.advertise_addr)
        f1.shutdown()
        assert _wait_for(lambda: f2._ha.role == ha_mod.LEADER)
        assert f2._ha.term == 3
        send_batch(200)
        want = sorted(list(range(0, 10)) + list(range(100, 110))
                      + list(range(200, 210)))
        assert _content(f2, "d", "s") == want  # no loss, no doubles
        c.close()


def test_deposed_leader_straggler_is_fenced_not_applied(tmp_path):
    """The split-brain write: the old leader, not yet aware it was
    deposed, mirrors a client mutation at its stale term. The new
    leader rejects it typed (naming BOTH terms), the frame is never
    applied there, and the old leader steps down on the rejection."""
    with ha_pool(tmp_path) as (leader, followers, _):
        follower = followers[0]
        c = RemoteClient(leader.advertise_addr, retry=FAST)
        c.create_database("d")
        c.create_set("d", "s", type_name="object")
        c.send_data("d", "s", [{"i": 1}])
        assert _content(follower, "d", "s") == [1]

        # scripted promotion: the follower becomes leader at term 2
        # while the old leader still believes it leads at term 1
        follower._promote_self()
        assert follower._ha.role == ha_mod.LEADER
        assert follower._ha.term == 2
        assert leader._ha.role == ha_mod.LEADER  # stale belief

        fenced0 = _counter("ha.stragglers_rejected")
        straggler = RemoteClient(leader.advertise_addr,
                                 retry=RetryPolicy(max_attempts=1))
        with pytest.raises(NotLeaderError) as ei:
            straggler.send_data("d", "s", [{"i": 2}])
        assert ei.value.retryable
        # the rejection names the stale and the current term
        assert "term 1" in str(ei.value) and "term 2" in str(ei.value)
        assert _counter("ha.stragglers_rejected") == fenced0 + 1
        # never applied at the new leader — the authoritative store
        assert _content(follower, "d", "s") == [1]
        # the deposed leader learned its place from the mirror ack
        assert _wait_for(lambda: leader._ha.role == ha_mod.FOLLOWER)
        assert leader._ha.term == 2
        straggler.close()
        c.close()


# --- satellite 1: coalesce-waiter tokens survive failover -------------

def test_coalesce_waiter_token_survives_failover_no_reexecute(tmp_path):
    """PR 9 gap, closed: a coalesce WAITER's idempotency token never
    rode the mirror (only the flight leader's frame did). TOKEN_ALIAS
    replicates waiter→leader-token bindings, so the waiter's
    post-failover retry replays the cached reply instead of
    re-executing the job on the promoted follower."""
    with ha_pool(tmp_path) as (leader, followers, _):
        follower = followers[0]
        calls = {"leader": 0, "follower": 0}
        gate = threading.Event()

        def stub_for(name, ctl):
            def stub(p):
                calls[name] += 1
                if name == "leader":
                    gate.wait(15)  # hold the flight open for the waiter
                return MsgType.OK, {"ran": name}
            ctl.handlers[MsgType.EXECUTE_COMPUTATIONS] = stub

        stub_for("leader", leader)
        stub_for("follower", follower)

        payload = {"job_name": "alias-regress", "sinks": ["stub"]}
        replies = {}

        def run(tag, token):
            cli = RemoteClient(leader.advertise_addr, retry=FAST)
            try:
                replies[tag] = cli._request(
                    MsgType.EXECUTE_COMPUTATIONS,
                    dict(payload, **{IDEMPOTENCY_KEY: token}),
                    codec=CODEC_PICKLE)
            finally:
                cli.close()

        hits0 = _counter("sched.coalesce_hits")
        ta = threading.Thread(target=run, args=("A", "tok-flight"))
        ta.start()
        assert _wait_for(lambda: calls["leader"] == 1)
        tb = threading.Thread(target=run, args=("B", "tok-waiter"))
        tb.start()
        assert _wait_for(
            lambda: _counter("sched.coalesce_hits") == hits0 + 1)
        gate.set()
        ta.join(timeout=30)
        tb.join(timeout=30)
        assert calls["leader"] == 1  # single flight
        assert replies["A"] == replies["B"] == {"ran": "leader"}
        # the alias reached the follower's idempotency cache
        assert _wait_for(lambda: "tok-waiter" in follower._idem._done)

        leader.shutdown()
        assert _wait_for(lambda: follower._ha.role == ha_mod.LEADER)

        # the waiter's retry against the new leader: replayed from the
        # aliased token, NOT re-executed
        retry = RemoteClient(follower.advertise_addr, retry=FAST)
        reply = retry._request(
            MsgType.EXECUTE_COMPUTATIONS,
            dict(payload, **{IDEMPOTENCY_KEY: "tok-waiter"}),
            codec=CODEC_PICKLE)
        assert reply == {"ran": "follower"}  # the mirrored flight's
        assert calls["follower"] == 1  # mirror only — never re-ran
        retry.close()


# --- durable handoff: the spill log survives a leader restart ---------

def test_handoff_buffer_replays_after_leader_restart(tmp_path):
    """ha_mutlog on: ingest buffered for a degraded shard spills to
    disk; the leader process dies and restarts; the restored buffer
    drains EXACTLY the spilled batch to the readmitted shard — no
    loss, no doubles (the pre-PR gap: the buffer was memory-only, a
    leader restart silently dropped every pending handoff batch)."""
    kw = {"ha_mutlog": True}
    with ha_pool(tmp_path, n_followers=0, n_workers=1, arm=False,
                 storage_kwargs=kw,
                 leader_kwargs={"heartbeat_interval_s": 60.0}) \
            as (leader, _, workers):
        w0 = workers[0]
        w0_addr = w0.advertise_addr
        c = RemoteClient(leader.advertise_addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(3000))
        w0_rows = _local_rows(w0, "d", "t")
        assert w0_rows == 1500  # its slot of the 2-way range split
        leader._evict_shard(w0_addr, "test eviction")
        # refresh to the post-eviction epoch: a stale map would route
        # the shard's partition straight to the (still-live) worker
        # instead of the leader's handoff buffer
        c._placement_entry("d", "t", refresh=True)
        # CURRENT map: the degraded slot's partition buffers (and
        # spills) at the leader instead of reaching the shard
        c.send_table("d", "t", scaleout_table(3000, seed=2),
                     append=True)
        assert leader.shards.handoff_pending(w0_addr) == 1
        assert _local_rows(w0, "d", "t") == w0_rows
        c.close()
        leader.shutdown()  # the buffered batch dies with the process…

        # …except it doesn't: the restarted leader (on a FRESH port —
        # restore rebinds the persisted map's old advertise address)
        # restores placement + the spilled buffer from <root>/mutlog
        # and drains at readmit
        drained0 = _counter("shard.handoff_drained")
        leader2 = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"), **kw),
            port=0, workers=[w0_addr],
            **dict(_DAEMON_KW, heartbeat_interval_s=60.0))
        leader2.start()
        try:
            assert leader2.shards.handoff_pending(w0_addr) == 1
            assert leader2.shards.is_degraded(w0_addr)
            entry = leader2.placement.entry("d", "t")
            assert entry is not None  # replicated map survived too
            addrs = {sl["addr"] for sl in entry["slots"]}
            assert leader2.advertise_addr in addrs  # rebound to here
            assert leader2._try_readmit_shard(w0_addr)
            assert _counter("shard.handoff_drained") == drained0 + 1
            assert leader2.shards.handoff_pending(w0_addr) == 0
            # exact totals: the shard gained precisely its buffered
            # 1500-row partition, once
            assert _local_rows(w0, "d", "t") == w0_rows + 1500
            # the spill is consumed: a second restart replays nothing
            assert leader2.shards.load_spill() == 0
        finally:
            leader2.shutdown()


# --- flagship: sharded pool, leader kill, routed ingest continuity ----

def test_sharded_pool_failover_routed_ingest_exact_totals(tmp_path):
    """4 daemons (leader + HA follower + 2 shard workers), sharded
    set, leader killed mid routed ingest: the follower promotes,
    restores the replicated placement map with the dead leader's slot
    rebound to itself, pushes the bumped epochs, and the client's
    failover rotation lands every remaining batch — totals exact
    across the surviving pool."""
    with ha_pool(tmp_path, n_followers=1, n_workers=2) \
            as (leader, followers, workers):
        follower = followers[0]
        c = RemoteClient(leader.advertise_addr,
                         failover=[follower.advertise_addr],
                         retry=FAILOVER)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        # the placement map replicated to the HA follower on create
        assert _wait_for(
            lambda: (follower._ha.placement_wire() or {}).get("sets",
                                                             {}))
        batches, rows_each = 5, 3000
        done, failed = [], []

        def ingest():
            for i in range(batches):
                deadline = time.monotonic() + 40.0
                while True:
                    try:
                        c.send_table("d", "t",
                                     scaleout_table(rows_each, seed=i),
                                     append=True)
                        done.append(i)
                        break
                    except RetryableRemoteError:
                        if time.monotonic() > deadline:
                            failed.append(i)
                            break
                        time.sleep(0.05)

        t = threading.Thread(target=ingest)
        t.start()
        assert _wait_for(lambda: len(done) >= 1)
        leader.shutdown()  # mid routed ingest
        t.join(timeout=120)
        assert not t.is_alive()
        assert failed == [] and len(done) == batches
        assert _wait_for(lambda: follower._ha.role == ha_mod.LEADER)
        # the dead leader's slot rebound to the promoted follower
        entry = follower.placement.entry("d", "t")
        addrs = {sl["addr"] for sl in entry["slots"]}
        assert leader.advertise_addr not in addrs
        assert follower.advertise_addr in addrs
        # exact totals over the surviving pool: every batch exactly
        # once (the leader-slot rows survive via the mirror)
        total = sum(_local_rows(d, "d", "t")
                    for d in [follower] + workers)
        assert total == batches * rows_each
        c.close()
