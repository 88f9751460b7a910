"""Self-rebalancing placement (PR 19, serve/rebalance.py).

The pinned-formula skew detector + byte-bounded greedy planner as
pure-function units, then the live chaos suite over in-process pools:
the flagship pool-growth campaign under live traffic (zero
client-visible downtime — only typed retryable errors absorbed,
row-exact totals), shard death mid-RESHARD (typed abort, no loss, no
doubles), and a leader restart mid-campaign (the persisted post-move
map reloads and the prune reconcile completes the crashed drop leg).
"""

import threading
import time

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.serve import placement as PL
from netsdb_tpu.serve import rebalance as RB
from netsdb_tpu.serve.client import (
    RemoteClient,
    RetryPolicy,
    ShardUnavailableError,
)
from netsdb_tpu.serve.server import ServeController
from netsdb_tpu.workloads.scaleout import scaleout_table

from test_scaleout import _local_rows, pool

pytestmark = pytest.mark.chaos


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


def _checksum(t) -> int:
    return int(np.asarray(t["l_price"], dtype=np.int64).sum())


def _entry(ctl, db="d", s="hot"):
    e = ctl.placement.entry(db, s)
    assert e is not None
    return e


# --- pinned formula units --------------------------------------------

def test_set_heats_pinned_formula():
    snap = {
        "client-a": {
            "d:hot": {"requests": 4, "executor.chunks": 8,
                      "staged_bytes": 2 << 20},
            "*": {"requests": 100},  # unattributable: never placed
        },
        "client-b": {"d:hot": {"requests": 1},
                     "d:cold": {"staged_bytes": 1 << 20}},
    }
    heats = RB.set_heats(snap)
    # 4*1.0 + 8*0.25 + 2MiB*(1/MiB) = 8.0, plus client-b's 1 request
    assert heats["d:hot"] == pytest.approx(
        4 * RB.REQUEST_WEIGHT + 8 * RB.CHUNK_WEIGHT
        + (2 << 20) * RB.BYTE_WEIGHT + 1)
    assert heats["d:cold"] == pytest.approx(1.0)
    assert "*" not in heats


def test_addr_heats_live_only_and_fresh_member_zero():
    entries = {("d", "hot"): {"slots": [
        {"addr": "a:1", "state": PL.LIVE},
        {"addr": "b:2", "state": PL.LIVE},
        {"addr": "c:3", "state": PL.HANDOFF},  # degraded: no share
    ]}}
    heats = {"d:hot": 9.0}
    out = RB.addr_heats(entries, heats, ["a:1", "b:2", "c:3", "d:4"])
    assert out == {"a:1": 3.0, "b:2": 3.0, "c:3": 0.0, "d:4": 0.0}
    # emptiness never looks like skew; real imbalance does
    assert RB.skew_ratio({}) == 1.0
    assert RB.skew_ratio({"a": 0.0, "b": 0.0}) == 1.0
    assert RB.skew_ratio(out) == pytest.approx(3.0 / 1.5)


def test_plan_moves_strict_improvement_and_byte_cap():
    members = ["a:1", "b:2", "c:3", "d:4", "e:5"]
    entries = {
        ("d", "hot"): {"slots": [
            {"addr": m, "state": PL.LIVE} for m in members[:4]]},
        ("d", "cold"): {"slots": [
            {"addr": m, "state": PL.LIVE} for m in members[:4]]},
    }
    heats = {"d:hot": 80.0, "d:cold": 8.0}
    sizes = {(m, "d:hot"): 1000 for m in members[:4]}
    plan = RB.plan_moves(entries, heats, sizes, members, 0)
    # a hot slot lands on the fresh, slot-less member
    assert plan and plan[0]["set"] == "hot" and plan[0]["dst"] == "e:5"
    # a single uniform set over one-extra member cannot strictly
    # improve the max — the planner must settle, not churn
    one = {("d", "hot"): entries[("d", "hot")]}
    assert RB.plan_moves(one, {"d:hot": 80.0}, sizes, members, 0) == []
    # the byte bound stops the round, but the FIRST move always fits
    capped = RB.plan_moves(entries, heats, sizes, members, 10)
    assert len(capped) == 1
    # no heat signal at all: the fallback balances by slot count
    idle = RB.plan_moves(entries, {}, {}, members, 0)
    assert idle and idle[0]["dst"] == "e:5"


def test_plan_moves_respects_one_slot_per_member():
    # every member already owns a slot: nowhere legal to move
    members = ["a:1", "b:2"]
    entries = {("d", "t"): {"slots": [
        {"addr": "a:1", "state": PL.LIVE},
        {"addr": "b:2", "state": PL.LIVE}]}}
    assert RB.plan_moves(entries, {"d:t": 50.0},
                         {("a:1", "d:t"): 10}, members, 0) == []


def test_skew_detector_streak_and_idle_reset():
    members = ["a:1", "b:2"]
    entries = {("d", "t"): {"slots": [
        {"addr": "a:1", "state": PL.LIVE}]}}  # all heat on a:1
    det = RB.SkewDetector(ratio=1.5, windows=2)
    cum = 0.0
    ratio, sustained = det.observe({"d:t": (cum := cum + 100.0)},
                                   entries, members)
    assert ratio == pytest.approx(2.0) and not sustained
    assert det.streak == 1
    # an idle window (delta below MIN_WINDOW_HEAT) resets the streak
    ratio, sustained = det.observe({"d:t": cum + 1.0}, entries,
                                   members)
    assert not sustained and det.streak == 0
    cum += 1.0
    for i in range(2):
        ratio, sustained = det.observe({"d:t": (cum := cum + 100.0)},
                                       entries, members)
    assert sustained  # two consecutive hot windows
    assert det.streak == 0  # a verdict re-earns the next one


# --- seal / tombstone fencing ----------------------------------------

def test_seal_blocks_routed_writes_and_expires(tmp_path):
    with pool(tmp_path, n_workers=1) as (leader, _w, addr):
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", scaleout_table(200))
        # write-seal BOTH slots (what a move's seal leg does on the
        # source daemon) — sealing every owner keeps the failed
        # append all-or-nothing for the exactness check below
        for d in (leader, _w[0]):
            RB.handle_reshard(d, {"op": "seal", "db": "d",
                                  "set": "t"})
        assert RB.sealed(leader, "d", "t")
        with pytest.raises(ShardUnavailableError):
            c.send_table("d", "t", scaleout_table(100, seed=3),
                         append=True)
        # READS keep serving under the seal — zero downtime is the
        # whole point of write-only sealing
        assert c.get_table_streamed("d", "t").num_rows == 200
        for d in (leader, _w[0]):
            RB.handle_reshard(d, {"op": "unseal", "db": "d",
                                  "set": "t"})
        assert not RB.sealed(leader, "d", "t")
        # a seal left behind by a dead leader self-heals: TTL expiry
        with leader._shard_mu:
            leader._reshard_seals[("d", "t")] = \
                time.monotonic() + 0.05
        assert RB.sealed(leader, "d", "t")
        time.sleep(0.06)
        assert not RB.sealed(leader, "d", "t")
        c.send_table("d", "t", scaleout_table(100, seed=3),
                     append=True)
        assert c.get_table_streamed("d", "t").num_rows == 300
        c.close()


# --- the flagship: pool growth under live traffic --------------------

def test_pool_growth_rebalances_with_zero_downtime(tmp_path):
    """4-daemon pool under a live 80/20 read mix; a 5th daemon
    registers mid-run and the forced campaign moves slot ownership
    onto it. Clients see ZERO failures (typed retries absorbed inside
    the client), the moved slot serves from the new owner, and the
    post-campaign totals are row- and checksum-exact including writes
    sent during and after the campaign."""
    kw = {"rebalance": True}
    hot = scaleout_table(20_000, seed=1)
    cold = scaleout_table(2_000, seed=2)
    with pool(tmp_path, n_workers=3, storage_kwargs=kw) \
            as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "hot", type_name="table", placement="range")
        c.create_set("d", "cold", type_name="table",
                     placement="range")
        c.send_table("d", "hot", hot)
        c.send_table("d", "cold", cold)
        epoch0 = leader.placement.to_wire()["epoch"]
        moves0 = _counter("rebalance.moves")

        stop = threading.Event()
        failures = []

        def load():
            lc = RemoteClient(addr)
            n = 0
            try:
                while not stop.is_set():
                    name = "hot" if n % 5 else "cold"
                    try:
                        t = lc.get_table_streamed("d", name)
                        want = 20_000 if name == "hot" else 2_000
                        if t.num_rows < want:
                            failures.append(
                                f"{name} rows {t.num_rows}")
                    except Exception as e:  # noqa: BLE001 — ANY
                        failures.append(repr(e))  # escape fails it
                    n += 1
            finally:
                lc.close()

        threads = [threading.Thread(target=load, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        w4 = ServeController(
            Configuration(root_dir=str(tmp_path / "w4"), **kw),
            port=0)
        w4.start()
        try:
            res = c.add_worker(f"127.0.0.1:{w4.port}")
            committed = [m for m in (res["moves"] or [])
                         if m.get("ok")]
            assert committed, res
            # writes during the settled post-campaign epoch still land
            c.send_table("d", "hot", scaleout_table(1_000, seed=4),
                         append=True)
            time.sleep(0.3)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert failures == [], failures[:5]
            # the new member owns what the campaign moved to it
            w4_addr = f"127.0.0.1:{w4.port}"
            owned = [sl for e in (_entry(leader, "d", m["set"])
                                  for m in committed)
                     for sl in e["slots"] if sl["addr"] == w4_addr]
            assert owned and all(sl["state"] == PL.LIVE
                                 for sl in owned)
            assert _local_rows(w4, "d", committed[0]["set"]) > 0
            assert leader.placement.to_wire()["epoch"] > epoch0
            assert _counter("rebalance.moves") \
                >= moves0 + len(committed)
            # exact totals: nothing lost, nothing doubled
            back = c.get_table_streamed("d", "hot")
            assert back.num_rows == 21_000
            assert _checksum(back) == _checksum(hot) + _checksum(
                scaleout_table(1_000, seed=4))
            backc = c.get_table_streamed("d", "cold")
            assert backc.num_rows == 2_000
            assert _checksum(backc) == _checksum(cold)
            # the observability surface saw it: status + view
            view = c.placement_view()
            assert view["status"]["moves"]
            assert any(m["addr"] == w4_addr and m["slots"] >= 1
                       for m in view["members"])
        finally:
            w4.shutdown()
            c.close()


# --- chaos: shard death mid-RESHARD ----------------------------------

def test_dst_death_mid_reshard_aborts_typed(tmp_path):
    """The destination dies before the move's prepare leg: the move
    aborts TYPED (ok=False, rebalance.aborts ticks), the source is
    unsealed (writes resume), the dead member is evicted, and the
    totals are exact — nothing was lost to the corpse."""
    kw = {"rebalance": True}
    hot = scaleout_table(8_000, seed=1)
    with pool(tmp_path, n_workers=2, storage_kwargs=kw) \
            as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "hot", type_name="table", placement="range")
        c.send_table("d", "hot", hot)
        w4 = ServeController(
            Configuration(root_dir=str(tmp_path / "w4"), **kw),
            port=0)
        w4.start()
        w4_addr = f"127.0.0.1:{w4.port}"
        c.add_worker(w4_addr, campaign=False)
        w4.shutdown()  # dies between registration and the campaign
        aborts0 = _counter("rebalance.aborts")
        src = _entry(leader)["slots"][0]["addr"]
        res = leader.rebalancer.run_moves([{
            "db": "d", "set": "hot", "slot": 0,
            "src": src, "dst": w4_addr, "nbytes": 0}])
        assert len(res) == 1 and res[0]["ok"] is False
        assert res[0]["error"]
        assert _counter("rebalance.aborts") == aborts0 + 1
        # ownership unchanged; the dead destination got nothing
        assert _entry(leader)["slots"][0]["addr"] == src
        assert leader.shards.is_degraded(w4_addr)
        # the source unsealed: writes flow again, totals exact
        c.send_table("d", "hot", scaleout_table(1_000, seed=5),
                     append=True)
        back = c.get_table_streamed("d", "hot")
        assert back.num_rows == 9_000
        assert _checksum(back) == _checksum(hot) + _checksum(
            scaleout_table(1_000, seed=5))
        c.close()


def test_src_death_mid_reshard_rolls_handoff(tmp_path):
    """The source dies mid-move (its pull leg fails): typed abort,
    the dead member is evicted and its slots roll to HANDOFF under a
    bumped epoch — the standing PR 13 degradation story — and no row
    was doubled into the destination."""
    kw = {"rebalance": True}
    with pool(tmp_path, n_workers=2, storage_kwargs=kw) \
            as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "hot", type_name="table", placement="range")
        c.send_table("d", "hot", scaleout_table(8_000, seed=1))
        w4 = ServeController(
            Configuration(root_dir=str(tmp_path / "w4"), **kw),
            port=0)
        w4.start()
        w4_addr = f"127.0.0.1:{w4.port}"
        c.add_worker(w4_addr, campaign=False)
        victim = workers[0]
        victim_addr = victim.advertise_addr
        slot = next(i for i, sl in enumerate(_entry(leader)["slots"])
                    if sl["addr"] == victim_addr)
        victim_rows = _local_rows(victim, "d", "hot")
        assert victim_rows > 0
        epoch0 = _entry(leader)["epoch"]
        aborts0 = _counter("rebalance.aborts")
        victim.shutdown()  # dies holding a LIVE slot, mid-campaign
        # a real process death also severs established connections;
        # in-process shutdown only closes the listener, so drop the
        # leader's pooled link to complete the simulation
        leader.shards.drop_client(victim_addr)
        res = leader.rebalancer.run_moves([{
            "db": "d", "set": "hot", "slot": slot,
            "src": victim_addr, "dst": w4_addr, "nbytes": 0}])
        assert res[0]["ok"] is False
        assert _counter("rebalance.aborts") == aborts0 + 1
        e = _entry(leader)
        assert e["epoch"] > epoch0
        assert e["slots"][slot]["addr"] == victim_addr
        assert e["slots"][slot]["state"] == PL.HANDOFF
        assert leader.shards.is_degraded(victim_addr)
        # no doubles: the aborted move shipped nothing to w4 (the
        # prepare leg never even created the set there)
        with pytest.raises(KeyError):
            _local_rows(w4, "d", "hot")
        # and the victim's store still holds its partition intact
        # (nothing cleared by the abort — readmit can serve it again)
        assert _local_rows(victim, "d", "hot") == victim_rows
        w4.shutdown()
        c.close()


# --- chaos: leader restart mid-campaign ------------------------------

def test_leader_restart_mid_campaign_reconciles(tmp_path):
    """ha_mutlog on: a move COMMITS (epoch bumped, map persisted +
    replicated) but the leader dies before the drop leg runs on the
    source. The restarted leader reloads the POST-move map and its
    prune reconcile completes the crashed campaign: the source's
    stale registration is dropped, its local copy cleared and
    tombstoned — no lost rows, no doubles, scan-back exact."""
    kw = {"ha_mutlog": True, "rebalance": True}
    hot = scaleout_table(8_000, seed=1)
    daemons = []
    try:
        workers = []
        for i in range(3):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}"), **kw),
                port=0)
            w.start()
            daemons.append(w)
            workers.append(w)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"), **kw),
            port=0, workers=[w.advertise_addr for w in workers])
        leader.start()
        daemons.append(leader)
        c = RemoteClient(leader.advertise_addr)
        c.create_database("d")
        c.create_set("d", "hot", type_name="table", placement="range")
        c.send_table("d", "hot", hot)
        w4 = ServeController(
            Configuration(root_dir=str(tmp_path / "w4"), **kw),
            port=0)
        w4.start()
        daemons.append(w4)
        w4_addr = w4.advertise_addr
        c.add_worker(w4_addr, campaign=False)
        c.close()

        # crash window: every leg through commit+persist runs, the
        # drop on the source never does (the leader "dies" first)
        real_op = leader.rebalancer._op

        def crashing_op(addr, payload):
            if payload.get("op") == "drop":
                return {}
            return real_op(addr, payload)

        leader.rebalancer._op = crashing_op
        victim = workers[0]
        slot = next(i for i, sl in enumerate(_entry(leader)["slots"])
                    if sl["addr"] == victim.advertise_addr)
        res = leader.rebalancer.run_moves([{
            "db": "d", "set": "hot", "slot": slot,
            "src": victim.advertise_addr, "dst": w4_addr,
            "nbytes": 0}])
        assert res[0]["ok"] is True  # committed…
        moved_rows = _local_rows(w4, "d", "hot")
        assert moved_rows > 0
        # …but the source still holds its (now-unowned) copy
        assert _local_rows(victim, "d", "hot") == moved_rows
        leader.shutdown()

        leader2 = ServeController(
            Configuration(root_dir=str(tmp_path / "leader"), **kw),
            port=0, workers=[w.advertise_addr for w in workers]
            + [w4_addr])
        leader2.start()
        daemons.append(leader2)
        # the persisted POST-move map survived the crash
        e = _entry(leader2)
        assert e["slots"][slot]["addr"] == w4_addr
        # the prune reconcile completed the crashed drop leg: the
        # stale source copy is cleared and tombstoned (a routed frame
        # still riding the old epoch gets PlacementStale, not a
        # silent apply into the cleared set)
        assert _local_rows(victim, "d", "hot") == 0
        assert RB.tombstoned(victim, "d", "hot")
        # the MOVED partition survived the crash exactly — no loss,
        # no doubles (the leader's own local slot is the standing HA
        # story: it needs mirrored followers, not the rebalancer)
        assert _local_rows(w4, "d", "hot") == moved_rows
    finally:
        for d in daemons:
            d.shutdown()


# --- the advisor arm --------------------------------------------------

def test_advisor_commit_and_revert(tmp_path):
    """Rebalancer.advise — observe → propose → measure → commit or
    revert. A measure that improves commits the campaign (ticking
    rebalance.advisor_commits); one that regresses reverts every
    move, restoring the pre-campaign ownership."""
    from netsdb_tpu.learning.advisor import rebalance_candidates

    arms = rebalance_candidates()
    assert [a.specs["rebalance"] for a in arms] == [True, False]

    kw = {"rebalance": True}
    with pool(tmp_path, n_workers=2, storage_kwargs=kw) \
            as (leader, workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "hot", type_name="table", placement="range")
        c.create_set("d", "cold", type_name="table",
                     placement="range")
        c.send_table("d", "hot", scaleout_table(6_000, seed=1))
        c.send_table("d", "cold", scaleout_table(600, seed=2))
        w4 = ServeController(
            Configuration(root_dir=str(tmp_path / "w4"), **kw),
            port=0)
        w4.start()
        w4_addr = f"127.0.0.1:{w4.port}"
        try:
            c.add_worker(w4_addr, campaign=False)

            commits0 = _counter("rebalance.advisor_commits")
            seq = iter([1.0, 2.0])  # after > before: commit
            out = leader.rebalancer.advise(lambda: next(seq))
            assert out["decision"] == "commit", out
            assert _counter("rebalance.advisor_commits") > commits0
            assert any(sl["addr"] == w4_addr
                       for sl in _entry(leader)["slots"])

            # revert: pin the proposal to one concrete move (the
            # planner itself correctly sees a settled pool now), then
            # regress the measure — the inverse move must unwind it
            e = _entry(leader, "d", "cold")
            slot_c, src_c = next(
                (i, sl["addr"]) for i, sl in enumerate(e["slots"])
                if sl["addr"] != w4_addr)
            plan = [{"db": "d", "set": "cold", "slot": slot_c,
                     "src": src_c, "dst": w4_addr, "nbytes": 0}]
            leader.rebalancer.check = \
                lambda force=False: leader.rebalancer.run_moves(plan)
            seq = iter([2.0, 1.0])
            out = leader.rebalancer.advise(lambda: next(seq))
            assert out["decision"] == "revert", out
            assert _entry(leader, "d", "cold")["slots"][slot_c][
                "addr"] == src_c
            back = c.get_table_streamed("d", "hot")
            assert back.num_rows == 6_000
            assert c.get_table_streamed("d", "cold").num_rows == 600
        finally:
            w4.shutdown()
            c.close()
