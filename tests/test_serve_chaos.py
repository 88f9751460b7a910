"""Fault-injection suite for the serve control plane.

Every fault here is SEEDED or SCRIPTED (ChaosInjector) so the runs are
deterministic: frame drops, delays, corruption, truncation, follower
kill/hang mid-mirror. The acceptance contract under test: a client
request either succeeds after typed retries or raises a typed
retryable/fatal error — never an untyped exception, never a
double-applied mutation — and a killed follower reattaches via
checkpoint resync and passes a store-equality check against the leader.
"""

import threading
import time

import numpy as np
import pytest

from netsdb_tpu.config import Configuration
from netsdb_tpu.serve.chaos import ChaosInjector
from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
from netsdb_tpu.serve.errors import (
    AdmissionFullError,
    CorruptFrameError,
    DeadlineExceededError,
    FollowerDegradedError,
    RemoteError,
    RetryableRemoteError,
)
from netsdb_tpu.serve.server import ServeController

pytestmark = pytest.mark.chaos

FAST = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.1)


@pytest.fixture()
def server(tmp_path):
    chaos = ChaosInjector()
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "srv")),
                          port=0, chaos=chaos)
    port = ctl.start()
    yield ctl, f"127.0.0.1:{port}", chaos
    ctl.shutdown()


def _content(ctl, db, s):
    return sorted(r["i"] for r in ctl.library.get_set_iterator(db, s))


# --- typed hierarchy ----------------------------------------------------

def test_fatal_errors_are_not_retried(server):
    ctl, addr, _ = server
    c = RemoteClient(addr, retry=FAST)
    with pytest.raises(RemoteError) as ei:
        c.get_tensor("nodb", "nothing")
    assert not ei.value.retryable
    assert not isinstance(ei.value, RetryableRemoteError)
    assert c.last_attempts == 1  # fatal → raised immediately
    c.close()


def test_dropped_request_frame_is_retried(server):
    """The client's own send vanishes (reset before the server saw it);
    the retry resends and the mutation applies exactly once."""
    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    chaos.arm("drop")
    c.send_data("d", "s", [{"i": 1}])
    assert c.last_attempts >= 2 and c.total_retries >= 1
    assert _content(ctl, "d", "s") == [1]
    c.close()


def test_dropped_reply_is_deduplicated_by_idempotency_token(server):
    """The AMBIGUOUS failure: the server applied the mutation but the
    reply died on the wire. The retry carries the same idempotency
    token, so the server replays the cached reply instead of appending
    a second copy — the never-double-applied acceptance criterion."""
    ctl, addr, srv_chaos = server
    c = RemoteClient(addr, retry=FAST)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    srv_chaos.arm("drop")  # consumed by the next reply send
    c.send_data("d", "s", [{"i": 7}])
    assert c.last_attempts >= 2
    assert _content(ctl, "d", "s") == [7]  # exactly once
    c.close()


def test_truncated_reply_is_retried_and_deduplicated(server):
    ctl, addr, srv_chaos = server
    c = RemoteClient(addr, retry=FAST)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    srv_chaos.arm("truncate")
    c.send_data("d", "s", [{"i": 3}])
    assert _content(ctl, "d", "s") == [3]
    c.close()


def test_corrupt_request_frame_is_typed_and_retried(server):
    """A corrupted body decodes to garbage server-side → typed
    retryable CorruptFrame ERR (the request never executed); the
    resend applies exactly once."""
    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    chaos.arm("corrupt")
    c.send_data("d", "s", [{"i": 9}])
    assert c.last_attempts >= 2
    assert _content(ctl, "d", "s") == [9]
    c.close()


def test_corrupt_request_without_retries_raises_typed(server):
    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1), chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    chaos.arm("corrupt")
    with pytest.raises(CorruptFrameError):
        c.send_data("d", "s", [{"i": 1}])
    assert _content(ctl, "d", "s") == []  # never executed
    c.close()


def test_delayed_reply_times_out_then_retry_succeeds(server):
    """A reply stalled past the client's socket timeout surfaces as the
    retryable timeout family; the retry (fresh connection) succeeds."""
    ctl, addr, srv_chaos = server
    c = RemoteClient(addr, timeout=0.3, retry=FAST)
    assert c.ping()["uptime"] >= 0  # warm path, no chaos
    srv_chaos.arm("delay", delay_s=1.0)
    assert c.ping()["uptime"] >= 0
    assert c.last_attempts >= 2
    c.close()


def test_per_request_deadline_is_enforced(server):
    """Retries stop when the next backoff would cross the per-request
    deadline — the typed DeadlineExceededError, measured monotonic."""
    ctl, addr, srv_chaos = server
    c = RemoteClient(
        addr, retry=RetryPolicy(max_attempts=10, base_delay_s=0.2,
                                jitter=0.0, deadline_s=0.3))
    assert c.ping()["uptime"] >= 0
    for _ in range(4):
        srv_chaos.arm("drop")
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        c.ping()
    assert time.monotonic() - t0 < 2.0  # gave up at the deadline
    c.close()


def test_deadline_bounds_a_hung_attempt(server):
    """A server that accepts the frame and never answers must not hold
    the caller past its per-request deadline even with timeout=None —
    the attempt's socket timeout is capped at the remaining budget."""
    ctl, addr, srv_chaos = server
    c = RemoteClient(addr, retry=RetryPolicy(max_attempts=5,
                                             base_delay_s=0.05, jitter=0.0,
                                             deadline_s=0.4))
    assert c.ping()["uptime"] >= 0
    srv_chaos.arm("delay", delay_s=5.0)  # reply stalls far past deadline
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        c.ping()
    assert time.monotonic() - t0 < 2.0
    c.close()


def test_admission_queue_full_is_typed_retryable(tmp_path):
    """One slot, a slow job holding it: the second job is refused with
    the typed retryable AdmissionFull instead of wedging a thread."""
    from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet

    ctl = ServeController(Configuration(root_dir=str(tmp_path / "adm")),
                          port=0, max_jobs=1, admission_timeout_s=0.05)
    port = ctl.start()
    addr = f"127.0.0.1:{port}"
    try:
        boot = RemoteClient(addr)
        boot.create_database("d")
        boot.create_set("d", "in", type_name="object")
        boot.send_data("d", "in", [1, 2, 3])
        boot.close()

        def slow(x):
            time.sleep(1.0)
            return x

        def sink(tag):
            return WriteSet(Apply(ScanSet("d", "in"), slow,
                                  traceable=False), "d", tag)

        t = threading.Thread(
            target=lambda: RemoteClient(addr).execute_computations(
                sink("out_a"), job_name="hog", fetch_results=False))
        t.start()
        time.sleep(0.3)  # let the hog take the only slot
        c = RemoteClient(addr, retry=RetryPolicy(max_attempts=2,
                                                 base_delay_s=0.01))
        with pytest.raises(AdmissionFullError) as ei:
            c.execute_computations(sink("out_b"), job_name="refused",
                                   fetch_results=False)
        assert ei.value.retryable
        c.close()
        t.join(timeout=30)
    finally:
        ctl.shutdown()


def test_seeded_chaos_storm_converges(tmp_path):
    """Seeded probabilistic drops/truncation/corruption on BOTH
    directions, fault budget capped: every request must either succeed
    after retries or raise a typed RemoteError, and once the dust
    settles each set holds exactly one batch — no double-applies, no
    lost acks mistaken for lost mutations. Same seeds → same storm."""
    srv_chaos = ChaosInjector(seed=4242, drop=0.10, truncate=0.05,
                              max_faults=4)
    cli_chaos = ChaosInjector(seed=1234, drop=0.12, corrupt=0.08,
                              max_faults=6)
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "storm")),
                          port=0, chaos=srv_chaos)
    port = ctl.start()
    try:
        c = RemoteClient(
            f"127.0.0.1:{port}",
            retry=RetryPolicy(max_attempts=10, base_delay_s=0.01,
                              max_delay_s=0.05),
            chaos=cli_chaos)
        c.create_database("d")
        for i in range(12):
            c.create_set("d", f"k{i}", type_name="object")
            c.send_data("d", f"k{i}", [{"i": i}])
        # verification pass reads through the library (no wire, no chaos)
        for i in range(12):
            assert _content(ctl, "d", f"k{i}") == [i], f"set k{i} diverged"
        assert cli_chaos.faults or srv_chaos.faults, \
            "storm injected nothing — seeds/rates regressed"
        c.close()
    finally:
        ctl.shutdown()


def test_explicit_duplicate_token_replays_cached_reply(server):
    """Two different connections, same idempotency token → the second
    request is served from the completed-reply cache, not re-executed."""
    from netsdb_tpu.serve.protocol import CODEC_PICKLE, MsgType

    ctl, addr, _ = server
    c1 = RemoteClient(addr)
    c1.create_database("d")
    c1.create_set("d", "s", type_name="object")
    payload = {"db": "d", "set": "s", "items": [{"i": 5}],
               "__idem__": "tok-explicit-1"}
    r1 = c1._request(MsgType.SEND_DATA, payload, codec=CODEC_PICKLE)
    c2 = RemoteClient(addr)
    r2 = c2._request(MsgType.SEND_DATA, payload, codec=CODEC_PICKLE)
    assert r1 == r2
    assert _content(ctl, "d", "s") == [5]
    c1.close()
    c2.close()


def test_store_snapshot_roundtrip(tmp_path):
    from netsdb_tpu.storage import checkpoint

    snap = {"databases": ["d"], "types": [],
            "sets": [{"db": "d", "set": "s", "kind": "objects",
                      "type_name": "object", "persistence": "transient",
                      "items": [{"i": 1}, {"i": 2}]},
                     {"db": "d", "set": "w", "kind": "tensor",
                      "type_name": "tensor", "persistence": "transient",
                      "dense": np.arange(6, dtype=np.float32).reshape(2, 3),
                      "block_shape": [2, 2]}]}
    root = str(tmp_path / "snaps")
    checkpoint.save_store(root, snap, 1)
    checkpoint.save_store(root, snap, 2)
    assert checkpoint.list_steps(root) == [1, 2]
    back = checkpoint.load_store(root)  # latest
    assert back["databases"] == ["d"]
    np.testing.assert_allclose(back["sets"][1]["dense"],
                               snap["sets"][1]["dense"])


# --- v3 data plane: out-of-band segments + pipelined ingest ------------

def test_corrupt_oob_segment_is_detected_and_retried(server):
    """A bit flip INSIDE an out-of-band tensor segment — where msgpack's
    own framing cannot see it — must fail the per-segment adler32 →
    typed retryable CorruptFrame; the resend applies exactly once."""
    from netsdb_tpu.serve.protocol import MsgType, OOB_MIN_BYTES

    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "w")
    side = max(64, int((OOB_MIN_BYTES * 4 / 4) ** 0.5))
    a = np.arange(side * side, dtype=np.float32).reshape(side, side)
    chaos.arm("corrupt_seg", types=[MsgType.SEND_MATRIX])
    c.send_matrix("d", "w", a, (32, 32))
    assert c.last_attempts >= 2
    assert any(f[0] == "corrupt_seg" for f in chaos.faults)
    np.testing.assert_array_equal(
        np.asarray(ctl.library.get_tensor("d", "w").to_dense()), a)
    c.close()


def test_corrupt_oob_reply_segment_is_typed_and_retried(server):
    """Same fault on the REPLY direction: the tensor segment of a
    GET_TENSOR reply flips mid-wire → client-side checksum failure →
    typed retryable CorruptFrameError → the (idempotent) read retries
    and returns intact data."""
    from netsdb_tpu.serve.protocol import MsgType

    ctl, addr, srv_chaos = server
    c = RemoteClient(addr, retry=FAST)
    c.create_database("d")
    c.create_set("d", "w")
    a = np.random.default_rng(0).standard_normal((128, 128)).astype(
        np.float32)
    c.send_matrix("d", "w", a, (64, 64))
    srv_chaos.arm("corrupt_seg", types=[MsgType.OK])
    t = c.get_tensor("d", "w")
    assert c.last_attempts >= 2
    np.testing.assert_array_equal(t.to_dense(), a)
    c.close()


def test_truncate_inside_oob_segment_is_retried_exactly_once(server):
    """The chaos cut lands INSIDE a tensor segment (header, segment
    table and body all arrived whole): the server sees EOF mid-frame,
    never executes, and the retry applies the mutation exactly once."""
    from netsdb_tpu.serve.protocol import MsgType

    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "w")
    a = np.ones((256, 256), np.float32) * 3
    chaos.arm("truncate", types=[MsgType.SEND_MATRIX])
    c.send_matrix("d", "w", a, (64, 64))
    assert c.last_attempts >= 2
    np.testing.assert_array_equal(
        np.asarray(ctl.library.get_tensor("d", "w").to_dense()), a)
    c.close()


def test_dropped_mid_pipeline_chunk_retries_whole_ingest_once(server):
    """A chunk dropped MID-PIPELINE (frames already in flight behind
    it) aborts the conversation server-side; the client re-streams the
    whole logical ingest under the same idempotency token and the set
    holds exactly one copy."""
    from netsdb_tpu.serve.protocol import MsgType

    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    items = [{"i": i, "pad": "x" * 256} for i in range(400)]
    chaos.arm("drop", types=[MsgType.BULK_CHUNK])
    c.send_data("d", "s", items, pipeline=True, chunk_bytes=4 << 10)
    assert c.last_attempts >= 2
    assert _content(ctl, "d", "s") == list(range(400))
    c.close()


def test_corrupt_mid_pipeline_chunk_is_typed_and_applies_once(server):
    """A corrupted ingest chunk fails decode server-side → typed
    retryable CorruptFrame, conversation torn down; the retried stream
    applies exactly once (no partial batch ever lands — apply happens
    only at COMMIT)."""
    from netsdb_tpu.serve.protocol import MsgType

    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    items = [{"i": i, "pad": "y" * 200} for i in range(300)]
    chaos.arm("corrupt", types=[MsgType.BULK_CHUNK])
    c.send_data("d", "s", items, pipeline=True, chunk_bytes=4 << 10)
    assert c.last_attempts >= 2
    assert _content(ctl, "d", "s") == list(range(300))
    c.close()


def test_truncated_commit_restreams_exactly_once(server):
    """The COMMIT frame dies mid-wire: nothing applied (apply is
    commit-time), the retry re-streams, exactly one batch lands."""
    from netsdb_tpu.serve.protocol import MsgType

    ctl, addr, _ = server
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=FAST, chaos=chaos)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    chaos.arm("truncate", types=[MsgType.BULK_COMMIT])
    c.send_data("d", "s", [{"i": i} for i in range(200)], pipeline=True,
                chunk_bytes=1 << 10)
    assert c.last_attempts >= 2
    assert _content(ctl, "d", "s") == list(range(200))
    c.close()


def test_bulk_duplicate_token_replays_cached_reply(server):
    """The ambiguous-outcome contract for STREAMED ingest: a second
    conversation carrying the same idempotency token (the retry after
    a lost final ack) is answered from the completed-reply cache at
    BEGIN — the client never streams, the server never re-applies."""
    import pickle

    import numpy as _np

    from netsdb_tpu.serve.protocol import IDEMPOTENCY_KEY, MsgType

    ctl, addr, _ = server
    c1 = RemoteClient(addr)
    c1.create_database("d")
    c1.create_set("d", "s", type_name="object")
    items = [{"i": i} for i in range(50)]
    begin = {"op": int(MsgType.SEND_DATA),
             "meta": {"db": "d", "set": "s", "mode": "items"},
             IDEMPOTENCY_KEY: "tok-bulk-dup-1"}

    def chunks():
        blob = pickle.dumps(items, protocol=pickle.HIGHEST_PROTOCOL)
        yield {"n": len(items), "blob": _np.frombuffer(blob, _np.uint8)}

    s1 = c1._dial()
    try:
        r1 = c1._bulk_once(s1, begin, chunks)
    finally:
        s1.close()
    c2 = RemoteClient(addr)
    s2 = c2._dial()
    try:
        r2 = c2._bulk_once(s2, begin, chunks)
    finally:
        s2.close()
    assert r1 == r2
    assert _content(ctl, "d", "s") == list(range(50))  # exactly once
    c1.close()
    c2.close()


# --- follower kill / hang mid-mirror ----------------------------------

@pytest.fixture()
def cluster(tmp_path):
    """Leader + follower with test-speed heartbeats, plus a chaos
    injector on the leader→follower mirror path."""
    fchaos = ChaosInjector()
    fctl = ServeController(Configuration(root_dir=str(tmp_path / "f")),
                           port=0)
    fport = fctl.start()
    mctl = ServeController(Configuration(root_dir=str(tmp_path / "m")),
                           port=0, followers=[f"127.0.0.1:{fport}"],
                           follower_chaos=fchaos,
                           heartbeat_interval_s=0.1,
                           heartbeat_timeout_s=0.5,
                           heartbeat_misses=2,
                           mirror_ack_timeout_s=0.5,
                           resync_grace_s=2.0)
    mport = mctl.start()
    yield mctl, fctl, f"127.0.0.1:{mport}", fchaos
    mctl.shutdown()
    fctl.shutdown()


def _wait_reattached(mctl, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = mctl.follower_status()
        if st["active"] and not st["degraded"]:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"follower never reattached: {mctl.follower_status()}")


def test_follower_killed_mid_mirror_recovers_via_resync(cluster):
    """The headline scenario: a follower's connection dies mid-mirror.
    The client's request still succeeds (local apply + idempotent
    retry), the follower is evicted, then reattached via checkpoint
    resync — and the stores pass an equality check."""
    mctl, fctl, addr, fchaos = cluster
    c = RemoteClient(addr, retry=FAST)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    fchaos.arm("kill")
    c.send_data("d", "s", [{"i": 1}])  # mirror dies; local applies
    assert c.last_attempts >= 2  # first attempt got FollowerDegraded
    assert _content(mctl, "d", "s") == [1]  # exactly once on the leader
    assert any(f[0] == "kill" for f in fchaos.faults)

    _wait_reattached(mctl)
    assert _content(fctl, "d", "s") == [1]  # resync caught it up
    c.send_data("d", "s", [{"i": 2}])  # post-reattach frames mirror again
    assert _content(mctl, "d", "s") == _content(fctl, "d", "s") == [1, 2]
    c.close()


def test_follower_hang_mid_mirror_is_bounded_and_recovers(cluster):
    """A follower that ACCEPTS the frame but never acks within the
    mirror-ack timeout is evicted (the leader's handler thread is
    released — deadline discipline), then resynced to equality."""
    mctl, fctl, addr, fchaos = cluster
    c = RemoteClient(addr, retry=FAST)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    fchaos.arm("delay", delay_s=3.0)  # well past mirror_ack_timeout_s
    t0 = time.monotonic()
    c.send_data("d", "s", [{"i": 1}])
    assert time.monotonic() - t0 < 2.5  # did not wait out the hang
    assert _content(mctl, "d", "s") == [1]
    _wait_reattached(mctl)
    assert _content(mctl, "d", "s") == _content(fctl, "d", "s") == [1]
    c.close()


def test_mirror_forwards_idempotency_token_to_followers(cluster):
    """Mirrored frames carry the CLIENT's idempotency token to the
    followers, so a re-forwarded frame (local retryable failure →
    client retry) dedupes follower-side instead of double-applying."""
    from netsdb_tpu.serve.protocol import CODEC_PICKLE, MsgType

    mctl, fctl, addr, _ = cluster
    c = RemoteClient(addr)
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    payload = {"db": "d", "set": "s", "items": [{"i": 1}],
               "__idem__": "tok-fwd-1"}
    c._request(MsgType.SEND_DATA, payload, codec=CODEC_PICKLE)
    # the follower daemon saw and completed the SAME token...
    assert "tok-fwd-1" in fctl._idem._done
    # ...so replaying the frame straight at the follower is a no-op
    fc = RemoteClient(f"127.0.0.1:{fctl.port}")
    fc._request(MsgType.SEND_DATA, payload, codec=CODEC_PICKLE)
    assert sorted(r["i"] for r in
                  fctl.library.get_set_iterator("d", "s")) == [1]
    c.close()
    fc.close()


def test_paged_set_survives_resync(tmp_path):
    """A PAGED relation on the leader re-pages on the resynced follower
    (host chunk-table snapshot → paged re-ingest) — no silent drop, no
    evict→resync flap when later frames target the set."""
    from netsdb_tpu.relational.table import ColumnTable

    cfg = dict(page_size_bytes=4096, page_pool_bytes=16384)
    fctl = ServeController(
        Configuration(root_dir=str(tmp_path / "f"), **cfg), port=0)
    fport = fctl.start()
    fchaos = ChaosInjector()
    mctl = ServeController(
        Configuration(root_dir=str(tmp_path / "m"), **cfg), port=0,
        followers=[f"127.0.0.1:{fport}"], follower_chaos=fchaos,
        heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
        heartbeat_misses=2, mirror_ack_timeout_s=1.0)
    mport = mctl.start()
    try:
        c = RemoteClient(f"127.0.0.1:{mport}", retry=FAST)
        c.create_database("d")
        c.create_set("d", "pg", type_name="table", storage="paged")
        rows = [{"a": i, "b": float(i) * 0.5} for i in range(600)]
        c.send_table("d", "pg", rows)
        fchaos.arm("kill")
        c.create_set("d", "other", type_name="object")  # mirror dies here
        _wait_reattached(mctl)

        def rows_of(ctl):
            from netsdb_tpu.relational.outofcore import PagedColumns
            from netsdb_tpu.storage.store import SetIdentifier

            items = ctl.library.store.get_items(SetIdentifier("d", "pg"))
            assert len(items) == 1 and isinstance(items[0], PagedColumns), \
                items  # still a PAGED relation, not a densified one
            t = items[0].to_host_table()
            assert isinstance(t, ColumnTable)
            return sorted(zip(np.asarray(t.cols["a"]).tolist(),
                              np.asarray(t.cols["b"]).tolist()))

        # both sides still hold the full paged relation
        mt, ft = rows_of(mctl), rows_of(fctl)
        assert mt == ft and len(mt) == 600
        # and later frames targeting the paged set do not re-evict
        c.send_table("d", "pg", [{"a": 600, "b": 300.0}], append=True)
        time.sleep(0.5)
        assert not mctl.follower_status()["degraded"], \
            mctl.follower_status()
        c.close()
    finally:
        mctl.shutdown()
        fctl.shutdown()


def test_typed_error_surfaces_without_retries(cluster):
    """With client retries disabled the mid-mirror failure is visible
    as the typed retryable FollowerDegradedError (never an untyped
    RuntimeError), and the mutation still applied exactly once
    leader-side."""
    mctl, fctl, addr, fchaos = cluster
    c = RemoteClient(addr, retry=RetryPolicy(max_attempts=1))
    c.create_database("d")
    c.create_set("d", "s", type_name="object")
    fchaos.arm("kill")
    with pytest.raises(FollowerDegradedError) as ei:
        c.send_data("d", "s", [{"i": 4}])
    assert ei.value.retryable
    assert _content(mctl, "d", "s") == [4]
    _wait_reattached(mctl)
    assert _content(fctl, "d", "s") == [4]
    c.close()
