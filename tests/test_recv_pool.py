"""The receive-buffer pool of ``serve/protocol.py``: big out-of-band
segments land in recycled arenas, and an arena is recycled by the
LIFETIME of what was decoded over it, never earlier.

Every test installs a pool of its own in place of the process-wide one
and drives it through the real receive path (``recv_frame`` over a
socket pair, or a live daemon), because the hazard is in how NumPy
chains ``.base`` from the decoded array back to the lease, not in the
free list alone.
"""

import gc
import socket
import sys
import threading

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.serve import protocol as P
from netsdb_tpu.serve.client import RemoteClient
from netsdb_tpu.serve.server import ServeController

ROWS = 384  # x 1024 float32 = 1.5 MiB: over the floor, an arena of 2 MiB


@pytest.fixture()
def pool(monkeypatch):
    fresh = P._RecvPool()
    monkeypatch.setattr(P, "RECV_POOL", fresh)
    return fresh


@pytest.fixture()
def pair():
    a, b = socket.socketpair()
    a.settimeout(10)
    b.settimeout(10)
    yield a, b
    a.close()
    b.close()


def counts():
    c = obs.REGISTRY.counter
    return (c("serve.wire.recv_pool.hits").value,
            c("serve.wire.recv_pool.misses").value)


def batch(fill, rows=ROWS):
    return np.full((rows, 1024), fill, np.float32)


def ship(pair, arr, **recv):
    """One frame through send_frame and recv_frame; the decoded array."""
    a, b = pair
    t = threading.Thread(target=P.send_frame,
                         args=(a, P.MsgType.SEND_MATRIX, {"t": arr}))
    t.start()
    try:
        _, payload = P.recv_frame(b, **recv)
    finally:
        t.join(10)
    assert not t.is_alive()
    return payload["t"]


def arena_of(arr):
    """The address of the memory a decoded array lives in."""
    return arr.__array_interface__["data"][0]


def test_a_live_array_keeps_its_arena_out_of_the_pool(pool, pair):
    """The base-collapse hazard: were the lease an ndarray view, the
    second frame would land in the first array's memory."""
    h0, m0 = counts()
    first = ship(pair, batch(1.0))
    kept = first[5:7]              # a slice alone must hold the arena too
    del first
    second = ship(pair, batch(2.0))
    assert counts() == (h0, m0 + 2)            # no arena was free for it
    assert arena_of(second) != arena_of(kept) - 5 * 4096
    assert (kept == 1.0).all() and (second == 2.0).all()
    assert pool.retained_bytes == 0


def test_dropping_the_array_returns_the_arena_and_the_next_frame_hits(
        pool, pair):
    first = ship(pair, batch(1.0))
    where = arena_of(first)
    assert pool.retained_bytes == 0
    del first
    assert pool.retained_bytes == 2 << 20      # 1.5 MiB in the 1 MiB grain
    h0, m0 = counts()
    second = ship(pair, batch(2.0))
    assert counts() == (h0 + 1, m0)
    assert arena_of(second) == where and (second == 2.0).all()
    assert obs.REGISTRY.gauge(
        "serve.wire.recv_pool.retained_bytes").value == 0
    del second
    # a smaller segment that still fills half of the arena reuses it
    third = ship(pair, batch(3.0, rows=300))
    assert counts() == (h0 + 2, m0) and (third == 3.0).all()


def send_cut(sock, arr, keep, then_close):
    """A well-formed frame whose one segment stops after ``keep`` bytes
    (sent from a thread: a socket pair buffers far less than that)."""
    body, segs = P.encode_body_oob({"t": arr})
    header = P._HEADER.pack(P.MAGIC, P.CODEC_MSGPACK_OOB,
                            int(P.MsgType.SEND_MATRIX), len(body))
    wire = header + P._pack_segtable(segs) + body + bytes(segs[0][:keep])

    def run():
        sock.sendall(wire)
        if then_close:
            sock.shutdown(socket.SHUT_WR)

    t = threading.Thread(target=run)
    t.start()
    return t


@pytest.mark.parametrize("fault, message", [
    ("truncated", "peer closed mid-frame"),
    ("stalled", "peer stalled mid-frame"),
])
def test_a_frame_cut_mid_segment_raises_typed_and_leaks_no_arena(
        pool, pair, fault, message):
    del_me = ship(pair, batch(1.0))
    del del_me
    assert (len(pool._free), pool.retained_bytes) == (1, 2 << 20)
    a, b = pair
    t = send_cut(a, batch(2.0), keep=1 << 20,
                 then_close=fault == "truncated")
    with pytest.raises(P.ProtocolError, match=message):
        P.recv_frame(b, mid_frame_timeout=0.5)
    t.join(10)
    assert not t.is_alive()
    gc.collect()        # the traceback held the lease until here
    assert (len(pool._free), pool.retained_bytes) == (1, 2 << 20)
    assert not pool._returned


def test_a_corrupted_pooled_segment_still_fails_its_checksum(pool, pair):
    a, b = pair
    arr = batch(1.0)
    body, segs = P.encode_body_oob({"t": arr})
    bad = bytearray(segs[0])
    bad[1 << 20] ^= 0x10
    header = P._HEADER.pack(P.MAGIC, P.CODEC_MSGPACK_OOB,
                            int(P.MsgType.SEND_MATRIX), len(body))
    t = threading.Thread(target=a.sendall, args=(
        header + P._pack_segtable(segs) + body + bytes(bad),))
    t.start()
    _, codec, raw, segments, _, _ = P.recv_frame_raw(b)
    t.join(10)
    assert isinstance(segments[0][0], P._Lease)
    with pytest.raises(ValueError, match="checksum"):
        P.decode_body(raw, codec, False, segments=segments)
    # and the honest bytes decode from the same pairs' shape
    assert (ship(pair, arr) == 1.0).all()


def test_the_free_list_never_passes_its_caps(monkeypatch, pair):
    small = P._RecvPool(max_bytes=6 << 20, max_arenas=3)
    monkeypatch.setattr(P, "RECV_POOL", small)
    rng = np.random.default_rng(7)
    live = []
    for i in range(40):
        rows = int(rng.integers(256, 1025))          # 1 to 4 MiB
        live.append(ship(pair, batch(float(i), rows=rows)))
        if len(live) > int(rng.integers(0, 6)):
            live.pop(int(rng.integers(0, len(live))))
        assert len(small._free) <= 3
        assert small.retained_bytes <= 6 << 20
        assert small.retained_bytes == sum(a.nbytes for a in small._free)
    for i, arr in enumerate(live):
        assert (arr == arr[0, 0]).all()
    live.clear()
    assert len(small._free) <= 3 and small.retained_bytes <= 6 << 20
    # an arena larger than the whole cap is not kept at all
    huge = ship(pair, batch(9.0, rows=2048))         # 8 MiB
    del huge
    assert small.retained_bytes <= 6 << 20
    assert all(a.nbytes < 8 << 20 for a in small._free)


def test_a_big_arena_is_not_spent_on_a_small_segment(pool, pair):
    big = ship(pair, batch(1.0, rows=1024))          # 4 MiB
    del big
    h0, m0 = counts()
    small = ship(pair, batch(2.0, rows=ROWS))        # 1.5 MiB: under half
    assert counts() == (h0, m0 + 1)
    assert pool.retained_bytes == 4 << 20 and (small == 2.0).all()


def test_segments_under_the_floor_never_enter_the_pool(pool, pair):
    h0, m0 = counts()
    rows = P.RECV_POOL_MIN_BYTES // 4096 - 1         # 4 KiB short of it
    got = ship(pair, batch(1.0, rows=rows))
    assert got.nbytes >= P.OOB_MIN_BYTES             # it did ride out of band
    assert counts() == (h0, m0)
    assert isinstance(got.base, np.ndarray) and not isinstance(
        got.base.base, P._Lease)
    del got
    assert pool.retained_bytes == 0 and not pool._free
    at_floor = ship(pair, batch(1.0, rows=rows + 1))
    assert counts() == (h0, m0 + 1)
    assert isinstance(at_floor.base.base, P._Lease)


def test_two_threads_receiving_at_once_never_share_an_arena(pool):
    """More receivers than cores, a short switch interval: every array
    must hold its own sender's value from end to end while all of the
    others come and go through the same free list."""
    errors, rounds, workers = [], 12, 8
    old = sys.getswitchinterval()

    def work(k):
        a, b = socket.socketpair()
        a.settimeout(20)
        b.settimeout(20)
        try:
            held = None
            for r in range(rounds):
                want = float(k * 1000 + r)
                got = ship((a, b), batch(want))
                if not (got == want).all():
                    errors.append((k, r, "fresh"))
                if held is not None and not (held[1] == held[0]).all():
                    errors.append((k, r, "held"))
                held = (want, got)       # the previous one dies here
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append((k, repr(e)))
        finally:
            a.close()
            b.close()

    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(pool._free) <= P.RECV_POOL_MAX_ARENAS
    assert pool.retained_bytes == sum(a.nbytes for a in pool._free)


def test_pooled_arrays_are_writable_and_own_nothing(pool, pair):
    got = ship(pair, batch(1.0))
    assert got.flags.writeable and not got.flags.owndata
    got[0, 0] = -42.0
    assert got[0, 0] == -42.0 and got.dtype == np.float32
    assert got.shape == (ROWS, 1024)


def test_a_finalizer_inside_the_pools_lock_does_not_wait(pool, pair):
    """A lease can die inside a garbage collection that interrupts the
    very thread that holds the pool's lock; the finalizer must queue
    the arena and return."""
    got = ship(pair, batch(1.0))
    with pool._mu:
        del got                      # its finalizer runs here, lock held
        assert len(pool._returned) == 1 and not pool._free
    h0, m0 = counts()
    again = ship(pair, batch(2.0))   # the next lease admits it and hits
    assert counts() == (h0 + 1, m0) and (again == 2.0).all()


def test_stored_matrices_survive_later_frames_of_their_size(config, pool):
    """Through a live daemon: SEND_MATRIX replies while the set still
    reads the received array (on the CPU backend a device array may
    alias host memory for good), so a second matrix of the same size
    must not land in the first one's arena."""
    ctl = ServeController(config, port=0)
    rc = RemoteClient(f"127.0.0.1:{ctl.start()}")
    try:
        rc.create_database("d")
        h0, m0 = counts()
        sent = {}
        for i in range(4):
            name = f"s{i}"
            rc.create_set("d", name)
            sent[name] = np.random.default_rng(i).standard_normal(
                (512, 1024)).astype(np.float32)          # 2 MiB
            rc.send_matrix("d", name, sent[name], (256, 256))
        for name, want in sent.items():
            np.testing.assert_array_equal(
                rc.get_tensor("d", name).to_dense(), want)
        h1, m1 = counts()
        assert (h1 - h0) + (m1 - m0) >= 8    # 4 requests in, 4 replies out
    finally:
        rc.close()
        ctl.shutdown()


def test_the_pools_metrics_are_catalogued_and_documented():
    import os

    from netsdb_tpu.obs.export import CATALOG
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "METRICS.md")).read()
    for name, kind in (("serve.wire.recv_pool.hits", "counter"),
                       ("serve.wire.recv_pool.misses", "counter"),
                       ("serve.wire.recv_pool.retained_bytes", "gauge")):
        assert CATALOG[name][0] == kind
        assert f"| `{name}` | {kind} |" in doc
