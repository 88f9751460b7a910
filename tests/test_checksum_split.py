"""A large out-of-band segment's checksum, split across the host's cores.

``segment_checksum`` reduces a segment of ``CHECKSUM_SPLIT_BYTES`` or more
in ``CHECKSUM_PARTS`` parts side by side. Addition mod 2^64 and xor are
associative, so the value is the single pass's to the bit: the segment
table, ``PROTO_VERSION`` and every byte on the wire stay as they were, and
a peer that splits and one that does not verify each other's frames.
"""

import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.serve import protocol as P
from netsdb_tpu.serve.chaos import ChaosInjector
from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
from netsdb_tpu.serve.errors import CorruptFrameError
from netsdb_tpu.serve.protocol import MsgType
from netsdb_tpu.serve.server import ServeController

T = P.CHECKSUM_SPLIT_BYTES
MAX_PARTS = max(P.CHECKSUM_PARTS, 8)


def single_pass(mv) -> int:
    """The checksum as one pass computes it: two reductions over the whole
    buffer, the tail of under 8 bytes, the mix."""
    mv = memoryview(mv).cast("B")
    n = mv.nbytes
    main = n - (n & 7)
    s = x = 0
    if main:
        a = np.frombuffer(mv[:main], np.uint64)
        s = int(np.add.reduce(a, dtype=np.uint64))
        x = int(np.bitwise_xor.reduce(a))
    if n & 7:
        tail = int.from_bytes(mv[main:], "little")
        s = (s + tail) & P._MASK64
        x ^= tail
    acc = P._mix64(P._mix64(s) ^ x ^ n)
    return (acc ^ (acc >> 32)) & 0xFFFFFFFF


def random_bytes(n: int, seed: int) -> bytearray:
    return bytearray(np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes())


def split_counter() -> int:
    return obs.REGISTRY.counter("serve.wire.checksum.split_bytes").value


@pytest.mark.parametrize("n", range(18))
def test_a_short_segment_split_equals_the_single_pass(n, monkeypatch):
    # below the real threshold these lengths never split: forced here, so
    # that parts of no word and of one word, and the tail alone, are held
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 0)
    data = random_bytes(n, n)
    want = single_pass(data)
    for parts in range(1, MAX_PARTS + 1):
        monkeypatch.setattr(P, "CHECKSUM_PARTS", parts)
        assert P.segment_checksum(memoryview(data)) == want, parts


@pytest.mark.parametrize("n,splits", [(T - 8, False), (T - 1, False),
                                      (T, True), (T + 3, True),
                                      (64 << 20, True)])
def test_at_the_threshold_and_past_it_the_value_is_the_single_pass(
        n, splits, monkeypatch):
    data = random_bytes(n, 7)
    want = single_pass(data)
    for parts in range(1, MAX_PARTS + 1):
        monkeypatch.setattr(P, "CHECKSUM_PARTS", parts)
        before = split_counter()
        assert P.segment_checksum(memoryview(data)) == want, parts
        assert split_counter() - before == (n if splits else 0)


def test_part_bounds_fall_on_words_and_cover_the_segment():
    a = np.arange(1001, dtype=np.uint64)
    for parts in range(1, MAX_PARTS + 1):
        s, x = P._split_sum_xor(a, parts)
        assert s == int(a.sum()) and x == int(np.bitwise_xor.reduce(a))


def test_single_bit_flips_are_caught_near_part_boundaries_too(monkeypatch):
    """3,000 single-bit flips of a segment above the threshold, half of
    them within 8 bytes of a boundary between two parts: no miss."""
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 20)
    parts = P.CHECKSUM_PARTS
    n = (1 << 20) + 3
    data = random_bytes(n, 11)
    c0 = P.segment_checksum(memoryview(data))
    assert c0 == single_pass(data)
    words = n // 8
    edges = [8 * (words * i // parts) for i in range(1, parts)] or [n // 2]
    rng = np.random.default_rng(12)
    for trial in range(3000):
        if trial % 2:
            edge = edges[int(rng.integers(len(edges)))]
            i = edge + int(rng.integers(-8, 8))
        else:
            i = int(rng.integers(n))
        bit = 1 << int(rng.integers(8))
        data[i] ^= bit
        got = P.segment_checksum(memoryview(data))
        assert got != c0, (trial, i, bit)
        if trial % 300 == 0:
            assert got == single_pass(data)
        data[i] ^= bit


def test_callers_side_by_side_share_the_pool(monkeypatch):
    """More callers than cores, each splitting into more parts than there
    are threads, with the interpreter switching threads every 10 us: every
    caller gets its own buffer's value."""
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 12)
    bufs = [random_bytes((1 << 16) + k, 100 + k) for k in range(24)]
    want = [single_pass(b) for b in bufs]
    got = [None] * len(bufs)

    def caller(k):
        for _ in range(20):
            got[k] = P.segment_checksum(memoryview(bufs[k]))
            if got[k] != want[k]:
                return

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        monkeypatch.setattr(P, "CHECKSUM_PARTS", MAX_PARTS + 3)
        threads = [threading.Thread(target=caller, args=(k,))
                   for k in range(len(bufs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == want


def _frame_bytes(payload) -> bytes:
    class Sink:
        def __init__(self):
            self.out = bytearray()

        def sendmsg(self, buffers):
            for b in buffers:
                self.out += bytes(b)
            return sum(memoryview(b).nbytes for b in buffers)

    sink = Sink()
    P.send_frame(sink, MsgType.SEND_MATRIX, payload)
    return bytes(sink.out)


def _round_trip(payload, send_split: bool, recv_split: bool, monkeypatch):
    a, b = socket.socketpair()
    split_at = lambda on: 1 << 16 if on else 1 << 62  # noqa: E731
    try:
        monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", split_at(send_split))
        # the frame is whole, table and checksums, before the receiving
        # side's setting is put in place
        sent = _frame_bytes(payload)
        writer = threading.Thread(target=a.sendall, args=(sent,))
        writer.start()
        monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", split_at(recv_split))
        typ, got = P.recv_frame(b)
        writer.join()
    finally:
        a.close()
        b.close()
    return sent, typ, got


@pytest.mark.parametrize("send_split,recv_split", [(True, False),
                                                   (False, True)])
def test_a_frame_checksummed_one_way_verifies_the_other(
        send_split, recv_split, monkeypatch):
    x = np.random.default_rng(5).standard_normal((300, 301)).astype(
        np.float32)  # 361,200 bytes: one segment over the lowered threshold
    payload = {"tensor": {"data": x}}
    sent, typ, got = _round_trip(payload, send_split, recv_split,
                                 monkeypatch)
    assert typ == MsgType.SEND_MATRIX
    np.testing.assert_array_equal(got["tensor"]["data"], x)
    # the same bytes on the wire whichever way the checksum was reduced
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 62)
    assert _frame_bytes(payload) == sent
    assert P.PROTO_VERSION == 3


def test_a_flipped_byte_in_a_split_segment_fails_the_decode(monkeypatch):
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 16)
    body, segs = P.encode_body_oob({"t": np.ones(1 << 18, np.uint8)})
    crc = P.segment_checksum(segs[0])
    bad = bytearray(segs[0])
    bad[len(bad) // 2] ^= 0x10
    with pytest.raises(ValueError, match="checksum"):
        P.decode_body(body, P.CODEC_MSGPACK_OOB, False,
                      segments=[(bad, crc)])


@pytest.fixture()
def server(tmp_path):
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "srv")),
                          port=0)
    port = ctl.start()
    yield ctl, f"127.0.0.1:{port}"
    ctl.shutdown()


def test_chaos_corrupt_seg_on_a_split_segment_is_a_typed_corrupt_frame(
        server, monkeypatch):
    """``corrupt_seg`` flips a byte in the middle of the largest segment,
    which is where two parts meet: the daemon's split verification fails
    it as the retryable CorruptFrame, and the resend applies once."""
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 16)
    monkeypatch.setattr(P, "CHECKSUM_PARTS", 2)
    ctl, addr = server
    a = np.arange(256 * 256, dtype=np.float32).reshape(256, 256)
    chaos = ChaosInjector()
    once = RemoteClient(addr, retry=RetryPolicy(max_attempts=1),
                        chaos=chaos)
    once.create_database("d")
    once.create_set("d", "w")
    chaos.arm("corrupt_seg", types=[MsgType.SEND_MATRIX])
    split_before = split_counter()
    with pytest.raises(CorruptFrameError):
        once.send_matrix("d", "w", a, (64, 64))
    assert any(f[0] == "corrupt_seg" for f in chaos.faults)
    # both ends split: the client's table and the daemon's verification
    assert split_counter() - split_before == 2 * a.nbytes
    once.close()
    chaos = ChaosInjector()
    c = RemoteClient(addr, retry=RetryPolicy(max_attempts=5,
                                             base_delay_s=0.01,
                                             max_delay_s=0.1), chaos=chaos)
    chaos.arm("corrupt_seg", types=[MsgType.SEND_MATRIX])
    c.send_matrix("d", "w", a, (64, 64))
    assert c.last_attempts >= 2
    np.testing.assert_array_equal(
        np.asarray(ctl.library.get_tensor("d", "w").to_dense()), a)
    c.close()


def test_both_ends_time_their_checksums_in_spans_of_their_own(
        server, monkeypatch):
    monkeypatch.setattr(P, "CHECKSUM_SPLIT_BYTES", 1 << 16)
    ctl, addr = server
    a = np.arange(128 * 256, dtype=np.float32).reshape(128, 256)
    c = RemoteClient(addr)
    c.create_database("d")
    c.create_set("d", "w")
    with c.request_trace("ship") as tr:
        c.send_matrix("d", "w", a, (64, 64))
    cp = tr.profile()
    c.close()
    spans = {s["name"]: s for s in cp["spans"]}
    check = spans["client.encode.checksum"]
    assert check["parent"] == spans["client.encode"]["id"]
    assert check["counters"]["bytes"] == a.nbytes
    (sp,) = ctl.trace_ring.find(cp["qid"])
    spans = {s["name"]: s for s in sp["spans"]}
    check, decode = spans["server.decode.checksum"], spans["server.decode"]
    assert check["parent"] == decode["id"]
    assert check["depth"] == decode["depth"] + 1
    assert check["counters"]["bytes"] == a.nbytes
    assert check["start_s"] == decode["start_s"]
    assert 0 < check["duration_s"] <= decode["duration_s"]
    # a frame without segments records no checksum span
    with obs.trace("bare", origin="client") as tr:
        with obs.span("client.send"):
            P.send_frame(_Null(), MsgType.PING, {"x": 1},
                         encode_span="client.encode")
    assert "client.encode.checksum" not in {
        s["name"] for s in tr.profile()["spans"]}


class _Null:
    def sendmsg(self, buffers):
        return sum(memoryview(b).nbytes for b in buffers)


def test_a_forked_child_splits_on_threads_of_its_own():
    """The pool's threads do not survive a fork: the child starts with
    none and makes its own, instead of queueing parts nobody takes."""
    script = (
        "import os, sys\n"
        "import numpy as np\n"
        "from netsdb_tpu.serve import protocol as P\n"
        "P.CHECKSUM_SPLIT_BYTES = 1 << 16\n"
        "P.CHECKSUM_PARTS = 4\n"
        "data = np.arange(1 << 17, dtype=np.uint64).tobytes()\n"
        "want = P.segment_checksum(data)\n"
        "pid = os.fork()\n"
        "if pid == 0:\n"
        "    os._exit(0 if P.segment_checksum(data) == want else 1)\n"
        "_, status = os.waitpid(pid, 0)\n"
        "sys.exit(os.waitstatus_to_exitcode(status))\n")
    proc = subprocess.run([sys.executable, "-c", script], timeout=120,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
