"""One request, one trace, one clock (PR 25): a shipped score is ONE
client trace whose query id rides every one of its frames; spans name their
cause; profiles carry a wall-clock anchor that orders the two sides;
the daemon counts the bytes of workload frames on the socket.
"""

import socket
import threading
import time

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.models.ff import FFModel
from netsdb_tpu.models.serving import ff_serving
from netsdb_tpu.obs.trace import QueryTrace
from netsdb_tpu.serve import protocol as P
from netsdb_tpu.serve.server import ServeController

F, H, L, B = 12, 8, 5, 16


def _weights(rng):
    def ints(shape):
        return rng.integers(-4, 4, size=shape).astype(np.float32)
    return ints((H, F)), ints((H,)), ints((L, H)), ints((L,))


def _serving(tmp_path, name="d", **cfg):
    """A solo daemon with an FF model deployed behind ``ff_serving``:
    the input set is range-placed over the pool's one slot, so a score
    takes the ROUTED ingest path (slot threads) like the benchmark's."""
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / name), **cfg), port=0)
    addr = f"127.0.0.1:{ctl.start()}"
    rng = np.random.default_rng(3)
    model = FFModel(db="ffobs", block=(4, 4))
    weights = _weights(rng)

    def load(c):
        model.setup(c)
        model.load_weights(c, *weights)

    srv = ff_serving(model, addr, block=model.block)
    srv.deploy(load)
    batch = rng.integers(-4, 4, size=(B, F)).astype(np.float32)
    return ctl, srv, batch


@pytest.fixture()
def served(tmp_path):
    ctl, srv, batch = _serving(tmp_path)
    yield ctl, srv, batch
    srv.close()
    ctl.shutdown()


def _scored(ctl, srv, batch):
    """Score once warm, once more under observation; returns (client
    profile, the daemon's profiles of its query id)."""
    srv.score(batch)
    seen = {p["qid"] for p in obs.DEFAULT_RING.last()}
    srv.score(batch)
    new = [p for p in obs.DEFAULT_RING.last()
           if p["origin"] == "client" and p["qid"] not in seen]
    assert len(new) == 1, [p["qid"] for p in new]
    (cp,) = new
    # the daemon rings a profile AFTER its reply has gone out
    deadline = time.monotonic() + 10.0
    while len(ctl.trace_ring.find(cp["qid"])) < 3 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    return cp, ctl.trace_ring.find(cp["qid"])


def _names(prof):
    return [s["name"] for s in prof["spans"]]


def _by_dispatch(profiles):
    out = {}
    for p in profiles:
        for n in _names(p):
            if n.startswith("server.dispatch:"):
                out[n.split(":", 1)[1]] = p
    return out


def test_a_score_is_one_client_trace_and_a_daemon_profile_a_frame(served):
    ctl, srv, batch = served
    cp, sps = _scored(ctl, srv, batch)
    names = _names(cp)
    assert names.count("models.score") == 1
    # a score is three frames (ship the batch, execute, read the
    # scores back): their client halves landed in the ONE client
    # profile — the first from a slot thread of the routed ingest
    for n in ("client.send", "client.encode", "client.wait"):
        assert names.count(n) == 3, names
    assert len(sps) == 3
    assert {p["qid"] for p in sps} == {cp["qid"]}
    by = _by_dispatch(sps)
    assert set(by) == {"SEND_MATRIX", "EXECUTE_COMPUTATIONS",
                       "GET_TENSOR"}
    ingest = _names(by["SEND_MATRIX"])
    for n in ("server.recv", "server.decode", "store.ingest",
              "server.reply"):
        assert n in ingest, ingest
    (ing,) = [s for s in by["SEND_MATRIX"]["spans"]
              if s["name"] == "store.ingest"]
    assert ing["counters"]["bytes"] == batch.nbytes
    assert "server.recv" in _names(by["EXECUTE_COMPUTATIONS"])
    # the dead counter is gone with the clock it stood for
    assert "models.score_s" not in cp["counters"]
    # the client half shipped once and merged into EVERY daemon profile
    assert srv._client().flush_traces(10.0)
    merged = ctl.trace_ring.find(cp["qid"])
    assert all(p.get("client", {}).get("qid") == cp["qid"]
               for p in merged), merged


def test_every_parent_is_a_span_of_the_profile_that_contains_it(served):
    ctl, srv, batch = served
    cp, sps = _scored(ctl, srv, batch)
    eps = 1e-6
    for prof in [cp] + sps:
        by_id = {s["id"]: s for s in prof["spans"]}
        assert len(by_id) == len(prof["spans"])  # ids are unique
        assert any(s["parent"] == 0 for s in prof["spans"])
        for s in prof["spans"]:
            if s["parent"] == 0:
                continue
            parent = by_id[s["parent"]]  # KeyError = a dangling cause
            assert parent["start_s"] - eps <= s["start_s"], (s, parent)
            assert s["start_s"] + s["duration_s"] <= \
                parent["start_s"] + parent["duration_s"] + eps, (s, parent)
            assert s["depth"] == parent["depth"] + 1
    # the slot thread's spans name the span that was open where the
    # trace was captured: the caller's models.score
    (score,) = [s for s in cp["spans"] if s["name"] == "models.score"]
    sends = [s for s in cp["spans"] if s["name"] == "client.send"]
    assert [s["parent"] for s in sends] == [score["id"]] * 3
    assert score["parent"] == 0


def test_anchors_order_both_sides_as_it_happened(served):
    """Every step below CAUSES the next (a header cannot land before
    its send began, a wait cannot end before its reply began), so on
    one clock the anchored times must rise."""
    ctl, srv, batch = served

    def at(prof, name, end=False, nth=0):
        s = [s for s in prof["spans"] if s["name"] == name][nth]
        return prof["t0_unix_ns"] / 1e9 + s["start_s"] \
            + (s["duration_s"] if end else 0.0)

    def out_of_order():
        cp, sps = _scored(ctl, srv, batch)
        by = _by_dispatch(sps)
        order = [at(cp, "models.score")]
        for nth, typ in enumerate(("SEND_MATRIX", "EXECUTE_COMPUTATIONS",
                                   "GET_TENSOR")):
            sp = by[typ]
            order += [at(cp, "client.send", nth=nth),
                      at(sp, "server.recv"),
                      at(sp, "server.recv", end=True),
                      at(sp, "server.decode", end=True),
                      at(sp, f"server.dispatch:{typ}"),
                      at(sp, "server.reply"),
                      at(cp, "client.wait", end=True, nth=nth)]
        order.append(at(cp, "models.score", end=True))
        # recv then decode, back-dated onto real timeline
        sm = by["SEND_MATRIX"]
        assert at(sm, "server.recv") == pytest.approx(
            sm["t0_unix_ns"] / 1e9)
        assert at(sm, "server.decode") == pytest.approx(
            at(sm, "server.recv", end=True))
        # an anchor and its perf_counter origin are read microseconds
        # apart, never on the same tick
        slack = 2e-3
        return [(a, b) for a, b in zip(order, order[1:]) if a > b + slack]

    # a thread descheduled between its two clock reads skews one
    # profile by a scheduler quantum; a misplaced anchor skews them all
    assert any(not out_of_order() for _ in range(3))


def test_backdate_moves_the_anchor_with_the_start():
    tr = QueryTrace("q")
    a0 = tr.profile()["t0_unix_ns"]
    assert abs(a0 - time.time_ns()) < 5 * 10**9
    tr.backdate(0.25)
    assert tr.profile()["t0_unix_ns"] == a0 - 250_000_000
    tr.record("early", 0.25, start_s=0.0)
    assert tr.finish()["total_s"] >= 0.25


def test_adopt_carries_the_trace_and_the_open_span_across_threads():
    with obs.trace("qx", ring=obs.TraceRing(4)) as tr:
        with obs.span("outer") as outer:
            captured = obs.capture()

            def work():
                assert obs.current_trace() is None
                with obs.adopt(captured):
                    with obs.span("worker"):
                        with obs.span("worker.inner"):
                            pass
                assert obs.current_trace() is None

            t = threading.Thread(target=work)
            t.start()
            t.join()
    by = {s["name"]: s for s in tr.profile()["spans"]}
    assert by["worker"]["parent"] == outer.id
    assert by["worker"]["depth"] == outer.depth + 1
    assert by["worker.inner"]["parent"] == by["worker"]["id"]
    with obs.adopt(None):  # no trace to carry: a no-op
        assert obs.current_trace() is None


def _wire_counters(settle_out=None):
    """(bytes_in, bytes_out). The daemon counts a reply after it has
    sent it, so a caller that has just read that reply may look first:
    ``settle_out`` waits for bytes_out to reach a value."""
    out = obs.REGISTRY.counter("serve.wire.bytes_out")
    deadline = time.monotonic() + 5.0
    while settle_out is not None and out.value < settle_out \
            and time.monotonic() < deadline:
        time.sleep(0.001)
    return obs.REGISTRY.counter("serve.wire.bytes_in").value, out.value


def test_wire_bytes_count_workload_frames_exactly(tmp_path):
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "w")),
                          port=0)
    port = ctl.start()
    sock = socket.create_connection(("127.0.0.1", port))
    try:
        P.send_frame(sock, P.MsgType.HELLO,
                     {"token": None, "proto": P.PROTO_VERSION})
        assert P.recv_frame(sock)[0] == P.MsgType.OK

        def ask(typ, payload):
            sent = P.send_frame(sock, typ, payload)
            rtyp, _, _, _, got, _ = P.recv_frame_raw(sock)
            assert rtyp == P.MsgType.OK
            return sent, got

        in0, out0 = _wire_counters()  # the handshake is not a request
        _, got_a = ask(P.MsgType.CREATE_DATABASE, {"db": "w"})
        _, got_b = ask(P.MsgType.CREATE_SET, {"db": "w", "set": "m",
                                              "type_name": "tensor"})
        in1, out1 = _wire_counters(settle_out=out0 + got_a + got_b)
        assert out1 == out0 + got_a + got_b and in1 > in0
        dense = np.arange(64 * 32, dtype=np.float32).reshape(64, 32)
        payload = {"db": "w", "set": "m",
                   "tensor": P.tensor_to_wire(dense, (8, 8))}
        body, segs = P.encode_body_oob(payload)
        assert len(segs) == 1
        sent, got = ask(P.MsgType.SEND_MATRIX, payload)
        # header + segment count + one table entry + body + the segment
        assert sent == 15 + 4 + 12 + len(body) + dense.nbytes
        in2, out2 = _wire_counters(settle_out=out1 + got)
        assert in2 - in1 == sent
        assert out2 - out1 == got
        # introspection frames and their replies are left out, as they
        # are from serve.requests
        ask(P.MsgType.COLLECT_STATS, {})
        ask(P.MsgType.PUT_TRACE, {"qid": "nobody",
                                  "profile": {"qid": "nobody"}})
        ask(P.MsgType.GET_TRACE, {"last": 1})
        ask(P.MsgType.LIST_SETS, {"db": "w"})  # a workload frame again
        in3, out3 = _wire_counters(settle_out=out2 + 1)
        assert 0 < in3 - in2 < 64 and 0 < out3 - out2 < 256
    finally:
        sock.close()
        ctl.shutdown()


def test_with_obs_off_nothing_is_recorded_and_score_still_answers(tmp_path):
    ctl, srv, batch = _serving(tmp_path, "on")
    try:
        want = np.asarray(srv.score(batch).to_dense())
    finally:
        srv.close()
        ctl.shutdown()
    obs.set_enabled(False)
    try:
        ctl, srv, batch = _serving(tmp_path, "off", obs_enabled=False)
        try:
            ring = len(obs.DEFAULT_RING)
            got = np.asarray(srv.score(batch).to_dense())
            assert len(obs.DEFAULT_RING) == ring
            assert len(ctl.trace_ring) == 0
            assert obs.current_trace() is None
        finally:
            srv.close()
            ctl.shutdown()
    finally:
        obs.set_enabled(True)
    np.testing.assert_array_equal(got, want)
