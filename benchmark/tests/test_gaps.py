"""``gaps.py``: idle seconds put down to spans, on hand-made intervals and in both cells' rehearsal."""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import gaps  # noqa: E402
from test_harness import bench_json, metric_names, run_cell  # noqa: E402

NEW = {"recv_s_per_req", "ingest_s_per_req", "wire_bytes_per_req", "idle_wire_s_per_req",
       "idle_ingest_s_per_req", "idle_dispatch_s_per_req", "idle_client_s_per_req",
       "idle_unattributed_share"}


def test_idle_is_the_window_less_the_union_of_the_operations():
    ops = [(5, 8), (2, 4), (3, 6), (12, 30), (-3, 1)]
    assert gaps.idle_intervals(ops, 0, 20) == [(1, 2), (8, 12)]
    assert gaps.idle_intervals([], 0, 20) == [(0, 20)]
    assert gaps.idle_intervals([(0, 20)], 0, 20) == []


def test_pieces_go_to_the_deepest_span_and_are_shared_among_requests_in_flight():
    # request A: a score whose wait (10..90) holds the daemon's dispatch (20..80) and, inside that,
    # the set write (30..50); request B overlaps from 60; nothing covers 100..110
    spans = [
        (0, 100, 0, "client", "A"),                      # models.score
        (10, 90, 1, "wire", "A"),                        # client.wait
        (20, 80, gaps.DAEMON_RANK, "dispatch", "A"),     # server.dispatch:*
        (30, 50, gaps.DAEMON_RANK + 1, "ingest", "A"),   # store.ingest
        (40, 45, gaps.DAEMON_RANK + 2, None, "A"),       # a span of no known layer: falls through
        (60, 120, 1, "wire", "B"),                       # the other caller's wait
        (115, 120, 2, None, "B"),
    ]
    idle = [(0, 70), (75, 110), (115, 118)]
    got = gaps.attribute(idle, spans)
    want = {
        # A alone to 60: score 0..10, wait 10..20, dispatch 20..30 and 50..60, ingest 30..50;
        # 60..70 and 75..80 halved between A's dispatch and B's wait; 80..90 between the two waits;
        # 90..100 between A's score and B's wait; 100..110 and 115..118 B's wait alone
        "client": 10 + 5,
        "wire": 10 + 5 + 2.5 + 10 + 5 + 10 + 3,
        "dispatch": 20 + 5 + 2.5,
        "ingest": 20,
        "unattributed": 0,
    }
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in idle))
    # a piece under nothing, and one under a span of no known layer only
    got = gaps.attribute([(0, 10), (20, 30)], [(22, 28, 0, None, "C"), (5, 8, 0, "wire", "D")])
    assert got == pytest.approx({"wire": 3, "unattributed": 7 + 10, "ingest": 0, "dispatch": 0,
                                 "client": 0})


def run_of(profiles, client_profiles, ops, lo_ns, hi_ns, opened_s, requests=2, synced=True):
    trace = SimpleNamespace(devices={"/device:TPU:0": ops}, lo_ns=lo_ns, hi_ns=hi_ns,
                            synced=synced)
    return {"trace": trace, "window": SimpleNamespace(opened=opened_s), "requests": requests,
            "profiles": profiles, "client_profiles": client_profiles}


def test_profiles_reach_the_traces_clock_through_their_anchor():
    # the window opened at wall second 1000 and stands at 5e9 ns on the trace's clock
    opened, lo = 1000.0, 5e9
    client = {"qid": "q", "t0_unix_ns": int(1000.1e9), "spans": [
        {"name": "models.score", "start_s": 0.0, "duration_s": 0.8, "depth": 0},
        {"name": "client.wait", "start_s": 0.1, "duration_s": 0.6, "depth": 1}]}
    daemon = {"qid": "q", "t0_unix_ns": int(1000.3e9), "spans": [
        {"name": "server.recv", "start_s": 0.0, "duration_s": 0.1, "depth": 0},
        {"name": "server.dispatch:SEND_MATRIX", "start_s": 0.1, "duration_s": 0.25, "depth": 0},
        {"name": "store.ingest", "start_s": 0.15, "duration_s": 0.1, "depth": 1}]}
    ops = [("fusion", lo + 0.6e9, 0.2e9)]          # busy 0.6..0.8 s into the window
    run = run_of([daemon], [client], ops, lo, lo + 1e9, opened)
    got = gaps.by_layer(run)
    assert got == pytest.approx({
        "unattributed": 0.1 + 0.1,         # 0..0.1 before the request, 0.9..1 after its last span
        "client": 0.1 + 0.1,               # the score outside its wait: 0.1..0.2 and 0.8..0.9
        "wire": 0.1 + 0.1,                 # the wait alone 0.2..0.3, the daemon's receive 0.3..0.4
        "dispatch": 0.05 + 0.05,           # 0.4..0.45 and 0.55..0.6; its last 0.05 s the chip is busy
        "ingest": 0.1,                     # 0.45..0.55
        "idle": 0.8,
    })
    assert sum(got[k] for k in gaps.KEYS) == pytest.approx(got["idle"])
    assert gaps.per_request(run, "ingest") == pytest.approx(0.05)
    # a program whose profiles carry no anchor, or a trace without the marker: nothing to read
    bare = [{k: v for k, v in p.items() if k != "t0_unix_ns"} for p in (daemon, client)]
    assert gaps.by_layer(run_of(bare[:1], bare[1:], ops, lo, lo + 1e9, opened)) is None
    assert gaps.per_request(run_of(bare[:1], bare[1:], ops, lo, lo + 1e9, opened), "wire") is None
    assert gaps.by_layer(run_of([daemon], [client], ops, lo, lo + 1e9, opened, synced=False)) is None


@pytest.mark.parametrize("cell", ["ff14k-stored", "ff14k-shipped"])
def test_traced_rehearsal_prints_the_attribution_and_it_adds_up(cell):
    proc, lines = run_cell(cell, "--rehearse-cpu", trace=1, seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    want = NEW & metric_names(bench_json(), "per_layer", cell)
    assert len(want) == (8 if cell == "ff14k-shipped" else 5)
    assert want <= set(metrics)
    dev, requests = result["device"], result["window"]["requests"]
    idle_per_req = (dev["window_s"] - dev["busy_s"]) / requests
    parts = sum(v for k, v in metrics.items() if k.startswith("idle_") and k.endswith("_per_req"))
    parts += metrics["idle_unattributed_share"] * idle_per_req
    assert parts == pytest.approx(idle_per_req, rel=1e-6)
    assert 0 <= metrics["idle_unattributed_share"] < 0.5
    assert metrics["recv_s_per_req"] > 0 and metrics["wire_bytes_per_req"] > 0
    if cell == "ff14k-shipped":
        assert metrics["ingest_s_per_req"] > 0 and metrics["idle_ingest_s_per_req"] > 0
        # the batch and the scores cross the socket, and the daemon counts them
        cfg = json.load(open(os.path.join(os.path.dirname(HERE), "configs",
                                          "ff-amazoncat14k.json")))
        traffic = json.load(open(os.path.join(os.path.dirname(HERE), "traffic", "shipped.json")))
        cfg.update(cfg["rehearsal"])
        rows = {**traffic["params"], **traffic.get("rehearsal", {})}["rows"]
        payload = 4 * rows * (cfg["features"] + cfg["labels"])
        assert payload < metrics["wire_bytes_per_req"] < payload + 16384
        # SEND_MATRIX has spans now: the wire reads more than the EXECUTE frame's two milliseconds
        assert metrics["wire_s_per_req"] > metrics["recv_s_per_req"]
