"""``layer_metrics/checksum_s_per_req.py`` and ``checksum_split_share.py``: on recorded spans and
counters, on a program without them, and in the shipped cell's rehearsal, whose 38 kB batches stay
under the size at which a checksum is split."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loading import load_module  # noqa: E402
from test_harness import bench_json, metric_names, run_cell  # noqa: E402

NAMES = ("checksum_s_per_req", "checksum_split_share")
readers = {name: load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
                             "bench_metric_" + name) for name in NAMES}


def profile(qid, *spans):
    return {"qid": qid, "spans": [{"name": n, "duration_s": d} for n, d in spans]}


def traced(requests, client, daemon):
    return {"requests": requests, "client_profiles": client, "profiles": daemon}


def test_seconds_are_both_ends_checksum_spans_over_the_requests():
    client = [profile("a", ("client.send", 0.2), ("client.encode", 0.03),
                      ("client.encode.checksum", 0.007)),
              profile("b", ("client.encode.checksum", 0.005))]
    daemon = [profile("a", ("server.decode", 0.03), ("server.decode.checksum", 0.006)),
              profile("b", ("server.decode.checksum", 0.002)),
              profile("warm-up", ("server.decode.checksum", 9.0))]   # not a window's request
    got = readers["checksum_s_per_req"].read(traced(2, client, daemon))
    assert got == pytest.approx((0.007 + 0.005 + 0.006 + 0.002) / 2)
    # one end alone still reads
    assert readers["checksum_s_per_req"].read(traced(2, client, [])) == pytest.approx(0.006)


def test_seconds_without_the_spans_are_none():
    # the parent commit records neither span; a window of no request reads nothing
    bare = [profile("a", ("client.send", 0.2), ("client.encode", 0.03))]
    assert readers["checksum_s_per_req"].read(
        traced(1, bare, [profile("a", ("server.decode", 0.03))])) is None
    assert readers["checksum_s_per_req"].read(
        traced(0, [profile("a", ("client.encode.checksum", 0.1))], [])) is None


def stats(**counters):
    return {"metrics": {"counters": {"serve.wire.bytes_in": 1.0, **{
        "serve.wire.checksum." + k: float(v) for k, v in counters.items()}}}}


def test_the_share_is_split_bytes_over_all_checksummed_bytes_of_the_window():
    read = readers["checksum_split_share"].read
    run = {"before": stats(bytes=1000, split_bytes=900),
           "after": stats(bytes=1000 + 313_410_000, split_bytes=900 + 305_940_480)}
    assert read(run) == pytest.approx(305_940_480 / 313_410_000)
    # a split counter the program never made reads 0
    assert read({"before": stats(bytes=5), "after": stats(bytes=40)}) == 0.0


def test_the_share_of_nothing_checksummed_is_none():
    read = readers["checksum_split_share"].read
    assert read({"before": stats(), "after": stats()}) is None
    assert read({"before": {"metrics": {}}, "after": {"metrics": {}}}) is None
    assert read({"before": stats(bytes=7, split_bytes=0), "after": stats(bytes=7)}) is None


def test_both_are_listed_for_the_shipped_cell_alone_and_read_in_its_rehearsal():
    bench = bench_json()
    for name in NAMES:
        assert name in metric_names(bench, "per_layer", "ff14k-shipped")
        assert name not in metric_names(bench, "per_layer", "ff14k-stored")
        entry = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert entry["layer"] == "client, wire, codec" and entry["moves"] == "request_p50_s"
        assert entry["workloads"] == ["ff14k-shipped"]
    proc, lines = run_cell("ff14k-shipped", "--rehearse-cpu", trace=1, seconds=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["checksum_s_per_req"]["value"] > 0
    # the rehearsal's batches are far under the split's size: every byte took the one pass
    assert metrics["checksum_split_share"] == {"value": 0.0, "unit": "ratio"}
    assert metrics["checksum_s_per_req"]["value"] < metrics["wire_s_per_req"]["value"]
