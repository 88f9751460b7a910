"""``layer_metrics/recv_pool_hit_share.py``: on recorded counters, on a program without them, and in
the shipped cell's rehearsal, whose 38 kB batches stay under the pool's floor."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loading import load_module  # noqa: E402
from test_harness import bench_json, metric_names, run_cell  # noqa: E402

NAME = "recv_pool_hit_share"
reader = load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", NAME + ".py"),
                     "bench_metric_" + NAME)


def stats(**counters):
    return {"metrics": {"counters": {"serve.wire.bytes_in": 1.0, **{
        "serve.wire.recv_pool." + k: float(v) for k, v in counters.items()}}}}


def test_the_share_is_hits_over_pooled_receives_of_the_window():
    # the warm-up's two misses and 30 hits lie before the window; the window adds 77 hits, 1 miss
    run = {"before": stats(hits=30, misses=2), "after": stats(hits=107, misses=3)}
    assert reader.read(run) == 77 / 78
    assert reader.read({"before": stats(hits=5, misses=2), "after": stats(hits=5, misses=9)}) == 0.0


def test_a_counter_the_warm_up_never_made_reads_zero():
    # the registry makes a counter at its first increment: a warm-up of one request is one miss
    run = {"before": stats(misses=1), "after": stats(hits=225, misses=1)}
    assert reader.read(run) == 1.0
    assert reader.read({"before": stats(), "after": stats(hits=3, misses=1)}) == 0.75


def test_nothing_to_read_is_none_and_does_not_raise():
    # the parent commit has no such counters; the stored cell's frames never reach the floor
    assert reader.read({"before": stats(), "after": stats()}) is None
    assert reader.read({"before": {"metrics": {}}, "after": {"metrics": {}}}) is None
    assert reader.read({"before": stats(hits=4, misses=1),
                        "after": stats(hits=4, misses=1)}) is None


def test_it_is_listed_for_the_shipped_cell_alone_and_the_rehearsal_leaves_it_out():
    bench = bench_json()
    assert NAME in metric_names(bench, "per_layer", "ff14k-shipped")
    assert NAME not in metric_names(bench, "per_layer", "ff14k-stored")
    entry = [m for m in bench["per_layer"] if m["name"] == NAME][0]
    assert entry == {"name": NAME, "unit": "ratio", "better": "higher", "source": "program_counter",
                     "layer": "client, wire, codec", "moves": "request_p50_s",
                     "workloads": ["ff14k-shipped"]}
    proc, lines = run_cell("ff14k-shipped", "--rehearse-cpu", trace=1, seconds=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert "wire_bytes_per_req" in result["metrics"] and NAME not in result["metrics"]
