"""``layer_metrics/ingest_pad_share.py``: on recorded counters, on a program without them, on a
window that ingested nothing, and in the shipped cell's rehearsal, whose 8-row batches are blocked
by the rehearsal model's 32-row block (under a lane, so the model's block stands) and padded."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loading import load_module  # noqa: E402
from test_harness import bench_json, metric_names, run_cell  # noqa: E402

NAME = "ingest_pad_share"
reader = load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", NAME + ".py"),
                     "bench_metric_" + NAME)


def stats(**counters):
    return {"metrics": {"counters": {"serve.wire.bytes_in": 1.0, **{
        "store.ingest." + k: float(v) for k, v in counters.items()}}}}


def test_the_share_is_pad_rows_over_all_rows_placed_in_the_window():
    # the fill's weights and the warm-up's two batches lie before the window; the window ships
    # 279 batches of 128 rows, padded to 512 rows each before the row block followed the batch
    before = stats(rows=17_000, pad_rows=1_000)
    assert reader.read({"before": before,
                        "after": stats(rows=17_000 + 279 * 128,
                                       pad_rows=1_000 + 279 * 384)}) == 0.75
    assert reader.read({"before": before,
                        "after": stats(rows=17_000 + 279 * 128, pad_rows=1_000)}) == 0.0


def test_a_pad_counter_the_program_never_made_reads_zero():
    # the registry makes a counter at its first increment, and an increment of 0 makes it too;
    # a missing one reads 0 all the same
    assert reader.read({"before": stats(), "after": stats(rows=128)}) == 0.0
    assert reader.read({"before": stats(rows=5), "after": stats(rows=8, pad_rows=1)}) == 0.25


def test_a_program_without_the_counters_reads_none():
    # the parent commit counts no ingested rows: the metric is null on its side
    assert reader.read({"before": stats(), "after": stats()}) is None
    assert reader.read({"before": {"metrics": {}}, "after": {"metrics": {}}}) is None


def test_a_window_that_ingested_nothing_reads_none_and_the_line_leaves_it_out():
    # the stored cells fill their sets before the window and ship nothing in it; the harness
    # prints a metric only where its reader returns a number
    filled = stats(rows=8 * 512 + 2_048, pad_rows=0)
    assert reader.read({"before": filled, "after": filled}) is None


def test_it_is_listed_for_the_shipped_cell_alone_and_read_in_its_rehearsal():
    bench = bench_json()
    assert NAME in metric_names(bench, "per_layer", "ff14k-shipped")
    assert NAME not in metric_names(bench, "per_layer", "ff14k-stored")
    entry = [m for m in bench["per_layer"] if m["name"] == NAME][0]
    assert entry == {"name": NAME, "unit": "ratio", "better": "lower", "source": "program_counter",
                     "layer": "storage ingest", "moves": "request_p50_s",
                     "workloads": ["ff14k-shipped"]}
    proc, lines = run_cell("ff14k-shipped", "--rehearse-cpu", trace=1, seconds=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    # 8 rows a batch in one 32-row block: 24 zero rows of every 32
    assert result["metrics"][NAME] == {"value": 0.75, "unit": "ratio"}
