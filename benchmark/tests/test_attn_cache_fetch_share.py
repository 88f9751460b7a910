"""``layer_metrics/attn_cache_fetch_share.py``: on recorded counters, on a program without them, and
in the sessions cell's rehearsal, whose small heads take the whole pass."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loading import load_module  # noqa: E402
from test_harness import bench_json, metric_names, run_cell  # noqa: E402

NAME = "attn_cache_fetch_share"
CELL = "olmo7b-sessions16"
reader = load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", NAME + ".py"),
                     "bench_metric_" + NAME)


def stats(**counters):
    return {"metrics": {"counters": {"session.decode_steps": 1.0, **{
        "decode.attn." + k: float(v) for k, v in counters.items()}}}}


def test_the_share_is_rows_fetched_over_rows_held_of_the_window():
    # the warm-up's steps lie before the window; the window adds 1,000 steps of 16 slots x 4,608
    # rows x 4 layers held, and sessions of 2,500 tokens in blocks of 256 at occupancy 15 of 16
    held = 1000 * 4 * 16 * 4608
    fetched = 1000 * 4 * (15 * 2560 + 256)
    run = {"before": stats(rows_fetched=7e6, rows_held=9e6),
           "after": stats(rows_fetched=7e6 + fetched, rows_held=9e6 + held)}
    assert reader.read(run) == fetched / held
    assert 0.5 < reader.read(run) < 0.65
    # the whole pass fetches what is held
    assert reader.read({"before": stats(), "after": stats(rows_fetched=held, rows_held=held)}) == 1.0


def test_nothing_to_read_is_none_and_does_not_raise():
    # the parent commit has no such counters; a window without a decode step counts nothing
    assert reader.read({"before": stats(), "after": stats()}) is None
    assert reader.read({"before": {"metrics": {}}, "after": {"metrics": {}}}) is None
    assert reader.read({"before": stats(rows_fetched=4, rows_held=8),
                        "after": stats(rows_fetched=4, rows_held=8)}) is None


def test_it_is_listed_for_the_sessions_cell_alone_and_the_rehearsal_reads_the_whole_pass():
    bench = bench_json()
    assert NAME in metric_names(bench, "per_layer", CELL)
    assert NAME not in metric_names(bench, "per_layer", "ff14k-stored")
    entry = [m for m in bench["per_layer"] if m["name"] == NAME][0]
    assert entry == {"name": NAME, "unit": "ratio", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "rows_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] == entry
    proc, lines = run_cell(CELL, "--rehearse-cpu", trace=1, seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    # heads of 32 are not whole lanes: the rehearsal's step takes cached_attention
    assert result["metrics"][NAME]["value"] == 1.0
