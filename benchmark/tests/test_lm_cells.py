"""The two cells PR 27 adds, rehearsed on the CPU: ``pytest benchmark/tests``.

``olmo7b-sessions16`` (a hybrid language model through sessions) and ``ff14k-stored8``
(eight callers on the FF deployment). Every run here is ``--rehearse-cpu``: the
configuration's ``rehearsal`` sizes, the daemon on the CPU; never a time or a rate.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

LM, FF8 = "olmo7b-sessions16", "ff14k-stored8"


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(cell, *extra, trace=0, seconds=1, seed=2**31 + 54321):
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace), "--rehearse-cpu", *extra]
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1]), json.loads(lines[-2])


def metric_names(kind, cell):
    return {m["name"] for m in bench_json()[kind] if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", [LM, FF8])
def test_rehearsal_prints_the_contracts_line(cell):
    result, setup = run_cell(cell)
    assert result["rehearsal"] is True and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == metric_names("end_to_end", cell)
    assert {"rows_per_s", "request_p50_s", "setup_s"} <= set(result["metrics"])
    assert result["checks"]["compiles_in_window"] == [0.0, 0.0]
    assert setup["setup_s"] == result["metrics"]["setup_s"]["value"]
    if cell == LM:
        assert set(result["checks"]) >= {"logit_gap_max", "id_gap_max"}


def chip_only(name):
    """Shares of a roofline or of a peak, device seconds of a program and the device's memory
    are the chip's to give: a rehearsal has none."""
    return (name.endswith("_roofline") or "mfu" in name
            or name in ("prefill_s_per_req", "hbm_peak_bytes"))


@pytest.mark.parametrize("cell", [LM, FF8])
def test_traced_rehearsal_reports_every_metric_that_names_the_cell_or_no_cell(cell):
    """What the driver's check holds a traced run to: each per-layer metric whose ``workloads``
    hold the cell, and each that lists none, is in the line (PR 27 was refused for
    ``sched_wait_s_per_req``, absent from the sessions cell)."""
    result, _ = run_cell(cell, trace=1)
    assert result["correct"] is True
    got, due = set(result["metrics"]), metric_names("per_layer", cell)
    assert got <= due
    assert not [n for n in due - got if not chip_only(n)], sorted(due - got)
    assert not [n for n in got if chip_only(n) and n != "hbm_peak_bytes"]
    if cell == LM:
        assert result["metrics"]["state_host_bytes_per_step"]["value"] == 0.0
        assert 0 < result["metrics"]["decode_batch_occupancy"]["value"] <= 1
        assert result["metrics"]["sched_wait_s_per_req"]["value"] > 0


def test_a_fault_in_the_delta_rule_update_comes_out_not_correct():
    result, _ = run_cell(LM, "--fault", os.path.join(HERE, "faults_lm.py") + ":delta_rule_altered")
    assert result["correct"] is False
    value, limit = result["checks"]["logit_gap_max"]
    assert value > limit


def test_the_lower_precision_control_comes_out_not_correct():
    """bfloat16 products and state, at the rehearsal size: over the limit that the program,
    rehearsed above, stays under."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_lm.py"), "--rehearse-cpu", "--seeds", "21",
         "22", "--tokens", "150", "260"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 2
    for line in lines:
        assert line["correct"] is False
        value, limit = line["numbers"]["logit_gap_max"]
        assert value > limit


def test_the_work_functions_add_up_to_the_issues_arithmetic():
    """The parameter counts ISSUE 27 wrote down, from the configuration's file."""
    import lm_work
    from loading import load_json

    cfg = load_json(os.path.join(BENCH, "configs", "olmo-hybrid-7b-16l.json"))
    assert lm_work.counts(cfg) == (12, 4)
    lin = lm_work.layer_matrix_params(cfg, lm_work.LINEAR)
    full = lm_work.layer_matrix_params(cfg, lm_work.FULL)
    assert round(lin / 1e6, 1) == 215.5 and round(full / 1e6, 1) == 185.8
    assert lm_work.state_bytes_per_slot_layer(cfg) == 2211840 + 69120
    assert lm_work.cache_bytes_per_token_layer(cfg) == 15360
    assert lm_work.touches_state("%f = f32[16,96,5760]{2,1,0} fusion(f32[12,16,96,5760] %p)", cfg)
    assert not lm_work.touches_state("%f = bf16[16,3840]{1,0} fusion(...)", cfg)
