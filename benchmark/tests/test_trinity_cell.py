"""The cell PR 35 adds, rehearsed on the CPU: ``pytest benchmark/tests``.

``trinity-sessions32``: a five-layer cut of Trinity-Large-Preview (sparse experts of which
this chip holds a share, sliding-window and full attention mixed by layer) through sessions.
Every run here is ``--rehearse-cpu``: the configuration's ``rehearsal`` sizes (the same
five-layer pattern at small widths, 16 experts of which 4 are held with 2 a token, a window of
192 in a ring of 256 rows, sessions closed at 448 tokens so that rings wrap), the daemon on
the CPU; never a time or a rate.
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

CELL = "trinity-sessions32"
CONFIG = os.path.join(BENCH, "configs", "trinity-large-ep8-5l.json")


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(*extra, trace=0, seconds=1, seed=2**31 + 35035):
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace), "--rehearse-cpu", *extra]
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1]), json.loads(lines[-2])


def metric_names(kind):
    return {m["name"] for m in bench_json()[kind] if "workloads" not in m or CELL in m["workloads"]}


def test_rehearsal_prints_the_contracts_line_and_is_correct():
    result, setup = run_cell()
    assert result["rehearsal"] is True and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == metric_names("end_to_end")
    assert {"rows_per_s", "request_p50_s", "setup_s"} <= set(result["metrics"])
    assert result["checks"]["compiles_in_window"] == [0.0, 0.0]
    assert setup["setup_s"] == result["metrics"]["setup_s"]["value"]
    assert set(result["checks"]) >= {"logit_gap_max", "logit_gap_rms", "id_gap_max",
                                     "route_alternatives", "near_tie_share"}
    assert 1 <= result["checks"]["route_alternatives"][0] <= 16


def chip_only(name):
    """Shares of a roofline or of a peak and the device's memory are the chip's to give."""
    return name.endswith("_roofline") or "mfu" in name or name == "hbm_peak_bytes"


def test_traced_rehearsal_reports_every_metric_that_names_the_cell_or_no_cell():
    """What the driver's check holds a traced run to: each per-layer metric whose ``workloads``
    hold the cell, and each that lists none (``wire_s_per_req``, ``sched_wait_s_per_req``,
    ``compiles_in_window``, ``device_busy_s_per_req``, ``device_idle_share``, ``hbm_peak_bytes``,
    ``backend_start_s``), is in the line; the chip's own excepted, which a rehearsal has not."""
    result, _ = run_cell(trace=1)
    assert result["correct"] is True
    got, due = set(result["metrics"]), metric_names("per_layer")
    assert {"moe_lm_mfu", "moe_decode_step_roofline", "moe_experts_roofline", "wire_s_per_req",
            "sched_wait_s_per_req", "compiles_in_window", "backend_start_s"} <= due
    assert got <= due
    assert not [n for n in due - got if not chip_only(n)], sorted(due - got)
    assert not [n for n in got if chip_only(n) and n != "hbm_peak_bytes"]
    assert result["metrics"]["sched_wait_s_per_req"]["value"] > 0


def test_the_older_cells_fetch_share_is_still_its_own_and_reads_the_whole_pass():
    """What ``test_attn_cache_fetch_share.py`` held of ``BENCHMARK.json`` and of the older
    sessions cell's rehearsal, but for "the last entry", which a metric appended since makes
    untrue (``tests/conftest.py`` deselects that case for tier-1): the metric lists the older
    sessions cell alone, the counters behind it now count by layer type, and that cell's small
    heads still read everything held."""
    name, older = "attn_cache_fetch_share", "olmo7b-sessions16"
    entry = [m for m in bench_json()["per_layer"] if m["name"] == name][0]
    assert entry == {"name": name, "unit": "ratio", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "rows_per_s", "workloads": [older]}
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", older, "--seed",
            str(2**31 + 12345), "--seconds", "2", "--trace", "1", "--rehearse-cpu"]
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads([ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert result["correct"] is True and result["metrics"][name]["value"] == 1.0


@pytest.mark.parametrize("fault", ["held_expert_altered", "window_read_too_far"])
def test_a_fault_comes_out_not_correct(fault):
    result, _ = run_cell("--fault", os.path.join(HERE, "faults_trinity.py") + ":" + fault)
    assert result["correct"] is False
    assert any(result["checks"][n][0] > result["checks"][n][1]
               for n in ("logit_gap_rms", "logit_gap_max"))


def test_the_lower_precision_control_comes_out_not_correct():
    """bfloat16 products, at the rehearsal size, on as many histories past the window plus a
    chunk as a rehearsal checks: over the limit that the program, rehearsed above, stays under."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_trinity.py"), "--rehearse-cpu", "--seeds",
         "21", "22", "--tokens", "300", "340", "380", "420", "460", "500", "280", "320"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 2
    for line in lines:
        assert line["correct"] is False
        value, limit = line["numbers"]["logit_gap_rms"]
        assert value > limit


def test_the_work_functions_add_up_to_the_issues_arithmetic():
    """The parameter counts ISSUE 35 wrote down, from the configuration's file."""
    import moe_work
    from loading import load_json

    cfg = load_json(CONFIG)
    assert moe_work.layers(cfg) == [("sliding_attention", False), ("sliding_attention", True),
                                    ("full_attention", True), ("sliding_attention", True),
                                    ("sliding_attention", True)]
    assert moe_work.counts(cfg) == (4, 1, 4)
    assert round(moe_work.attention_params(cfg) / 1e6, 1) == 62.9
    assert round(moe_work.expert_params(cfg) / 1e6, 1) == 28.3
    assert round(moe_work.router_params(cfg) / 1e6, 1) == 0.8
    assert round(moe_work.layer_params(cfg, True, 32) / 1e6) == 998
    assert round(moe_work.layer_params(cfg, False) / 1e6) == 176
    assert round(2 * moe_work.resident_params(cfg) / 1e9, 2) == 8.64
    assert moe_work.cache_bytes_per_token_layer(cfg) == 4096
    assert moe_work.expected_pairs(cfg, 32) == 4 * 16.0     # 16 pairs a layer a step
    # the whole model by the same count, every layer with all its experts: 398.6 B
    whole = dict(cfg, **cfg["published"], first_layer=0)
    assert round((moe_work.resident_params(whole)) / 1e9, 1) == 398.6


def test_the_configuration_states_the_published_widths_and_its_cut():
    from loading import load_json

    cfg = load_json(CONFIG)
    want = {"hidden_size": 3072, "num_attention_heads": 48, "num_key_value_heads": 8,
            "head_dim": 128, "moe_intermediate_size": 3072, "intermediate_size": 12288,
            "experts_routed": 256, "num_experts_per_tok": 4, "num_shared_experts": 1,
            "sliding_window": 4096, "route_scale": 2.448, "rope_theta": 10000,
            "rms_norm_eps": 1e-05, "num_hidden_layers": 5, "num_dense_layers": 1,
            "num_experts": 32, "vocab_size": 25024}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6,
                                "num_experts": 256, "vocab_size": 200192}
    assert len(cfg["layer_types"]) == 60 and cfg["stands_for"]
    entry = {c["name"]: c for c in bench_json()["configs"]}["trinity-large-ep8-5l"]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


@pytest.mark.parametrize("text,touches", [
    # the kernel: the held experts' stacks as the step program passes them
    ("%grouped_ffn = f32[640,3072]{1,0} custom-call(s32[40]{0} %a, s32[1]{0} %b, bf16[640,3072]"
     "{1,0} %x, bf16[32,2,3072,3072]{3,2,1,0} %gu, bf16[32,3072,3072]{2,1,0} %dn)", True),
    # the XLA form: a tile's expert gathered, then batched products
    ("%gather = bf16[40,2,3072,3072]{3,2,1,0} gather(bf16[32,2,3072,3072]{3,2,1,0} %gu, "
     "s32[40,1]{1,0} %i)", True),
    ("%dot = f32[40,16,3072]{2,1,0} dot(bf16[40,16,3072]{2,1,0} %h, bf16[40,3072,3072]{2,1,0} %w)",
     True),
    # the stacks as they are stored, two-dimensional
    ("%bitcast = bf16[32,2,3072,3072]{3,2,1,0} bitcast(bf16[196608,3072]{1,0} %p)", True),
    ("%copy = bf16[98304,3072]{1,0} copy(bf16[98304,3072]{1,0} %p)", True),
    # not experts: the shared expert, attention's projections, a cache, the rows of a tile
    ("%dot = f32[32,3072]{1,0} dot(bf16[32,3072]{1,0} %h, bf16[3072,3072]{1,0} %w)", False),
    ("%dot = f32[32,14336]{1,0} dot(bf16[32,3072]{1,0} %x, bf16[14336,3072]{1,0} %w)", False),
    ("%attn = f32[32,8,16,128]{3,2,1,0} custom-call(bf16[32,8,5120,128]{3,2,1,0} %k)", False),
    ("%gather = f32[32,4,3072]{2,1,0} gather(f32[640,3072]{1,0} %ys, s32[32,4]{1,0} %row)", False)])
def test_which_operations_touch_the_experts(text, touches):
    import moe_work
    from loading import load_json

    assert moe_work.touches_experts(text, load_json(CONFIG)) is touches
