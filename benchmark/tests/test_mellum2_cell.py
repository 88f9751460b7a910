"""The cell ``mellum2-sessions64``, rehearsed on the CPU: ``pytest benchmark/tests``.

An eight-layer cut of Mellum 2 12B-A2.5B (64 softmax-routed experts a layer, all held; a
1,024-token window beside YaRN-scaled full attention) through sessions. Every run here is
``--rehearse-cpu``: the configuration's ``rehearsal`` sizes (the same eight-layer pattern at
small widths, 16 experts with 2 a token, heads of 8 on one key/value head, a window of 128 in
a ring of 256 rows, YaRN from position 64 on, sessions closed at 448 tokens so that rings
wrap), the daemon on the CPU; never a time or a rate. No other cell is rehearsed from this
file: two files that rehearse one cell race over its state directory.
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

CELL = "mellum2-sessions64"
CONFIG = os.path.join(BENCH, "configs", "mellum2-12b-a2.5b-8l.json")


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(*extra, trace=0, seconds=1, seed=2**31 + 42042):
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
    assert 1 <= result["checks"]["route_alternatives"][0] <= 2 ** 8


def chip_only(name):
    """Shares of a roofline or of a peak and the device's memory are the chip's to give."""
    return name.endswith("_roofline") or "mfu" in name or name == "hbm_peak_bytes"


def test_traced_rehearsal_reports_every_metric_that_names_the_cell_or_no_cell():
    """What a traced run of the cell is held to: each per-layer metric whose ``workloads`` hold
    the cell, and each that lists none, is in the line; the chip's own excepted, which a
    rehearsal has not."""
    result, _ = run_cell(trace=1)
    assert result["correct"] is True
    got, due = set(result["metrics"]), metric_names("per_layer")
    assert {"mellum2_lm_mfu", "mellum2_experts_roofline", "mellum2_decode_step_roofline",
            "wire_s_per_req", "sched_wait_s_per_req", "compiles_in_window",
            "backend_start_s"} <= due
    assert not {"moe_lm_mfu", "lm_mfu", "decode_step_s"} & due
    assert got <= due
    assert not [n for n in due - got if not chip_only(n)], sorted(due - got)
    assert not [n for n in got if chip_only(n) and n != "hbm_peak_bytes"]
    assert result["metrics"]["sched_wait_s_per_req"]["value"] > 0


def test_a_fault_comes_out_not_correct():
    """The full layers served without YaRN's scaling (``faults_mellum2.py``): the expert fault of
    ``faults_trinity.py`` is the older sparse cell's test to hold."""
    result, _ = run_cell("--fault", os.path.join(HERE, "faults_mellum2.py") + ":yarn_unscaled")
    assert result["correct"] is False
    assert any(result["checks"][n][0] > result["checks"][n][1]
               for n in ("logit_gap_rms", "logit_gap_max"))


def test_the_lower_precision_control_comes_out_not_correct():
    """bfloat16 products, at the rehearsal size, on as many histories past YaRN's original
    context and a window as a rehearsal checks: over the limit that the program, rehearsed
    above, stays under."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_mellum2.py"), "--rehearse-cpu", "--seeds",
         "21", "22", "--tokens", "200", "240", "280", "320", "360", "400", "440", "448"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 2
    for line in lines:
        assert line["correct"] is False
        value, limit = line["numbers"]["logit_gap_rms"]
        assert value > limit


def test_the_work_functions_add_up_to_the_cuts_arithmetic():
    """The cut's counts, from the configuration's file: 417.7 M parameters a
    layer, 7.59 GB of weights, 6.34 GB of experts read a step when every expert of the eight
    layers is touched, a slab of 6.17 GB for 64 slots."""
    import mellum2_work
    from loading import load_json

    cfg = load_json(CONFIG)
    kinds = ["sliding_attention", "full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"] + ["sliding_attention"] * 2
    assert mellum2_work.layers(cfg) == [(k, True) for k in kinds]
    assert mellum2_work.counts(cfg) == (6, 2, 8)
    assert round(mellum2_work.attention_params(cfg) / 1e6, 2) == 21.23
    assert round(mellum2_work.router_params(cfg) / 1e6, 2) == 0.15
    assert round(mellum2_work.expert_params(cfg) / 1e6, 2) == 6.19
    assert round(mellum2_work.layer_params(cfg, 64) / 1e6, 1) == 417.7
    assert round(2 * mellum2_work.resident_params(cfg) / 1e9, 2) == 7.59
    assert round(mellum2_work.experts_bytes(cfg, 8 * 64) / 1e9, 2) == 6.34
    assert round(mellum2_work.slab_bytes(cfg) / 1e9, 2) == 6.17
    assert mellum2_work.cache_bytes_per_token_layer(cfg) == 2048
    assert mellum2_work.expected_pairs(cfg, 64) == 64 * 8 * 8     # 8 pairs an expert
    # the whole model by the same count: 28 layers, embedding and head, 12.15 B (the name's
    # 12B)
    whole = dict(cfg, num_hidden_layers=28, first_layer=0)
    assert round(mellum2_work.resident_params(whole) / 1e9, 2) == 12.15


def test_the_configuration_states_the_published_widths_and_its_cut():
    from loading import load_json

    cfg = load_json(CONFIG)
    want = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "moe_intermediate_size": 896, "intermediate_size": 7168,
            "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": True,
            "sliding_window": 1024, "vocab_size": 98304, "rms_norm_eps": 1e-06,
            "num_hidden_layers": 8, "first_layer": 2, "tie_word_embeddings": False,
            "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28 and cfg["stands_for"]
    assert set(cfg["mlp_layer_types"]) == {"sparse"}
    for key in ("qk_norm", "norm_placement", "rotary", "attention_factor", "window", "routing",
                "mtp_head", "dtype", "vocab"):
        assert cfg["assumed"][key]
    entry = {c["name"]: c for c in bench_json()["configs"]}["mellum2-12b-a2.5b-8l"]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_ids_cover_the_whole_vocabulary():
    """A prompt's ids are the 32-bit hash times 98,304 shifted down by 32 in 64-bit arithmetic:
    every id can be drawn, none wraps to a small one, and the three thirds of the vocabulary
    are drawn alike (16 bits through ``datagen.scaled`` would wrap; 15 bits would reach one id
    in three)."""
    import numpy as np

    from loading import load_json, load_module

    deployment = load_module(os.path.join(BENCH, "deployments", "lm_sessions_mellum2.py"),
                             "bench_deployment_lm_sessions_mellum2")
    cfg = load_json(CONFIG)
    traffic = load_json(os.path.join(BENCH, "traffic", "sessions64.json"))
    ops = deployment.Ops(cfg, traffic, 2**33 + 5)
    ids = ops._ids(123456789, 1 << 20)
    assert ids.min() >= 0 and ids.max() < 98304
    counts = np.bincount(ids // 32768, minlength=3)
    assert abs(counts / counts.sum() - 1 / 3).max() < 0.005
    assert len(np.unique(ids)) > 0.99 * 98304 * (1 - np.exp(-(1 << 20) / 98304))
    assert ops.callers == 64 and ops.first == [2048, 4096, 6144, 8192]
    assert ops.later == [256, 512, 768, 1024]


@pytest.mark.parametrize("text,touches", [
    # the kernel: the experts' stacks as the step program passes them, one block an expert
    ("%grouped_ffn = f32[1536,2304]{1,0} custom-call(s32[96]{0} %a, s32[1]{0} %b, "
     "bf16[1536,2304]{1,0} %x, bf16[64,2,896,2304]{3,2,1,0} %gu, bf16[64,2304,896]{2,1,0} %dn)",
     True),
    # the XLA form: a tile's expert gathered
    ("%gather = bf16[96,2,896,2304]{3,2,1,0} gather(bf16[64,2,896,2304]{3,2,1,0} %gu, "
     "s32[96,1]{1,0} %i)", True),
    # the stacks as they are stored, two-dimensional
    ("%copy = bf16[114688,2304]{1,0} copy(bf16[114688,2304]{1,0} %p)", True),
    # not experts: the router, attention's projections, a cache, the rows of a tile
    ("%dot = f32[64,64]{1,0} dot(f32[64,2304]{1,0} %u, f32[64,2304]{1,0} %w)", False),
    ("%dot = f32[64,5120]{1,0} dot(bf16[64,2304]{1,0} %x, bf16[5120,2304]{1,0} %w)", False),
    ("%attn = f32[64,4,8,128]{3,2,1,0} custom-call(bf16[64,4,17408,128]{3,2,1,0} %k)", False),
    ("%gather = f32[64,8,2304]{2,1,0} gather(f32[1536,2304]{1,0} %ys, s32[64,8]{1,0} %row)",
     False)])
def test_which_operations_touch_the_experts(text, touches):
    import mellum2_work
    from loading import load_json

    assert mellum2_work.touches_experts(text, load_json(CONFIG)) is touches
