"""The harness, rehearsed on the CPU: ``pytest benchmark/tests``.

Every run here is ``--rehearse-cpu``: the configuration's ``rehearsal`` sizes, the
daemon on the CPU. It shows that the path works and that ``correct`` can fail; it
never yields a time or a rate.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


def bench_json(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def waiting_root(tmp_path_factory):
    """A checkout in which the waiting cells are admitted: their files are under ``benchmark/``
    already, so all it takes is their entries (``waiting/*.json``) in ``BENCHMARK.json``."""
    root = tmp_path_factory.mktemp("waiting")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_json()
    for path in sorted(glob.glob(os.path.join(BENCH, "waiting", "*.json"))):
        with open(path) as f:
            for key, entries in json.load(f)["entries"].items():
                bench[key] += entries
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def root_of(cell, waiting_root):
    """Where ``cell`` is an entry: this checkout, or the one with the waiting cells admitted."""
    admitted = {w["name"] for w in bench_json()["workloads"]}
    return (ROOT, None) if cell in admitted else (waiting_root, {"PYTHONPATH": ROOT})


def run_cell(cell, *extra, root=ROOT, trace=0, seconds=1, seed=2**31 + 12345, env=None):
    argv = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell,
            "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace), *extra]
    proc = subprocess.run(argv, cwd=root, capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, **(env or {})))
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def metric_names(bench, kind, cell):
    return {m["name"] for m in bench[kind] if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", ["ff14k-stored", "ff14k-shipped", "tpch30-fold-outofcore"])
def test_rehearsal_prints_the_contracts_line(cell, waiting_root):
    root, env = root_of(cell, waiting_root)
    proc, lines = run_cell(cell, "--rehearse-cpu", root=root, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, setup = json.loads(lines[-1]), json.loads(lines[-2])
    assert REQUIRED <= set(result) and result["rehearsal"] is True
    assert list(result)[-1] == "checks"          # the numbers compared come last
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == metric_names(bench_json(root), "end_to_end", cell)
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in result["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert result["checks"]["compiles_in_window"] == [0.0, 0.0]
    # the line before says where set-up went, and the parts add up to setup_s
    parts = setup["setup_parts"]
    named = ("before_spawn_s", "daemon_spawn_to_listening_s", "clients_opened_s", "warm_up_s",
             "other_s")
    assert abs(sum(parts[k] for k in named) - setup["setup_s"]) < 1e-6
    assert setup["setup_s"] == result["metrics"]["setup_s"]["value"]
    for name, (value, limit) in result["checks"].items():
        assert f"compared {name}: {value!r} (limit {limit!r})" in proc.stderr


@pytest.mark.parametrize("cell", ["ff14k-stored", "tpch30-fold-outofcore"])
def test_traced_rehearsal_reports_per_layer_metrics(cell, waiting_root):
    root, env = root_of(cell, waiting_root)
    proc, lines = run_cell(cell, "--rehearse-cpu", trace=1, root=root, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    allowed = metric_names(bench_json(root), "per_layer", cell)
    got = set(result["metrics"])
    assert got <= allowed
    assert {"wire_s_per_req", "plan_s_per_req", "executor_s_per_req", "compiles_in_window",
            "device_idle_share", "device_busy_s_per_req", "backend_start_s"} <= got
    # a share of a roofline or of a peak is a device number: a rehearsal leaves it out
    assert not [n for n in got if n.endswith("_roofline") or "mfu" in n]
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    idle = result["metrics"]["device_idle_share"]["value"]
    assert idle == pytest.approx(1 - dev["busy_s"] / dev["window_s"], abs=1e-12)
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    if cell == "tpch30-fold-outofcore":
        # block by block: a scan served partly from HBM reads between 0 and 1, not 0
        assert 0 < result["metrics"]["devcache_hit_share"]["value"] < 1


def test_without_the_rehearsal_flag_a_cpu_is_refused():
    proc, lines = run_cell("ff14k-stored")
    assert proc.returncode != 0
    assert not lines                              # no result line
    assert "'cpu'" in proc.stderr and "tpu" in proc.stderr


def test_only_the_benchmarks_files_is_not_enough(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    proc, lines = run_cell("ff14k-stored", "--rehearse-cpu", root=str(tmp_path),
                           env={"PYTHONPATH": ""})
    assert proc.returncode != 0 and not lines


@pytest.mark.parametrize("cell,fault", [("ff14k-stored", "ff_answer_altered"),
                                        ("ff14k-shipped", "ff_answer_altered"),
                                        ("tpch30-fold-outofcore", "tpch_answer_altered")])
def test_an_altered_answer_comes_out_not_correct(cell, fault, waiting_root):
    """The whole run, chip look-up aside, with the program broken underneath."""
    root, env = root_of(cell, waiting_root)
    proc, lines = run_cell(cell, "--rehearse-cpu", "--fault",
                           os.path.join(HERE, "faults.py") + ":" + fault, root=root, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any(v > lim for v, lim in result["checks"].values())


def test_a_new_cell_configuration_and_metric_are_files_only(tmp_path):
    """A later PR adds entries and files; no file that is there is edited."""
    root = tmp_path
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_json()
    base = json.load(open(os.path.join(BENCH, "configs", "ff-amazoncat14k.json")))
    base["name"] = "ff-other"
    base["rehearsal"]["hidden"] = 32
    with open(root / "benchmark" / "configs" / "ff-other.json", "w") as f:
        json.dump(base, f)
    with open(root / "benchmark" / "traffic" / "stored3.json", "w") as f:
        json.dump({"loop": "closed", "clients": 3, "request": "score_stored"}, f)
    with open(root / "benchmark" / "layer_metrics" / "requests_seen.py", "w") as f:
        f.write("def read(run):\n    return run['requests'] or None\n")
    bench["configs"].append({"name": "ff-other", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmark/configs/ff-other.json"})
    bench["workloads"].append({"name": "ff-other-stored3", "config": "ff-other",
                               "traffic": "stored3", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests", "better": "higher",
                               "source": "program_counter", "layer": "client, wire, codec",
                               "moves": "rows_per_s", "workloads": ["ff-other-stored3"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    proc, lines = run_cell("ff-other-stored3", "--rehearse-cpu", root=str(root), trace=1,
                           env={"PYTHONPATH": ROOT})
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert result["metrics"]["requests_seen"]["value"] == result["attempted"]


# ---- the yardstick's arithmetic -------------------------------------------------

def test_trace_reduction_on_the_recorded_trace():
    """Five 2048-cubed float32 products on a TPU v5 lite (``data/probe_v5e.xplane.pb``)."""
    import xplane

    red = xplane.read_file(os.path.join(HERE, "data", "probe_v5e.xplane.pb"))
    assert list(red.devices) == ["/device:TPU:0"]
    events = red.devices["/device:TPU:0"]
    assert len(events) == 15
    red.lo_ns = min(s for _, s, _ in events)
    red.hi_ns = max(s + d for _, s, d in events)
    kernel = red.op_seconds(lambda text: "convolution" in text)
    assert kernel == pytest.approx(465548e-9, rel=1e-6)        # 93.2 us each, by hand
    assert red.busy_s == pytest.approx(465633e-9, rel=1e-3)    # the copies run just before each
    assert red.window_s == pytest.approx(46574283e-9, rel=1e-6)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.99, abs=0.001)
    top = red.top_ops()
    assert top[0][0] == "convolution_tanh_fusion f32[2048,2048]"
    assert red.idle_gaps()[0][1] == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    # clipped to half the window, only what lies inside counts
    red.hi_ns = events[7][1]
    assert red.op_seconds(lambda text: "convolution" in text) == pytest.approx(
        (93206 + 93138) * 1e-9, rel=1e-6)


def test_union_of_intervals():
    import xplane

    assert xplane.union_seconds([(0, 10), (5, 20), (30, 40)], 0, 100) == pytest.approx(30e-9)
    assert xplane.union_seconds([(0, 10), (5, 20), (30, 40)], 8, 35) == pytest.approx(17e-9)
    assert xplane.union_seconds([], 0, 100) == 0.0


def test_work_counts_at_the_published_widths():
    import peaks
    import work

    f, h, l = 597540, 1024, 14588
    assert work.ff_layer1_flops(512, f, h) == 2 * 512 * 597540 * 1024 == 626566103040
    assert work.ff_score_flops(512, f, h, l) == 626566103040 + 2 * 512 * 1024 * 14588
    assert work.ff_layer1_bytes(512, f, h) == 4 * (1024 * 597540 + 512 * 597540 + 1024 * 512)
    # two requests read w1 twice
    assert (work.ff_layer1_bytes(1024, f, h, requests=2) - work.ff_layer1_bytes(1024, f, h)
            == 4 * 1024 * 597540)
    assert work.fold_bytes(179998372, 7) == 179998372 * 28
    v5e = peaks.peaks_for("TPU v5 lite")
    # memory bound: 3.67 GB over 819 GB/s is 4.49 ms; the product alone would take 3.18 ms
    assert work.roofline_seconds(work.ff_layer1_flops(512, f, h), work.ff_layer1_bytes(512, f, h),
                                 v5e) == pytest.approx(4.4852e-3, rel=1e-4)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9")
    assert work.touches_features("%f = f32[1024,512]{1,0} fusion(f32[2,1168,512,512]{3,2,1,0} %a)", f)
    assert not work.touches_features("%f = f32[14848,512]{1,0} fusion(f32[14848,1024]{1,0} %a)", f)


def test_datagen_is_the_same_on_the_host_and_under_jax():
    import jax.numpy as jnp
    import numpy as np

    import datagen

    key = datagen.stream_key(2**31 + 7, "w1")
    whole = np.asarray(datagen.matrix(jnp, jnp.uint32(key), 40, 1200, -8, row0=jnp.uint32(3)))
    part = datagen.matrix(np, key, 7, 100, -8, row0=3 + 5, col0=1000, ld=1200)
    assert np.array_equal(whole[5:12, 1000:1100], part)
    assert whole.dtype == np.float32 and np.abs(whole).max() < 2.0 ** -8


def test_the_tpch_reference_repeats_the_loaders_rows():
    import numpy as np
    from loading import load_json, load_module as load

    cfg = load_json(os.path.join(BENCH, "configs", "tpch-sf30-lineitem.json"))
    cfg.update(cfg["rehearsal"])

    dep = load(os.path.join(BENCH, "deployments", "tpch.py"), "dep_tpch")
    ref = load(os.path.join(BENCH, "configs", cfg["reference"]), "ref_tpch")
    gen, seed, rows = cfg["generator"], 2**31 + 99, 50000
    cols = dep.columns(np, dep.stream_keys(seed), 0, rows, gen, dep.day_numbers(gen))
    assert cols["l_shipdate"].min() >= 19920102 and cols["l_shipdate"].max() <= 19981201
    assert set(np.unique(cols["l_discount"])) <= {np.float32(c) * np.float32(0.01) for c in range(11)}
    assert cols["l_quantity"].min() == 1 and cols["l_quantity"].max() == 50
    assert 900.0 <= cols["l_extendedprice"].min() and cols["l_extendedprice"].max() <= 104950.0
    want = ref.block_answers(gen, [ref.stream_key(seed, f"lineitem.{n}") for n in (1, 2, 3)],
                             0, rows, "float64")
    m = cols["l_shipdate"] <= 19980902
    group = (cols["l_returnflag"] * 2 + cols["l_linestatus"])[m]
    assert np.array_equal(np.bincount(group, minlength=6), want["count"])
    assert want["count"][[1, 5]].sum() == 0          # A and R lines are never open
    got = np.bincount(group, weights=cols["l_extendedprice"][m].astype(np.float64), minlength=6)
    assert np.allclose(got, want["sum_base_price"], rtol=1e-12)


def test_the_bfloat16_control_fails_the_tpch_comparison():
    """control_tpch.py at the rehearsal size: the reference in the program's place, a precision lower."""
    proc = subprocess.run([sys.executable, os.path.join(HERE, "control_tpch.py"), "--rehearse-cpu",
                           "--seeds", "1", "2", str(2**31 + 3)],
                          capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(rows) == 3
    for row in rows:
        assert row["correct"] is False, row
        assert row["numbers"]["q01_counts_wrong"] == [0.0, 0.0]     # counts are integers either way


def test_the_lower_precision_control_fails_the_ff_comparison():
    """control_ff.py at the rehearsal size: three bfloat16 passes in place of six."""
    proc = subprocess.run([sys.executable, os.path.join(HERE, "control_ff.py"), "--rehearse-cpu",
                           "--seeds", "1", "2", str(2**31 + 3), "--precisions", "highest", "high"],
                          capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(rows) == 6
    for row in rows:
        assert row["correct"] is (row["precision"] == "highest"), row
