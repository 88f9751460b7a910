"""chip_smoke.py kept from rotting: the CPU rehearsal passes end to end,
and without the flag a machine with no TPU gets a non-zero exit that
names the platform found — never a result. Its four-chip companion,
chip_multichip.py, is rehearsed the same way on four virtual devices."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
MULTICHIP = os.path.join(REPO, "chip_multichip.py")
PHASES = ["daemon_start", "ff_f32", "ff_bf16", "sessions_lstm",
          "sessions_transformer_layer", "paged_fold", "daemon_stop",
          "kernels"]


def test_dryrun_cpu_passes_every_phase(tmp_path):
    cache = str(tmp_path / "cc")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    proc = subprocess.run([sys.executable, SMOKE, "--dryrun-cpu"],
                          env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary, verdict = map(json.loads, proc.stdout.strip().splitlines())
    # the last line is the verdict alone, with exactly the driver's keys
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["count"], int)
    assert summary["ok"] is True and summary["dryrun"] is True
    assert summary["device"] == verdict["device"]
    assert summary["platform"] == "cpu"
    assert list(summary["phases"]) == PHASES
    assert all(p["ok"] for p in summary["phases"].values())
    assert summary["spawn_to_first_reply_s"] > 0
    # the cache went where the variable says and nowhere else: not
    # under the daemon's --root, not under the smoke's state directory
    cc = summary["compile_cache"]
    assert cc["dir"] == cache and cc["entries_before"] == 0
    assert cc["entries_after"] == len(os.listdir(cache)) > 0
    out = os.path.join(REPO, "chip_smoke_out")
    for _dir, subdirs, _files in os.walk(out):
        assert "compile_cache" not in subdirs, _dir
        assert ".jax_compile_cache" not in subdirs, _dir
    for log in ("daemon.log", "kernels.log", "summary.json"):
        assert os.path.exists(os.path.join(out, log)), log


def test_without_flag_there_is_no_cpu_path():
    proc = subprocess.run([sys.executable, SMOKE], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line
    assert "platform 'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr


def _four_cpu_devices():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")


def test_multichip_dryrun_cpu_passes_every_check():
    proc = subprocess.run([sys.executable, MULTICHIP, "--dryrun-cpu"],
                          env=_four_cpu_devices(), cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True and summary["dryrun"] is True
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 4}
    assert list(summary["checks"]) == [
        "oversized_placement", "placed_q01", "ff_2x2",
        "dryrun_multichip_4", "ring_attention", "pool_landing_observation"]
    q01 = summary["checks"]["placed_q01"]
    assert q01["shard_devices"] == [0, 1, 2, 3]
    assert sum(q01["shard_rows"]) == q01["rows"] + 3  # padded row axis


def test_multichip_without_flag_needs_four_chips():
    proc = subprocess.run([sys.executable, MULTICHIP],
                          env=_four_cpu_devices(), cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "need 4 tpu devices" in proc.stderr
