#!/usr/bin/env python3
"""netsdb_tpu's benchmark: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<name>.json``, a deployment of the daemon with its data) under a
traffic mix (``traffic/<name>.json``). This process is the client side: it
starts ``launcher.py`` (the daemon, the only process on the chip), waits for it
to have filled the database from the seed, warms the cell's shapes, drives the
mix for ``--seconds`` through ``RemoteClient``, and then checks what the window
produced against the configuration's plain reference. It never initialises a
JAX backend (asserted before it prints).

The last line of stdout is the result. The line before it gives the seconds of
each part of set-up. ``--rehearse-cpu`` runs the same path at the
configuration's ``rehearsal`` sizes with the daemon on the CPU, for the sandbox
and the tests; its result says ``"rehearsal": true`` and is never a measurement.
"""

from __future__ import annotations

import time

T_ENTRY = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STATE = os.path.join(ROOT, ".benchmark_state")
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from loading import load_json, load_module  # noqa: E402


# these add up to setup_s; the launcher's own parts ("daemon") and the harness's work beside the
# daemon's start ("beside_daemon_*") lie inside daemon_spawn_to_listening_s
SETUP_PARTS = ("before_spawn_s", "daemon_spawn_to_listening_s", "clients_opened_s", "warm_up_s",
               "other_s")


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def process_start() -> float:
    """time.time() at which the kernel started this process (to 10 ms), else this module's entry."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
        return started if 0 <= T_ENTRY - started < 60 else T_ENTRY
    except (OSError, ValueError, IndexError):
        return T_ENTRY


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def tail(path: str, nbytes: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"({path}: {e})"


class Daemon:
    """The launcher child and its control channel."""

    def __init__(self, config_path: str, seed: int, state: str, rehearse: bool, fault=None):
        self.log_path = os.path.join(state, "daemon.log")
        argv = [sys.executable, os.path.join(HERE, "launcher.py"), "--config", config_path,
                "--seed", str(seed), "--root", os.path.join(state, "root")]
        if rehearse:
            argv.append("--rehearse-cpu")
        if fault:
            argv += ["--fault", fault]
        env = dict(os.environ)
        env["NETSDB_TPU_HOME"] = os.path.join(state, "home")
        self.spawned = time.time()
        with open(self.log_path, "wb") as logf:
            self.proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdin=subprocess.PIPE,
                                         stdout=subprocess.PIPE, stderr=logf, text=True)

    def read(self, timeout_s: float) -> dict:
        """The next control reply; the daemon's death or silence is an error."""
        box = {}
        t = threading.Thread(target=lambda: box.update(line=self.proc.stdout.readline()),
                             daemon=True)
        t.start()
        t.join(timeout_s)
        line = box.get("line")
        if not line:
            raise RuntimeError(
                f"the daemon gave no reply within {timeout_s:.0f}s (exit code "
                f"{self.proc.poll()}); its log ends:\n{tail(self.log_path)}")
        return json.loads(line)

    def control(self, timeout_s: float = 120.0, **cmd) -> dict:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        out = self.read(timeout_s)
        if not out.get("ok"):
            raise RuntimeError(f"daemon control {cmd.get('op')}: {out.get('error')}")
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.control(op="quit", timeout_s=30)
                self.proc.wait(timeout=60)
            except Exception:  # noqa: BLE001 - it is killed below either way
                pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=60)


def run_cell(args, fault=None) -> int:
    started = process_start()
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"no workload {args.workload!r} in BENCHMARK.json; it has {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(ROOT, config_entry["file"])
    cfg = load_json(config_path)
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if args.rehearse_cpu:
        cfg.update(cfg.get("rehearsal", {}))
        traffic.setdefault("params", {}).update(traffic.get("rehearsal", {}))

    # the daemon first: its start (backend, data made on the device) is the long pole, and this
    # process's own imports and host-side data run beside it
    state = os.path.join(STATE, args.workload)
    shutil.rmtree(state, ignore_errors=True)
    os.makedirs(os.path.join(state, "home"))
    parts = {"before_spawn_s": time.time() - started}
    daemon = Daemon(config_path, args.seed, state, args.rehearse_cpu, fault)
    ops = None
    ctxs = []
    try:
        t = time.time()
        import jax._src.xla_bridge as xla_bridge
        import numpy as np

        import loadgen
        from netsdb_tpu import obs
        from netsdb_tpu.serve.client import RemoteClient

        # netsdb_tpu.obs re-exports the function trace() over the submodule's name
        obs_trace = importlib.import_module("netsdb_tpu.obs.trace")
        deployment = load_module(os.path.join(HERE, "deployments", cfg["deployment"] + ".py"),
                                 "bench_deployment_" + cfg["deployment"])
        parts["beside_daemon_imports_s"] = time.time() - t
        t = time.time()
        ops = deployment.Ops(cfg, traffic, args.seed)
        parts["beside_daemon_client_data_s"] = time.time() - t
        try:
            ready = daemon.read(timeout_s=900)
        except RuntimeError as e:
            if daemon.proc.poll() == 3:   # the launcher found another platform and said which
                log(f"no accelerator: {tail(daemon.log_path, 400).strip()}")
                return 3
            raise e
        parts["daemon_spawn_to_listening_s"] = time.time() - daemon.spawned
        parts["daemon"] = ready["parts"]
        device = ready["device"]
        want = "cpu" if args.rehearse_cpu else "tpu"
        if device["platform"] != want or device["count"] < cell["chips"]:
            log(f"the daemon runs on platform {device['platform']!r} ({device['kind']} x"
                f"{device['count']}); the cell needs {cell['chips']} x {want!r}. There is no "
                f"CPU path; --rehearse-cpu is the rehearsal.")
            return 3
        addr = f"127.0.0.1:{ready['port']}"
        admin = RemoteClient(addr)

        t = time.time()
        ops.addr = addr
        ctxs = [ops.open_client(k) for k in range(traffic["clients"])]
        parts["clients_opened_s"] = time.time() - t
        t = time.time()
        warmed = loadgen.warm_up(ops, ctxs)
        parts["warm_up_s"] = time.time() - t
        parts["warm_up_requests"] = warmed

        # the client's ring keeps every request of the window, for the traced run's readers
        obs_trace.DEFAULT_RING = obs.TraceRing(1 << 16)
        before = admin.collect_stats()
        trace_dir = os.path.join(state, "trace")
        trace_wall = None
        if args.trace:
            trace_wall = [daemon.control(op="trace_start", dir=trace_dir)["wall_ns"]]

        for ctx in ctxs:
            ctx["counters"] = {}      # what a deployment's client side counts, of the window alone
        win = loadgen.run_window(ops, ctxs, traffic, args.seed, args.seconds)
        setup_s = win.opened - started

        if args.trace:
            trace_wall.append(daemon.control(op="trace_stop", timeout_s=300)["wall_ns"])
        after = admin.collect_stats()
        memory = daemon.control(op="memory")
        profiles = []
        if args.trace:
            for ctx in ctxs:
                client = ctx.get("client") or ctx["srv"]._client()
                client.flush_traces(10.0)
            profiles = admin.get_trace(last=cfg.get("daemon", {}).get("trace_ring", 64))[
                "profiles"]
        client_profiles = obs_trace.DEFAULT_RING.last()
        client_counters = {}
        for ctx in ctxs:
            for name, value in ctx["counters"].items():
                client_counters[name] = client_counters.get(name, 0.0) + value
        answers = [a for ctx in ctxs for a in ops.answers(ctx)]
        for ctx in ctxs:
            ops.close_client(ctx)
        ctxs = []
        admin.close()
    except BaseException:
        log(f"FAILED; the daemon's log ends:\n{tail(daemon.log_path)}")
        raise
    finally:
        for ctx in ctxs:
            try:
                ops.close_client(ctx)
            except Exception:  # noqa: BLE001 - the run has already failed
                pass
        daemon.stop()
    parts["daemon_exit_code"] = daemon.proc.returncode

    # ---- correct: the plain reference, after the window, the daemon gone ----
    t = time.time()
    reference = load_module(os.path.join(os.path.dirname(config_path), cfg["reference"]),
                            "bench_reference")
    sample_rng = np.random.default_rng([args.seed & 0xFFFFFFFF, args.seed >> 32, 0xC0])
    numbers = dict(reference.check(cfg, args.seed, answers, sample_rng))
    compiles = (after["metrics"]["compile"]["misses"] - before["metrics"]["compile"]["misses"])
    numbers["compiles_in_window"] = (float(compiles), 0.0)
    numbers["requests_failed"] = (float(len(win.failed)), 0.0)
    numbers["daemon_exit_code"] = (float(abs(daemon.proc.returncode or 0)), 0.0)
    correct = all(value <= limit for value, limit in numbers.values())
    reference_s = time.time() - t

    lat = win.latencies()
    rows = sum(r for _, _, _, r in win.requests)
    measured = {
        "rows_per_s": rows / win.seconds,
        "request_p50_s": statistics.median(lat) if lat else float("nan"),
        "request_p90_s": loadgen.percentile(lat, 0.9) if lat else float("nan"),
        "request_mean_s": statistics.fmean(lat) if lat else float("nan"),
        "setup_s": setup_s,
    }
    dev = {"platform": device["platform"], "kind": device["kind"], "count": device["count"],
           "memory_peak_bytes": memory["peak_bytes"]}
    result = {"correct": bool(correct), "attempted": len(win.requests) + len(win.failed),
              "failed": len(win.failed)}
    if not args.trace:
        result["metrics"] = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                             for m in bench["end_to_end"] if applies(m, args.workload)}
    else:
        import xplane

        t = time.time()
        reduction = xplane.reduce_dir(trace_dir, trace_wall, win, args.rehearse_cpu)
        run = {"cell": args.workload, "cfg": cfg, "traffic": traffic, "window": win,
               "requests": len(win.requests), "rows": rows, "setup_parts": parts,
               "profiles": profiles, "client_counters": client_counters,
               "client_profiles": client_profiles, "before": before, "after": after,
               "trace": reduction, "device_kind": device["kind"], "memory": memory,
               "rehearsal": bool(args.rehearse_cpu)}
        metrics = {}
        for m in bench["per_layer"]:
            if not applies(m, args.workload):
                continue
            reader = load_module(os.path.join(HERE, "layer_metrics", m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
        parts["trace_reduction_s"] = time.time() - t
    result["device"] = dev
    if args.rehearse_cpu:
        result["rehearsal"] = True
    result["window"] = {"seconds": win.seconds, "requests": len(win.requests), "rows": rows,
                        "request_max_s": lat[-1] if lat else None, "reference_s": reference_s,
                        "first_failures": win.failed[:3]}
    # what the daemon's cache and staging counted over the window: read by nothing, kept so that a
    # run that reads far off says why (PERF.md, the fold cell's two speeds)
    counted = after["metrics"].get("counters", {})
    result["window"]["counters"] = {
        name: value - before["metrics"].get("counters", {}).get(name, 0)
        for name, value in counted.items() if name.startswith(("devcache.", "staging."))}
    result["checks"] = {k: [v, lim] for k, (v, lim) in numbers.items()}

    if xla_bridge.backends_are_initialized():
        log("the harness process initialised a JAX backend; only the daemon may hold the chip")
        return 4
    parts_sum = {k: v for k, v in parts.items() if k in SETUP_PARTS}
    parts["other_s"] = setup_s - sum(parts_sum.values())
    print(json.dumps({"setup_s": setup_s, "setup_parts": parts}), flush=True)
    for name, (value, limit) in numbers.items():
        log(f"compared {name}: {value!r} (limit {limit!r})")
    log(f"correct: {correct}")
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None, fault=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU rehearsal at the configuration's rehearsal sizes; never a result")
    ap.add_argument("--fault", default=None,
                    help="tests only: path.py:function that breaks the program inside the daemon")
    args = ap.parse_args(argv)
    return run_cell(args, fault=fault or args.fault)


if __name__ == "__main__":
    sys.exit(main())
