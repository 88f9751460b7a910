#!/usr/bin/env python3
"""Several runs of one cell in one call, and what they spread by.

    python3 benchmark/tools/runs.py --workload ff14k-stored --seeds 101 102 103 --seconds 10 \
        [--trace 0|1] [--out chiprun_out/ff14k-stored.a.jsonl] [--rehearse-cpu]

Each run is a fresh ``run.py`` process, one after another. Every run's set-up
line and result line go to ``--out`` as they come. At the end it prints, for
each metric and each part of set-up, the median, the range, and the spread the
builder's contract uses: (Q3 - Q1) / median with ``statistics.quantiles(n=4)``.
The first run of a call may compile; ``--skip-first`` leaves it out of the summary.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def spread(values) -> float | None:
    if len(values) < 2 or not statistics.median(values):
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def summarise(rows: dict) -> None:
    for name, values in rows.items():
        values = [v for v in values if isinstance(v, (int, float))]
        if not values:
            continue
        sp = spread(values)
        print(f"{name:36s} n={len(values):2d} median={statistics.median(values):.6g} "
              f"min={min(values):.6g} max={max(values):.6g} "
              f"spread={'-' if sp is None else format(sp, '.4f')}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-first", action="store_true")
    ap.add_argument("--stop-on-fail", action="store_true", help="make no further run after a bad one")
    ap.add_argument("--timeout", type=float, default=600.0, help="seconds one run may take")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows: dict = {}
    bad = 0
    for n, seed in enumerate(args.seeds):
        argv = [sys.executable, RUN, "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace)]
        if args.rehearse_cpu:
            argv.append("--rehearse-cpu")
        t0 = time.time()
        # a session of its own, so that a run past its time is ended with its daemon and nothing else
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                                start_new_session=True)
        try:
            proc.stdout, proc.stderr = proc.communicate(timeout=args.timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            _, err = proc.communicate()
            print(f"run {n} seed {seed}: no end within {args.timeout:.0f}s; stderr ends:\n"
                  f"{err[-3000:]}", flush=True)
            bad += 1
            if args.stop_on_fail:
                break
            continue
        wall = time.time() - t0
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        record = {"seed": seed, "rc": proc.returncode, "wall_s": wall, "trace": args.trace}
        try:
            record["result"] = json.loads(lines[-1])
            record["setup"] = json.loads(lines[-2])
        except (IndexError, ValueError):
            record["stderr_tail"] = proc.stderr[-3000:]
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(record) + "\n")
        res = record.get("result")
        ok = proc.returncode == 0 and res is not None and res.get("correct")
        bad += not ok
        print(f"run {n} seed {seed} rc {proc.returncode} wall {wall:.1f}s correct "
              f"{res and res.get('correct')} checks {res and res.get('checks')}", flush=True)
        if not ok:
            print(proc.stderr[-3000:], flush=True)
            if args.stop_on_fail:
                break
        if res is None or (args.skip_first and n == 0):
            continue
        for k, v in res["metrics"].items():
            rows.setdefault(k, []).append(v["value"])
        rows.setdefault("run_wall_s", []).append(wall)
        rows.setdefault("memory_peak_bytes", []).append(res["device"].get("memory_peak_bytes"))
        rows.setdefault("window.requests", []).append(res.get("window", {}).get("requests"))
        rows.setdefault("window.reference_s", []).append(res.get("window", {}).get("reference_s"))
        parts = record["setup"]["setup_parts"]
        for k, v in list(parts.items()) + [("daemon." + k, v) for k, v in
                                            parts.get("daemon", {}).items()]:
            if isinstance(v, (int, float)):
                rows.setdefault("setup." + k, []).append(v)
    summarise(rows)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
