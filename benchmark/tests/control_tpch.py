#!/usr/bin/env python3
"""The TPC-H cell's control: the plain reference in the program's place, in bfloat16.

The configuration states float32. The nearest precision below is bfloat16: every
stored value and every product rounded to it (sums stay exact). This script
evaluates both queries that way from the seed and hands the answers to the same
``compare`` that decides a run's ``correct``. It is NumPy alone, so it gives the
same numbers on any machine; at the cell's own size it takes some seconds a seed
(``python3 benchmark/tests/control_tpch.py --seeds 41 42 43``); ``test_harness.py``
runs it at the rehearsal size.

Prints one JSON line per seed: each number compared and its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from loading import load_json, load_module  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(BENCH, "configs", "tpch-sf30-lineitem.json"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[41, 42, 43])
    ap.add_argument("--rehearse-cpu", action="store_true", help="the rehearsal's sizes and limits")
    args = ap.parse_args()
    cfg = load_json(args.config)
    if args.rehearse_cpu:
        cfg.update(cfg["rehearsal"])
    ref = load_module(os.path.join(os.path.dirname(args.config), cfg["reference"]),
                      "tpch_reference")
    for seed in args.seeds:
        want = ref.answers(cfg, seed)
        low = ref.answers(cfg, seed, precision="bfloat16")
        served = [{"q01": dict(low, valid=low["count"] > 0), "q06": {"revenue": [low["revenue"]]}}]
        numbers = ref.compare(cfg, want, served)
        print(json.dumps({"seed": seed, "precision": "bfloat16", "rows": cfg["rows"],
                          "numbers": {k: list(v) for k, v in numbers.items()},
                          "correct": all(v <= lim for v, lim in numbers.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
