#!/usr/bin/env python3
"""The sparse-expert cell's control: the plain reference put in the program's place, in lower precision.

The configuration states bfloat16 operands with float32 accumulation. The nearest precision
below keeps the operands and rounds every matrix product's result to bfloat16 (what
``preferred_element_type=bfloat16`` would give), the router's among them. This script makes a
session's history from the seed, past the window plus a prefill chunk as every compared
session is, runs the reference over it with that rounding switched on (``ROUND_PRODUCT``), and
hands the logits to the same ``check`` that decides a run's ``correct``, which compares them
with the reference as it stands, alternatives near a routing tie included. It is NumPy on the
host at the cell's own widths (the device plays no part in it); ``test_trinity_cell.py`` runs
it at the rehearsal size.

Prints one JSON line per seed: each number compared and its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from loading import load_json, load_module  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    default=os.path.join(BENCH, "configs", "trinity-large-ep8-5l.json"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12])
    ap.add_argument("--tokens", type=int, nargs="+", default=[5400, 8192, 12288],
                    help="history lengths of the sessions handed to the check, as many at a "
                         "time as the configuration checks in a run (one at full size, so a "
                         "line a length; eight at the rehearsal's); a compared session holds "
                         "5,121 to 16,384 tokens")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cfg = load_json(args.config)
    if args.rehearse_cpu:
        cfg.update(cfg["rehearsal"])
    import numpy as np

    import datagen

    path = os.path.join(os.path.dirname(args.config), cfg["reference"])
    ref = load_module(path, "moe_reference")
    low = load_module(path, "moe_reference_low")
    low.ROUND_PRODUCT = low.to_bfloat16
    per = int(cfg["check"]["answers_checked"])
    for seed, g in ((seed, g) for seed in args.seeds for g in range(0, len(args.tokens), per)):
        t0 = time.time()
        answers = []
        for n, length in list(enumerate(args.tokens))[g:g + per]:
            h = datagen.mix(np, np.arange(length, dtype=np.uint32),
                            datagen.stream_key(seed, f"control{n}"))
            prompt = datagen.scaled(np, h, 8, 16, cfg["vocab_size"]).astype(np.int64)
            row = low.forward(
                cfg, lambda name, shape, rows=None: low.weight(cfg, seed, name, shape, rows),
                prompt, 1, every_row=False)["paths"][-1][0]
            chosen = int(np.argmax(row))
            # a session as the window's answers are: history, the last frame's ids, its last
            # logits, the turns so far
            answers.append((prompt.tolist() + [chosen], [chosen], row.astype(np.float32), 2))
        numbers = ref.check(cfg, seed, answers, np.random.default_rng(seed))
        print(json.dumps({"seed": seed, "precision": "bfloat16 products",
                          "tokens": args.tokens[g:g + per],
                          "seconds": round(time.time() - t0, 1),
                          "numbers": {k: list(v) for k, v in numbers.items()},
                          "correct": all(v <= lim for v, lim in numbers.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
