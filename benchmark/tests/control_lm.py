#!/usr/bin/env python3
"""The language-model cell's control: the plain reference put in the program's place, in lower precision.

The configuration states bfloat16 operands with float32 accumulation and a float32
recurrent state. The nearest precision below keeps the operands and rounds every
matrix product's result and the recurrent state after every token to bfloat16
(what ``preferred_element_type=bfloat16`` and a bfloat16 state slab would give).
This script makes a session's history from the seed, runs the reference over it
with those two roundings switched on (``ROUND_PRODUCT``, ``ROUND_STATE``), and hands
the logits to the same ``check`` that decides a run's ``correct``, which compares
them with the reference as it stands. It is NumPy on the host at the cell's own
widths (the device plays no part in it); ``test_lm_cells.py`` runs it at the
rehearsal size.

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
    ap.add_argument("--config", default=os.path.join(BENCH, "configs", "olmo-hybrid-7b-16l.json"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12, 13])
    ap.add_argument("--tokens", type=int, nargs="+", default=[2880],
                    help="history lengths of the sessions handed to the check, which draws as "
                         "many of them as the configuration checks in a run (one at full size); "
                         "a session past its first turn holds 1,200 to 4,096 tokens")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cfg = load_json(args.config)
    if args.rehearse_cpu:
        cfg.update(cfg["rehearsal"])
    import numpy as np

    import datagen

    path = os.path.join(os.path.dirname(args.config), cfg["reference"])
    ref = load_module(path, "lm_reference")
    low = load_module(path, "lm_reference_low")
    low.ROUND_PRODUCT = low.ROUND_STATE = low.to_bfloat16
    for seed in args.seeds:
        t0 = time.time()
        prompts = []
        for n, length in enumerate(args.tokens):
            h = datagen.mix(np, np.arange(length, dtype=np.uint32),
                            datagen.stream_key(seed, f"control{n}"))
            prompts.append(datagen.scaled(np, h, 8, 24, cfg["vocab_size"]).astype(np.int64))
        logits = low.forward(
            cfg, lambda name, shape, rows=None: low.weight(cfg, seed, name, shape, rows),
            prompts, [1] * len(prompts))
        answers = []
        for prompt, row in zip(prompts, logits):
            chosen = int(np.argmax(row[-1]))
            # a session past its first turn, as the window's answers are: history, the last
            # frame's ids, its last logits, the turns so far
            answers.append((prompt.tolist() + [chosen], [chosen], row[-1].astype(np.float32), 2))
        numbers = ref.check(cfg, seed, answers, np.random.default_rng(seed))
        print(json.dumps({"seed": seed, "precision": "bfloat16 products and state",
                          "tokens": args.tokens, "seconds": round(time.time() - t0, 1),
                          "numbers": {k: list(v) for k, v in numbers.items()},
                          "correct": all(v <= lim for v, lim in numbers.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
