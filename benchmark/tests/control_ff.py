#!/usr/bin/env python3
"""The FF cells' control: the plain forward pass put in the program's place, in lower precision.

The configuration states float32 at ``Precision.HIGHEST``. The nearest precision
below is ``HIGH`` (three bfloat16 passes). This script makes the deployment's
weights and one stored set on the device from the seed, computes the forward
pass with ``jax.numpy`` at each precision asked for, and hands the softmax to
the same ``check`` that decides a run's ``correct``. On the chip at the cell's
own size it is run by hand (``chiprun -- python3 benchmark/tests/control_ff.py
--seeds 11 12 13``); ``test_harness.py`` runs it at the rehearsal size.

Prints one JSON line per seed and precision: the number compared and its limit.
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


def split_bfloat16(jnp, a, pieces):
    """float32 -> ``pieces`` bfloat16 arrays whose sum approaches it (how the TPU feeds its MXU)."""
    out = []
    for _ in range(pieces):
        hi = a.astype(jnp.bfloat16)
        out.append(hi)
        a = a - hi.astype(jnp.float32)
    return out


def matmul_in_passes(jnp, a, b, passes):
    """A float32 product from bfloat16 passes with float32 accumulation: 6 is the TPU's
    HIGHEST (three pieces an operand), 3 its HIGH (two pieces, the low x low pass dropped).
    For a machine whose float32 product is exact whatever precision is asked, such as the CPU."""
    pa, pb = split_bfloat16(jnp, a, 3 if passes == 6 else 2), split_bfloat16(
        jnp, b, 3 if passes == 6 else 2)
    pairs = [(0, 0), (0, 1), (1, 0), (1, 1), (0, 2), (2, 0)] if passes == 6 else [(0, 0), (0, 1), (1, 0)]
    return sum(jnp.matmul(pa[i], pb[j], preferred_element_type=jnp.float32) for i, j in pairs)


def forward(cfg, seed, stream, row0, rows, precision, emulate_passes=None):
    """softmax(wo relu(w1 x^T + b1) + bo), labels x rows, float32 on the default device."""
    import jax
    import jax.numpy as jnp

    import datagen

    def product(a, b):
        if emulate_passes:
            return matmul_in_passes(jnp, a, b, emulate_passes)
        return jnp.matmul(a, b, precision=precision)

    f, h, l = cfg["features"], cfg["hidden"], cfg["labels"]
    sc = cfg["data"]["scale_pow2"]

    def mat(name, r, c, scale, r0=0):
        return datagen.matrix(jnp, jnp.uint32(datagen.stream_key(seed, name)), r, c, scale,
                              row0=jnp.uint32(r0), ld=c)

    @jax.jit
    def run():
        x = mat(stream, rows, f, sc["x"], row0)
        hid = jnp.maximum(product(mat("w1", h, f, sc["w1"]), x.T) + mat("b1", h, 1, sc["b1"]), 0.0)
        z = product(mat("wo", l, h, sc["wo"]), hid) + mat("bo", l, 1, sc["bo"])
        return jax.nn.softmax(z, axis=0)

    return run()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(BENCH, "configs", "ff-amazoncat14k.json"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12, 13])
    ap.add_argument("--precisions", nargs="+", default=["high"])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cfg = load_json(args.config)
    import jax

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        cfg.update(cfg["rehearsal"])
    import numpy as np

    ref = load_module(os.path.join(os.path.dirname(args.config), cfg["reference"]), "ff_reference")
    platform = jax.devices()[0].platform
    for seed in args.seeds:
        for name in args.precisions:
            # the CPU's float32 product is the same at every precision: there HIGH is made
            # of its three bfloat16 passes by hand
            emulate = {"high": 3, "default": 1}.get(name) if platform == "cpu" else None
            if emulate == 1:
                raise SystemExit("one bfloat16 pass is not emulated on the CPU")
            served = np.asarray(forward(cfg, seed, "x", 0, cfg["stored_rows"],
                                        getattr(jax.lax.Precision, name.upper()), emulate))
            numbers = ref.check(cfg, seed, [("x", 0, served)], np.random.default_rng(seed))
            value, limit = numbers["logit_gap_max"]
            print(json.dumps({"seed": seed, "precision": name, "platform": platform,
                              "logit_gap_max": value, "limit": limit,
                              "correct": bool(value <= limit)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
