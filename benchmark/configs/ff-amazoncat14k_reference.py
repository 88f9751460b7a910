"""Plain reference of the FF scoring deployment, and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing the program made: weights and
inputs are made again from the seed by ``datagen``'s rule, on the host, and the
forward pass is float64 NumPy, block by block over the features so that it
fits beside nothing else:

    h = relu(w1 x^T + b1);  z = wo h + bo;  log p = z - logsumexp(z)   (labels x rows)

The served answer is the softmax itself (labels x rows, float32). The number
compared is the widest gap, over the sampled rows and every label, between the
log of the served probability and the reference's log-probability.
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

FEATURE_BLOCK = 1024
THREADS = min(12, os.cpu_count() or 1)


def log_probs(cfg, seed: int, rows_by_stream) -> np.ndarray:
    """float64 log-softmax, (labels x n), for the named rows of the seeded inputs.

    ``rows_by_stream`` is a list of (stream name, global row index).
    """
    f, h, l = cfg["features"], cfg["hidden"], cfg["labels"]
    scale = cfg["data"]["scale_pow2"]
    k_w1 = datagen.stream_key(seed, "w1")
    keys = {s: datagen.stream_key(seed, s) for s, _ in rows_by_stream}

    streams = sorted({s for s, _ in rows_by_stream})
    by_stream = {s: [(n, row) for n, (t, row) in enumerate(rows_by_stream) if t == s]
                 for s in streams}

    def partial(c0: int) -> np.ndarray:
        cols = min(FEATURE_BLOCK, f - c0)
        w = datagen.matrix(np, k_w1, h, cols, scale["w1"], col0=c0, ld=f).astype(np.float64)
        x = np.empty((len(rows_by_stream), cols), np.float64)
        j = np.arange(c0, c0 + cols, dtype=np.uint32)[None, :]
        for stream, picks in by_stream.items():   # the sampled rows of one stream in one go
            i = np.array([row for _, row in picks], dtype=np.uint32)[:, None]
            x[[n for n, _ in picks]] = datagen.unit24(
                np, datagen.mix(np, i * np.uint32(f) + j, keys[stream]), scale["x"])
        return w @ x.T

    with ThreadPoolExecutor(THREADS) as pool:
        acc = sum(pool.map(partial, range(0, f, FEATURE_BLOCK)))
    b1 = datagen.matrix(np, datagen.stream_key(seed, "b1"), h, 1, scale["b1"]).astype(np.float64)
    wo = datagen.matrix(np, datagen.stream_key(seed, "wo"), l, h, scale["wo"]).astype(np.float64)
    bo = datagen.matrix(np, datagen.stream_key(seed, "bo"), l, 1, scale["bo"]).astype(np.float64)
    z = wo @ np.maximum(acc + b1, 0.0) + bo
    m = z.max(axis=0, keepdims=True)
    return z - (m + np.log(np.exp(z - m).sum(axis=0, keepdims=True)))


def check(cfg, seed: int, answers, rng) -> dict:
    """``answers``: list of (stream, first global row, served labels x rows float32).

    Returns {name: (value, limit)}; the run is correct when every value <= its limit.
    """
    l = cfg["labels"]
    picks = []  # (answer index, column)
    for a, (_, _, served) in enumerate(answers):
        if served.shape[0] != l:
            return {"answer_shape_wrong": (1.0, 0.0)}
        n = min(cfg["check"]["rows_per_answer"], served.shape[1])
        picks += [(a, int(c)) for c in sorted(rng.choice(served.shape[1], n, replace=False))]
    if not picks:
        return {"answers_missing": (1.0, 0.0)}
    want = log_probs(cfg, seed, [(answers[a][0], answers[a][1] + c) for a, c in picks])
    got = np.stack([answers[a][2][:, c] for a, c in picks], axis=1).astype(np.float64)
    bad = ~np.isfinite(got) | (got <= 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(np.log(got) - want)
    gap[bad] = np.inf
    return {"logit_gap_max": (float(gap.max()), float(cfg["check"]["logit_gap_max"]))}
