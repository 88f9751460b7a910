"""Model-family inference benchmarks: word2vec, LSTM, text classifier.

The reference ships these workloads (``src/word2vec/source/Word2Vec.cc``,
``src/LSTM`` + ``src/tests/source/LSTMTest.cc``,
``src/word2vec/source/TestSemanticClassifier.cc``) but publishes NO
performance numbers for them (BASELINE.md), so this module measures
both sides itself: the TPU path through this framework and the
netsDB-equivalent CPU path (numpy f64 block GEMMs — the per-worker
Eigen compute model) on this host.

Timing: device via ``utils.timing.scan_slope_seconds`` (see there);
CPU baselines by direct wall timing.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.ops.lstm import LSTMParams, lstm_cell
from netsdb_tpu.utils.timing import device_seconds


def _device_seconds(loop, *args) -> Optional[float]:
    return device_seconds(lambda n: float(loop(*args, n)))


def _cpu_median_seconds(fn, repeats: int = 3) -> float:
    """Median wall time of ``fn()`` — same median-of-repeats discipline
    as the device side, so one cold run (BLAS pool spin-up, scheduler
    hiccup) cannot inflate the published speedup."""
    fn()  # warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def bench_word2vec(vocab: int = 100_000, dim: int = 512,
                   batch: int = 65536, seed: int = 0) -> Dict[str, float]:
    """Embedding serving. TPU path = gather; CPU baseline = the
    reference's one-hot x table blocked matmul (Word2Vec.cc:19-80)."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((vocab, dim)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, vocab, batch), jnp.int32)

    @partial(jax.jit, static_argnums=2)
    def loop(t, i, n):
        def step(carry, _):
            out = jnp.take(t, (i + carry) % vocab, axis=0)
            return jnp.sum(out).astype(jnp.int32) % vocab, None
        c, _ = jax.lax.scan(step, jnp.int32(0), None, length=n)
        return c

    dev = _device_seconds(loop, table, ids)

    # CPU equivalent at reduced batch, linear in batch: one-hot matmul.
    # One (chunk, vocab) one-hot is built OUTSIDE the timed region and
    # reused for cpu_batch/chunk GEMMs — identical timed FLOPs to the
    # single (cpu_batch, vocab) matmul (GEMM cost is independent of
    # which rows are hot) at ~200 MB instead of ~1.6 GB of one-hot
    cpu_batch = 2048
    chunk = 256
    onehot = np.zeros((chunk, vocab))
    onehot[np.arange(chunk), rng.integers(0, vocab, chunk)] = 1.0
    tbl64 = np.asarray(table, np.float64)

    def onehot_matmul():
        for _ in range(cpu_batch // chunk):
            onehot @ tbl64

    cpu = _cpu_median_seconds(onehot_matmul) / cpu_batch
    out = {"vocab": vocab, "dim": dim, "batch": batch,
           "cpu_onehot_matmul_ids_per_sec": round(1.0 / cpu, 1)}
    if dev is not None:
        out["tpu_lookup_ids_per_sec"] = round(batch / dev, 1)
        out["speedup"] = round((batch / dev) * cpu, 1)
    else:
        out["below_device_noise"] = True
    return out


def bench_lstm(hidden: int = 1024, inp: int = 1024, batch: int = 1024,
               block: int = 512, seed: int = 0) -> Dict[str, float]:
    """One LSTM cell step (8 matmuls + gates — the reference's
    LSTMTest DAG) in cells/s of (hidden x batch) state updates."""
    rng = np.random.default_rng(seed)

    def bt(r, c):
        return BlockedTensor.from_dense(
            rng.standard_normal((r, c)).astype(np.float32), (block, block))

    def bias(r):
        return BlockedTensor.from_dense(
            rng.standard_normal((r, 1)).astype(np.float32) * 0.1, (block, 1))

    params = LSTMParams(
        w_i=bt(hidden, inp), w_f=bt(hidden, inp), w_c=bt(hidden, inp),
        w_o=bt(hidden, inp),
        u_i=bt(hidden, hidden), u_f=bt(hidden, hidden),
        u_c=bt(hidden, hidden), u_o=bt(hidden, hidden),
        b_i=bias(hidden), b_f=bias(hidden), b_c=bias(hidden),
        b_o=bias(hidden),
    )
    x = bt(inp, batch)
    h0 = bt(hidden, batch)
    c0 = bt(hidden, batch)

    @partial(jax.jit, static_argnums=3)
    def loop(p, xx, state, n):
        def step(carry, _):
            h, c = carry
            # x must depend on the carry: with a loop-invariant x, XLA
            # hoists the four W·x matmuls out of the scan and the
            # "cell step" measures only half its matmuls (observed as
            # 2x-over-peak throughput)
            x_t = xx.with_data(xx.data + jnp.sum(h.data) * 1e-20)
            h2, c2 = lstm_cell(p, x_t, h, c, "bfloat16")
            return (h2, c2), None
        (h, c), _ = jax.lax.scan(step, state, None, length=n)
        return jnp.sum(h.data) + jnp.sum(c.data)

    dev = _device_seconds(loop, params, x, (h0, c0))

    # CPU equivalent: same 8 GEMMs + gates in f64 numpy at reduced batch
    cpu_batch = 128
    w = {k: np.asarray(getattr(params, k).to_dense(), np.float64)
         for k in ("w_i", "w_f", "w_c", "w_o", "u_i", "u_f", "u_c", "u_o")}
    xs = rng.standard_normal((inp, cpu_batch))
    hs = rng.standard_normal((hidden, cpu_batch))

    def cpu_cell():
        for gate_w, gate_u in (("w_i", "u_i"), ("w_f", "u_f"),
                               ("w_c", "u_c"), ("w_o", "u_o")):
            z = w[gate_w] @ xs + w[gate_u] @ hs
            _ = 1.0 / (1.0 + np.exp(-z))

    cpu = _cpu_median_seconds(cpu_cell) / cpu_batch
    out = {"hidden": hidden, "input": inp, "batch": batch,
           "cpu_cell_rows_per_sec": round(1.0 / cpu, 1)}
    if dev is not None:
        out["tpu_cell_rows_per_sec"] = round(batch / dev, 1)
        out["speedup"] = round((batch / dev) * cpu, 1)
    else:
        out["below_device_noise"] = True
    return out


def bench_text_classifier(vocab: int = 50_000, dim: int = 512,
                          labels: int = 16, batch: int = 16384,
                          seed: int = 0) -> Dict[str, float]:
    """word2vec layer + SemanticClassifier FC layer
    (``TestSemanticClassifier.cc`` / ``SemanticClassifier.h``): docs/s."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((vocab, dim)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((labels, dim)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((labels,)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, vocab, batch), jnp.int32)

    @partial(jax.jit, static_argnums=4)
    def loop(t, ww, bb, i, n):
        def step(carry, _):
            feats = jnp.take(t, (i + carry) % vocab, axis=0)  # (batch, dim)
            logits = feats @ ww.T + bb
            probs = jax.nn.softmax(logits, axis=1)
            return jnp.sum(probs).astype(jnp.int32) % vocab, None
        c, _ = jax.lax.scan(step, jnp.int32(0), None, length=n)
        return c

    dev = _device_seconds(loop, table, w, b, ids)

    cpu_batch = 4096
    t64 = np.asarray(table, np.float64)
    w64 = np.asarray(w, np.float64)
    cids = rng.integers(0, vocab, cpu_batch)

    def cpu_cls():
        feats = t64[cids]
        logits = feats @ w64.T + np.asarray(b, np.float64)
        e = np.exp(logits - logits.max(1, keepdims=True))
        _ = e / e.sum(1, keepdims=True)

    cpu = _cpu_median_seconds(cpu_cls) / cpu_batch
    out = {"vocab": vocab, "dim": dim, "labels": labels, "batch": batch,
           "cpu_docs_per_sec": round(1.0 / cpu, 1)}
    if dev is not None:
        out["tpu_docs_per_sec"] = round(batch / dev, 1)
        out["speedup"] = round((batch / dev) * cpu, 1)
    else:
        out["below_device_noise"] = True
    return out


def run_model_bench(scale: float = 1.0, seed: int = 0) -> Dict[str, Dict]:
    s = lambda v: max(int(v * scale), 1)
    return {
        "word2vec": bench_word2vec(vocab=s(100_000), dim=s(512),
                                   batch=s(65536), seed=seed),
        "lstm": bench_lstm(hidden=s(1024), inp=s(1024), batch=s(1024),
                           block=min(s(512), 512), seed=seed),
        "text_classifier": bench_text_classifier(
            vocab=s(50_000), dim=s(512), labels=max(s(16), 2),
            batch=s(16384), seed=seed),
    }
