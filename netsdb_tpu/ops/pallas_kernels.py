"""Pallas TPU kernels for the hot ops.

The reference hand-writes its per-block math in Eigen inside join UDFs
(``FFTransposeMult.h:80-92``); the TPU analogue of "hand-tuned inner
loop" is a pallas kernel. XLA already fuses the elementwise chains this
framework emits, so pallas is reserved for the patterns XLA cannot
schedule optimally by itself — above all attention, where the online-
softmax accumulator must live in VMEM across k-blocks instead of
round-tripping (S x S) logits through HBM.

``flash_attention`` follows the standard TPU flash pattern: grid
(batch*heads, q_blocks, k_blocks) with the k-block dimension innermost
(sequential on TPU), accumulators (m, l, acc) in VMEM scratch carried
across k iterations, causal blocks skipped entirely when fully masked.
Interpret mode on the CPU backend only (tests); see
``ops.common.pallas_interpret``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # softmax runs in the exp2 domain: one VPU
# exp2 replaces exp (which lowers to exp2 * extra multiply per element)


def _prescale_q(q, scale):
    """Fold (softmax_scale * log2 e) into q ONCE per element — outside
    the kernel, where XLA fuses it into the producing op. The fold in
    `_fold_block` then emits logits directly in the exp2 domain with no
    per-logit multiply (s^2/2 VPU ops saved; +4.6 TFLOP/s at 8k causal
    bf16 on v5e). Numerics: f32 inputs scale exactly as before (one f32
    multiply, just hoisted). bf16 inputs pay ONE extra bf16 rounding of
    the scaled q per element (~2^-9 relative) that the old post-dot f32
    multiply did not have — within the bf16 path's existing oracle
    tolerances, traded for the per-logit multiply."""
    return (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)


def _fold_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                q_start, k_start, block_q: int, block_k: int,
                causal: bool):
    """The shared online-softmax fold: combine one (q-block, k-block)
    pair into the VMEM accumulators (m, l, acc) — used verbatim by both
    the single-chip kernel and the ring-step carry kernel so their
    numerics cannot diverge. ``q_start``/``k_start`` are GLOBAL
    positions (ints or traced scalars). q must arrive PRE-SCALED by
    (softmax_scale * log2 e) — see `_prescale_q`."""

    def _compute(masked: bool):
        # dtype policy matches ops.common.mxu_dot: f32 inputs run the MXU
        # multi-pass (HIGHEST, exact); bf16 inputs are the reduced-
        # precision opt-in and ride the native bf16 path
        precision = (jax.lax.Precision.HIGHEST
                     if q_ref.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        # logits arrive directly in the exp2 domain: the WRAPPERS
        # pre-multiply q by (scale * log2 e) once per q element, so the
        # per-logit scalar multiply that used to follow this dot is
        # gone — s^2/2 VPU multiplies saved, measured +4.6 TFLOP/s at
        # 8k causal bf16 on v5e (112.2 -> 116.8)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision)
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        m_prev = m_ref[:]
        block_max = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp2(logits - m_new)
        correction = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=1, keepdims=True)
        # the P·V dot rides the MXU in the input dtype (bf16 inputs →
        # native bf16 pass; f32 inputs keep the exact path)
        pv = p.astype(v.dtype) if v.dtype == jnp.bfloat16 else p
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision)
        m_ref[:] = m_new

    if not causal:
        _compute(False)
        return
    # fully-masked blocks (first key beyond the last query) skip all
    # compute; only DIAGONAL blocks are partially masked — the bulk of
    # the lower triangle runs the unmasked path, skipping the iota
    # compare + select (measured +2.5 TFLOP/s at 8k on v5e)
    live = q_start + block_q - 1 >= k_start
    diag = live & (q_start < k_start + block_k - 1)

    @pl.when(diag)
    def _compute_diag():
        _compute(True)

    @pl.when(live & ~diag)
    def _compute_full():
        _compute(False)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, causal: bool,
                  num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _fold_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                qi * block_q, ki * block_k, block_q, block_k, causal)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
            o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    out_vma=None) -> jax.Array:
    """Fused attention: q/k/v (B, H, S, D) → (B, H, S, D). Numerically
    equivalent to ``ops.attention.attention``; never materializes the
    (S, S) score matrix in HBM.

    Default 1024x1024 blocks measured fastest on v5e at D=128: 116.4
    TFLOP/s useful (causal-halved) @8k bf16 after the exp2-domain
    softmax with q PRE-SCALED by (scale*log2e) outside the kernel
    (r3: kills the per-logit scalar multiply, +4.6 TFLOP/s),
    native-bf16 P·V pass, and diagonal-only masking — a full sweep of
    other block shapes all measured slower (512x1024: 97.7, 2048x512:
    65.2; 2048-square exceeds VMEM). jax's own reference TPU flash
    kernel (TUNED BlockSizes — its defaults are ~7x slower) measures
    119.6 at 8k and 99.6 at 4k, where this kernel now reads 100.6 —
    parity to +1%: the remaining 8k gap (~3%) and the ~60% MFU cap are
    the v5e VPU softmax chain that cannot overlap the two MXU passes.
    A triangular-grid variant that schedules only lower-triangle
    blocks measured the same — dead blocks were already free — and was
    removed."""
    import math

    b, h, s, d = q.shape
    # shrink defaulted blocks to divisors of s (gcd keeps the largest
    # power-of-two factor, so e.g. s=2560 with the 1024 default runs
    # 512-blocks); an explicitly tuned block that does not divide s is
    # a caller mistake — warn rather than silently run a slower tile
    explicit_q, explicit_k = block_q is not None, block_k is not None
    block_q = block_q if explicit_q else 1024
    block_k = block_k if explicit_k else 1024
    gq, gk = math.gcd(min(block_q, s), s), math.gcd(min(block_k, s), s)
    changed = [f"block_q {block_q}->{gq}"] if explicit_q and gq != block_q else []
    if explicit_k and gk != block_k:
        changed.append(f"block_k {block_k}->{gk}")
    if changed:
        import warnings

        warnings.warn(
            f"flash_attention: explicitly requested block size does not "
            f"divide seq {s}; falling back ({', '.join(changed)})",
            stacklevel=2)
    block_q, block_k = gq, gk
    if block_q < 8 or block_k < 8:
        raise ValueError(
            f"seq {s} shares no usable block size with requested blocks "
            f"(gcd gives {block_q}, {block_k}; need >= 8 sublanes)")
    if interpret is None:
        from netsdb_tpu.ops.common import pallas_interpret

        interpret = pallas_interpret()
    scale = scale if scale is not None else d ** -0.5
    bh = b * h
    qf = _prescale_q(q.reshape(bh, s, d), scale)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    num_q = s // block_q
    num_k = s // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        num_k_blocks=num_k)

    out_shape = (jax.ShapeDtypeStruct((bh, s, d), q.dtype,
                                      vma=frozenset(out_vma))
                 if out_vma else
                 jax.ShapeDtypeStruct((bh, s, d), q.dtype))
    scratch = [
        _vmem((block_q, 1), jnp.float32),   # running max m
        _vmem((block_q, 1), jnp.float32),   # running denom l
        _vmem((block_q, d), jnp.float32),   # running numerator acc
    ]
    out = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, qi, ki: (b_, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, qi, ki: (b_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, qi, ki: (b_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, qi, ki: (b_, qi, 0)),
        # inside a shard_map manual region, shard_map's vma check needs
        # to know which mesh axes the output varies over — callers there
        # pass out_vma={axis_name} (see parallel.ring ulysses path)
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        # bh and q-blocks are independent; only the k dimension carries
        # the online-softmax state — tell Mosaic so it can pipeline
        compiler_params=None if interpret else _tpu_params(
            ("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def _tpu_params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


# ------------------------------------------------------- ring-step kernel

def _flash_carry_kernel(off_ref, q_ref, k_ref, v_ref,
                        acc_in_ref, l_in_ref, m_in_ref,
                        acc_out_ref, l_out_ref, m_out_ref,
                        m_s, l_s, acc_s, *,
                        block_q: int, block_k: int, causal: bool,
                        num_k_blocks: int):
    """One ring-attention step: fold a rotating k/v chunk into the
    online-softmax carry (acc, l, m), all in VMEM across this chunk's
    k-blocks. Positions are GLOBAL: ``off_ref`` holds (q_offset,
    k_offset) — traced per-device values inside shard_map, which is why
    they arrive as an operand instead of compile-time constants.

    Carry convention: m and l live in the exp2 domain (pre-scaled by
    log2 e), matching :func:`_flash_kernel`; the caller finalizes with
    ``acc / l`` after the last step. m/l arrays are lane-padded to 128
    with only lane 0 meaningful (TPU blocks need a full lane dim)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = off_ref[0, 0]
    k_off = off_ref[0, 1]

    @pl.when(ki == 0)
    def _init():
        m_s[:] = m_in_ref[0][:, :1]
        l_s[:] = l_in_ref[0][:, :1]
        acc_s[:] = acc_in_ref[0]

    _fold_block(q_ref, k_ref, v_ref, m_s, l_s, acc_s,
                q_off + qi * block_q, k_off + ki * block_k,
                block_q, block_k, causal)

    @pl.when(ki == num_k_blocks - 1)
    def _write():
        acc_out_ref[0] = acc_s[:]
        l_out_ref[0] = jnp.broadcast_to(l_s[:], l_out_ref[0].shape)
        m_out_ref[0] = jnp.broadcast_to(m_s[:], m_out_ref[0].shape)


def flash_attention_step(q: jax.Array, k: jax.Array, v: jax.Array,
                         acc: jax.Array, l: jax.Array, m: jax.Array,
                         q_offset, k_offset,
                         causal: bool = True,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         out_vma=None):
    """Fold one k/v chunk into a running flash accumulator — the pallas
    ring-attention step (:mod:`netsdb_tpu.parallel.ring` rotates k/v
    with ppermute and calls this per arriving chunk, replacing the
    naive ``_block_attn`` fold the round-1 ring used).

    q (bh, s_q, d); k/v (bh, s_k, d); acc (bh, s_q, d) f32;
    l/m (bh, s_q, 128) f32 lane-padded (lane 0 meaningful).
    Returns updated (acc, l, m). Finalize with
    ``acc / max(l[..., :1], tiny)`` after the last chunk.
    """
    import math

    bh, s_q, d = q.shape
    s_k = k.shape[1]
    if interpret is None:
        from netsdb_tpu.ops.common import pallas_interpret

        interpret = pallas_interpret()
    scale = scale if scale is not None else d ** -0.5
    q = _prescale_q(q, scale)
    block_q = math.gcd(1024, s_q)
    block_k = math.gcd(1024, s_k)
    num_q = s_q // block_q
    num_k = s_k // block_k
    off = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                     jnp.asarray(k_offset, jnp.int32)]).reshape(1, 2)

    kernel = functools.partial(
        _flash_carry_kernel, block_q=block_q, block_k=block_k,
        causal=causal, num_k_blocks=num_k)

    def shp(arr):
        if out_vma:
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype,
                                        vma=frozenset(out_vma))
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    qspec = pl.BlockSpec((1, block_q, d), lambda b_, qi, ki: (b_, qi, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b_, qi, ki: (b_, ki, 0))
    lspec = pl.BlockSpec((1, block_q, 128), lambda b_, qi, ki: (b_, qi, 0))
    acc2, l2, m2 = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[pl.BlockSpec((1, 2), lambda b_, qi, ki: (0, 0)),
                  qspec, kspec, kspec, qspec, lspec, lspec],
        out_specs=(qspec, lspec, lspec),
        out_shape=(shp(acc), shp(l), shp(m)),
        scratch_shapes=[
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(
            ("parallel", "parallel", "arbitrary")),
    )(off, q, k, v, acc, l, m)
    return acc2, l2, m2
