"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference's only "scale the big dimension" mechanism is its
relational SUMMA shuffle (SURVEY §2.6/§5); the TPU framework makes long
sequences first-class with the two standard schemes:

- **Ring attention** (`ring_attention`): q/k/v sharded on the sequence
  axis; k/v blocks rotate around the mesh ring with ``ppermute`` while
  each device accumulates its queries' online-softmax state — ICI
  transfers overlap compute, sequence length scales with the number of
  devices. Causal masking uses global block offsets.
- **Ulysses / all-to-all** (`ulysses_attention`): ``all_to_all``
  re-shards from sequence-parallel to head-parallel, runs full local
  attention per head group, and re-shards back — two collectives,
  no ring.

Both run under ``shard_map`` over a named mesh axis and are validated
against single-device attention on the virtual CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from netsdb_tpu.ops.attention import NEG_INF, _block_attn, attention_dispatch


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          scale: float):
    """Per-device body: rotate k/v around the ring, fold each arriving
    block into the online-softmax accumulator (naive XLA fold — the
    off-TPU / odd-shape fallback)."""
    n_dev = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    q = q * scale

    q_pos = (my_idx * s_local + jnp.arange(s_local))[:, None]

    def step(i, carry):
        num, den, mx, k_cur, v_cur = carry
        # rotation sends j→j+1, so after i steps device m holds the block
        # that ORIGINATED at device (m - i) % n
        src = (my_idx - i) % n_dev
        k_pos = (src * s_local + jnp.arange(s_local))[None, :]
        mask = (q_pos >= k_pos) if causal else jnp.ones(
            (s_local, s_local), jnp.bool_)
        num, den, mx = _block_attn(q, k_cur, v_cur, num, den, mx, mask)
        # rotate: pass k/v to the next device in the ring
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return num, den, mx, k_nxt, v_nxt

    # derive initial carries from q so they inherit its varying manual
    # axis (a plain zeros() is axis-invariant and fails scan's carry check)
    num0 = jnp.zeros_like(q)
    den0 = jnp.zeros_like(q[..., :1])
    max0 = jnp.full_like(q[..., :1], NEG_INF)
    num, den, _, _, _ = jax.lax.fori_loop(
        0, n_dev, step, (num0, den0, max0, k, v))
    return num / jnp.maximum(den, 1e-30)


def _ring_attention_flash_local(q, k, v, axis_name: str, causal: bool,
                                scale: float):
    """Per-device ring body folding each arriving k/v chunk with the
    pallas flash-carry kernel (``ops.pallas_kernels.flash_attention_step``)
    instead of the naive XLA fold (which round-trips every step's
    (s_local, s_local) logits through HBM)."""
    from netsdb_tpu.ops.pallas_kernels import flash_attention_step

    n_dev = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    bh = b * h
    qf = q.reshape(bh, s_local, d)
    kf = k.reshape(bh, s_local, d)
    vf = v.reshape(bh, s_local, d)

    # carries derive from qf so they inherit its varying manual axis
    acc0 = jnp.zeros_like(qf, dtype=jnp.float32)
    pad = jnp.zeros((128,), jnp.float32)
    l0 = jnp.zeros_like(qf[:, :, :1], dtype=jnp.float32) + pad
    m0 = jnp.full_like(qf[:, :, :1], NEG_INF, dtype=jnp.float32) + pad

    def step(i, carry):
        acc, l, m, k_cur, v_cur = carry
        src = (my_idx - i) % n_dev
        acc, l, m = flash_attention_step(
            qf, k_cur, v_cur, acc, l, m,
            q_offset=my_idx * s_local, k_offset=src * s_local,
            causal=causal, scale=scale)
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, l, m, k_nxt, v_nxt

    acc, l, _, _, _ = jax.lax.fori_loop(
        0, n_dev, step, (acc0, l0, m0, kf, vf))
    out = acc / jnp.maximum(l[:, :, :1], 1e-30)
    return out.astype(q.dtype).reshape(b, h, s_local, d)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis: str = "data", causal: bool = True,
                   scale: Optional[float] = None,
                   impl: Optional[str] = None) -> jax.Array:
    """q/k/v (B, H, S, D) sequence-sharded over ``axis``; returns the
    exact attention output with the same sharding.

    ``impl``: None auto-selects — the pallas flash-carry fold on TPU
    when the local chunk is lane-aligned, the naive XLA fold otherwise;
    'flash' / 'naive' force a path.
    """
    from netsdb_tpu.ops.common import on_tpu

    d = q.shape[-1]
    s_local = q.shape[2] // mesh.shape[axis]
    scale = scale if scale is not None else d ** -0.5
    if impl is None:
        impl = ("flash" if on_tpu() and s_local % 128 == 0 and d % 128 == 0
                else "naive")
    body = (_ring_attention_flash_local if impl == "flash"
            else _ring_attention_local)
    spec = P(None, None, axis, None)
    # the flash body feeds device-varying ring offsets into the pallas
    # kernel as an operand, which the static varying-axes inference
    # cannot type (jax suggests check_vma=False for exactly this); the
    # in/out specs still pin every array's sharding explicitly
    fn = jax.shard_map(
        functools.partial(body, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=(impl != "flash"))
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool, scale):
    """seq-sharded → all_to_all → head-sharded full attention → back."""
    n_dev = jax.lax.psum(1, axis_name)

    def seq_to_heads(t):  # (B, H, S/n, D) → (B, H/n, S, D)
        return jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(t):  # (B, H/n, S, D) → (B, H, S/n, D)
        return jax.lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # after the re-shard each device holds the FULL sequence for its
    # head group, so the local attention is where the (S, S) memory
    # blow-up would happen — dispatch picks the pallas flash kernel on
    # TPU (VMEM accumulators, no (S,S) in HBM), full attention on CPU;
    # out_vma tells shard_map's vma check the kernel output varies over
    # this mesh axis (pallas out_shape carries no annotation by itself)
    out = attention_dispatch(qh, kh, vh, causal=causal, scale=scale,
                             out_vma={axis_name})
    return heads_to_seq(out)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis: str = "data", causal: bool = True,
                      scale: Optional[float] = None) -> jax.Array:
    """Ulysses sequence parallelism: heads must divide the axis size."""
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"heads {q.shape[1]} not divisible by mesh axis "
                         f"{axis}={n}")
    spec = P(None, None, axis, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
