"""Shared op helpers: dtype policy and padding-mask maintenance.

Invariant maintained by every op in this package: a ``BlockedTensor``'s
padded margin is ZERO. Ops whose elementwise function does not map 0→0
(sigmoid, exp, softmax) re-mask their output; masked reductions use ±inf
neutral fills. This replaces the reference's ragged last blocks
(``src/FF/headers/FFMatrixBlock.h:79-87``) — XLA needs static shapes, so
we pad and mask instead.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from netsdb_tpu.core.blocked import BlockedTensor


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether a pallas kernel must run in interpret mode: compiled on
    a TPU backend, interpreted on the CPU (tests), an error anywhere
    else — interpreting on an unrecognised accelerator would hide the
    device behind a slow emulation."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas kernels here target TPU (Mosaic); backend {backend!r} "
        f"is neither 'tpu' nor 'cpu' (interpret mode)")


def mxu_dot(a: jax.Array, b: jax.Array, compute_dtype: Optional[str] = None,
            accum_dtype=jnp.float32) -> jax.Array:
    """Matmul routed onto the MXU, accumulating in ``accum_dtype``
    (f32 unless the caller overrides it — e.g. the FF inference chain
    keeps hidden activations in bf16 to halve their HBM traffic).

    ``compute_dtype=None`` means full input-dtype accuracy: on TPU the
    MXU's DEFAULT precision decomposes f32 into single-pass bfloat16,
    which loses ~3 decimal digits — far from the reference's f64 Eigen
    results — so we force HIGHEST (multi-pass) unless the caller opts
    into reduced precision by setting ``compute_dtype='bfloat16'``."""
    if compute_dtype is not None:
        a = a.astype(compute_dtype)
        b = b.astype(compute_dtype)
        precision = jax.lax.Precision.DEFAULT
    else:
        precision = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=accum_dtype,
        precision=precision,
    )


def remask(t: BlockedTensor) -> BlockedTensor:
    """Zero the padded margin (needed after non-zero-preserving ops)."""
    if not t.meta.is_padded:
        return t
    return t.with_data(t.data * t.mask(t.data.dtype))


def neutral_fill(t: BlockedTensor, fill: float) -> jax.Array:
    """Padded data with the margin replaced by ``fill`` (for max/min
    reductions where zero is not neutral)."""
    if not t.meta.is_padded:
        return t.data
    m = t.mask(jnp.bool_)
    return jnp.where(m, t.data, jnp.asarray(fill, t.data.dtype))
