"""Mixture-of-experts layer with expert parallelism.

No reference analogue (netsDB has no experts, SURVEY §2.6 row
"TP/SP/EP … absent"); added so the framework's parallelism classification is
complete. Top-1 token routing with a capacity limit, the classic
dispatch/combine einsum formulation: dispatch (tokens→expert slots) and
combine (expert outputs→tokens) are one-hot tensors, so expert compute
is dense batched matmuls on the MXU, and sharding the EXPERT dimension
over a mesh axis makes XLA insert the token all-to-alls — expert
parallelism without hand-written routing collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_HI = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoEParams:
    w_gate: jax.Array  # (d, n_experts)
    w_up: jax.Array    # (n_experts, d, hidden)
    w_down: jax.Array  # (n_experts, hidden, d)


def init_moe_params(d: int, hidden: int, n_experts: int,
                    seed: int = 0) -> MoEParams:
    rng = np.random.default_rng(seed)
    return MoEParams(
        w_gate=jnp.asarray(rng.standard_normal((d, n_experts)),
                           jnp.float32) * d ** -0.5,
        w_up=jnp.asarray(rng.standard_normal((n_experts, d, hidden)),
                         jnp.float32) * d ** -0.5,
        w_down=jnp.asarray(rng.standard_normal((n_experts, hidden, d)),
                           jnp.float32) * hidden ** -0.5,
    )


def moe_forward(params: MoEParams, x: jax.Array,
                capacity_factor: float = 2.0,
                mesh: Optional[Mesh] = None,
                expert_axis: str = "model") -> jax.Array:
    """x: (tokens, d) → (tokens, d). Tokens over an expert's capacity are
    dropped (standard top-1 switch behavior). With ``mesh``, expert-dim
    tensors are sharding-constrained to ``expert_axis`` (EP)."""
    tokens, d = x.shape
    n_experts = params.w_gate.shape[1]
    capacity = max(1, int(capacity_factor * tokens / n_experts))

    logits = jnp.einsum("td,de->te", x, params.w_gate, precision=_HI)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)            # (tokens,)
    gate = jnp.max(probs, axis=-1)                     # (tokens,)

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)
    position = jnp.cumsum(onehot, axis=0) * onehot - 1  # (tokens, experts)
    pos_in_expert = position.max(axis=-1)
    keep = pos_in_expert < capacity

    # dispatch: (tokens, experts, capacity) one-hot
    dispatch = (jax.nn.one_hot(expert_idx, n_experts, dtype=x.dtype)[:, :, None]
                * jax.nn.one_hot(pos_in_expert, capacity, dtype=x.dtype)[:, None, :])
    dispatch = dispatch * keep[:, None, None].astype(x.dtype)
    combine = dispatch * gate[:, None, None].astype(x.dtype)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, x, precision=_HI)
    if mesh is not None:
        spec = NamedSharding(mesh, P(expert_axis, None, None))
        expert_in = jax.lax.with_sharding_constraint(expert_in, spec)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, params.w_up,
                               precision=_HI))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params.w_down, precision=_HI)
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(expert_axis, None, None)))
    return jnp.einsum("tec,ecd->td", combine, expert_out, precision=_HI)


def moe_forward_dense_oracle(params: MoEParams, x: jax.Array,
                             capacity_factor: float = 2.0) -> jax.Array:
    """Reference implementation: loop over tokens in Python — used only
    by tests to validate routing/capacity semantics."""
    tokens, d = x.shape
    n_experts = params.w_gate.shape[1]
    capacity = max(1, int(capacity_factor * tokens / n_experts))
    logits = np.asarray(x @ params.w_gate)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    out = np.zeros_like(np.asarray(x))
    counts = np.zeros(n_experts, np.int64)
    for t in range(tokens):
        e = int(probs[t].argmax())
        if counts[e] >= capacity:
            counts[e] += 1  # token dropped (position past capacity)
            continue
        counts[e] += 1
        h = np.asarray(jax.nn.gelu(jnp.asarray(
            np.asarray(x[t]) @ np.asarray(params.w_up[e]))))
        y = h @ np.asarray(params.w_down[e])
        out[t] = probs[t, e] * y
    return jnp.asarray(out)
