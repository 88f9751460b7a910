"""The session-served sparse-expert language model: how it is filled; its callers are ``lm_sessions``'s.

A configuration of ``model_type`` afmoe (``configs/trinity-large-ep8-5l.json``): sliding-window
and full attention mixed by layer, grouped-query heads, a sigmoid output gate, sparse experts
of which this chip holds a share, behind the program's one block module
(``netsdb_tpu/models/hybrid_lm.py``, the same decode kind). ``spec_of`` reads the model's
published keys and the serving sizes beside them into that module's spec; ``fill`` makes every
weight set on the device from the seed by ``datagen``'s rule (matrices rounded to bfloat16;
norm gains ``1 + u/8`` and the selection bias in float32) and writes the spec into the
database.

``Ops`` is ``lm_sessions.Ops`` (that file loaded by its path, nothing of it edited): the same
request kind ``turn``, the same warm-up, the same client counters and two more (below). A
prompt's ids are drawn over this deployment's slice of the vocabulary with 16 bits of the
hash, so that ``bits + log2(vocab) < 32`` and the product in ``datagen.scaled`` cannot wrap
(with 24 bits it wraps for any vocabulary over 256: PERF.md section 7, 27).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import datagen  # noqa: E402
from loading import load_module  # noqa: E402

lm_sessions = load_module(os.path.join(HERE, "lm_sessions.py"), "bench_deployment_lm_sessions")

DB = lm_sessions.DB
KIND = lm_sessions.KIND
VECTORS = {"norm_pre_mix", "norm_mix", "norm_pre_ffn", "norm_ffn", "final_norm", "q_norm",
           "k_norm", "route_bias"}


def spec_of(cfg):
    from netsdb_tpu.models import hybrid_lm

    first = cfg.get("first_layer", 0)
    return hybrid_lm.make_spec(
        layer_types=cfg["layer_types"][first:first + cfg["num_hidden_layers"]],
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        lin_heads=0, lin_dk=0, lin_dv=0, eps=cfg["rms_norm_eps"], slots=cfg["slots"],
        cache_tokens=cfg["cache_tokens"], prefill_chunks=cfg["prefill_chunks"],
        delta_chunk=1, dtype=cfg["dtype"], xla_options=cfg.get("xla_options"),
        kv_heads=cfg["num_key_value_heads"], window=cfg["sliding_window"],
        rope_theta=cfg["rope_theta"], qk_norm="head", attn_gate=True, pre_norms=True,
        embed_scale=cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else None,
        dense_layers=cfg["num_dense_layers"],
        moe={"experts": cfg["experts_routed"], "top_k": cfg["num_experts_per_tok"],
             "intermediate": cfg["moe_intermediate_size"], "route_scale": cfg["route_scale"],
             "first": cfg.get("experts_first", 0), "held": cfg["num_experts"]})


def fill(library, cfg, seed):
    """Every weight set of the model, made on the device, and the spec, into the database."""
    import jax
    import jax.numpy as jnp

    from netsdb_tpu.models import hybrid_lm

    spec = spec_of(cfg)
    scale = cfg["data"]["scale_pow2"]
    dtype = jnp.dtype(cfg["dtype"])
    programs = {}

    def weights(name, shape, is_matrix):
        leaf = name.rsplit(".", 1)[-1]
        key = jnp.uint32(datagen.stream_key(seed, name))
        if leaf not in VECTORS:
            build = (shape, scale[leaf])
            if build not in programs:   # key is an argument: one program a shape, every seed
                programs[build] = jax.jit(lambda key: datagen.matrix(
                    jnp, key, shape[0], shape[1], scale[leaf]).astype(dtype))
            return programs[build](key)
        if leaf == "route_bias":
            return datagen.matrix(jnp, key, shape[0], shape[1], scale[leaf])
        return 1.0 + datagen.matrix(jnp, key, shape[0], shape[1], 0) * jnp.float32(0.125)

    t0 = time.time()
    hybrid_lm.deploy(library, DB, spec, weights)
    jax.block_until_ready(library.get_tensor(DB, "lm_head").data)
    return {"weights_made_s": time.time() - t0}


class Ops(lm_sessions.Ops):
    """``lm_sessions.Ops`` with ids over this deployment's slice of the vocabulary, and with
    two more client counters for the work functions (``benchmark/moe_work.py``): the cache
    positions that a sliding layer's attention saw, ``lm_prefill_window_sum`` and
    ``lm_decode_window_sum``, as ``lm_*_context_sum`` count a full layer's."""

    def _ids(self, key: int, n: int) -> np.ndarray:
        h = datagen.mix(np, np.arange(n, dtype=np.uint32), key)
        return datagen.scaled(np, h, 8, 16, self.vocab).astype(np.int32)

    def issue(self, ctx, turn):
        rows = super().issue(ctx, turn)
        if turn == "warm_up":
            return rows
        # the turn's tokens lie at the end of the history: each saw the tokens before it, a
        # sliding layer the last ``sliding_window`` of them at most (its own key is the
        # window's last, and is left out as the context sums leave it out)
        n_new, end = len(ctx["last"]), len(ctx["history"])
        seen = np.minimum(np.arange(end - rows, end), self.cfg["sliding_window"] - 1)
        counters = ctx["counters"]
        counters["lm_prefill_window_sum"] = counters.get("lm_prefill_window_sum", 0) + \
            int(seen[:rows - n_new].sum())
        counters["lm_decode_window_sum"] = counters.get("lm_decode_window_sum", 0) + \
            int(seen[rows - n_new:].sum())
        return rows
