"""The session-served softmax-routed sparse-expert language model: how it is filled; its callers
are ``lm_sessions_moe``'s.

A configuration of ``model_type`` mellum (``configs/mellum2-12b-a2.5b-8l.json``): sliding-window
and YaRN-scaled full attention mixed by layer, grouped-query heads, every layer's feed-forward
64 softmax-routed experts of which a token takes 8, no shared expert, plain pre-norm, behind the
program's one block module (``netsdb_tpu/models/hybrid_lm.py``, the same decode kind).
``spec_of`` reads the model's published keys and the serving sizes beside them into that
module's spec; ``fill`` makes every weight set on the device from the seed by ``datagen``'s rule
(matrices rounded to bfloat16; norm gains ``1 + u/8`` in float32) and writes the spec into the
database.

``Ops`` is ``lm_sessions_moe.Ops`` (that file loaded by its path, nothing of it edited): the
same request kind ``turn``, warm-up and client counters, with ids drawn over the whole
vocabulary in another way. ``datagen.scaled`` multiplies a field of the hash by the vocabulary
in 32 bits, which wraps once ``bits + log2(vocab)`` reaches 32: with 16 bits over 98,304 ids it
wraps, and with 15 bits only every third id can be drawn. So an id here is the whole 32-bit
hash times 98,304, shifted down by 32, in 64-bit arithmetic: every id can be drawn, none twice
as often as another but for one in 43,690.
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

lm_sessions_moe = load_module(os.path.join(HERE, "lm_sessions_moe.py"),
                              "bench_deployment_lm_sessions_moe")

DB = lm_sessions_moe.DB
KIND = lm_sessions_moe.KIND
VECTORS = {"norm_pre_mix", "norm_pre_ffn", "final_norm", "q_norm", "k_norm"}


def spec_of(cfg):
    from netsdb_tpu.models import hybrid_lm

    first = cfg.get("first_layer", 0)
    end = first + cfg["num_hidden_layers"]
    if any(mlp != "sparse" for mlp in cfg["mlp_layer_types"][first:end]):
        raise ValueError("every layer built must be an expert layer")
    return hybrid_lm.make_spec(
        layer_types=cfg["layer_types"][first:end],
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        lin_heads=0, lin_dk=0, lin_dv=0, eps=cfg["rms_norm_eps"], slots=cfg["slots"],
        cache_tokens=cfg["cache_tokens"], prefill_chunks=cfg["prefill_chunks"],
        delta_chunk=1, dtype=cfg["dtype"], xla_options=cfg.get("xla_options"),
        kv_heads=cfg["num_key_value_heads"], window=cfg["sliding_window"],
        qk_norm="head", norm_place="pre", rope=cfg["rope_parameters"], dense_layers=0,
        moe={"experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
             "intermediate": cfg["moe_intermediate_size"], "scoring": "softmax",
             "shared": 0, "first": 0, "held": cfg["num_experts"]})


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
        return 1.0 + datagen.matrix(jnp, key, shape[0], shape[1], 0) * jnp.float32(0.125)

    t0 = time.time()
    hybrid_lm.deploy(library, DB, spec, weights)
    jax.block_until_ready(library.get_tensor(DB, "lm_head").data)
    return {"weights_made_s": time.time() - t0}


class Ops(lm_sessions_moe.Ops):
    """``lm_sessions_moe.Ops`` with ids over the whole vocabulary by a 64-bit multiply-shift."""

    def _ids(self, key: int, n: int) -> np.ndarray:
        h = datagen.mix(np, np.arange(n, dtype=np.uint32), key).astype(np.uint64)
        return ((h * np.uint64(self.vocab)) >> np.uint64(32)).astype(np.int32)
