"""The gated delta rule's one-token update as a kernel
(``ops/delta_rule.py::gated_delta_step_flat``), in interpret mode on the
CPU, against the rule's other forms. Shapes are ``(B, H, dk, dv)``: the
tier-1 model's, the published head shape, and one the kernel does not
take (``H dv`` is no multiple of 128 lanes), which must fall back to
the XLA form and still equal. The last test compiles the kernel at the
published widths for a v5e that is described, not attached: what
interpret mode cannot refuse (tiling, VMEM), the chip's compiler does;
beside it the whole step program of the benchmark's model, with the
attention kernel of ``tests/test_decode_attention.py`` (one file holds
libtpu: a second file's fixture would skip on another worker).
"""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.models import hybrid_lm
from netsdb_tpu.ops import delta_rule

SHAPES = [(3, 4, 16, 32), (2, 30, 96, 192), (3, 3, 16, 24)]
TOKENS = 8


def _inputs(rng, shape, tokens):
    b, h, dk, dv = shape
    q = rng.standard_normal((tokens, b, h, dk)).astype(np.float32)
    k = rng.standard_normal((tokens, b, h, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((tokens, b, h, dv)).astype(np.float32)
    log_alpha = (-np.exp(rng.uniform(-3, 1, (tokens, b, h))) * 0.3
                 ).astype(np.float32)
    beta = rng.uniform(0, 2, (tokens, b, h)).astype(np.float32)
    s0 = rng.standard_normal((b, h, dk, dv)).astype(np.float32)
    return s0, q, k, v, log_alpha, beta


def _flat(S):
    return jnp.stack([delta_rule.heads_on_lanes(s) for s in S])


def _heads(S_flat, h):
    return np.stack([np.asarray(delta_rule.heads_first(s, h))
                     for s in S_flat])


def _fused_layers(shape):
    """What a step program built for a one-linear-layer model of this
    head shape reports (traced, never run)."""
    _, h, dk, dv = shape
    spec = hybrid_lm.make_spec(
        layer_types=[hybrid_lm.LINEAR], hidden=32, intermediate=64,
        vocab=64, heads=2, head_dim=16, lin_heads=h, lin_dk=dk, lin_dv=dv,
        slots=2, cache_tokens=64, prefill_chunks=(64,), dtype="float32")
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, (s, _) in hybrid_lm.weight_shapes(spec).items()}
    slab = {n: jax.ShapeDtypeStruct(e["shape"], jnp.dtype(e["dtype"]))
            for n, e in hybrid_lm.state_layout(spec).items()}
    gauge = obs.REGISTRY.gauge("decode.gdn_step.fused_layers")
    gauge.set(-1)
    jax.eval_shape(hybrid_lm.build_step(spec), params, slab,
                   jax.ShapeDtypeStruct((2,), jnp.bool_))
    assert obs.REGISTRY.snapshot()["gauges"][
        "decode.gdn_step.fused_layers"] == gauge.value
    return gauge.value


@pytest.mark.parametrize("shape", SHAPES,
                         ids=["tier1", "published", "fallback"])
def test_the_kernel_path_equals_the_rules_other_forms(shape):
    b, h, dk, dv = shape
    fits = delta_rule.step_kernel_fits(dk, h * dv)
    assert fits == (shape != SHAPES[-1])
    assert _fused_layers(shape) == (1 if fits else 0)
    s0, q, k, v, la, beta = _inputs(np.random.default_rng(dk), shape, TOKENS)
    step = jax.jit(delta_rule.gated_delta_step_flat)

    # one token: the update as written, and the XLA form
    S1, o1 = step(_flat(s0), q[0], k[0], v[0], la[0], beta[0])
    S_w, o_w = delta_rule.gated_delta_step(s0, q[0], k[0], v[0], la[0],
                                           beta[0])
    S_x, o_x = delta_rule.gated_delta_step_flat_xla(
        _flat(s0), q[0], k[0], v[0], la[0], beta[0])
    scale = np.sqrt(dk)          # sums of dk products of O(1) terms
    np.testing.assert_allclose(_heads(S1, h), np.asarray(S_w), atol=2e-6)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o_w),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S_x), atol=2e-6)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o_x),
                               atol=1e-6 * scale)

    # eight tokens: the token-by-token scan, a sequence a row
    S, outs = _flat(s0), []
    for t in range(TOKENS):
        S, o = step(S, q[t], k[t], v[t], la[t], beta[t])
        outs.append(np.asarray(o))
    outs = np.stack(outs)
    for row in range(b):
        S_r, o_r = delta_rule.gated_delta_recurrent(
            s0[row], q[:, row], k[:, row], v[:, row], la[:, row],
            beta[:, row])
        np.testing.assert_allclose(_heads(S[row:row + 1], h)[0],
                                   np.asarray(S_r), atol=1e-5)
        np.testing.assert_allclose(outs[:, row], np.asarray(o_r),
                                   atol=2e-6 * scale)

    # a row with beta = 0 and log_alpha = 0 keeps its state, bit for bit
    la0, beta0 = la[0].copy(), beta[0].copy()
    la0[1], beta0[1] = 0.0, 0.0
    S_m, _ = step(_flat(s0), q[0], k[0], v[0], la0, beta0)
    np.testing.assert_array_equal(np.asarray(S_m)[1],
                                  np.asarray(_flat(s0))[1])
    np.testing.assert_array_equal(np.asarray(S_m)[0], np.asarray(S1)[0])

    # a row's result does not depend on the other rows
    mine = (_flat(s0), q[0], k[0], v[0], la[0], beta[0])
    o_s0, *o_rest = _inputs(np.random.default_rng(99), shape, 1)
    theirs = (_flat(o_s0),) + tuple(a[0] for a in o_rest)
    S_b, o_b = step(*[jnp.concatenate([jnp.asarray(a)[:1],
                                       jnp.asarray(o)[1:]])
                      for a, o in zip(mine, theirs)])
    np.testing.assert_array_equal(np.asarray(S_b)[0], np.asarray(S1)[0])
    np.testing.assert_array_equal(np.asarray(o_b)[0], np.asarray(o1)[0])
    assert np.abs(np.asarray(S_b)[1] - np.asarray(S1)[1]).max() > 0.1


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip; the topology is asked for only once a
    test of this file runs (one process at a time may hold libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_in_place_for_the_v5e(one_chip, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from netsdb_tpu.ops import common

    b, h, dk, dv = 16, 30, 96, 192
    state_bytes = 4 * b * dk * h * dv

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    monkeypatch.setattr(common, "pallas_interpret", lambda: False)
    # such a compile can be written to the persistent cache but not
    # read back without a chip: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            delta_rule.gated_delta_step_flat, donate_argnums=(0,)).lower(
                arg(b, dk, h * dv), arg(b, h, dk), arg(b, h, dk),
                arg(b, h, dv), arg(b, h), arg(b, h)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gdn_step" in text
    # the donated states are the kernel's aliased operand: no second
    # array of their size, no copy of one
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 8
    assert not re.search(rf"= f32\[{b},{dk},{h * dv}\]\S* copy\(", text)


def test_the_benchmarks_step_program_compiles_for_the_v5e(one_chip,
                                                          monkeypatch):
    """The decode step of ``benchmark/configs/olmo-hybrid-7b-16l.json``
    as the daemon compiles it: one ``decode_attention`` call a full
    layer and no whole pass over a cache left, one ``gdn_step`` a
    linear layer, the slab written in place."""
    from jax.experimental.compilation_cache import compilation_cache

    from netsdb_tpu.ops import common

    # the spec as the benchmark's deployment makes it from its file
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    loader = importlib.util.spec_from_file_location(
        "bench_lm_sessions", os.path.join(bench, "deployments",
                                          "lm_sessions.py"))
    deployment = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(deployment)
    with open(os.path.join(bench, "configs",
                           "olmo-hybrid-7b-16l.json")) as f:
        spec = deployment.spec_of(json.load(f))
    heads = spec["heads"]
    slots, rows = spec["slots"], hybrid_lm.cache_rows(spec)
    full = spec["layer_types"].count(hybrid_lm.FULL)
    linear = spec["layer_types"].count(hybrid_lm.LINEAR)
    assert (slots, heads, rows, full, linear) == (16, 30, 4608, 4, 12)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {n: arg(shape, spec["dtype"] if is_matrix else "float32")
              for n, (shape, is_matrix)
              in hybrid_lm.weight_shapes(spec).items()}
    layout = hybrid_lm.state_layout(spec)
    slab = {n: arg(e["shape"], e["dtype"]) for n, e in layout.items()}
    slab_bytes = sum(int(np.prod(e["shape"])) * jnp.dtype(e["dtype"]).itemsize
                     for e in layout.values())

    monkeypatch.setattr(common, "pallas_interpret", lambda: False)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            hybrid_lm.build_step(spec), donate_argnums=(1,)).lower(
                params, slab, arg((slots,), "bool")).compile(
                    compiler_options=spec["xla_options"])
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert obs.REGISTRY.gauge("decode.attn.ragged_layers").value == full
    assert obs.REGISTRY.gauge("decode.gdn_step.fused_layers").value == linear
    text = compiled.as_text()
    calls = re.findall(r'^\s*(\S+) = .*custom_call_target="tpu_custom_call"',
                       text, re.M)
    assert len([c for c in calls if "decode_attention" in c]) == full
    assert len([c for c in calls if "gdn_step" in c]) == linear
    assert len([c for c in calls if "cache_write_rows" in c]) == 2 * full
    assert len(calls) == 3 * full + linear
    # no array of a logit a cached token: the whole pass is gone
    assert f"f32[{slots},{heads},{rows}]" not in text
    # the donated slab (caches and states) is written in place
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < slab_bytes // 100


def test_the_three_bfloat16_pieces_sum_to_the_value_exactly():
    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.standard_normal(4096) * np.exp(rng.uniform(-20, 20, 4096)),
        [0.0, 1.0, -1.0, 2.0 ** -100, np.float32(1) + 2.0 ** -23,
         np.finfo(np.float32).max]]).astype(np.float32)
    pieces = jax.jit(delta_rule._bf16_pieces)(x)
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    total = sum(np.asarray(p, np.float32) for p in pieces)
    np.testing.assert_array_equal(total, x)
