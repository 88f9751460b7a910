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

Since PR 34 the chunked form of prefill has a kernel too
(``gated_delta_chunked`` -> ``gdn_chunk``): held here against its XLA
body and the token recurrence, and the benchmark's two prefill
programs are compiled for the described v5e like the step program.

Since PR 35 the benchmark has a second model behind the same block
module (sparse experts, sliding and full attention: ``tests/
test_afmoe_lm.py``); its step program and its 1,024-token prefill
program are compiled here for the same described chip, with the
grouped-query ring form of ``decode_attention`` and the experts' kernel
``grouped_ffn``, and the benchmark's predicate for "an operation that
reads expert matrices" is held to the kernel and to its XLA form.
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


# --- the chunked form's kernel (prefill) --------------------------------

CHUNK = 128
# (heads, L, dk, dv): the published head shape at two group sizes and
# both prefill lengths, and one small shape that fits
CHUNKED = [(2, 128, 96, 192), (5, 128, 96, 192), (2, 512, 96, 192),
           (5, 512, 96, 192), (3, 256, 16, 32)]


def _sequence(rng, heads, length, dk, dv, state=True):
    s0, q, k, v, la, beta = _inputs(rng, (1, heads, dk, dv), length)
    s0 = s0[0] if state else np.zeros_like(s0[0])
    return (s0,) + tuple(a[:, 0] for a in (q, k, v, la, beta))


_chunked = jax.jit(delta_rule.gated_delta_chunked, static_argnums=(6,))
_chunked_xla = jax.jit(delta_rule.gated_delta_chunked_xla,
                       static_argnums=(6,))
_recurrent = jax.jit(delta_rule.gated_delta_recurrent)


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=tol)


@pytest.mark.parametrize("state", [False, True], ids=["from-zero", "onto"])
@pytest.mark.parametrize("shape", CHUNKED, ids=lambda s: "x".join(map(str, s)))
def test_the_chunked_kernel_equals_its_xla_body_and_the_recurrence(shape,
                                                                   state):
    heads, length, dk, dv = shape
    assert delta_rule.chunk_kernel_fits(CHUNK, dk, dv)
    args = _sequence(np.random.default_rng(length + heads), heads, length,
                     dk, dv, state)
    got = _chunked(*args, CHUNK)
    assert got[0].shape == (heads, dk, dv)
    assert got[1].shape == (length, heads, dv)
    _close(got, _chunked_xla(*args, CHUNK), 5e-6)
    _close(got, _recurrent(*args), 2e-5)


def test_a_padded_tail_leaves_the_chunked_kernels_state_bit_for_bit():
    heads, dk, dv = 5, 96, 192
    s0, q, k, v, la, beta = _sequence(np.random.default_rng(8), heads, 512,
                                      dk, dv)
    # nothing but padding: the state comes back as it went in
    zero = np.zeros_like(la)
    S, _ = _chunked(s0, q, k, v, zero, zero, CHUNK)
    np.testing.assert_array_equal(np.asarray(S), s0)
    # two chunks of tokens, two of padding: the state of the two alone
    la[256:], beta[256:] = 0.0, 0.0
    S, O = _chunked(s0, q, k, v, la, beta, CHUNK)
    S2, O2 = _chunked(s0, q[:256], k[:256], v[:256], la[:256], beta[:256],
                      CHUNK)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S2))
    np.testing.assert_array_equal(np.asarray(O)[:256], np.asarray(O2))
    # a chunk that ends in padding: the recurrence over what counts
    la[200:], beta[200:] = 0.0, 0.0
    S, O = _chunked(s0, q, k, v, la, beta, CHUNK)
    S_r, O_r = _recurrent(s0, q[:200], k[:200], v[:200], la[:200],
                          beta[:200])
    _close((S, O[:200]), (S_r, O_r), 2e-5)


@pytest.mark.parametrize("beta_at,decay_at", [
    (1e-4, None), (2.0 - 1e-4, None), (None, -30.0), (None, -1e-6),
    (2.0 - 1e-4, -1e-6)],
    ids=["beta-near-0", "beta-near-2", "decay-near-0", "decay-near-1",
         "beta-near-2-undamped"])
def test_the_chunked_kernel_at_the_gates_edges(beta_at, decay_at):
    s0, q, k, v, la, beta = _sequence(np.random.default_rng(21), 2, 256,
                                      96, 192)
    if beta_at is not None:
        beta[:] = beta_at
    if decay_at is not None:
        la[:] = decay_at
    got = _chunked(s0, q, k, v, la, beta, CHUNK)
    assert np.isfinite(np.asarray(got[0])).all()
    assert np.isfinite(np.asarray(got[1])).all()
    want = _recurrent(s0, q, k, v, la, beta)
    scale = max(1.0, float(np.abs(np.asarray(want[0])).max()))
    _close(got, want, 3e-5 * scale)
    _close(got, _chunked_xla(s0, q, k, v, la, beta, CHUNK), 3e-5 * scale)


def test_two_calls_that_pass_the_state_equal_one_recurrent_pass():
    s0, q, k, v, la, beta = _sequence(np.random.default_rng(4), 5, 1024,
                                      96, 192)
    S, O1 = _chunked(s0, q[:512], k[:512], v[:512], la[:512], beta[:512],
                     CHUNK)
    S, O2 = _chunked(S, q[512:], k[512:], v[512:], la[512:], beta[512:],
                     CHUNK)
    S_r, O_r = _recurrent(s0, q, k, v, la, beta)
    _close((S, np.concatenate([O1, O2])), (S_r, O_r), 3e-5)


def _prefill_fused_layers(chunk, dk, dv):
    """What a prefill program built for a model of twelve linear layers
    of this head shape reports (traced, never run)."""
    spec = hybrid_lm.make_spec(
        layer_types=[hybrid_lm.LINEAR] * 12, hidden=32, intermediate=64,
        vocab=64, heads=2, head_dim=16, lin_heads=2, lin_dk=dk, lin_dv=dv,
        slots=2, cache_tokens=256, prefill_chunks=(2 * chunk,),
        delta_chunk=chunk, dtype="float32")
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, (s, _) in hybrid_lm.weight_shapes(spec).items()}
    slab = {n: jax.ShapeDtypeStruct(e["shape"], jnp.dtype(e["dtype"]))
            for n, e in hybrid_lm.state_layout(spec).items()}
    gauge = obs.REGISTRY.gauge("prefill.gdn_chunk.fused_layers")
    gauge.set(-1)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(hybrid_lm.build_prefill(spec, 2 * chunk))(
        params, slab, scalar,
        jax.ShapeDtypeStruct((2 * chunk,), jnp.int32), scalar, scalar)
    assert obs.REGISTRY.snapshot()["gauges"][
        "prefill.gdn_chunk.fused_layers"] == gauge.value
    return gauge.value, str(jaxpr)


@pytest.mark.parametrize("shape,fits", [
    ((128, 96, 192), True), ((128, 16, 32), True), ((64, 96, 192), False),
    ((256, 96, 192), False), ((128, 12, 32), False)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_nothing_but_the_shape_chooses_the_chunked_kernel(shape, fits):
    chunk, dk, dv = shape
    assert delta_rule.chunk_kernel_fits(chunk, dk, dv) == fits
    layers, jaxpr = _prefill_fused_layers(chunk, dk, dv)
    assert layers == (12 if fits else 0)
    assert jaxpr.count("gdn_chunk") == (12 if fits else 0)
    assert ("triangular_solve" in jaxpr) != fits
    if not fits:
        # the dispatcher's result is the XLA body's, bit for bit
        args = _sequence(np.random.default_rng(2), 2, 2 * chunk, dk, dv)
        got, want = _chunked(*args, chunk), _chunked_xla(*args, chunk)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


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


BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(name, path):
    loader = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(module)
    return module


def _benchmarks_model(one_chip, deployment="lm_sessions",
                      config="olmo-hybrid-7b-16l"):
    """(cfg, spec, params, slab, slab bytes) of a configuration of the
    benchmark (``benchmark/configs/olmo-hybrid-7b-16l.json``), the spec
    as the benchmark's deployment makes it from its file, the arrays as
    shapes on the described chip."""
    import sys

    sys.path.insert(0, BENCH)     # a deployment imports the harness's own
    deployment = _load("bench_" + deployment, os.path.join(
        BENCH, "deployments", deployment + ".py"))
    sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    spec = deployment.spec_of(cfg)

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
    return cfg, spec, params, slab, slab_bytes, arg


def _compile_for_the_chip(monkeypatch, program, args, options):
    from jax.experimental.compilation_cache import compilation_cache

    from netsdb_tpu.ops import common

    monkeypatch.setattr(common, "pallas_interpret", lambda: False)
    # such a compile can be written to the persistent cache but not
    # read back without a chip: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(program, donate_argnums=(1,)).lower(*args).compile(
            compiler_options=options)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _custom_calls(text):
    return re.findall(r'^\s*(\S+) = .*custom_call_target="tpu_custom_call"',
                      text, re.M)


def test_the_benchmarks_step_program_compiles_for_the_v5e(one_chip,
                                                          monkeypatch):
    """The decode step of ``benchmark/configs/olmo-hybrid-7b-16l.json``
    as the daemon compiles it: one ``decode_attention`` call a full
    layer and no whole pass over a cache left, one ``gdn_step`` a
    linear layer, the slab written in place."""
    _, spec, params, slab, slab_bytes, arg = _benchmarks_model(one_chip)
    heads = spec["heads"]
    slots, rows = spec["slots"], hybrid_lm.cache_rows(spec)
    full = spec["layer_types"].count(hybrid_lm.FULL)
    linear = spec["layer_types"].count(hybrid_lm.LINEAR)
    assert (slots, heads, rows, full, linear) == (16, 30, 4608, 4, 12)
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_step(spec),
        (params, slab, arg((slots,), "bool")), spec["xla_options"])
    assert obs.REGISTRY.gauge("decode.attn.ragged_layers").value == full
    assert obs.REGISTRY.gauge("decode.gdn_step.fused_layers").value == linear
    text = compiled.as_text()
    calls = _custom_calls(text)
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


def _no_scores_reach_hbm(text, heads, chunk, caches):
    """No loop over key blocks, and no array of a score a (query, key)
    pair: queries (or ``chunk`` times a key/value head's query heads)
    by a cache's rows or by a block of them."""
    assert not re.search(r"\bwhile\(", text)
    keys = "|".join(str(k) for t in caches
                    for k in (t, 1024, 512, 256) if t % k == 0)
    assert not re.search(
        rf"f32\[(\d+,)+({chunk}|{heads * chunk}),({keys})\]", text)


@pytest.mark.parametrize("chunk", [128, 512])
def test_the_benchmarks_prefill_programs_compile_for_the_v5e(
        one_chip, monkeypatch, chunk):
    """The two prefill programs of the same model: the chunked rule is
    one ``gdn_chunk`` call a linear layer, whose text carries a shape
    the benchmark's roofline looks for
    (``benchmark/lm_work.py::touches_chunk_solve``), a chunk's attention
    one ``prefill_attention`` call a full layer but the last (whose
    output feeds only the head), and neither a triangular solve, a loop
    nor an array of scores is left."""
    lm_work = _load("bench_lm_work", os.path.join(BENCH, "lm_work.py"))
    cfg, spec, params, slab, slab_bytes, arg = _benchmarks_model(one_chip)
    assert chunk in spec["prefill_chunks"]
    linear = spec["layer_types"].count(hybrid_lm.LINEAR)
    full = spec["layer_types"].count(hybrid_lm.FULL)
    scalar = arg((), "int32")
    gauge = obs.REGISTRY.gauge("prefill.gdn_chunk.fused_layers")
    fused = obs.REGISTRY.gauge("prefill.attn.fused_layers")
    gauge.set(-1)
    fused.set(-1)
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_prefill(spec, chunk),
        (params, slab, scalar, arg((chunk,), "int32"), scalar, scalar),
        spec["xla_options"])
    assert gauge.value == linear == 12
    assert fused.value == full == 4
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    rule = [line for line in calls if "gdn_chunk" in line]
    assert len(rule) == linear
    assert len([c for c in calls if "prefill_attention" in c]) == full - 1
    assert len(calls) == linear + full - 1
    for line in rule:
        assert lm_work.touches_chunk_solve(line, cfg)
    assert "triangular" not in text.lower()
    assert 'custom_call_target="Invert' not in text
    _no_scores_reach_hbm(text, spec["heads"], chunk,
                         [hybrid_lm.cache_rows(spec)])
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes


def _sparse_model(one_chip):
    return _benchmarks_model(one_chip, "lm_sessions_moe",
                             "trinity-large-ep8-5l")


def test_the_sparse_models_step_program_compiles_for_the_v5e(one_chip,
                                                            monkeypatch):
    """The decode step of ``benchmark/configs/trinity-large-ep8-5l.json``:
    one ``decode_attention`` call an attention layer (four over rings,
    one over the full cache), one ``grouped_ffn`` an expert layer, which
    the benchmark's predicate finds and no other call, the slab written
    in place, 8.64 GB of weights."""
    moe_work = _load("bench_moe_work", os.path.join(BENCH, "moe_work.py"))
    cfg, spec, params, slab, slab_bytes, arg = _sparse_model(one_chip)
    assert (hybrid_lm.cache_rows(spec), hybrid_lm.cache_rows(
        spec, hybrid_lm.SLIDING), spec["slots"]) == (17408, 5120, 32)
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert round(weights / 1e9, 2) == 8.64 and round(slab_bytes / 1e9,
                                                     2) == 4.97
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_step(spec),
        (params, slab, arg((32,), "bool")), spec.get("xla_options"))
    assert obs.REGISTRY.gauge("decode.attn.ragged_layers").value == 5
    text = compiled.as_text()
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = _custom_calls(text)
    assert len([c for c in names if "decode_attention" in c]) == 5
    assert len([c for c in names if "cache_write_rows" in c]) == 10
    assert len([c for c in names if "grouped_ffn" in c]) == 4
    assert len(names) == 19
    for line in lines:
        assert moe_work.touches_experts(line, cfg) == ("grouped_ffn" in line)
    # no array of a logit a cached token: no whole pass over a cache
    assert "f32[32,8,5120]" not in text and "f32[32,8,17408]" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < slab_bytes // 100


@pytest.mark.parametrize("chunk", [256, 1024])
def test_the_sparse_models_prefill_program_compiles_for_the_v5e(
        one_chip, monkeypatch, chunk):
    """Its two prefill programs: the experts' kernel on the three expert
    layers whose feed-forward a prompt needs (the last layer's feeds
    only the head), which the benchmark's predicate finds and no other
    call; a chunk's attention one ``prefill_attention`` call on the
    full layer and on three rings (the last ring's output feeds only
    the head), no loop over key blocks and no array of scores left; the
    slab written in place, and what it needs beside weights and slab
    fits the chip."""
    moe_work = _load("bench_moe_work", os.path.join(BENCH, "moe_work.py"))
    cfg, spec, params, slab, slab_bytes, arg = _sparse_model(one_chip)
    assert chunk in spec["prefill_chunks"]
    scalar = arg((), "int32")
    fused = obs.REGISTRY.gauge("prefill.attn.fused_layers")
    fused.set(-1)
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_prefill(spec, chunk),
        (params, slab, scalar, arg((chunk,), "int32"), scalar, scalar),
        spec.get("xla_options"))
    assert fused.value == len(spec["layer_types"]) == 5
    text = compiled.as_text()
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = _custom_calls(text)
    assert len([c for c in names if "grouped_ffn" in c]) == 3
    assert len([c for c in names if "prefill_attention" in c]) == 4
    assert len(names) == 7
    for line in lines:
        assert moe_work.touches_experts(line, cfg) == ("grouped_ffn" in line)
    _no_scores_reach_hbm(text, spec["heads"] // spec["kv_heads"], chunk,
                         [hybrid_lm.cache_rows(spec),
                          hybrid_lm.cache_rows(spec, hybrid_lm.SLIDING)])
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < 1 << 30


def _softmax_routed_model(one_chip):
    return _benchmarks_model(one_chip, "lm_sessions_mellum2",
                             "mellum2-12b-a2.5b-8l")


def test_the_softmax_routed_models_step_compiles_for_the_v5e(one_chip,
                                                            monkeypatch):
    """The decode step of ``benchmark/configs/mellum2-12b-a2.5b-8l.json``:
    one ``decode_attention`` call an attention layer (six rings, two full
    caches), one ``grouped_ffn`` an expert layer with experts 896 wide in
    one block (the benchmark's predicate finds those and no other call),
    the slab written in place; 7.59 GB of weights, 6.17 GB of slab."""
    import sys

    sys.path.insert(0, BENCH)     # the work module imports moe_work
    try:
        mellum2_work = _load("bench_mellum2_work",
                             os.path.join(BENCH, "mellum2_work.py"))
    finally:
        sys.path.remove(BENCH)
    cfg, spec, params, slab, slab_bytes, arg = _softmax_routed_model(one_chip)
    assert (hybrid_lm.cache_rows(spec), hybrid_lm.cache_rows(
        spec, hybrid_lm.SLIDING), spec["slots"]) == (17408, 2048, 64)
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    assert round(weights / 1e9, 2) == 7.59
    assert slab_bytes == mellum2_work.slab_bytes(cfg) + 2 * 4 * 64
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_step(spec),
        (params, slab, arg((64,), "bool")), spec.get("xla_options"))
    assert obs.REGISTRY.gauge("decode.attn.ragged_layers").value == 8
    assert obs.REGISTRY.gauge("decode.moe.kernel_layers").value == 8
    assert obs.REGISTRY.gauge("decode.attn.rope_scaled_layers").value == 2
    text = compiled.as_text()
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = _custom_calls(text)
    assert len([c for c in names if "decode_attention" in c]) == 8
    assert len([c for c in names if "cache_write_rows" in c]) == 16
    assert len([c for c in names if "grouped_ffn" in c]) == 8
    assert len(names) == 32
    for line in lines:
        assert mellum2_work.touches_experts(line, cfg) == (
            "grouped_ffn" in line)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < slab_bytes // 100


@pytest.mark.parametrize("chunk", [256, 1024])
def test_the_softmax_routed_models_prefill_compiles_for_the_v5e(
        one_chip, monkeypatch, chunk):
    """Its two prefill programs: ``grouped_ffn`` on the seven expert
    layers whose feed-forward a prompt needs and ``prefill_attention`` on
    the seven attention layers whose output does (the last layer's feed
    only the head), no loop and no array of scores left, the slab
    written in place."""
    cfg, spec, params, slab, slab_bytes, arg = _softmax_routed_model(one_chip)
    scalar = arg((), "int32")
    gauge = obs.REGISTRY.gauge("prefill.moe.kernel_layers")
    gauge.set(-1)
    compiled = _compile_for_the_chip(
        monkeypatch, hybrid_lm.build_prefill(spec, chunk),
        (params, slab, scalar, arg((chunk,), "int32"), scalar, scalar),
        spec.get("xla_options"))
    assert gauge.value == 7
    names = _custom_calls(compiled.as_text())
    assert len([c for c in names if "grouped_ffn" in c]) == 7
    assert len([c for c in names if "prefill_attention" in c]) == 7
    assert len(names) == 14
    _no_scores_reach_hbm(compiled.as_text(),
                         spec["heads"] // spec["kv_heads"], chunk,
                         [hybrid_lm.cache_rows(spec),
                          hybrid_lm.cache_rows(spec, hybrid_lm.SLIDING)])
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slab_bytes
    assert memory.temp_size_in_bytes < 1 << 30


def test_the_experts_xla_form_is_found_by_the_benchmarks_predicate(
        one_chip, monkeypatch):
    """The other side of the fallback: the grouped product in plain XLA
    at the published widths (8 tiles of 16 rows), compiled for the chip;
    the operations that read the gathered expert matrices match."""
    from netsdb_tpu.ops import experts

    moe_work = _load("bench_moe_work", os.path.join(BENCH, "moe_work.py"))
    cfg, spec, _, _, _, arg = _sparse_model(one_chip)
    d, f, held = 3072, 3072, 32

    def product(xs, slab, tile_expert, used, w_gate_up, w_down):
        del slab
        return experts.grouped_ffn_xla(xs, tile_expert, used, w_gate_up,
                                       w_down, 16)

    compiled = _compile_for_the_chip(
        monkeypatch, product,
        (arg((128, d), "float32"), arg((1,), "int32"), arg((8,), "int32"),
         arg((), "int32"), arg((held, 2, f, d), "bfloat16"),
         arg((held, d, f), "bfloat16")), None)
    found = [line for line in compiled.as_text().splitlines()
             if moe_work.touches_experts(line, cfg)]
    assert any(re.search(r"\b(fusion|convolution|dot)\(", line)
               for line in found)


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
