"""The decode step's attention as a kernel that reads each row's cache
up to the row's own length (``ops/attention.py::decode_attention``), in
interpret mode on the CPU, against ``cached_attention``: its block walk
(the same arithmetic, block for block) and its whole pass. The compile
for a described v5e is in ``tests/test_delta_rule_kernel.py``, beside
the other kernel's (one file holds libtpu).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.models import hybrid_lm
from netsdb_tpu.ops import attention
from netsdb_tpu.ops.attention import (DECODE_BLOCK, cached_attention,
                                      decode_attention,
                                      decode_attention_fits)

HEADS, BLOCKS, DIM = 3, 3, 128
TOKENS = BLOCKS * DECODE_BLOCK
# nothing, one key, a block less one, a block and one, the whole cache,
# somewhere inside the second block
RAGGED = [-1, 0, DECODE_BLOCK - 1, DECODE_BLOCK, TOKENS - 1,
          DECODE_BLOCK + 44]


def _inputs(seed, dtype, rows=len(RAGGED)):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((rows, 1, HEADS, DIM)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((rows, HEADS, TOKENS, DIM)), dtype)
    v = jnp.asarray(rng.standard_normal((rows, HEADS, TOKENS, DIM)), dtype)
    return q, k, v


# a probability enters the value product in the cache's dtype, rounded
# against the largest logit SO FAR: the whole pass and a block walk
# round a bfloat16 cache's differently (about 2^-9 of a value of O(1))
@pytest.mark.parametrize("dtype,whole_tol", [("bfloat16", 4e-3),
                                             ("float32", 2e-6)])
def test_ragged_rows_equal_cached_attention(dtype, whole_tol):
    q, k, v = _inputs(0, dtype)
    pos = jnp.asarray(RAGGED, jnp.int32)
    out = jax.jit(decode_attention)(q, k, v, pos)
    assert out.shape == (len(RAGGED), 1, HEADS, DIM)
    assert out.dtype == jnp.float32
    walk = cached_attention(q, k, v, pos[:, None], block_size=DECODE_BLOCK)
    whole = cached_attention(q, k, v, pos[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(walk), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               atol=whole_tol)
    # a row that sees nothing yields zeros; one key yields its value
    np.testing.assert_array_equal(np.asarray(out)[0], 0.0)
    np.testing.assert_allclose(
        np.asarray(out)[1, 0], np.asarray(v, np.float32)[1, :, 0],
        atol=1e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_rows_output_does_not_depend_on_the_other_rows(dtype):
    q, k, v = _inputs(1, dtype)
    q2, k2, v2 = _inputs(2, dtype)
    pos = jnp.asarray(RAGGED, jnp.int32)
    step = jax.jit(decode_attention)
    mine = np.asarray(step(q, k, v, pos))
    for row in range(len(RAGGED)):
        # the same row among other rows' queries, caches AND lengths
        others = jnp.asarray(np.roll(RAGGED, row + 1), jnp.int32)
        here = lambda a, b: b.at[row].set(a[row])     # noqa: E731
        mixed = step(here(q, q2), here(k, k2), here(v, v2),
                     here(pos, others))
        np.testing.assert_array_equal(np.asarray(mixed)[row], mine[row])
    assert np.abs(np.asarray(mixed)[1] - mine[1]).max() > 0.01


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_nothing_past_a_rows_last_block_is_read(dtype):
    q, k, v = _inputs(3, dtype)
    pos = jnp.asarray(RAGGED, jnp.int32)
    clean = np.asarray(jax.jit(decode_attention)(q, k, v, pos))
    # every block wholly past a row's length, NaN (a row that sees
    # nothing has its first block fetched and its body skipped: NaN too)
    first_unread = (np.maximum(np.asarray(RAGGED), -1) + DECODE_BLOCK) \
        // DECODE_BLOCK * DECODE_BLOCK
    past = jnp.asarray(np.arange(TOKENS)[None, :] >= first_unread[:, None])
    poison = lambda c: jnp.where(past[:, None, :, None],  # noqa: E731
                                 jnp.asarray(np.nan, c.dtype), c)
    assert bool(jnp.isnan(poison(k)[0]).all())
    out = np.asarray(jax.jit(decode_attention)(q, poison(k), poison(v), pos))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)
    # the whole pass, for contrast, multiplies a zero weight by them
    assert np.isnan(np.asarray(cached_attention(
        q, poison(k), poison(v), pos[:, None]))).any()


@pytest.mark.parametrize("t,d,dtype,fits", [
    (18 * DECODE_BLOCK, 128, "bfloat16", True),
    (DECODE_BLOCK, 256, "float32", True),
    (DECODE_BLOCK + 128, 128, "bfloat16", False),   # not whole blocks
    (2 * DECODE_BLOCK, 32, "bfloat16", False),      # not whole lanes
    (2 * DECODE_BLOCK, 128, "float16", False)])
def test_what_the_kernel_takes(t, d, dtype, fits):
    assert decode_attention_fits(t, d, dtype) == fits
    if not fits:
        z = jnp.zeros((1, 2, t, d), dtype)
        with pytest.raises(ValueError, match="whole"):
            decode_attention(jnp.zeros((1, 1, 2, d)), z, z,
                             jnp.zeros((1,), jnp.int32))


def _ragged_layers(head_dim, cache_tokens, monkeypatch):
    """What a step program built for a two-full-layer model with such
    caches reports, and which attention it called (traced, never run)."""
    spec = hybrid_lm.make_spec(
        layer_types=[hybrid_lm.FULL, hybrid_lm.LINEAR, hybrid_lm.FULL],
        hidden=32, intermediate=64, vocab=64, heads=2, head_dim=head_dim,
        lin_heads=2, lin_dk=8, lin_dv=16, slots=2,
        cache_tokens=cache_tokens, prefill_chunks=(64,), dtype="float32")
    called = []
    for name in ("decode_attention", "cached_attention"):
        real = getattr(attention, name)
        monkeypatch.setattr(
            hybrid_lm, name,
            lambda *a, _real=real, _name=name, **kw: (
                called.append(_name), _real(*a, **kw))[1])
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, (s, _) in hybrid_lm.weight_shapes(spec).items()}
    slab = {n: jax.ShapeDtypeStruct(e["shape"], jnp.dtype(e["dtype"]))
            for n, e in hybrid_lm.state_layout(spec).items()}
    gauge = obs.REGISTRY.gauge("decode.attn.ragged_layers")
    gauge.set(-1)
    jax.eval_shape(hybrid_lm.build_step(spec), params, slab,
                   jax.ShapeDtypeStruct((2,), jnp.bool_))
    assert obs.REGISTRY.snapshot()["gauges"][
        "decode.attn.ragged_layers"] == gauge.value
    return gauge.value, called


@pytest.mark.parametrize("head_dim,kernel", [(128, True), (16, False)],
                         ids=["whole-lanes", "small-heads"])
def test_the_step_takes_the_kernel_where_the_cache_fits(head_dim, kernel,
                                                        monkeypatch):
    layers, called = _ragged_layers(head_dim, 192, monkeypatch)
    assert layers == (2 if kernel else 0)
    assert called == ["decode_attention" if kernel
                      else "cached_attention"] * 2
