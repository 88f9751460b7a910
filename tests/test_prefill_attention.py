"""A prompt chunk's attention as a kernel that keeps the scores on the
chip (``ops/attention.py::prefill_attention``), in interpret mode on the
CPU, against ``cached_attention``: its block walk at the kernel's block
size (the same arithmetic, block for block, in the same order) and its
whole pass. The compiles for a described v5e are in
``tests/test_delta_rule_kernel.py``, beside the other kernels' (one file
holds libtpu).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.models import hybrid_lm
from netsdb_tpu.ops import attention
from netsdb_tpu.ops.attention import (DECODE_BLOCK, cached_attention,
                                      prefill_attention,
                                      prefill_attention_fits, prefill_tiles,
                                      prefill_walk)

SLOTS, SLOT, HKV, DIM = 3, 1, 2, 128
CHUNK, TOKENS, WINDOW = 64, 3 * DECODE_BLOCK, 3 * DECODE_BLOCK - 64
# (window, tokens the slot holds before the chunk): a full cache whose
# chunk crosses a block's end; a ring whose chunk crosses the ring's
# end on its second lap; a ring whose session is shorter than the window
LAYOUTS = {"full": (None, 230), "ring-over-the-end": (WINDOW, TOKENS + 740),
           "ring-short-session": (WINDOW, 100)}
# every query counts; the last tile partly; one tile partly and two not
# at all (query tiles of 16 tokens, below)
VALID = [CHUNK, CHUNK - 8, 24]


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Query tiles of 16 tokens (6 heads a key/value head; 8 of a
    float32 cache) and 32 (one): several tiles a chunk at these sizes."""
    monkeypatch.setattr(attention, "PREFILL_TILE_ROWS", 32)


def _inputs(seed, group, dtype):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((CHUNK, HKV * group, DIM)),
                    jnp.float32)
    k = jnp.asarray(rng.standard_normal((SLOTS, HKV, TOKENS, DIM)), dtype)
    v = jnp.asarray(rng.standard_normal((SLOTS, HKV, TOKENS, DIM)), dtype)
    return q, k, v


def _kernel(q, k, v, pos0, n_valid, window, slot=SLOT):
    return jax.jit(lambda *a: prefill_attention(*a, window=window))(
        q, k, v, slot, pos0, n_valid)


def _oracle(q, k, v, pos0, n_valid, window, block):
    at = pos0 + jnp.arange(CHUNK)
    return cached_attention(
        q[None], k, v, jnp.where(jnp.arange(CHUNK) < n_valid, at, -1)[None],
        block_size=block, row0=SLOT, window=window,
        k_pos=(hybrid_lm._ring_pos(pos0 + CHUNK - 1, TOKENS) if window
               else None))[0]


def _rows_seen(pos0, queries, window):
    """Which of the cache's rows hold a key that one of the chunk's
    ``queries`` (their numbers in the chunk) sees, from the oracle's own
    mask."""
    q_pos = (pos0 + np.asarray(queries))[:, None]
    k_pos = np.arange(TOKENS)[None] if window is None else np.asarray(
        hybrid_lm._ring_pos(pos0 + CHUNK - 1, TOKENS))[None]
    seen = (k_pos <= q_pos) & (k_pos >= 0)
    if window is not None:
        seen &= k_pos > q_pos - window
    return seen.any(0)


# a probability enters the value product in the cache's dtype, rounded
# against the largest logit SO FAR: the whole pass and a block walk
# round a bfloat16 cache's differently (about 2^-9 of a value of O(1))
@pytest.mark.parametrize("dtype,whole_tol", [("bfloat16", 4e-3),
                                             ("float32", 2e-6)])
@pytest.mark.parametrize("n_valid", VALID)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("group", [1, 6], ids=["one-head", "six-heads"])
def test_the_kernel_equals_cached_attention(group, layout, n_valid, dtype,
                                            whole_tol):
    window, pos0 = LAYOUTS[layout]
    q, k, v = _inputs(0, group, dtype)
    tq, bk = prefill_tiles(CHUNK, TOKENS, group, dtype)
    # several query tiles a chunk: six heads' as small as a tile gets
    assert (tq, bk) == (32 // jnp.dtype(dtype).itemsize if group == 6
                        else 32, DECODE_BLOCK)
    out = _kernel(q, k, v, pos0, n_valid, window)
    assert out.shape == (CHUNK, HKV * group, DIM)
    assert out.dtype == jnp.float32
    walk = _oracle(q, k, v, pos0, n_valid, window, bk)
    whole = _oracle(q, k, v, pos0, n_valid, window, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(walk), atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               atol=whole_tol)
    # a query past n_valid yields zeros, one that counts does not
    np.testing.assert_array_equal(np.asarray(out)[n_valid:], 0.0)
    assert np.abs(np.asarray(out)[:n_valid]).min(-1).max() > 0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("group", [1, 6], ids=["one-head", "six-heads"])
def test_nothing_past_what_a_chunk_sees_is_read(group, layout, dtype):
    """Every block of the slot's cache that holds no key a query of the
    chunk sees (past the slot's length; in a ring, older than the
    window, here one of 200 tokens so that a block of three is), NaN:
    the kernel's result is the clean one, bit for bit."""
    window, pos0 = LAYOUTS[layout]
    window = window and 200
    n_valid = CHUNK - 8
    q, k, v = _inputs(3, group, dtype)
    clean = np.asarray(_kernel(q, k, v, pos0, n_valid, window))
    _, bk = prefill_tiles(CHUNK, TOKENS, group, dtype)
    seen = np.nonzero(_rows_seen(pos0, range(n_valid), window))[0] // bk
    unread = ~np.isin(np.arange(TOKENS) // bk, seen)
    assert unread.any()
    poison = lambda c: jnp.where(  # noqa: E731
        jnp.asarray(unread)[None, None, :, None],
        jnp.asarray(np.nan, c.dtype), c)
    out = np.asarray(_kernel(q, poison(k), poison(v), pos0, n_valid, window))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)
    # the whole pass, for contrast, multiplies a zero weight by them
    assert np.isnan(np.asarray(_oracle(q, poison(k), poison(v), pos0,
                                       n_valid, window, None))).any()


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "ring"])
def test_other_slots_caches_are_never_read(window):
    q, k, v = _inputs(4, 6, "bfloat16")
    mine = np.asarray(_kernel(q, k, v, 300, CHUNK, window))
    others = jnp.arange(SLOTS)[:, None, None, None] != SLOT
    poison = lambda c: jnp.where(others, jnp.asarray(np.nan, c.dtype), c)  # noqa: E731
    out = np.asarray(_kernel(q, poison(k), poison(v), 300, CHUNK, window))
    np.testing.assert_array_equal(out, mine)
    # and the slot is the prefetched one: another slot's cache, another
    # result
    assert np.abs(np.asarray(_kernel(q, k, v, 300, CHUNK, window, slot=2))
                  - mine).max() > 0.01


@pytest.mark.parametrize("group", [1, 6], ids=["one-head", "six-heads"])
@pytest.mark.parametrize("n_valid", VALID)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_tiles_walk_is_the_blocks_its_queries_see(layout, n_valid, group):
    """``prefill_walk`` (the kernel's block map, and on the host the
    counters' arithmetic) gives each query tile the blocks that hold a
    key one of its queries sees: none fewer, none more."""
    window, pos0 = LAYOUTS[layout]
    tq, bk = prefill_tiles(CHUNK, TOKENS, group, "bfloat16")
    n_all = TOKENS // bk
    total = 0
    for qi in range(CHUNK // tq):
        first, used = prefill_walk(np, qi, pos0, n_valid, tq, bk, TOKENS,
                                   window)
        live = range(qi * tq, min((qi + 1) * tq, n_valid))
        want = set(np.nonzero(_rows_seen(pos0, live, window))[0] // bk)
        assert {(int(first) + i) % n_all for i in range(int(used))} == want
        total += int(used)
    assert 0 < total <= (CHUNK // tq) * n_all


@pytest.mark.parametrize("chunk,t,d,dtype,fits", [
    (1024, 68 * DECODE_BLOCK, 128, "bfloat16", True),
    (128, 18 * DECODE_BLOCK, 128, "bfloat16", True),
    (16, DECODE_BLOCK, 256, "bfloat16", True),
    (8, DECODE_BLOCK, 128, "float32", True),
    (8, DECODE_BLOCK, 128, "bfloat16", False),     # not a whole query tile
    (64, DECODE_BLOCK + 128, 128, "bfloat16", False),   # not whole blocks
    (64, 2 * DECODE_BLOCK, 32, "bfloat16", False),      # not whole lanes
    (64, 2 * DECODE_BLOCK, 128, "float16", False)])
def test_what_the_kernel_takes(chunk, t, d, dtype, fits):
    assert prefill_attention_fits(chunk, t, d, dtype) == fits
    if not fits:
        z = jnp.zeros((1, 2, t, d), dtype)
        with pytest.raises(ValueError, match="whole"):
            prefill_attention(jnp.zeros((chunk, 2, d)), z, z, 0, 0, chunk)


def test_a_ring_must_hold_the_window_and_the_chunk():
    z = jnp.zeros((1, 1, 2 * DECODE_BLOCK, DIM), jnp.bfloat16)
    with pytest.raises(ValueError, match="ring"):
        prefill_attention(jnp.zeros((CHUNK, 1, DIM)), z, z, 0, 0, CHUNK,
                          window=2 * DECODE_BLOCK - CHUNK + 1)


def test_the_tiles_follow_the_shapes(monkeypatch):
    monkeypatch.setattr(attention, "PREFILL_TILE_ROWS", 1536)
    # the benchmark's two models: 48 heads on 8 over a full cache and a
    # ring, 30 on 30
    assert prefill_tiles(1024, 17408, 6, "bfloat16") == (256, 1024)
    assert prefill_tiles(256, 5120, 6, "bfloat16") == (256, 1024)
    assert prefill_tiles(512, 4608, 1, "bfloat16") == (512, 1536)
    assert prefill_tiles(128, 4608, 1, "bfloat16") == (128, 1536)
    # a chunk that no halving brings under the limit keeps whole tiles
    assert prefill_tiles(48, 768, 64, "bfloat16") == (48, DECODE_BLOCK)


def _prefill_layers(head_dim, chunk, monkeypatch):
    """What a prefill program built for a model of four attention layers
    and one linear with such heads reports, and which attention it
    called (traced, never run)."""
    spec = hybrid_lm.make_spec(
        layer_types=[hybrid_lm.FULL, hybrid_lm.LINEAR, hybrid_lm.SLIDING,
                     hybrid_lm.FULL, hybrid_lm.SLIDING],
        hidden=32, intermediate=64, vocab=64, heads=2, head_dim=head_dim,
        lin_heads=2, lin_dk=8, lin_dv=16, slots=2, cache_tokens=704,
        window=128, prefill_chunks=(chunk,), delta_chunk=min(chunk, 64),
        dtype="float32")
    called = []
    for name in ("prefill_attention", "cached_attention"):
        real = getattr(attention, name)
        monkeypatch.setattr(
            hybrid_lm, name,
            lambda *a, _real=real, _name=name, **kw: (
                called.append(_name), _real(*a, **kw))[1])
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, (s, _) in hybrid_lm.weight_shapes(spec).items()}
    slab = {n: jax.ShapeDtypeStruct(e["shape"], jnp.dtype(e["dtype"]))
            for n, e in hybrid_lm.state_layout(spec).items()}
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    gauge = obs.REGISTRY.gauge("prefill.attn.fused_layers")
    gauge.set(-1)
    jax.eval_shape(hybrid_lm.build_prefill(spec, chunk), params, slab, scalar,
                   jax.ShapeDtypeStruct((chunk,), jnp.int32), scalar, scalar)
    assert obs.REGISTRY.snapshot()["gauges"][
        "prefill.attn.fused_layers"] == gauge.value
    return spec, gauge.value, called


@pytest.mark.parametrize("head_dim,chunk,kernel", [
    (128, 64, True), (16, 64, False), (128, 4, False)],
    ids=["whole-lanes", "small-heads", "a-chunk-under-a-tile"])
def test_the_prefill_program_takes_the_kernel_where_the_shapes_fit(
        head_dim, chunk, kernel, monkeypatch):
    spec, layers, called = _prefill_layers(head_dim, chunk, monkeypatch)
    # four attention layers' shapes decide; the last one's attention
    # feeds only the head and is not traced
    assert layers == (4 if kernel else 0)
    assert called == ["prefill_attention" if kernel
                      else "cached_attention"] * 3
    # the host's counters: the kernel reads what the chunk sees, the
    # walk in plain XLA all the slot's cache holds
    early = hybrid_lm.prefill_blocks_read(spec, chunk, 0, chunk)
    late = hybrid_lm.prefill_blocks_read(spec, chunk, 250, chunk - 1)
    assert early[1] == late[1] > 0
    if kernel:
        # two full layers of 768 rows (the last layer's ring is not
        # computed) and a ring of 256, in blocks of 256: one block each
        # where the session is young, the full layers' second once the
        # chunk reaches it, their third never
        assert early[0] < late[0] < late[1]
    else:
        assert early[0] == late[0] == late[1]


def test_the_hosts_count_follows_the_chunks_position(monkeypatch):
    """At the benchmark's shapes: a 1,024-token chunk of the sparse
    model onto a young and an old session, and a padded one."""
    monkeypatch.setattr(attention, "PREFILL_TILE_ROWS", 1536)
    spec = hybrid_lm.make_spec(
        layer_types=[hybrid_lm.SLIDING, hybrid_lm.SLIDING, hybrid_lm.FULL,
                     hybrid_lm.SLIDING, hybrid_lm.SLIDING],
        hidden=64, intermediate=64, vocab=64, heads=48, head_dim=128,
        kv_heads=8, lin_heads=0, lin_dk=0, lin_dv=0, delta_chunk=1,
        slots=2, cache_tokens=16384, window=4096,
        prefill_chunks=(256, 1024))
    tiles, heads = 1024 // 256, 8
    held = heads * tiles * (17 + 3 * 5)
    # a session's first chunk: tile i of the full layer and of a ring
    # sees block 0 alone
    assert hybrid_lm.prefill_blocks_read(spec, 1024, 0, 1024) == (
        heads * tiles * 4, held)
    # at 8,192 tokens the full layer's tiles read 9 blocks each and a
    # ring's all 5
    assert hybrid_lm.prefill_blocks_read(spec, 1024, 8192, 1024) == (
        heads * tiles * (9 + 3 * 5), held)
    # a chunk of which half counts: half the tiles read nothing
    assert hybrid_lm.prefill_blocks_read(spec, 1024, 8192, 512) == (
        heads * tiles // 2 * (9 + 3 * 5), held)
