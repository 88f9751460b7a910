"""The sparse-expert language model with windowed and full attention
(``model_type`` afmoe: Trinity's block) behind ``models/hybrid_lm.py``,
at a small size on the CPU.

What is held here, each against the plain reference
(``netsdb_tpu/models/reference/afmoe_lm.py``: float32 NumPy, no cache,
no ring, no batching, no sorting of tokens) or against plain attention:

* prefill then decode through the caches equals the reference's full
  forward for histories shorter than, equal to and several times the
  window, in every slot, with chunks that straddle the ring's end, on
  the XLA forms and on the kernels (``decode_attention`` over a ring
  and grouped-query heads, ``grouped_ffn``);
* the same through ``SESSION_OPEN`` and the decode scheduler, several
  turns, with the step's routing counts in the registry's counters and
  one copy of the weights on the device;
* grouped-query ``decode_attention`` and ``cached_attention`` against
  plain attention, over a ring whose first visible block is not block 0;
* the grouped expert product against the loop over experts, with an
  expert that gets no token and one that gets all;
* the share test: eight shares' routed parts plus the shared expert
  counted once add up to the uncut reference's whole layer;
* the counts a step returns equal those recomputed from the
  reference's routing; cache rows are counted by layer type;
* the two reference files are one.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.models import decode as decode_mod
from netsdb_tpu.models import hybrid_lm
from netsdb_tpu.models.reference import afmoe_lm as reference
from netsdb_tpu.ops import attention, experts
from netsdb_tpu.serve.client import RemoteClient
from netsdb_tpu.serve.server import ServeController

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention"]
VOCAB = 320


def model(head_dim=32, expert_width=64, window=64, chunks=(32, 64),
          cache=512, dtype="bfloat16", held=4, first=0, slots=4):
    """(spec, the reference's configuration keys) of a five-layer model
    of Trinity's pattern: one dense layer, four expert layers; 6 query
    heads on 2 key/value heads; 16 routed experts, 2 a token."""
    hidden, heads, kv = 128, 6, 2
    spec = hybrid_lm.make_spec(
        layer_types=TYPES, hidden=hidden, intermediate=256, vocab=VOCAB,
        heads=heads, head_dim=head_dim, lin_heads=0, lin_dk=0, lin_dv=0,
        eps=1e-5, slots=slots, cache_tokens=cache, prefill_chunks=chunks,
        delta_chunk=1, dtype=dtype, kv_heads=kv, window=window,
        rope_theta=10000.0, qk_norm="head", attn_gate=True, pre_norms=True,
        embed_scale=hidden ** 0.5, dense_layers=1,
        moe={"experts": 16, "top_k": 2, "intermediate": expert_width,
             "route_scale": 2.448, "first": first, "held": held})
    cfg = {"hidden_size": hidden, "intermediate_size": 256,
           "moe_intermediate_size": expert_width, "vocab_size": VOCAB,
           "num_attention_heads": heads, "num_key_value_heads": kv,
           "head_dim": head_dim, "layer_types": TYPES,
           "num_hidden_layers": 5, "num_dense_layers": 1,
           "experts_routed": 16, "num_experts": held,
           "experts_first": first, "num_experts_per_tok": 2,
           "route_scale": 2.448, "sliding_window": window,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "prefill_chunks": list(chunks)}
    return spec, cfg


def _params(spec, seed):
    make = hybrid_lm.random_weights(spec, seed)
    params = {n: jnp.asarray(make(n, shape, is_matrix)) for n, (shape, is_matrix)
              in hybrid_lm.weight_shapes(spec).items()}
    host = {n: np.asarray(v, np.float32) for n, v in params.items()}

    def weights(name, shape, rows=None):
        w = host[name].reshape(shape)
        return w if rows is None else w[np.asarray(rows)]

    return params, weights


def _serve(spec, params, lengths, new, seed):
    """Slot ``i`` consumes a prompt of ``lengths[i]`` tokens through the
    prefill programs, then every slot decodes ``new`` tokens side by
    side. Returns the histories, the last step's logits and every
    step's counts."""
    layout = hybrid_lm.state_layout(spec)
    slab = {n: jnp.zeros(e["shape"], e["dtype"]) for n, e in layout.items()}
    step = jax.jit(hybrid_lm.build_step(spec))
    prefill = {c: jax.jit(hybrid_lm.build_prefill(spec, c))
               for c in spec["prefill_chunks"]}
    rng = np.random.default_rng(seed)
    hist = {}
    for slot, n in enumerate(lengths):
        tokens = rng.integers(0, spec["vocab"], n).astype(np.int32)
        hist[slot] = list(tokens)
        plan, at = hybrid_lm.plan_chunks(spec, n - 1), 0
        for j, (size, count) in enumerate(plan):
            ids = np.zeros(size, np.int32)
            ids[:count] = tokens[at:at + count]
            at += count
            slab = prefill[size](
                params, slab, np.int32(slot), ids, np.int32(count),
                np.int32(tokens[-1] if j == len(plan) - 1 else -1))
    active = np.zeros(spec["slots"], bool)
    active[:len(lengths)] = True
    counts = []
    for _ in range(new):
        slab, ids, logits = step(params, slab, active)
        ids = np.asarray(ids)
        counts.append(ids[spec["slots"]:])
        for slot in hist:
            hist[slot].append(int(ids[slot]))
    return hist, np.asarray(logits), counts


# window 64, chunks of 32 and 64: a ring of 256 rows (whole blocks of the
# decode kernel). 40 < window; 65 = window + 1 token; 300 and 700 wrap
# the ring, 700 twice, and their chunks straddle its end
LENGTHS = [40, 65, 300, 700]


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.1)])
def test_prefill_then_decode_equals_the_reference_forward(dtype, tol,
                                                          monkeypatch):
    spec, cfg = model(dtype=dtype, cache=1024)
    if dtype == "float32":
        # the reference rounds operands to the deployment's bfloat16;
        # with float32 weights the same equations must agree closely
        monkeypatch.setattr(reference, "to_bfloat16",
                            lambda a, scratch=None: np.asarray(a, np.float32))
    params, weights = _params(spec, 3)
    hist, logits, counts = _serve(spec, params, LENGTHS, 4, 3)
    refs = {}
    for slot, h in hist.items():
        assert hybrid_lm.cache_rows(spec, hybrid_lm.SLIDING) == 256 < len(h) \
            or slot < 2
        refs[slot] = reference.forward(cfg, weights, np.asarray(h[:-1]), 4)
        rows = [p[0] for p in refs[slot]["paths"]]
        assert np.abs(logits[slot] - rows[-1]).max() <= tol
        chosen = [row[i] for row, i in zip(rows, h[-4:])]
        assert max(row.max() - c for row, c in zip(rows, chosen)) <= 2 * tol
    if dtype == "float32":
        # what the last step returned after its ids, recomputed from the
        # reference's routing of each slot's last token
        here = [refs[s]["chosen"][:, -1] for s in hist]      # (layers, k)
        local = np.stack(here, 1).reshape(4, -1)             # by layer
        held = [row[(row >= 0) & (row < 4)] for row in local]
        assert list(counts[-1]) == [
            sum(len(h) for h in held), sum(len(set(h)) for h in held),
            max(np.bincount(h, minlength=4).max() for h in held)]


def test_the_kernels_path_equals_the_reference_forward():
    """Heads of 128 and experts 128 wide: the step's attention is
    ``decode_attention`` over grouped-query heads (on the sliding layers
    over a ring) and the experts' product is ``grouped_ffn``."""
    spec, cfg = model(head_dim=128, expert_width=128, window=256,
                      chunks=(128, 256), cache=1024)
    assert hybrid_lm.cache_rows(spec, hybrid_lm.SLIDING) == 512
    gauge = obs.REGISTRY.gauge("decode.attn.ragged_layers")
    gauge.set(-1)
    params, weights = _params(spec, 4)
    hist, logits, _ = _serve(spec, params, [100, 257, 900], 3, 4)
    assert gauge.value == 5
    for slot, h in hist.items():
        ref = reference.forward(cfg, weights, np.asarray(h[:-1]), 1)
        assert np.abs(logits[slot] - ref["paths"][-1][0]).max() <= 0.1


def test_the_two_reference_files_are_one():
    with open(os.path.join(ROOT, "netsdb_tpu", "models", "reference",
                           "afmoe_lm.py")) as a, \
            open(os.path.join(ROOT, "benchmark", "configs",
                              "trinity-large-ep8-5l_reference.py")) as b:
        assert a.read() == b.read()


# --- through SESSION_OPEN and the decode scheduler ----------------------

@contextlib.contextmanager
def _daemon(tmp_path):
    ctl = ServeController(Configuration(root_dir=str(tmp_path / "d0")),
                          port=0)
    ctl.start()
    try:
        yield ctl
    finally:
        ctl.shutdown()


def _counter(name):
    return obs.REGISTRY.counter(name).value


def test_sessions_equal_the_reference_and_count_their_routing(tmp_path):
    """Two sessions through the daemon, one past the window: logits
    against the reference, the routing counters against the reference's
    choices over the decode steps, no state across the host, the
    registered parameters the stored arrays."""
    spec, cfg = model(dtype="float32")
    decode_mod.clear_decode_programs()
    with _daemon(tmp_path) as ctl:
        hybrid_lm.deploy(ctl.library, "lm", spec,
                         hybrid_lm.random_weights(spec, 5))
        c = RemoteClient(ctl.advertise_addr)
        before = {n: _counter("decode.moe." + n)
                  for n in ("pairs", "experts_touched", "experts_held")}
        rows0 = [_counter("decode.attn.rows_fetched"),
                 _counter("decode.attn.rows_held")]
        host0 = _counter("session.state_host_bytes")
        rng = np.random.default_rng(9)
        hist, last = {}, {}
        for i, plan in enumerate([[(50, 3)], [(150, 2), (40, 3)]]):
            h = c.open_session("lm", kind="hybrid_lm")
            hist[i] = []
            for n_prompt, n_new in plan:
                prompt = rng.integers(0, VOCAB, n_prompt).astype(np.int32)
                ids = h.generate(tokens=prompt, new_tokens=n_new,
                                 deadline_s=120.0)
                hist[i] += list(prompt) + list(ids)
            last[i] = (list(ids), h.last_logits())
            assert h.steps == len(hist[i])
            h.close()
        assert _counter("session.state_host_bytes") == host0

        def weights(name, shape, rows=None):
            w = np.asarray(ctl.library.get_tensor("lm", name).to_dense(),
                           np.float32).reshape(shape)
            return w if rows is None else w[np.asarray(rows)]

        # float32 weights: the reference's operand rounding is off
        reference_round, reference.to_bfloat16 = (
            reference.to_bfloat16, lambda a, scratch=None: np.asarray(a, np.float32))
        try:
            pairs = 0
            for i, h in hist.items():
                ids, logits = last[i]
                ref = reference.forward(cfg, weights, np.asarray(h[:-1]),
                                        len(ids))
                assert np.abs(logits - ref["paths"][-1][0]).max() <= 2e-4
                # decode steps consumed the last prompt token of each
                # turn and every generated token but the last
                steps = {0: [49, 50, 51],
                         1: [149, 150, 191, 192, 193]}[i]
                chosen = ref["chosen"][:, steps]
                pairs += int(((chosen >= 0) & (chosen < 4)).sum())
        finally:
            reference.to_bfloat16 = reference_round
        assert _counter("decode.moe.pairs") - before["pairs"] == pairs
        assert _counter("decode.moe.experts_held") - before[
            "experts_held"] == 8 * 4 * 4        # 8 steps, 4 layers, 4 held
        touched = _counter("decode.moe.experts_touched") - before[
            "experts_touched"]
        assert 0 < touched <= pairs
        # cache rows by layer type: 4 slots, a full layer of 576 rows + 64
        # rounded to 768, four rings of 256; heads of 32 take the whole pass
        held = _counter("decode.attn.rows_held") - rows0[1]
        assert held == 8 * 4 * (768 + 4 * 256)
        assert _counter("decode.attn.rows_fetched") - rows0[0] == held
        reg = ctl.sessions.runtime._reg("lm")
        for name in ("embed", "l00.w_gate_up", "l01.w_experts_gate_up",
                     "l04.w_experts_down", "l02.w_router", "l03.route_bias"):
            assert reg["params"][name] is ctl.library.get_tensor(
                "lm", name).data
        c.close()


def test_cache_rows_are_counted_by_layer_type():
    """Where the kernel runs, a sliding layer's fetch stops at its
    window (from the block of its oldest visible key) and a full layer's
    at the length; an idle slot costs a block a layer."""
    spec, _ = model(head_dim=128, window=256, chunks=(128, 256), cache=2048)
    full, ring = 2048 + 256, 256 + 256
    assert (hybrid_lm.cache_rows(spec), hybrid_lm.cache_rows(
        spec, hybrid_lm.SLIDING)) == (full, ring)
    fetched, held = hybrid_lm.cache_rows_read(spec, [100, 1000])
    assert held == 4 * (full + 4 * ring)
    # lengths 100 and 1000: a full layer 1 + 4 blocks; a ring 1 block and
    # the 256 keys from position 744 (row 232 of the ring): 2 blocks;
    # two idle slots a block each
    assert fetched == 256 * ((1 + 4 + 2) + 4 * (1 + 2 + 2))


# --- grouped-query attention over caches and rings ----------------------

def _plain(q, k, v, visible):
    """softmax(q k^T / sqrt(d)) v in float64; q (H, D), k, v (Hkv, T, D),
    visible (T,) bool."""
    group = q.shape[0] // k.shape[0]
    out = np.zeros(q.shape, np.float64)
    for a in range(q.shape[0]):
        logits = (k[a // group] @ q[a]) / np.sqrt(q.shape[1])
        logits = np.where(visible, logits, -np.inf)
        p = np.exp(logits - logits.max())
        out[a] = (p / p.sum()) @ v[a // group]
    return out


@pytest.mark.parametrize("window", [None, 512])
def test_grouped_query_attention_equals_plain_attention(window):
    """6 query heads on 2 key/value heads, one query a row: the kernel
    and the whole pass against plain attention; with a window the cache
    is a ring of 1,024 rows that positions up to 3,000 have wrapped, so
    a row's first visible block is not block 0."""
    rng = np.random.default_rng(1)
    b, h, hkv, t, d = 5, 6, 2, 1024, 128
    q = rng.standard_normal((b, 1, h, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    pos = np.array([0, 300, 1023, -1, 700] if window is None
                   else [0, 300, 1500, -1, 3000], np.int32)
    got = np.asarray(attention.decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos),
        window=window))
    k_pos = None if window is None else hybrid_lm._ring_pos(
        jnp.asarray(pos), t)
    whole = np.asarray(attention.cached_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pos)[:, None], window=window, k_pos=k_pos))
    for r in range(b):
        at = np.arange(t) if window is None else \
            pos[r] - (pos[r] - np.arange(t)) % t
        visible = (at >= 0) & (at <= pos[r])
        if window is not None:
            visible &= at > pos[r] - window
        if pos[r] < 0:
            assert not got[r].any() and not whole[r].any()
            continue
        want = _plain(q[r, 0], k[r], v[r], visible)
        assert np.abs(got[r, 0] - want).max() < 2e-5
        assert np.abs(whole[r, 0] - want).max() < 2e-5


def test_a_chunks_grouped_queries_over_a_ring_equal_plain_attention():
    """A prefill chunk's queries over one slot of a ring cache, in
    blocks, the chunk written around the ring's end."""
    rng = np.random.default_rng(2)
    h, hkv, t, d, c, window = 6, 2, 512, 32, 64, 256
    pos0 = 990                       # rows 478 .. 511, then 0 .. 29
    cache_k = rng.standard_normal((3, hkv, t, d)).astype(np.float32)
    cache_v = rng.standard_normal((3, hkv, t, d)).astype(np.float32)
    new_k = rng.standard_normal((hkv, c, d)).astype(np.float32)
    new_v = rng.standard_normal((hkv, c, d)).astype(np.float32)
    kc = np.asarray(hybrid_lm._ring_write(jnp.asarray(cache_k),
                                          jnp.asarray(new_k), 1,
                                          jnp.int32(pos0)))
    vc = np.asarray(hybrid_lm._ring_write(jnp.asarray(cache_v),
                                          jnp.asarray(new_v), 1,
                                          jnp.int32(pos0)))
    want_k, want_v = cache_k.copy(), cache_v.copy()
    for i in range(c):
        want_k[1, :, (pos0 + i) % t] = new_k[:, i]
        want_v[1, :, (pos0 + i) % t] = new_v[:, i]
    assert np.array_equal(kc, want_k) and np.array_equal(vc, want_v)
    q = rng.standard_normal((1, c, h, d)).astype(np.float32)
    q_pos = np.where(np.arange(c) < 50, pos0 + np.arange(c), -1)
    k_pos = np.asarray(hybrid_lm._ring_pos(pos0 + c - 1, t))
    got = np.asarray(attention.cached_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(q_pos, jnp.int32)[None], block_size=128, row0=1,
        k_pos=jnp.asarray(k_pos), window=window))
    for i in range(c):
        if q_pos[i] < 0:
            assert not got[0, i].any()
            continue
        visible = (k_pos >= 0) & (k_pos <= q_pos[i]) & \
            (k_pos > q_pos[i] - window)
        assert visible.sum() == window
        want = _plain(q[0, i], kc[1], vc[1], visible)
        assert np.abs(got[0, i] - want).max() < 2e-5


def test_what_a_ring_takes():
    with pytest.raises(ValueError, match="ring"):
        attention.decode_attention(
            jnp.zeros((1, 1, 2, 128)), jnp.zeros((1, 2, 512, 128)),
            jnp.zeros((1, 2, 512, 128)), jnp.zeros((1,), jnp.int32),
            window=512)
    with pytest.raises(ValueError, match="window"):
        hybrid_lm.make_spec(
            layer_types=TYPES, hidden=64, intermediate=64, vocab=64,
            heads=2, head_dim=32, lin_heads=0, lin_dk=0, lin_dv=0,
            prefill_chunks=(64,), delta_chunk=1, window=32)


# --- the experts' grouped product ---------------------------------------

def _loop_over_experts(u, idx, weights, valid, w_gate_up, w_down, first):
    """float64: each held expert over the tokens that chose it."""
    held = w_gate_up.shape[0]
    out = np.zeros(u.shape, np.float64)
    sizes = np.zeros(held, int)
    for e in range(held):
        rows, slot = np.nonzero((idx == first + e) & valid[:, None])
        sizes[e] = len(rows)
        x = u[rows].astype(np.float64)
        g, up = x @ w_gate_up[e, 0].T, x @ w_gate_up[e, 1].T
        out[rows] += weights[rows, slot][:, None] * (
            (g / (1 + np.exp(-g)) * up) @ w_down[e].T)
    return out, [sizes.sum(), (sizes > 0).sum(), sizes.max()]


@pytest.mark.parametrize("f,kernel", [(128, True), (48, False)])
def test_the_grouped_product_equals_the_loop_over_experts(f, kernel):
    """4 held experts (numbers 8 to 11 of 16), 3 a token: expert 9 is
    chosen by every token, expert 10 by none, tokens 5 and 6 do not
    count. On the kernel and on its XLA form."""
    rng = np.random.default_rng(6)
    t, d, held, first = 40, 128, 4, 8
    assert experts.grouped_ffn_fits(d, f, jnp.float32) is kernel
    u = rng.standard_normal((t, d)).astype(np.float32)
    w_gate_up = (rng.standard_normal((held, 2, f, d)) / 8).astype(np.float32)
    w_down = (rng.standard_normal((held, d, f)) / 8).astype(np.float32)
    others = np.array([e for e in range(16) if e not in (9, 10)])
    idx = np.stack([np.full(t, 9)] + list(
        rng.permuted(np.tile(others, (t, 1)), axis=1).T[:2]), 1)
    weights = rng.uniform(0.2, 1.0, (t, 3)).astype(np.float32)
    valid = np.ones(t, bool)
    valid[5:7] = False
    got, counts = experts.held_experts_ffn(
        jnp.asarray(u), jnp.asarray(idx, jnp.int32), jnp.asarray(weights),
        jnp.asarray(valid), jnp.asarray(w_gate_up), jnp.asarray(w_down),
        first, 16)
    want, sizes = _loop_over_experts(u, idx, weights, valid, w_gate_up,
                                     w_down, first)
    assert np.abs(np.asarray(got) - want).max() < 1e-3
    assert not np.asarray(got)[5:7].any()
    assert list(np.asarray(counts)) == sizes and sizes[2] == t - 2
    plan = experts.plan_tiles(jnp.asarray(idx, jnp.int32),
                              jnp.asarray(valid), first, held, 16)
    assert np.asarray(plan["sizes"])[2] == 0      # expert 10: no tile
    assert 2 not in np.asarray(plan["tile_expert"])[:int(plan["used"])]


def test_no_pair_routed_here_gives_zeros():
    rng = np.random.default_rng(7)
    u = rng.standard_normal((8, 128)).astype(np.float32)
    got, counts = experts.held_experts_ffn(
        jnp.asarray(u), jnp.full((8, 2), 3, jnp.int32),
        jnp.ones((8, 2), jnp.float32), jnp.ones(8, bool),
        jnp.ones((2, 2, 128, 128), jnp.float32),
        jnp.ones((2, 128, 128), jnp.float32), 8, 16)
    assert not np.asarray(got).any() and not np.asarray(counts).any()


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Each of eight chips routes every token over all 16 experts and
    computes the part of its own 2; the eight parts and the shared
    expert, counted once, are the uncut reference's whole expert layer."""
    spec, cfg = model(dtype="float32", held=16)
    rng = np.random.default_rng(8)
    d, fe = 128, 64
    u = rng.standard_normal((50, d)).astype(np.float32)
    w = {"w_router": rng.standard_normal((16, d)) / 11,
         "route_bias": rng.uniform(-1, 1, (1, 16)) / 64,
         "w_experts_gate_up": rng.standard_normal((16 * 2 * fe, d)) / 11,
         "w_experts_down": rng.standard_normal((16 * d, fe)) / 8,
         "w_shared_gate_up": rng.standard_normal((2 * fe, d)) / 11,
         "w_shared_down": rng.standard_normal((d, fe)) / 8}
    w = {n: a.astype(np.float32) for n, a in w.items()}
    reference_round, reference.to_bfloat16 = (
        reference.to_bfloat16, lambda a, scratch=None: np.asarray(a, np.float32))
    try:
        select, s = reference.route(cfg, w.__getitem__, u)
        chosen = np.argsort(-select, axis=1, kind="stable")[:, :2]
        whole = reference.experts_part(cfg, w.__getitem__, u, chosen, s) \
            + reference.gated(u, w["w_shared_gate_up"], w["w_shared_down"])
    finally:
        reference.to_bfloat16 = reference_round
    idx, weights = experts.route(jnp.asarray(u), jnp.asarray(w["w_router"]),
                                 jnp.asarray(w["route_bias"]), 2, 2.448)
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(chosen, 1))
    total = np.asarray(hybrid_lm._gated(
        jnp.asarray(u), jnp.asarray(w["w_shared_gate_up"]),
        jnp.asarray(w["w_shared_down"])))
    pairs = 0
    for share in range(8):
        part, counts = experts.held_experts_ffn(
            jnp.asarray(u), idx, weights, jnp.ones(50, bool),
            jnp.asarray(w["w_experts_gate_up"].reshape(16, 2, fe, d)[
                2 * share:2 * share + 2]),
            jnp.asarray(w["w_experts_down"].reshape(16, d, fe)[
                2 * share:2 * share + 2]), 2 * share, 16)
        total = total + np.asarray(part)
        pairs += int(counts[0])
    assert pairs == 50 * 2                  # every pair on exactly one chip
    assert np.abs(total - whole).max() < 2e-4


# --- the rows the comparison leaves out --------------------------------------

@pytest.mark.parametrize("length", [60, 129, 200, 330])
def test_rows_no_compared_position_sees_change_nothing(length, monkeypatch):
    """Without ``every_row`` the reference computes, layer by layer, the
    rows that a tail position can see through the layers above (the
    last layer for the tail, the sliding layer under it from a window
    before that, the full layer from a window before that again, the
    two layers under the full one whole) and the tail's logits, forks
    near a tie included, are those of the whole pass; what was left out
    reads ``inf`` and -1. Histories shorter than the window, of two
    windows, three, and five."""
    spec, cfg = model(dtype="float32")
    _, weights = _params(spec, 14)
    tokens = np.random.default_rng(length).integers(0, VOCAB, length)
    whole = reference.forward(cfg, weights, tokens, 3, eps=0.05)
    pruned = reference.forward(cfg, weights, tokens, 3, eps=0.05,
                               every_row=False)
    assert [len(p) for p in pruned["paths"]] == [len(p) for p in whole["paths"]]
    for a, b in zip(whole["paths"], pruned["paths"]):
        for row_a, row_b in zip(a, b):
            assert np.abs(row_a - row_b).max() < 1e-5
    # expert layers are the model's layers 1 to 4; the window is 64
    first = [0, max(0, length - 3 - 126), max(0, length - 3 - 63), length - 3]
    for layer, n in enumerate(first):
        assert np.isinf(pruned["margins"][layer][:n]).all()
        assert (pruned["chosen"][layer][:n] == -1).all()
        # (a product over fewer rows may sum in another order: last bits)
        assert np.abs(pruned["margins"][layer][n:]
                      - whole["margins"][layer][n:]).max() < 1e-5
        clear = whole["margins"][layer][n:] > 1e-4
        assert np.array_equal(pruned["chosen"][layer][n:][clear],
                              whole["chosen"][layer][n:][clear])


# --- the comparison near a routing tie ----------------------------------

def test_the_check_tries_the_other_choice_near_a_routing_tie(monkeypatch):
    """Served logits that took the OTHER expert at a near tie (the
    fifth score in place of the fourth, at two layers) are held against
    that path: with ``route_tie_eps`` wide enough to call every margin a
    tie the last position has its 16 paths and one of them is the served
    one; with no tolerance for ties the same logits are far off."""
    spec, cfg = model(dtype="float32")
    _, weights = _params(spec, 12)
    monkeypatch.setattr(reference, "weight",
                        lambda cfg, seed, name, shape, rows=None:
                        weights(name, shape, rows))
    tokens = np.random.default_rng(12).integers(0, VOCAB, 330)
    own = reference.forward(cfg, weights, tokens, 2)
    forked = reference.forward(cfg, weights, tokens, 2, eps=1.0)
    assert [len(p) for p in own["paths"]] == [1, 1]
    assert [len(p) for p in forked["paths"]] == [16, 16]
    assert np.abs(own["paths"][-1][0] - forked["paths"][-1][0]).max() < 1e-5
    # (paths that differ only in experts held elsewhere are the same)
    served = max(forked["paths"][-1],
                 key=lambda p: np.abs(p - own["paths"][-1][0]).max())
    far = np.abs(served - own["paths"][-1][0]).max()
    assert far > 1e-2
    chosen = int(np.argmax(served))
    answers = [(list(tokens) + [chosen], [chosen], served, 2)]
    limits = {"answers_checked": 1, "logit_gap_max": 1e-4,
              "logit_gap_rms": 1e-4, "id_gap_max": 1e-4}
    numbers = reference.check(
        dict(cfg, check=dict(limits, route_tie_eps=1.0)), 0, answers,
        np.random.default_rng(0))
    assert numbers["route_alternatives"] == (16.0, 16.0)
    assert numbers["near_tie_share"][0] == 1.0
    assert all(v <= lim for v, lim in numbers.values())
    numbers = reference.check(
        dict(cfg, check=dict(limits, route_tie_eps=0.0)), 0, answers,
        np.random.default_rng(0))
    assert numbers["route_alternatives"][0] == 1.0
    assert numbers["logit_gap_max"][0] >= far - 1e-5
    # a history that has not passed the window plus a chunk is not compared
    short = [(list(tokens[:100]) + [chosen], [chosen], served, 2)]
    assert reference.check(dict(cfg, check=dict(limits, route_tie_eps=0.0)),
                           0, short, np.random.default_rng(0)) == {
        "no_session_past_the_window": (1.0, 0.0)}
