"""The hybrid language model (gated-delta-rule layers beside full
attention) served through sessions, at a small size on the CPU.

What is held here, each against the plain reference
(``netsdb_tpu/models/reference/hybrid_lm.py``: float32, no cache, no
batching, the delta rule token by token) or against the program
itself:

* the chunked delta rule equals the token recurrence, also across a
  chunk boundary, with ``beta > 1``, and with a masked (padded) tail;
* sessions (prefill, then decode over several turns, 3 sessions
  batched) equal the reference's full forward pass, logits compared;
* a session alone equals the same session in a batch, bit for bit;
* a slot reused after a close equals a fresh daemon's;
* spill and revive of BOTH kinds of state (recurrent and cache) equal
  an uninterrupted run;
* a retried frame under one idempotency token is applied once;
* a warm step moves no state across the host and compiles nothing.
"""

import contextlib
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.models import decode as decode_mod
from netsdb_tpu.models import hybrid_lm
from netsdb_tpu.models.reference import hybrid_lm as reference
from netsdb_tpu.ops import delta_rule
from netsdb_tpu.serve.client import RemoteClient
from netsdb_tpu.serve.protocol import (CODEC_PICKLE, IDEMPOTENCY_KEY,
                                       MsgType)
from netsdb_tpu.serve.server import ServeController

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = (["linear_attention"] * 3 + ["full_attention"]) * 2   # 2 periods
VOCAB = 384
SPEC = hybrid_lm.make_spec(
    layer_types=TYPES, hidden=128, intermediate=256, vocab=VOCAB, heads=4,
    head_dim=32, lin_heads=4, lin_dk=16, lin_dv=32, slots=4,
    cache_tokens=512, prefill_chunks=(64, 128))
# the reference reads a model's published config keys
CFG = {"hidden_size": 128, "intermediate_size": 256, "vocab_size": VOCAB,
       "num_attention_heads": 4, "num_hidden_layers": len(TYPES),
       "layer_types": TYPES, "linear_num_key_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 32,
       "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6}
# the same model with ONE attention head of 128: whole lanes, so the
# step's attention is the kernel ``decode_attention`` (the heads of 32
# above take ``cached_attention``); the session tests run on both
# and the same model with delta-chunks of 128 tokens: the shape whose
# chunked rule in prefill is the kernel ``gdn_chunk`` (chunks of 64
# take the XLA body); prompts of 150 and 70 tokens then run a 256- and
# a 128-token prefill chunk that end in padding
SPECS = {"heads-of-32": (SPEC, CFG),
         "one-head-of-128": (dict(SPEC, heads=1, head_dim=128),
                             dict(CFG, num_attention_heads=1)),
         "delta-chunks-of-128": (dict(SPEC, delta_chunk=128,
                                      prefill_chunks=[128, 256]), CFG)}
# bfloat16 operands on both sides, but a rounding that falls the other
# way on one side moves a logit by about a bfloat16 step of the
# activations; float32 weights (below) agree to 1e-4
LOGIT_TOL = 0.25


def _counter(name):
    return obs.REGISTRY.counter(name).value


@contextlib.contextmanager
def _daemon(tmp_path, name="d0", **cfg_kw):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / name), **cfg_kw), port=0)
    ctl.start()
    try:
        yield ctl
    finally:
        ctl.shutdown()


@pytest.fixture(params=list(SPECS))
def both_specs(request, monkeypatch):
    """Runs a test once a spec of ``SPECS``, as the module's ``SPEC``
    and ``CFG``; with the head of 128 the kernel must have been on the
    step's path and on prefill's and their counters must say that they
    read less than the slab holds; with delta-chunks of 128 the chunked rule's kernel must
    have been on prefill's path, and with chunks of 64 its XLA body."""
    spec, cfg = SPECS[request.param]
    monkeypatch.setitem(globals(), "SPEC", spec)
    monkeypatch.setitem(globals(), "CFG", cfg)
    # a program compiled for an earlier test sets no gauge again
    decode_mod.clear_decode_programs()
    rows = [_counter("decode.attn.rows_fetched"),
            _counter("decode.attn.rows_held")]
    blocks = [_counter("prefill.attn.key_blocks_read"),
              _counter("prefill.attn.key_blocks_held")]
    yield request.param
    kernel = request.param == "one-head-of-128"
    # prefill's attention likewise: the kernel on the head of 128, and
    # of a slot's three blocks of 256 keys only those a chunk sees
    assert obs.REGISTRY.gauge("prefill.attn.fused_layers").value == (
        TYPES.count("full_attention") if kernel else 0)
    read, kept = (_counter("prefill.attn.key_blocks_read") - blocks[0],
                  _counter("prefill.attn.key_blocks_held") - blocks[1])
    assert kept > 0
    assert (read < kept) if kernel else (read == kept)
    assert obs.REGISTRY.gauge("decode.attn.ragged_layers").value == (
        TYPES.count("full_attention") if kernel else 0)
    fetched, held = (_counter("decode.attn.rows_fetched") - rows[0],
                     _counter("decode.attn.rows_held") - rows[1])
    # four slots of 768 rows: a session of under 256 tokens is a block
    assert held > 0 and held % (2 * 4 * 768) == 0
    assert (fetched <= held / 2) if kernel else (fetched == held)
    assert obs.REGISTRY.gauge("prefill.gdn_chunk.fused_layers").value == (
        TYPES.count("linear_attention")
        if request.param == "delta-chunks-of-128" else 0)


def _deploy(ctl, spec=None, seed=5, db="lm"):
    # through the daemon's own library, as a deployment fills it: the
    # wire's array codec carries no bfloat16
    spec = spec or SPEC
    hybrid_lm.deploy(ctl.library, db, spec,
                     hybrid_lm.random_weights(spec, seed))
    return RemoteClient(ctl.advertise_addr)


def _weights_of(ctl, db="lm"):
    def weights(name, shape, rows=None):
        w = np.asarray(ctl.library.get_tensor(db, name).to_dense(),
                       np.float32).reshape(shape)
        return w if rows is None else w[np.asarray(rows)]
    return weights


def _prompt(rng, n):
    return rng.integers(0, VOCAB, n).astype(np.int32)


# --- the delta rule's three forms --------------------------------------

def _rule_inputs(rng, length, beta_hi):
    h, dk, dv = 3, 16, 24
    q = rng.standard_normal((length, h, dk)).astype(np.float32)
    k = rng.standard_normal((length, h, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * 4
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((length, h, dv)).astype(np.float32)
    log_alpha = (-np.exp(rng.uniform(-3, 1, (length, h))) * 0.3
                 ).astype(np.float32)
    beta = rng.uniform(0, beta_hi, (length, h)).astype(np.float32)
    s0 = rng.standard_normal((h, dk, dv)).astype(np.float32)
    return s0, q, k, v, log_alpha, beta


@pytest.mark.parametrize("length,beta_hi", [(64, 1.0), (192, 1.0),
                                            (128, 2.0)])
def test_chunked_delta_rule_equals_the_recurrence(length, beta_hi):
    args = _rule_inputs(np.random.default_rng(length), length, beta_hi)
    s_r, o_r = delta_rule.gated_delta_recurrent(*args)
    s_c, o_c = delta_rule.gated_delta_chunked(*args, chunk=64)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=2e-5)


def test_a_masked_tail_leaves_the_state_alone():
    s0, q, k, v, la, beta = _rule_inputs(np.random.default_rng(1), 128, 2.0)
    valid = 75          # crosses the chunk boundary at 64
    la[valid:], beta[valid:] = 0.0, 0.0
    s_c, o_c = delta_rule.gated_delta_chunked(s0, q, k, v, la, beta, 64)
    s_r, o_r = delta_rule.gated_delta_recurrent(
        s0, q[:valid], k[:valid], v[:valid], la[:valid], beta[:valid])
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_c)[:valid], np.asarray(o_r),
                               atol=2e-5)


def test_one_step_equals_one_token_of_the_recurrence():
    s0, q, k, v, la, beta = _rule_inputs(np.random.default_rng(2), 4, 2.0)
    s, outs = jnp.asarray(s0)[None], []
    for t in range(4):
        s, o = delta_rule.gated_delta_step(
            s, q[t][None], k[t][None], v[t][None], la[t][None],
            beta[t][None])
        outs.append(np.asarray(o[0]))
    s_r, o_r = delta_rule.gated_delta_recurrent(s0, q, k, v, la, beta)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s_r), atol=1e-6)
    np.testing.assert_allclose(np.stack(outs), np.asarray(o_r), atol=1e-6)


def test_the_step_calls_the_update_through_the_modules_global(monkeypatch):
    """The benchmark's fault (``benchmark/tests/faults_lm.py``) puts a
    wrapper of six positional arguments, one layer's states ``(slots,
    dk, H dv)`` first, in place of ``hybrid_lm.gated_delta_step_flat``
    and calls ``delta_rule.gated_delta_step_flat`` with ``1.5 * beta``:
    the step program must be built through that seam, so its logits
    change."""
    make = hybrid_lm.random_weights(SPEC, 3)
    params = {n: jnp.asarray(make(n, shape, m)) for n, (shape, m)
              in hybrid_lm.weight_shapes(SPEC).items()}
    active = np.arange(SPEC["slots"]) != 2

    def two_steps():
        slab = {n: jnp.zeros(e["shape"], e["dtype"])
                for n, e in hybrid_lm.state_layout(SPEC).items()}
        slab["tok"] = jnp.arange(SPEC["slots"], dtype=jnp.int32) + 7
        step = jax.jit(hybrid_lm.build_step(SPEC))
        slab, _, _ = step(params, slab, active)
        slab, _, logits = step(params, slab, active)
        return slab, np.asarray(logits)

    slab, logits = two_steps()
    # the idle slot's states are as they were
    assert not np.asarray(slab["S0"])[2].any()
    assert np.asarray(slab["S0"])[1].any()
    seen = []

    def altered(S, q, k, v, log_alpha, beta):
        seen.append(S.shape)
        return delta_rule.gated_delta_step_flat(S, q, k, v, log_alpha,
                                                1.5 * beta)

    monkeypatch.setattr(hybrid_lm, "gated_delta_step_flat", altered)
    _, faulty = two_steps()
    lin = SPEC["lin_heads"] * SPEC["lin_dv"]
    assert seen == [(SPEC["slots"], SPEC["lin_dk"], lin)] * TYPES.count(
        "linear_attention")
    assert np.abs(faulty - logits)[active].max() > 1e-3


def test_the_two_reference_files_are_one():
    with open(os.path.join(ROOT, "netsdb_tpu", "models", "reference",
                           "hybrid_lm.py")) as a, \
            open(os.path.join(ROOT, "benchmark", "configs",
                              "olmo-hybrid-7b-16l_reference.py")) as b:
        assert a.read() == b.read()


# --- sessions against the reference ------------------------------------

def _turns(handles, plans, rng):
    """Run the planned turns of every session, the sessions of one
    round concurrently; returns each session's history and, per turn,
    (ids, logits of the last step)."""
    hist = {i: [] for i in handles}
    got = {i: [] for i in handles}
    for round_ in range(max(len(p) for p in plans.values())):
        errors = []

        def drive(i):
            try:
                if round_ >= len(plans[i]):
                    return
                n_prompt, n_new = plans[i][round_]
                prompt = _prompt(np.random.default_rng(
                    1000 * i + round_), n_prompt)
                ids = handles[i].generate(tokens=prompt, new_tokens=n_new,
                                          deadline_s=120.0)
                hist[i] += list(prompt) + list(ids)
                got[i].append((list(ids), handles[i].last_logits()))
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((i, e))

        ts = [threading.Thread(target=drive, args=(i,)) for i in handles]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert errors == []
    return hist, got


@pytest.mark.parametrize("dtype,tol", [("bfloat16", LOGIT_TOL),
                                       ("float32", 2e-4)])
def test_sessions_equal_the_reference_forward(tmp_path, dtype, tol,
                                              monkeypatch, both_specs):
    """3 sessions batched, prefill then decode over several turns
    (prompts of 150 and 70 tokens cross the 64- and 128-token chunks;
    a later turn appends nothing and only generates): the logits of
    every turn's last step, and the ids it chose, against the
    reference's full forward over the whole history."""
    spec = dict(SPEC, dtype=dtype)
    if dtype == "float32":
        # the reference rounds operands to the deployment's bfloat16;
        # with float32 weights the same equations must agree closely
        monkeypatch.setattr(reference, "to_bfloat16",
                            lambda a: np.asarray(a, np.float32))
    with _daemon(tmp_path) as ctl:
        c = _deploy(ctl, spec)
        clients = [RemoteClient(ctl.advertise_addr) for _ in range(3)]
        handles = {i: clients[i].open_session("lm", kind="hybrid_lm")
                   for i in range(3)}
        plans = {0: [(150, 5), (64, 4), (0, 2)], 1: [(70, 3), (129, 2)],
                 2: [(200, 4)]}
        host0 = _counter("session.state_host_bytes")
        hist, got = _turns(handles, plans, np.random.default_rng(0))
        assert _counter("session.state_host_bytes") == host0
        assert ctl.sessions.arena.stats()["reads"] == 0
        weights = _weights_of(ctl)
        for i, turns in got.items():
            ids, logits = turns[-1]
            ref = reference.forward(CFG, weights,
                                    [np.asarray(hist[i][:-1])],
                                    [len(ids)])[0]
            assert int(np.argmax(logits)) == ids[-1]
            assert np.abs(logits - ref[-1]).max() <= tol
            chosen = ref[np.arange(len(ids)), np.asarray(ids)]
            assert (ref.max(-1) - chosen).max() <= 2 * tol
            assert handles[i].steps == len(hist[i])
        for h in handles.values():
            h.close()
        for cc in clients + [c]:
            cc.close()


def _one_session(ctl, plan, seed=7, sid=None):
    c = RemoteClient(ctl.advertise_addr)
    h = c.open_session("lm", kind="hybrid_lm", session_id=sid)
    out = []
    for n_prompt, n_new in plan:
        prompt = _prompt(np.random.default_rng(seed + n_prompt), n_prompt)
        ids = h.generate(tokens=prompt, new_tokens=n_new, deadline_s=120.0)
        out.append((ids.tobytes(), h.last_logits().tobytes()))
    return c, h, out


PLAN = [(100, 4), (64, 3)]


def test_alone_equals_batched_bit_for_bit(tmp_path, both_specs):
    with _daemon(tmp_path) as ctl:
        _deploy(ctl).close()
        c0, h0, alone = _one_session(ctl, PLAN)
        h0.close()
        c0.close()
        # the same session again, now beside two others in the batch
        clients = [RemoteClient(ctl.advertise_addr) for _ in range(2)]
        others = {i: clients[i].open_session("lm", kind="hybrid_lm")
                  for i in range(2)}
        result = {}
        t = threading.Thread(target=lambda: result.update(
            run=_one_session(ctl, PLAN)))
        t.start()
        _turns(others, {0: [(150, 6), (70, 6)], 1: [(90, 8)]}, None)
        t.join(timeout=300)
        c1, h1, batched = result["run"]
        assert batched == alone
        assert ctl.sessions.batcher.snapshot()["max_occupancy"] >= 2
        for h in list(others.values()) + [h1]:
            h.close()
        for cc in clients + [c1]:
            cc.close()


PHASES = ["server.sched.session_wait", "session.admit",
          "session.turn.prefill", "session.turn.first_token",
          "session.turn.decode", "session.retire", "session.turn.reply"]


def _turn_profiles(ctl, skip=()):
    """{qid: spans} of the daemon's GENERATE frames, each a turn, but
    those whose query id is in ``skip``."""
    c = RemoteClient(ctl.advertise_addr)
    profiles = c.get_trace(last=256)["profiles"]
    c.close()
    return {p["qid"]: p["spans"] for p in profiles
            if p.get("origin") == "server" and p["qid"] not in skip
            and any(s["name"] == "session.coalesce" for s in p["spans"])}


def _phases(spans):
    """A turn's phases by name, held to tile its ``session.coalesce``:
    its children, in order, inside it, summing to it within 2 ms."""
    by_name = {s["name"]: s for s in spans}
    co = by_name["session.coalesce"]
    phases = [by_name[n] for n in PHASES]
    assert all(s["parent"] == co["id"] for s in phases)
    assert co["start_s"] <= phases[0]["start_s"] + 1e-6
    for a, b in zip(phases, phases[1:]):
        assert a["start_s"] + a["duration_s"] <= b["start_s"] + 1e-4
    end = phases[-1]["start_s"] + phases[-1]["duration_s"]
    assert end <= co["start_s"] + co["duration_s"] + 1e-6
    assert abs(sum(s["duration_s"] for s in phases)
               - co["duration_s"]) <= 2e-3
    first, decode = (by_name["session.turn.first_token"],
                     by_name["session.turn.decode"])
    assert abs(first["start_s"] + first["duration_s"]
               - decode["start_s"]) <= 1e-5
    return {n: by_name[n].get("counters", {}) for n in PHASES}


def test_a_turns_phases_tile_it_and_count_the_prompts_ahead(tmp_path,
                                                            both_specs):
    """A session alone, then two whose prompts take several chunks each,
    admitted in one iteration: the later one waits out the earlier
    one's chunks, and the earlier one's decode steps hold the later
    one's; the steps' spans count every prompt token dispatched."""
    with _daemon(tmp_path) as ctl:
        _deploy(ctl).close()
        # an idle model's leader lingers for peers: long enough here
        # that both turns of the pair join its first iteration
        ctl.sessions.batcher.window_s = 1.0
        c0, h0, _ = _one_session(ctl, [(100, 4)])
        h0.close()
        c0.close()
        alone = _turn_profiles(ctl)
        (spans,) = alone.values()
        assert _phases(spans)["session.turn.decode"] == {
            "steps": 3, "chunk_steps": 0, "chunk_step_s": 0.0}

        tokens0 = _counter("session.prefill_tokens")
        clients = [RemoteClient(ctl.advertise_addr) for _ in range(2)]
        handles = [cc.open_session("lm", kind="hybrid_lm")
                   for cc in clients]
        gate = threading.Barrier(2)

        def turn(h, seed):
            gate.wait()
            h.generate(tokens=_prompt(np.random.default_rng(seed), 300),
                       new_tokens=5, deadline_s=120.0)

        ts = [threading.Thread(target=turn, args=(h, i))
              for i, h in enumerate(handles)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        dispatched = _counter("session.prefill_tokens") - tokens0
        pair = list(_turn_profiles(ctl, skip=alone).values())
        assert len(pair) == 2
        early, late = sorted(
            (_phases(spans) for spans in pair),
            key=lambda p: p["session.turn.prefill"]["chunks_ahead"])
        chunks = early["session.turn.prefill"]["chunks"]
        assert chunks >= 2
        assert early["session.turn.prefill"]["chunks_ahead"] == 0
        assert late["session.turn.prefill"] == {
            "tokens": 299, "chunks": chunks, "chunks_ahead": chunks}
        # the earlier turn's later steps ran the later one's chunks
        assert early["session.turn.decode"]["steps"] == 4
        assert early["session.turn.decode"]["chunk_steps"] == min(chunks, 4)
        assert early["session.turn.decode"]["chunk_step_s"] > 0
        assert late["session.turn.decode"] == {
            "steps": 4, "chunk_steps": 0, "chunk_step_s": 0.0}
        # nothing was dispatched after the last step
        assert sum(s["counters"]["prefill_tokens"]
                   for spans in pair for s in spans
                   if s["name"] == "session.step") == dispatched
        for h in handles:
            h.close()
        for cc in clients:
            cc.close()


def test_a_reused_slot_equals_a_fresh_daemons(tmp_path, both_specs):
    with _daemon(tmp_path, "fresh") as ctl:
        _deploy(ctl).close()
        c, h, fresh = _one_session(ctl, PLAN)
        h.close()
        c.close()
    with _daemon(tmp_path, "reused") as ctl:
        _deploy(ctl).close()
        c, h, _ = _one_session(ctl, [(180, 9), (100, 5)], seed=99)
        slot = ctl.library.store.device_cache().session_get(
            h.sid, "lm", "slot", touch=False)["slot"]
        h.close()
        c, h, reused = _one_session(ctl, PLAN)
        assert ctl.library.store.device_cache().session_get(
            h.sid, "lm", "slot", touch=False)["slot"] == slot
        assert reused == fresh
        h.close()
        c.close()


def test_spill_and_revive_of_both_kinds_of_state(tmp_path, both_specs):
    """Between two turns the lease expires: the slot's recurrent state,
    convolution window and key/value cache all go to the arena and come
    back; the next turn equals an uninterrupted run's."""
    with _daemon(tmp_path, "steady") as ctl:
        _deploy(ctl).close()
        c, h, steady = _one_session(ctl, PLAN)
        h.close()
        c.close()
    with _daemon(tmp_path, "spilled") as ctl:
        _deploy(ctl).close()
        c = RemoteClient(ctl.advertise_addr)
        h = c.open_session("lm", kind="hybrid_lm")
        spills0 = _counter("session.slab.spills")
        revives0 = _counter("session.slab.revives")
        out = []
        for n_prompt, n_new in PLAN:
            prompt = _prompt(np.random.default_rng(7 + n_prompt), n_prompt)
            ids = h.generate(tokens=prompt, new_tokens=n_new)
            out.append((ids.tobytes(), h.last_logits().tobytes()))
            ctl.library.store.device_cache().session_sweep(now=1e18)
            layers = ctl.sessions.arena.snapshot_slot(h.sid, "lm")["layers"]
            assert {"S0", "S5", "conv", "k0", "v1", "pos",
                    "tok"} <= set(layers)
            assert np.abs(layers["S0"]["v"]).max() > 0
            assert np.abs(np.asarray(layers["k0"]["v"],
                                     np.float32)).max() > 0
        assert out == steady
        assert _counter("session.slab.spills") == spills0 + 2
        assert _counter("session.slab.revives") == revives0 + 1
        assert ctl.sessions.arena.stats()["reads"] > 0
        h.close()
        c.close()


def test_a_retried_frame_is_applied_once(tmp_path):
    with _daemon(tmp_path) as ctl:
        c = _deploy(ctl)
        h = c.open_session("lm", kind="hybrid_lm")
        frame = {"db": "lm", "set": h.sid, "sid": h.sid,
                 "tokens": _prompt(np.random.default_rng(3), 90),
                 "new_tokens": 4, IDEMPOTENCY_KEY: "turn-1-token"}
        rep1 = c._request(MsgType.GENERATE, dict(frame), codec=CODEC_PICKLE)
        assert rep1["steps"] == 94
        # the daemon's own token cache forgets; the record that travels
        # with the session does not
        ctl._idem = type(ctl._idem)()
        rep2 = c._request(MsgType.GENERATE, dict(frame), codec=CODEC_PICKLE)
        assert rep2["steps"] == 94
        assert np.asarray(rep2["ids"]).tobytes() \
            == np.asarray(rep1["ids"]).tobytes()
        assert ctl.sessions.table.steps(h.sid) == 94
        rep3 = c._request(MsgType.GENERATE,
                          dict(frame, tokens=np.zeros(0, np.int32),
                               **{IDEMPOTENCY_KEY: "turn-2-token"}),
                          codec=CODEC_PICKLE)
        assert rep3["steps"] == 98
        h.close()
        c.close()


def test_warm_turns_compile_nothing_and_one_copy_of_the_weights(tmp_path):
    with _daemon(tmp_path) as ctl:
        c = _deploy(ctl)
        h = c.open_session("lm", kind="hybrid_lm")
        rng = np.random.default_rng(11)
        h.generate(tokens=_prompt(rng, 150), new_tokens=3)   # 128 + 64
        misses0 = c.collect_stats()["metrics"]["compile"]["misses"]
        traces0 = decode_mod.decode_stats()["traces"]
        h.generate(tokens=_prompt(rng, 130), new_tokens=5)
        h.generate(tokens=_prompt(rng, 60), new_tokens=2)
        assert c.collect_stats()["metrics"]["compile"]["misses"] == misses0
        assert decode_mod.decode_stats()["traces"] == traces0
        # the registered parameters ARE the stored arrays
        reg = ctl.sessions.runtime._reg("lm")
        for name in ("embed", "l00.w_in", "l03.w_qkv", "l00.conv"):
            assert reg["params"][name] is ctl.library.get_tensor(
                "lm", name).data
        with pytest.raises(Exception, match="caches"):
            h.generate(tokens=_prompt(rng, 200), new_tokens=40)
        h.close()
        c.close()


@pytest.mark.parametrize("options,refused", [
    ({"xla_cpu_enable_fast_min_max": True}, None),
    ({"no_such_xla_option": "1"}, "no_such_xla_option")])
def test_a_specs_xla_options_reach_the_compiler(tmp_path, options,
                                                refused):
    """A model is compiled with XLA's defaults unless its spec, the
    database's record, names options: then the step and prefill
    programs are compiled with them (an unknown one is refused by
    name, which is the proof that the compiler was handed it)."""
    assert "xla_options" not in SPEC
    spec = dict(SPEC, xla_options=options)
    with _daemon(tmp_path) as ctl:
        _deploy(ctl, spec).close()
        rt = ctl.sessions.runtime
        assert rt.register_model("lm", "hybrid_lm")["xla_options"] == options
        slab = rt.new_slab("lm")
        ids = np.zeros(64, np.int32)
        if refused:
            with pytest.raises(Exception, match=refused):
                rt.prefill("lm", slab, 0, ids, 3, 5)
        else:
            slab = rt.prefill("lm", slab, 0, ids, 3, 5)
            slab, outs = rt.step("lm", slab, np.arange(4) == 0)
            assert outs["ids"].shape == (4,)


def test_shipped_weights_and_spec_install_as_the_deployment_did(tmp_path):
    """What a daemon ships to a worker that adopts a session (dense
    weights and the spec) installs through the worker's own library to
    the same registered model: same spec, same stored blocks, no copy."""
    with _daemon(tmp_path) as ctl:
        _deploy(ctl).close()
        rt = ctl.sessions.runtime
        spec = rt.register_model("lm", "hybrid_lm")
        assert rt.stores_spec("lm")
        shipped = ctl.sessions._export_weights("lm")
        rt.install_model("lm2", "hybrid_lm", shipped, spec)
        assert rt.register_model("lm2", "hybrid_lm") == spec
        for name in ("embed", "l00.w_in", "l03.w_qkv", "l00.conv"):
            a = ctl.library.get_tensor("lm", name)
            b = ctl.library.get_tensor("lm2", name)
            assert a.meta.block_shape == b.meta.block_shape
            assert rt._reg("lm2")["params"][name] is b.data
            np.testing.assert_array_equal(np.asarray(a.data, np.float32),
                                          np.asarray(b.data, np.float32))
