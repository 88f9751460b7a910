"""The session-served language model deployment: how it is filled, and the turns its callers send.

``fill`` runs in the launcher (the process on the chip): it makes the model's
weights on the device from the seed, one tensor a set, through the library's
``send_matrix``, and writes the model's spec into the database. ``Ops`` runs in
the harness process and speaks to the daemon through ``RemoteClient`` and
sessions only: ``open_session``, ``SessionHandle.generate`` (token ids in, the
chosen ids back), ``last_logits`` after the window, ``close``.

A request is one TURN of a caller's live session: append a prompt of ids drawn
from the seed over the whole vocabulary, generate ``new_tokens``. A session's
first turn brings a document, later turns are short, each length drawn from the
seed by the generator's rule (``loadgen.py``: every caller from a stream of its
own, in shuffled passes over the choices); when the next turn would pass
``session_tokens`` the caller closes the session and opens a new one, so slots
are retired and reused inside the window.

Warm-up. Sixteen users of an assistant are not all in the same turn of their
session; callers that are would close their sessions, and bring their next
documents, in waves. So warm-up leaves each caller where such a user might be:
caller ``k`` sends the first ``2 + phase_k`` turns of its traffic, the phases
spread evenly over ``session_turns_mean`` (the turns a session of these lengths
lasts) and dealt to the callers by a shuffle from the seed. The callers warm up
side by side, as they run in the window. The window's own traffic is not
touched by this: it goes on from wherever each caller's session stands.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

DB = "lm"
KIND = "hybrid_lm"
VECTORS = {"norm_mix", "norm_ffn", "final_norm", "q_norm", "k_norm", "o_norm", "a_log",
           "dt_bias", "conv"}


def spec_of(cfg):
    from netsdb_tpu.models import hybrid_lm

    heads = cfg["num_attention_heads"]
    return hybrid_lm.make_spec(
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], heads=heads, head_dim=cfg["hidden_size"] // heads,
        lin_heads=cfg["linear_num_key_heads"], lin_dk=cfg["linear_key_head_dim"],
        lin_dv=cfg["linear_value_head_dim"], conv_k=cfg["linear_conv_kernel_dim"],
        eps=cfg["rms_norm_eps"], slots=cfg["slots"], cache_tokens=cfg["cache_tokens"],
        prefill_chunks=cfg["prefill_chunks"], delta_chunk=cfg["delta_chunk"],
        dtype=cfg["dtype"], xla_options=cfg.get("xla_options"))


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
        if leaf not in VECTORS:
            build = (shape, scale[leaf])
            if build not in programs:   # key is an argument: one program a shape, every seed
                programs[build] = jax.jit(lambda key: datagen.matrix(
                    jnp, key, shape[0], shape[1], scale[leaf]).astype(dtype))
            return programs[build](jnp.uint32(datagen.stream_key(seed, name)))
        u = datagen.matrix(jnp, jnp.uint32(datagen.stream_key(seed, name)), shape[0], shape[1], 0)
        if leaf == "a_log":
            return -1.0 + 2.0 * u
        if leaf == "dt_bias":
            return -3.0 + u
        if leaf == "conv":
            return u * jnp.float32(2.0 ** scale["conv"])
        return 1.0 + u * jnp.float32(0.125)

    t0 = time.time()
    hybrid_lm.deploy(library, DB, spec, weights)
    jax.block_until_ready(library.get_tensor(DB, "lm_head").data)
    return {"weights_made_s": time.time() - t0}


class Ops:
    """The client side of the deployment's request kind ``turn`` (``traffic/*.json`` names it)."""

    def __init__(self, cfg, traffic, seed):
        if traffic["request"] != "turn":
            raise ValueError(f"the lm_sessions deployment has no request kind "
                             f"{traffic['request']!r}")
        self.addr = None     # the harness sets it once the daemon listens
        self.cfg = cfg
        self.seed = seed
        p = traffic["params"]
        self.first = list(range(p["first_turn"][0], p["first_turn"][1] + 1, p["first_turn"][2]))
        self.later = list(range(p["later_turn"][0], p["later_turn"][1] + 1, p["later_turn"][2]))
        self.new_tokens = int(p["new_tokens"])
        self.session_tokens = int(p["session_tokens"])
        self.callers = int(traffic["clients"])
        # a mean session: the document, then turns of the mean length up to the limit
        later_mean = sum(self.later) / len(self.later) + self.new_tokens
        first_mean = sum(self.first) / len(self.first) + self.new_tokens
        self.session_turns_mean = 1 + int((self.session_tokens - first_mean) // later_mean)
        order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xFA5E]).permutation(
            self.callers)
        self.phase = [int(order[k]) * self.session_turns_mean // self.callers
                      for k in range(self.callers)]
        self.vocab = cfg["vocab_size"]
        self.ctxs = []

    # ---- one caller ----------------------------------------------------
    def open_client(self, k: int):
        from netsdb_tpu.serve.client import RemoteClient

        ctx = {"k": k, "client": RemoteClient(self.addr), "session": None, "history": [],
               "last": None, "turns": 0}
        self.ctxs.append(ctx)
        if k == 0:
            # every prefill chunk length the window can use, once, in a session of its own:
            # which lengths a caller's first two turns meet depends on the seed
            h = ctx["client"].open_session(DB, kind=KIND)
            for n, chunk in enumerate(sorted(self.cfg["prefill_chunks"])):
                ids = self._ids(datagen.stream_key(self.seed, f"warm{n}"), chunk + (n == 0))
                h.generate(tokens=ids, new_tokens=1, deadline_s=900.0)
            h.close()
        return ctx

    def close_client(self, ctx) -> None:
        if ctx["session"] is not None:
            try:
                ctx["session"].close()
            except Exception:  # noqa: BLE001 - the daemon may be gone already
                pass
        ctx["client"].close()

    def _ids(self, key: int, n: int) -> np.ndarray:
        """``n`` ids over the whole vocabulary from a 32-bit key, by ``datagen``'s rule."""
        h = datagen.mix(np, np.arange(n, dtype=np.uint32), key)
        return datagen.scaled(np, h, 8, 24, self.vocab).astype(np.int32)

    def schedule(self, k: int, rng):
        """Endless seeded sequence of turns for caller ``k``: (a key for the prompt's ids, the
        length if the turn opens a session, the length if it does not). Each length comes from
        shuffled passes over its choices, by the generator's rule; both are drawn every turn, so
        a document's length is the draw that falls on its session's opening turn: in effect an
        independent uniform draw."""
        def passes(choices):
            while True:
                for i in rng.permutation(len(choices)):
                    yield choices[i]

        first, later = passes(self.first), passes(self.later)
        while True:
            yield int(rng.integers(1 << 32)), next(first), next(later)

    def warm_requests(self, k: int):
        """One request, on the last caller: every caller's warm-up turns, side by side."""
        return ["warm_up"] if k == self.callers - 1 else []

    def _warm_up(self) -> int:
        """Caller ``k`` sends the first ``2 + phase_k`` turns of a seeded stream of its own (not
        the window's), on a thread a caller. Returns the rows of all of them."""
        rows = [0] * len(self.ctxs)

        def caller(ctx):
            k = ctx["k"]
            turns = self.schedule(k, np.random.default_rng(
                [self.seed & 0xFFFFFFFF, self.seed >> 32, 0x3A93, k]))
            for _ in range(2 + self.phase[k]):
                rows[k] += self.issue(ctx, next(turns))

        threads = [threading.Thread(target=caller, args=(ctx,)) for ctx in self.ctxs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(rows)

    def issue(self, ctx, turn):
        """One turn, returning when the daemon has answered it. Returns prompt + generated ids."""
        if turn == "warm_up":
            return self._warm_up()
        key, n_first, n_prompt = turn
        if ctx["session"] is not None and \
                len(ctx["history"]) + n_prompt + self.new_tokens > self.session_tokens:
            ctx["session"].close()
            ctx["session"] = None
        if ctx["session"] is None:
            ctx["session"] = ctx["client"].open_session(DB, kind=KIND)
            ctx["history"], ctx["turns"] = [], 0
            n_prompt = n_first
        prompt = self._ids(key, n_prompt)
        before = len(ctx["history"])
        ids = ctx["session"].generate(tokens=prompt, new_tokens=self.new_tokens,
                                      deadline_s=600.0)
        ctx["history"] += prompt.tolist() + ids.tolist()
        ctx["last"] = [int(i) for i in ids]
        ctx["turns"] += 1
        counters = ctx.setdefault("counters", {})
        # what the work functions need of the window's turns (benchmark/lm_work.py): tokens
        # through prefill and decode, and the cache positions the decode steps attended over
        n_new = len(ids)
        counters["lm_prompt_tokens"] = counters.get("lm_prompt_tokens", 0) + n_prompt
        counters["lm_new_tokens"] = counters.get("lm_new_tokens", 0) + n_new
        counters["lm_decode_context_sum"] = counters.get("lm_decode_context_sum", 0) + \
            n_new * (before + n_prompt) + n_new * (n_new - 1) // 2
        counters["lm_prefill_context_sum"] = counters.get("lm_prefill_context_sum", 0) + \
            n_prompt * before + n_prompt * (n_prompt - 1) // 2
        return n_prompt + n_new

    def answers(self, ctx):
        """The live session's history, its last turn's ids, that turn's last float32 logits, and
        the turns the session has had."""
        if ctx["session"] is None or not ctx["last"]:
            return []
        return [(list(ctx["history"]), list(ctx["last"]), ctx["session"].last_logits(),
                 ctx["turns"])]
