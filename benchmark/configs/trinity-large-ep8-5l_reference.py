"""Plain reference of the sparse-expert language model with windowed and full attention
(``model_type`` afmoe), and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing the program made: the weights are made again
from the seed by ``datagen``'s rule, on the host, one tensor at a time, rounded to bfloat16 as
the deployment rounds them, and used as float32. The forward pass is float32 NumPy over a
session's whole history: no cache, no ring, no batching, no sorting of tokens by expert; each
expert's tokens are gathered in a loop over the experts held.

The equations (``x`` the residual stream, four RMSNorms a layer, no biases)::

    x0 = sqrt(hidden) embed[id];   a = x + N2(Attn(N1 x));   y = a + N4(F(N3 a))
    logits = W_lm N(y_last_layer)

    Attn(u): q = W_q u (heads of head_dim), k = W_k u, v = W_v u (kv heads), g = W_g u
             RMSNorm with a learned gain over each head's values of q and of k
             sliding layer: rotary positions on q and k (theta, the halves of a head rotated
             against each other); key j visible to query i when i - window < j <= i
             full layer: no positions; every j <= i visible
             o = softmax(q k^T / sqrt(head_dim)) v, heads / kv heads query heads a kv head
             W_o (o * sigmoid(g))
    F dense:  W_down(silu(W_gate u) * W_up u)
    F expert: s = sigmoid(W_r u) in float32 over all routed experts; the top_k experts with
              the largest s + b; w_e = route_scale s_e / (sum of the chosen s + 1e-20);
              F(u) = E_shared(u) + sum over the chosen e THAT ARE HELD of w_e E_e(u),
              every expert a gated SiLU block. The part of the experts held elsewhere is
              left out, as on the chip this stands for.

Matrix products take their operands as the deployment stores and feeds them (weights and
activations rounded to bfloat16, float32 sums): the stated dtype and not an approximation of
it. The router's product alone takes the float32 activations as they are.

Routing is a step function. The served program and this reference see router inputs that
differ in the last digits, so near a tie between the ``top_k``-th and the next score they can
choose different experts for the same token, and the token's logits then differ by an expert's
whole output. The comparison is built for that: for each position it compares (the last
frame's), at each expert layer where the margin between the ``top_k``-th and the next
``s + b`` is under ``check.route_tie_eps``, the token's path is evaluated under both choices
(2 a layer at most, ``2 ** expert layers`` in all; only that token's own path, the history's
keys and values stand), and the gaps compared are those of the path nearest the served
logits. ``route_alternatives`` is the number of paths of the last position; ``near_tie_share``
the share of (token, expert layer) pairs of the whole history under ``eps`` (a reading, with
no limit). Earlier tokens' own near ties reach the compared logits only through attention
over thousands of keys and are part of the gaps' measured size.

What is compared, for each checked answer (a session's history and the float32 logits of its
last frame's last step): ``logit_gap_rms``, ``logit_gap_max`` and ``id_gap_max`` as the hybrid
model's reference has them, but ``logit_gap_rms`` is the root mean square over the logits of
ALL the sessions checked in a run and not the largest session's: it is the number that tells
a lower precision apart, the two lie a factor of 1.5 apart here, and a mean over several
sessions spreads less than one session's. How many a run checks is the configuration's
(``check.answers_checked``): one at full size, where a session's forward takes a minute or
more and the run has a time limit, eight at the rehearsal's. ``ROUND_PRODUCT`` is the
identity; the lower-precision control (``tests/control_trinity.py``) sets it to a rounding to
bfloat16.

What a run's time goes to is this file's forward pass, so it computes no row that no
compared position can see (``forward``, ``every_row``) and runs its elementwise passes over
slices of the rows on threads: the arithmetic of every row kept is the whole pass's.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
THREADS = min(12, os.cpu_count() or 1)
ROW_BLOCK = 256         # rows of a matrix made in one task: small enough to stay in cache
QUERY_BLOCK = 512       # queries of one attention task: its scores stay in memory


def to_bfloat16(a: np.ndarray, scratch=None) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32. With ``scratch`` (a
    uint32 array of ``a``'s shape) a contiguous float32 ``a`` is rounded where it stands."""
    if scratch is not None:
        bits = a.view(np.uint32)
        np.right_shift(bits, np.uint32(16), out=scratch)
        scratch &= np.uint32(1)
        scratch += np.uint32(0x7FFF)
        bits += scratch
        bits &= np.uint32(0xFFFF0000)
        return a
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def identity(a):
    return a


def threaded(fn, *arrays):
    """``fn`` over slices of the arrays' rows, a slice a thread, joined again: the elementwise
    passes are NumPy calls that release the lock, and the arithmetic is the same."""
    if len(arrays[0]) < 4 * THREADS:
        return fn(*arrays)
    pieces = [np.array_split(a, THREADS) for a in arrays]
    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(fn, *pieces)))


def one_blas_thread():
    """Inside a pool of threads that each call the matrix library, hold that library to one
    thread a call where ``threadpoolctl`` is there to ask (eight threads that each start eight
    more spend their time waiting for one another: 13 s for 2 s, a 4,096-token layer)."""
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:
        import contextlib
        return contextlib.nullcontext()
    return threadpool_limits(limits=1, user_api="blas")


ROUND_PRODUCT = identity    # every matrix product's result


# --- weights from the seed, as the deployment makes them -----------------------------------

VECTORS = {"norm_pre_mix", "norm_mix", "norm_pre_ffn", "norm_ffn", "final_norm", "q_norm",
           "k_norm", "route_bias"}


def weight(cfg, seed: int, name: str, shape, rows=None) -> np.ndarray:
    """The tensor ``name`` (rows x cols) as float32; ``rows`` picks rows of a matrix."""
    import datagen   # benchmark/datagen.py: the harness has its directory on the path

    leaf = name.rsplit(".", 1)[-1]
    key = datagen.stream_key(seed, name)
    scale = cfg["data"]["scale_pow2"]
    if leaf == "route_bias":
        return datagen.matrix(np, key, shape[0], shape[1], scale[leaf])
    if leaf in VECTORS:
        return 1.0 + datagen.matrix(np, key, shape[0], shape[1], 0) * np.float32(0.125)
    if rows is None:
        # in blocks of rows on a few threads: the hash is NumPy passes that release the lock
        out = np.empty(shape, np.float32)

        def make(r0):
            n = min(ROW_BLOCK, shape[0] - r0)
            out[r0:r0 + n] = to_bfloat16(datagen.matrix(np, key, n, shape[1], scale[leaf],
                                                        row0=r0, ld=shape[1]))

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(make, range(0, shape[0], ROW_BLOCK)))
        return out
    i = np.asarray(rows, np.uint32)[:, None]
    j = np.arange(shape[1], dtype=np.uint32)[None, :]
    return to_bfloat16(datagen.unit24(np, datagen.mix(np, i * np.uint32(shape[1]) + j, key),
                                      scale[leaf]))


# --- the model's shape, from the configuration's keys ------------------------------------------

def layers(cfg):
    """[(layer type, whether its feed-forward is experts)] of the layers built: the published
    ``layer_types`` from ``first_layer`` on, the first ``num_dense_layers`` of them dense."""
    first = cfg.get("first_layer", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    return [(kind, j >= cfg["num_dense_layers"]) for j, kind in enumerate(kinds)]


def shapes(cfg, sparse: bool):
    d, f, fe = cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    hd = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {"norm_pre_mix": (1, d), "norm_mix": (1, d), "norm_pre_ffn": (1, d),
           "norm_ffn": (1, d), "w_qkv": (2 * hq + 2 * hkv, d), "wo": (d, hq),
           "q_norm": (1, hd), "k_norm": (1, hd)}
    if sparse:
        routed, held = cfg["experts_routed"], cfg["num_experts"]
        out.update(w_router=(routed, d), route_bias=(1, routed),
                   w_shared_gate_up=(2 * fe, d), w_shared_down=(d, fe),
                   w_experts_gate_up=(held * 2 * fe, d), w_experts_down=(held * d, fe))
    else:
        out.update(w_gate_up=(2 * f, d), w_down=(d, f))
    return out


# --- the forward pass -------------------------------------------------------------------------

def norm(x, gain, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * gain.reshape(-1)


def rms(x, gain, eps):
    return threaded(lambda x: norm(x, gain, eps), x)


def silu(x):
    return x / (1.0 + np.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def dense(x, w):
    """x W^T with the operand rounded to bfloat16 as the deployment feeds it; float32 sum."""
    return ROUND_PRODUCT(threaded(to_bfloat16, x) @ w.T)


def gated(u, w_gate_up, w_down):
    """W_down(silu(W_gate u) * W_up u), the gate's rows stacked over up's."""
    gu = dense(u, w_gate_up)
    f = gu.shape[1] // 2
    return dense(threaded(lambda gu: silu(gu[:, :f]) * gu[:, f:], gu), w_down)


def rope(x, pos, theta):
    """Rotary positions on x (T, heads, head_dim): a head's halves rotated against each other
    by pos * theta^(-2i / head_dim); the angle is the float32 product the deployment forms."""
    half = x.shape[-1] // 2
    inv = (float(theta) ** (-np.arange(half) / half)).astype(np.float32)
    ang = (np.asarray(pos, np.float32)[:, None] * inv).astype(np.float64)
    cos, sin = (np.cos(ang).astype(np.float32)[:, None, :],
                np.sin(ang).astype(np.float32)[:, None, :])
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(cfg, w, kind, u, pos):
    """q (scaled, T x heads x head_dim), k, v (T x kv heads x head_dim), each as it enters the
    products (bfloat16), and the output gate's input."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    proj = dense(u, w("w_qkv"))       # W_q, W_k, W_v, W_g stacked: one stored tensor
    pos = np.asarray(pos)

    def heads(c0, n, gain, scale):
        # columns c0 .. of proj as n heads: normed, turned where the layer has positions,
        # scaled, and rounded as they enter the products; slices of the rows on threads
        def rows(proj, pos):
            x = proj[:, c0:c0 + n * hd].reshape(-1, n, hd)
            if gain is not None:
                x = norm(x, gain, eps)
                if kind == SLIDING:
                    x = rope(x, pos, cfg["rope_theta"])
            return to_bfloat16(x * np.float32(scale) if scale != 1.0 else x)
        return threaded(rows, proj, pos)

    return (heads(0, h, w("q_norm"), hd ** -0.5), heads(h * hd, hkv, w("k_norm"), 1.0),
            heads((h + hkv) * hd, hkv, None, 1.0), proj[:, (h + 2 * hkv) * hd:])


def attend(cfg, kind, q, q_pos, k, v, k_pos):
    """softmax(q k^T) v for queries at ``q_pos`` over keys at ``k_pos`` (ascending), by the
    layer type's rule of what is visible. (T, heads x head_dim) float32.

    A block of queries a task, its heads one after the other through two buffers that the
    task makes once: the elementwise passes over a block's scores are what this takes its time
    for, and a fresh array a pass would be paid for page by page."""
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == SLIDING else None
    out = np.empty((len(q_pos), h * hd), np.float32)

    def task(r0):
        r1 = min(r0 + QUERY_BLOCK, len(q_pos))
        qp = q_pos[r0:r1]
        # the keys any query of the block sees: a contiguous run of the ascending positions
        lo = 0 if window is None else np.searchsorted(k_pos, qp.min() - window + 1)
        hi = np.searchsorted(k_pos, qp.max(), side="right")
        kp = k_pos[lo:hi]
        seen = kp[None, :] <= qp[:, None]
        if window is not None:
            seen &= kp[None, :] > qp[:, None] - window
        hidden = np.where(seen, np.float32(0), np.float32(-np.inf))
        p, scratch = np.empty(hidden.shape, np.float32), np.empty(hidden.shape, np.uint32)
        for a in range(h):
            kv = a // (h // hkv)
            np.matmul(q[r0:r1, a], k[lo:hi, kv].T, out=p)
            p += hidden
            p -= p.max(-1, keepdims=True)
            np.exp(p, out=p)
            total = p.sum(-1, keepdims=True)
            # the probabilities enter the second product in bfloat16, as the cache's dtype
            # has it
            out[r0:r1, a * hd:(a + 1) * hd] = ROUND_PRODUCT(
                to_bfloat16(p, scratch) @ v[lo:hi, kv]) / total

    with one_blas_thread(), ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(task, range(0, len(q_pos), QUERY_BLOCK)))
    return out


def route(cfg, w, u):
    """(selection scores s + b, scores s) over all routed experts, float32."""
    s = sigmoid(ROUND_PRODUCT(u @ w("w_router").T))
    return s + w("route_bias").reshape(-1), s


def experts_part(cfg, w, u, chosen, s):
    """sum over each row's chosen experts that are held of w_e E_e(u): a loop over the held
    experts, each over the rows that chose it."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, held = cfg.get("experts_first", 0), cfg["num_experts"]
    picked = np.take_along_axis(s, chosen, axis=1)
    weights = np.float32(cfg["route_scale"]) * picked / (picked.sum(-1, keepdims=True)
                                                         + np.float32(1e-20))
    out = np.zeros((len(u), d), np.float32)
    for e in range(held):
        rows, slot = np.nonzero(chosen == first + e)
        if len(rows):
            out[rows] += weights[rows, slot][:, None] * gated(
                u[rows], w("w_experts_gate_up")[e * 2 * fe:(e + 1) * 2 * fe],
                w("w_experts_down")[e * d:(e + 1) * d])
    return out


def forward(cfg, weights, tokens, tail: int, eps: float = 0.0, every_row: bool = True):
    """The model over one history. Returns a dict: ``paths``, for each of the last ``tail``
    positions the list of float32 logits rows of its paths (its own choice of experts first,
    then each alternative within ``eps`` of it); ``margins`` (expert layers x T), the gap
    between the top_k-th and the next selection score of every token; ``chosen`` (expert layers
    x T x top_k), the reference's own choices. Without ``every_row`` a layer is computed for
    the rows that some tail position can see through the layers above it, and for no other:
    the last layer for the tail alone, a layer under a sliding one from a window before that
    one's first row on, every layer under a full one whole (each from every row's keys and
    values that its queries see); the margins of the rows left out read ``inf`` and their
    choices -1. The arithmetic of the rows kept is that of the whole pass.

    Rows 0 .. T-1 of the stream are the tokens; rows after them are alternative paths of tail
    positions, each at its token's position, attending over the tokens' keys before it and its
    own. Layers outermost, so that each tensor is made once."""
    d, v, k_top = cfg["hidden_size"], cfg["vocab_size"], cfg["num_experts_per_tok"]
    eps_n = cfg["rms_norm_eps"]
    tokens = np.asarray(tokens)
    t = len(tokens)
    pos = np.arange(t)                       # position of every row of the stream
    origin = np.arange(t)                    # the tail position a row is a path of
    x = weights("embed", (v, d), rows=tokens) * np.float32(np.sqrt(d))
    margins, choices = [], []
    built = layers(cfg)
    # need[j]: the first position whose output of layer j some tail position can see
    need = [0 if every_row else t - tail] * len(built)
    for j in range(len(built) - 1, 0, -1):
        need[j - 1] = max(0, need[j] - cfg["sliding_window"] + 1) if built[j][0] == SLIDING \
            else 0
    for j, (kind, sparse) in enumerate(built):
        made = {}

        def w(leaf, j=j, sparse=sparse, made=made):
            if leaf not in made:
                made[leaf] = weights(f"l{j:02d}.{leaf}", shapes(cfg, sparse)[leaf])
            return made[leaf]

        q, k, val, gate = qkv(cfg, w, kind, rms(x, w("norm_pre_mix"), eps_n), pos)
        o = np.empty((len(x), q.shape[1] * q.shape[2]), np.float32)
        base = int(pos[0])                   # the stream's first row is this position's
        n, first = t - base, need[j] - base  # token rows held; those this layer leaves out
        o[first:n] = attend(cfg, kind, q[first:n], pos[first:n], k[:n], val[:n], pos[:n])
        for r in range(n, len(x)):
            # an alternative path: the tokens before it, and itself in its token's place
            at = int(pos[r]) - base
            own = k[at].copy(), val[at].copy()
            k[at], val[at] = k[r], val[r]
            o[r] = attend(cfg, kind, q[r:r + 1], pos[r:r + 1], k[:at + 1], val[:at + 1],
                          pos[:at + 1])[0]
            k[at], val[at] = own
        if first:
            x, o, gate, pos, origin = (m[first:] for m in (x, o, gate, pos, origin))
        a = x + rms(dense(threaded(lambda o, gate: o * sigmoid(gate), o, gate), w("wo")),
                    w("norm_mix"), eps_n)
        u = rms(a, w("norm_pre_ffn"), eps_n)
        if not sparse:
            f = gated(u, w("w_gate_up"), w("w_down"))
        else:
            select, s = route(cfg, w, u)
            order = np.argsort(-select, axis=1, kind="stable")
            ranked = np.take_along_axis(select, order[:, :k_top + 1], axis=1)
            margin = ranked[:, k_top - 1] - ranked[:, k_top]
            chosen = order[:, :k_top]
            margins.append(np.concatenate([np.full(need[j], np.inf, np.float32),
                                           margin[:t - need[j]]]))
            choices.append(np.concatenate([np.full((need[j], k_top), -1),
                                           chosen[:t - need[j]]]))
            # a tail row near a tie forks: the same stream so far, the other choice from here
            fork = [r for r in range(len(x))
                    if origin[r] >= t - tail and margin[r] < eps]
            if fork:
                other = chosen[fork].copy()
                other[:, k_top - 1] = order[fork, k_top]
                chosen = np.concatenate([chosen, other])
                u, s, a = (np.concatenate([m, m[fork]]) for m in (u, s, a))
                pos, origin = (np.concatenate([m, m[fork]]) for m in (pos, origin))
            f = experts_part(cfg, w, u, chosen, s) + gated(
                u, w("w_shared_gate_up"), w("w_shared_down"))
        x = a + rms(f, w("norm_ffn"), eps_n)
    rows = np.nonzero(origin >= t - tail)[0]
    logits = dense(rms(x[rows], weights("final_norm", (1, d)), eps_n),
                   weights("lm_head", (v, d)))
    paths = [[logits[n] for n, r in enumerate(rows) if origin[r] == p]
             for p in range(t - tail, t)]
    return {"paths": paths, "margins": np.array(margins), "chosen": np.array(choices)}


# --- the comparison ---------------------------------------------------------------------------

def check(cfg, seed: int, answers, rng) -> dict:
    """``answers``: list of (history ids, ids of the last frame, served float32 logits of the
    last frame's last step, turns of the session so far), one a live session. The sessions
    checked are drawn by ``rng`` among those whose history has passed the window plus one
    prefill chunk, so that every ring compared has wrapped.

    Returns {name: (value, limit)}; the run is correct when every value <= its limit."""
    if not answers:
        return {"answers_missing": (1.0, 0.0)}
    lim = cfg["check"]
    wrapped = cfg["sliding_window"] + max(cfg["prefill_chunks"])
    long = [a for a in answers if len(a[0]) > wrapped]
    if not long:
        return {"no_session_past_the_window": (1.0, 0.0)}
    picked = [long[i][:3] for i in rng.permutation(len(long))[:lim["answers_checked"]]]
    for history, ids, served in picked:
        if served.shape != (cfg["vocab_size"],) or len(ids) < 1 or len(history) <= len(ids) \
                or list(history[-len(ids):]) != list(ids):
            return {"answer_shape_wrong": (1.0, 0.0)}
    eps = float(lim["route_tie_eps"])
    logit_gap = squares = id_gap = tried = near = 0.0
    for history, ids, served in picked:
        got = np.asarray(served, np.float64)
        if not np.isfinite(got).all() or int(np.argmax(got)) != int(ids[-1]):
            return {"served_id_not_argmax_of_served_logits": (1.0, 0.0)}
        # the logits that chose id j of the frame are those after the token before it
        ref = forward(cfg, lambda name, shape, rows=None: weight(cfg, seed, name, shape, rows),
                      np.asarray(history[:-1], np.int64), len(ids), eps, every_row=False)
        last = min(ref["paths"][-1], key=lambda row: float(np.mean((got - row) ** 2)))
        logit_gap = max(logit_gap, float(np.abs(got - last).max()))
        squares += float(np.mean((got - last) ** 2))     # every session as many logits
        for rows, chosen in zip(ref["paths"], ids):
            id_gap = max(id_gap, min(float(row.max() - row[chosen]) for row in rows))
        tried = max(tried, float(len(ref["paths"][-1])))
        known = ref["margins"][np.isfinite(ref["margins"])]
        near = max(near, float(np.mean(known < eps)) if known.size else 0.0)
    return {"logit_gap_max": (logit_gap, float(lim["logit_gap_max"])),
            "logit_gap_rms": (float(np.sqrt(squares / len(picked))),
                              float(lim["logit_gap_rms"])),
            "id_gap_max": (id_gap, float(lim["id_gap_max"])),
            "route_alternatives": (tried, float(2 ** sum(s for _, s in layers(cfg)))),
            "near_tie_share": (near, 1.0)}
