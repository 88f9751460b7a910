"""Plain reference of the hybrid language model, and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing the program made: the weights
are made again from the seed by ``datagen``'s rule, on the host, one tensor at a
time, rounded to bfloat16 as the deployment rounds them, and used as float32.
The forward pass is float32 NumPy over a session's whole history: no cache, no
batching, no chunking, no kernels, the delta rule token by token.

The equations (``d`` hidden, no biases; the block is OLMo 2/3's reordered norm)::

    h = x + RMSNorm(Mixer(x));   y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))
    logits = W_lm RMSNorm(y_last_layer)

    full attention:  q, k = RMSNorm(W_q x), RMSNorm(W_k x) over the whole width; v = W_v x;
                     causal softmax(q k^T / sqrt(head_dim)) v per head; W_o
    linear attention (gated delta rule, Yang, Kautz, Hatamizadeh, ICLR 2025):
        c_t = silu(sum_{i<4} w_i u_{t-i}) for u in (W_q x, W_k x, W_v x)   (u_t = 0 for t < 0)
        q = l2norm(q) / sqrt(dk), k = l2norm(k) per head
        beta_t = 2 sigmoid(W_b x_t);  alpha_t = exp(-exp(A_log) softplus(W_a x_t + dt_bias))
        S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0;  o_t = S_t^T q_t
        out = W_o (RMSNorm_head(o_t) * silu(W_g x_t))

Departures from the published description (the configuration's ``assumed`` says why):
the norm placement and QK-norm are OLMo 2/3's; there is no rotary embedding because the
config's ``rope_theta`` is null; the convolution and the matrix products take their
operands as the deployment stores them (u and the weights rounded to bfloat16), since
that is the stated dtype and not an approximation of it; l2norm adds 1e-6 under the root.

What is compared, for each checked answer (a session's history and the float32 logits of
its last frame's last step, as the daemon wrote them into the session's output set):
``logit_gap_max``, the widest gap over the vocabulary between the served logits and the
reference's at that position; ``logit_gap_rms``, the root of the mean square of that gap over
the vocabulary (an average over a hundred thousand logits: it moves far less from seed to seed
than the one widest gap, and is the number that tells a lower precision apart); and ``id_gap_max``, over the ids of the last frame, how far
the reference's logit of the served id lies below the reference's largest logit at that
position (0 where the served id is the reference's argmax). ``ROUND_STATE`` and
``ROUND_PRODUCT`` are the identity; the lower-precision control
(``tests/control_lm.py``) sets them to a rounding to bfloat16.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"
THREADS = min(12, os.cpu_count() or 1)
ROW_BLOCK = 256         # rows of a matrix made in one task: small enough to stay in cache


def to_bfloat16(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32."""
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def identity(a):
    return a


def threaded(fn, *arrays, axis=0):
    """``fn`` over slices of the arrays along ``axis``, a slice a thread, joined again: the
    elementwise passes are NumPy calls that release the lock, and the arithmetic is the same."""
    pieces = [np.array_split(a, THREADS, axis) for a in arrays]
    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(fn, *pieces)), axis)


ROUND_STATE = identity      # the recurrent state after each token
ROUND_PRODUCT = identity    # every matrix product's result


# --- weights from the seed, as the deployment makes them -----------------------------------

VECTORS = {"norm_mix", "norm_ffn", "final_norm", "q_norm", "k_norm", "o_norm", "a_log",
           "dt_bias", "conv"}


def weight(cfg, seed: int, name: str, shape, rows=None) -> np.ndarray:
    """The tensor ``name`` (rows x cols) as float32; ``rows`` picks rows of a matrix."""
    import datagen   # benchmark/datagen.py: the harness has its directory on the path

    leaf = name.rsplit(".", 1)[-1]
    key = datagen.stream_key(seed, name)
    scale = cfg["data"]["scale_pow2"]
    if leaf in VECTORS:
        u = datagen.matrix(np, key, shape[0], shape[1], 0)
        if leaf == "a_log":
            return -1.0 + 2.0 * u
        if leaf == "dt_bias":
            return -3.0 + u
        if leaf == "conv":
            return u * np.float32(2.0 ** scale["conv"])
        return 1.0 + u * np.float32(0.125)
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


# --- the forward pass -------------------------------------------------------------------------

def rms(x, gain, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * gain.reshape(-1)


def silu(x):
    return x / (1.0 + np.exp(-x))


def softplus(x):
    return np.logaddexp(x, 0.0)


def dense(x, w):
    """x W^T with the operand rounded to bfloat16 as the deployment feeds it; float32 sum."""
    return ROUND_PRODUCT(threaded(to_bfloat16, x) @ w.T)


def full_attention(cfg, w, x):
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    d = h * hd
    proj = dense(x, w("w_qkv"))       # W_q, W_k, W_v stacked: one stored tensor
    q = to_bfloat16(rms(proj[:, :d], w("q_norm"), eps) * np.float32(hd ** -0.5))
    k = to_bfloat16(rms(proj[:, d:2 * d], w("k_norm"), eps))
    v = to_bfloat16(proj[:, 2 * d:])
    causal = np.tril(np.ones((t, t), bool))
    out = np.empty((t, h * hd), np.float32)

    def head(a):
        s = slice(a * hd, (a + 1) * hd)
        logits = np.where(causal, q[:, s] @ k[:, s].T, -np.inf)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        # the probabilities enter the second product in bfloat16, as the cache's dtype has it
        out[:, s] = ROUND_PRODUCT(to_bfloat16(p) @ v[:, s]) / p.sum(-1, keepdims=True)

    with ThreadPoolExecutor(THREADS) as pool:     # a head a task: the heads share nothing
        list(pool.map(head, range(h)))
    return dense(out, w("wo"))


def linear_attention(cfg, w, x):
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    width = cfg["linear_conv_kernel_dim"]
    t = x.shape[0]
    # W_q, W_k, W_v, W_g, W_b, W_a stacked in that order: one stored tensor
    proj = dense(x, w("w_in"))
    wide = h * (2 * dk + dv)
    u = threaded(to_bfloat16, proj[:, :wide])
    gate, b_in, a_in = (proj[:, wide:wide + h * dv], proj[:, wide + h * dv:wide + h * dv + h],
                        proj[:, wide + h * dv + h:])
    padded = np.concatenate([np.zeros((width - 1, u.shape[1]), np.float32), u])
    # by columns: a channel's convolution reads that channel alone
    c = threaded(lambda cols, taps: silu(sum(taps[i] * cols[width - 1 - i:width - 1 - i + t]
                                             for i in range(width))),
                 padded, w("conv"), axis=1)
    q = c[:, :h * dk].reshape(t, h, dk)
    k = c[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = c[:, 2 * h * dk:].reshape(t, h, dv)
    q = q / np.sqrt(np.sum(q * q, -1, keepdims=True) + 1e-6) * np.float32(dk ** -0.5)
    k = k / np.sqrt(np.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = 2.0 / (1.0 + np.exp(-b_in))
    alpha = np.exp(-np.exp(w("a_log").reshape(-1)) * softplus(a_in + w("dt_bias").reshape(-1)))
    S = np.zeros((h, dk, dv), np.float32)
    o = np.empty((t, h, dv), np.float32)
    for i in range(t):          # token by token: the rule as written
        S *= alpha[i][:, None, None]
        kS = np.matmul(k[i][:, None, :], S)[:, 0]
        S += k[i][:, :, None] * (beta[i][:, None] * (v[i] - kS))[:, None, :]
        S = ROUND_STATE(S)
        o[i] = np.matmul(q[i][:, None, :], S)[:, 0]
        if i % 64 == 63:
            # what has decayed below 1e-30 is set to zero: NumPy keeps subnormal numbers, whose
            # arithmetic costs a hundredfold, and a chip flushes them anyway
            S[np.abs(S) < 1e-30] = 0.0
    o = threaded(lambda o, gate: rms(o, w("o_norm"), cfg["rms_norm_eps"]).reshape(-1, h * dv)
                 * silu(gate), o, gate)
    return dense(o, w("wo"))


def shapes(cfg, kind):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    out = {"norm_mix": (1, d), "norm_ffn": (1, d), "w_gate_up": (2 * f, d), "w_down": (d, f)}
    if kind == FULL:
        out.update(w_qkv=(3 * d, d), wo=(d, d), q_norm=(1, d), k_norm=(1, d))
    else:
        out.update(w_in=(2 * h * (dk + dv) + 2 * h, d), wo=(d, h * dv),
                   conv=(cfg["linear_conv_kernel_dim"], h * (2 * dk + dv)),
                   a_log=(1, h), dt_bias=(1, h), o_norm=(1, dv))
    return out


def forward(cfg, weights, sequences, tails):
    """float32 logits (tails[n] x vocab) at the last ``tails[n]`` positions of each sequence.

    ``weights(name, shape, rows=None)`` gives a tensor as float32. Layers outermost, so that
    each tensor is made once for all sequences."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    eps = cfg["rms_norm_eps"]
    xs = [weights("embed", (v, d), rows=np.asarray(s)) for s in sequences]
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        made = {}

        def w(leaf, i=i, kind=kind, made=made):
            if leaf not in made:
                made[leaf] = weights(f"l{i:02d}.{leaf}", shapes(cfg, kind)[leaf])
            return made[leaf]

        mixer = full_attention if kind == FULL else linear_attention
        for n, x in enumerate(xs):
            h = x + rms(mixer(cfg, w, x), w("norm_mix"), eps)
            gu = dense(h, w("w_gate_up"))       # W_gate over W_up: one stored tensor
            f = cfg["intermediate_size"]
            a = threaded(lambda gu: silu(gu[:, :f]) * gu[:, f:], gu)
            xs[n] = h + rms(dense(a, w("w_down")), w("norm_ffn"), eps)
    gain = weights("final_norm", (1, d))
    head = weights("lm_head", (v, d))
    return [dense(rms(x[-t:], gain, eps), head) for x, t in zip(xs, tails)]


# --- the comparison ---------------------------------------------------------------------------

def check(cfg, seed: int, answers, rng) -> dict:
    """``answers``: list of (history ids, ids of the last frame, served float32 logits of the
    last frame's last step, turns of the session so far), one a live session. The sessions
    checked are drawn by ``rng`` among those past their first turn (all of them where none is),
    so that what is compared is a later turn: a prefill onto live state and a cache that has
    grown over several frames, in whichever slot the draw falls on.

    Returns {name: (value, limit)}; the run is correct when every value <= its limit."""
    if not answers:
        return {"answers_missing": (1.0, 0.0)}
    later = [a for a in answers if a[3] >= 2] or list(answers)
    picked = [later[i][:3] for i in rng.permutation(len(later))[:cfg["check"]["answers_checked"]]]
    for history, ids, served in picked:
        if served.shape != (cfg["vocab_size"],) or len(ids) < 1 or len(history) <= len(ids) \
                or list(history[-len(ids):]) != list(ids):
            return {"answer_shape_wrong": (1.0, 0.0)}
    # the logits that chose id j of the frame are those after the token before it
    want = forward(cfg, lambda name, shape, rows=None: weight(cfg, seed, name, shape, rows),
                   [np.asarray(h[:-1], np.int64) for h, _, _ in picked],
                   [len(ids) for _, ids, _ in picked])
    logit_gap = rms_gap = id_gap = 0.0
    for (history, ids, served), ref in zip(picked, want):
        got = np.asarray(served, np.float64)
        if not np.isfinite(got).all() or int(np.argmax(got)) != int(ids[-1]):
            return {"served_id_not_argmax_of_served_logits": (1.0, 0.0)}
        logit_gap = max(logit_gap, float(np.abs(got - ref[-1]).max()))
        rms_gap = max(rms_gap, float(np.sqrt(np.mean((got - ref[-1]) ** 2))))
        chosen = ref[np.arange(len(ids)), np.asarray(ids)]
        id_gap = max(id_gap, float((ref.max(-1) - chosen).max()))
    return {"logit_gap_max": (logit_gap, float(cfg["check"]["logit_gap_max"])),
            "logit_gap_rms": (rms_gap, float(cfg["check"]["logit_gap_rms"])),
            "id_gap_max": (id_gap, float(cfg["check"]["id_gap_max"]))}
