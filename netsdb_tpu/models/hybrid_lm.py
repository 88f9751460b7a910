"""A hybrid language model served through sessions: gated-delta-rule
(linear-attention) layers beside full-attention layers, one state slab.

The decode kind ``hybrid_lm`` of ``models/decode.py``. Weights are sets
of the model's database, one set a tensor (``l03.w_in``, ``embed``,
...), stored ``[out, in]`` the way ``models/ff.py`` stores its layers,
the projections that share an input stacked into one tensor (a linear
layer's ``w_in`` = q, k, v, output gate, beta, alpha; a full layer's
``w_qkv``; every layer's ``w_gate_up``), so that a layer is four
products; the
model's *spec* (layer types, widths, slots, cache tokens a slot, prefill
chunk lengths) is the one record of the object set ``spec`` and is what
``SESSION_OPEN`` reads. Nothing here comes from ``Configuration``.

The block (reordered norm, no biases)::

    h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

* full-attention mixer: ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm over
  the whole width of ``q`` and ``k``; no rotary embedding; causal
  ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``.
* linear-attention mixer: ``q~, k~, v~ = W_q x, W_k x, W_v x`` through a
  causal depthwise convolution of width ``conv_k`` and SiLU; per head
  ``q = l2norm(q) / sqrt(dk)``, ``k = l2norm(k)``; ``beta = 2
  sigmoid(W_b x)``; ``alpha = exp(-exp(A_log) softplus(W_a x +
  dt_bias))``; the gated delta rule (``ops/delta_rule.py``); ``o =
  RMSNorm_head(o) * silu(W_g x)``; ``W_o``.

State of one session, all of it in the model's slab (a slot axis in
every array, ``storage/devcache.SessionSlab``): for each linear layer
``i`` a recurrent state ``S{i}[slot, dk, H dv]`` float32,
``conv[lin_layer, conv_k - 1, slot, H (2 dk + dv)]``, for each full
layer ``i`` a key cache ``k{i}[slot, heads, cache_tokens + margin,
head_dim]`` and a value cache ``v{i}`` alike,
``pos[slot]`` (tokens consumed) and ``tok[slot]`` (the next input
token: the last id appended or generated, not consumed yet). The axes
are ordered for the chip's (8, 128) tiles: a state's heads lie along
the lanes (``dv`` = 192 alone would pad to 256), a cache's two minor
axes are tokens and ``head_dim``, and the three rows of a convolution
window are not a minor axis: no array is padded. Each full layer's
caches and each linear layer's states are arrays of their own: a
step's attention takes the caches as they stand (its kernel fetches of
each slot's cache the blocks the slot's own length reaches; a cache
whose shape the kernel does not take is read whole), and its
delta-rule kernel writes the donated states in place. Cut out of one
array with a layer axis they were copied, a gigabyte a layer a step.

Two programs over that slab, both donating it:

* ``prefill``: one slot, one chunk of ``C`` token ids of which
  ``n_valid`` count. Consumes them (the delta rule in its chunked form,
  attention over the slot's cache) and produces no logits.
* ``step``: every slot at once (row = slot, so no gather or scatter of
  state), masked by ``active``. Consumes ``tok``, writes the argmax of
  the float32 logits back to ``tok`` and returns ids and logits.

A spec may widen the block, key by key (a model without the key keeps
the block above; ``make_spec`` says what each key is): grouped-query
heads (``kv_heads``), ``sliding_attention`` layers whose keys are
visible for ``window`` tokens and whose caches are rings of ``window``
rows plus the margin whatever the session's length, rotary positions on
those layers (``rope_theta``), RMSNorm per head on ``q`` and ``k``
(``qk_norm`` "head"), a sigmoid output gate on attention
(``attn_gate``), a norm before each branch as well as after it
(``pre_norms``: four a layer), the embedding scaled (``embed_scale``),
and a feed-forward that is dense on the first ``dense_layers`` layers
and sparse experts (``moe``) on the rest: sigmoid routing over all
``experts``, the ``top_k`` chosen by score plus a selection bias, a
shared expert, and of the routed experts those HELD here
(``ops/experts.py``; the others' part of the result is left out, as on
a chip that shares the layer with others). The step then returns, after
its ids, how many (token, expert) pairs it routed to held experts, how
many distinct held experts they touched and the largest load of one
(``STEP_COUNTS``), summed (the load: the largest) over its expert
layers.

dtypes: weights and matrix operands in the weights' dtype (bfloat16 as
deployed), products accumulate in float32, the residual stream, norms,
softmax, the recurrent state and logits float32; the convolution state
and the key/value cache take the weights' dtype.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.ops.attention import (DECODE_BLOCK, cache_write_rows,
                                      cached_attention, decode_attention,
                                      decode_attention_fits,
                                      prefill_attention,
                                      prefill_attention_fits, prefill_tiles,
                                      prefill_walk)
from netsdb_tpu.ops import experts
from netsdb_tpu.ops.delta_rule import (chunk_kernel_fits,
                                       gated_delta_chunked,
                                       gated_delta_step_flat, heads_first,
                                       heads_on_lanes, step_kernel_fits)

KIND = "hybrid_lm"
SPEC_SET = "spec"
LINEAR, FULL = "linear_attention", "full_attention"
SLIDING = "sliding_attention"
#: what a step of a model with expert layers returns after its ids
STEP_COUNTS = ("pairs", "experts_touched", "max_load")
#: rows of a tile of the grouped expert product: a decode step's few
#: pairs an expert, a prefill chunk's many
STEP_TILE, PREFILL_TILE = 16, 64
#: where a prefill chunk's attention is not the kernel (``_fused``): it
#: reads a slot's cache in one pass while its float32 scores stay under
#: this many bytes, else in blocks of ``PREFILL_ATTN_BLOCK`` keys
PREFILL_ATTN_WHOLE = 1 << 29
PREFILL_ATTN_BLOCK = 1024


# --- the spec ---------------------------------------------------------

def make_spec(*, layer_types, hidden, intermediate, vocab, heads, head_dim,
              lin_heads, lin_dk, lin_dv, conv_k=4, eps=1e-6, slots=16,
              cache_tokens=4096, prefill_chunks=(128, 512),
              delta_chunk=64, dtype="bfloat16",
              xla_options=None, kv_heads=None, window=None,
              rope_theta=None, qk_norm=None, attn_gate=False,
              pre_norms=False, embed_scale=None, dense_layers=None,
              moe=None) -> Dict[str, Any]:
    """The record the database holds for a model. ``prefill_chunks``
    are multiples of ``delta_chunk``, the tokens a chunk of the delta
    rule's chunked form; the largest is the cache's margin, rounded up
    to the attention block. ``xla_options`` ({name: value}) are handed
    to the compiler with the model's step and prefill programs; a
    model that names none is compiled with XLA's defaults.

    The keys that widen the block are in the spec only where given:
    ``kv_heads`` (key/value heads, a divisor of ``heads``), ``window``
    (tokens a ``sliding_attention`` layer sees, the newest included),
    ``rope_theta`` (rotary positions on the sliding layers, halves of a
    head rotated against each other), ``qk_norm`` ("head": the gains of
    ``q_norm`` and ``k_norm`` are one head wide), ``attn_gate`` (``W_o
    (o * sigmoid(W_g u))``, the gate's rows stacked under ``w_qkv``'s),
    ``pre_norms`` (``a = x + N2(Attn(N1 x)); y = a + N4(F(N3 a))``),
    ``embed_scale`` (the embedding's factor), ``dense_layers`` with
    ``moe`` (``{"experts", "top_k", "intermediate", "route_scale",
    "first", "held"}``: layers from ``dense_layers`` on route each token
    to ``top_k`` of ``experts`` experts of width ``intermediate``, of
    which this deployment holds ``held`` from number ``first`` on, and
    add a shared expert of the same width)."""
    chunks = sorted(int(c) for c in prefill_chunks)
    if any(c % int(delta_chunk) for c in chunks):
        raise ValueError(f"prefill chunks {chunks} must be multiples of "
                         f"{delta_chunk}")
    spec = {"kind": KIND, "layer_types": list(layer_types),
            "hidden": int(hidden), "intermediate": int(intermediate),
            "vocab": int(vocab), "heads": int(heads),
            "head_dim": int(head_dim), "lin_heads": int(lin_heads),
            "lin_dk": int(lin_dk), "lin_dv": int(lin_dv),
            "conv_k": int(conv_k), "eps": float(eps), "slots": int(slots),
            "cache_tokens": int(cache_tokens), "prefill_chunks": chunks,
            "delta_chunk": int(delta_chunk), "dtype": str(dtype)}
    if xla_options:
        spec["xla_options"] = dict(xla_options)
    wide = {"kv_heads": kv_heads and int(kv_heads),
            "window": window and int(window),
            "rope_theta": rope_theta and float(rope_theta),
            "qk_norm": qk_norm, "attn_gate": bool(attn_gate),
            "pre_norms": bool(pre_norms),
            "embed_scale": embed_scale and float(embed_scale),
            "dense_layers": None if moe is None else int(dense_layers or 0),
            "moe": moe and dict(moe)}
    spec.update({k: v for k, v in wide.items() if v not in (None, False)})
    if SLIDING in spec["layer_types"] and not (window
                                              and window >= chunks[-1]):
        raise ValueError("sliding_attention layers need a window of a "
                         "prefill chunk at least")
    return spec


def _kv_heads(spec) -> int:
    return spec.get("kv_heads", spec["heads"])


def _is_expert_layer(spec, i: int) -> bool:
    return "moe" in spec and i >= spec["dense_layers"]


def cache_rows(spec, kind: str = FULL) -> int:
    """Rows of a slot's key/value cache as allocated, by layer type: the
    tokens it may hold (a full layer the session's, a sliding layer its
    window's: a ring, whatever the session's length) plus one largest
    prefill chunk (a padded chunk is written whole at ``pos``), rounded
    up to whole blocks of the decode step's attention kernel."""
    tokens = spec["window"] if kind == SLIDING else spec["cache_tokens"]
    rows = tokens + max(spec["prefill_chunks"])
    return -(-rows // DECODE_BLOCK) * DECODE_BLOCK


def _ragged(spec, kind: str = FULL) -> bool:
    """Whether the step's attention is the kernel that reads each
    slot's cache up to its own length: the cache's shape decides."""
    return decode_attention_fits(cache_rows(spec, kind), spec["head_dim"],
                                 spec["dtype"])


def cache_rows_read(spec, lengths) -> Tuple[int, int]:
    """(rows fetched, rows held) of the attention layers' caches by ONE
    decode step whose live slots see ``lengths`` keys each, in rows (a
    token's keys and values of one layer), by layer type: the kernel
    fetches what a live slot sees in whole blocks (of a full layer its
    length, of a sliding layer its window at most, from the block that
    holds the oldest visible key) and one block of an idle slot, the
    whole pass everything the slab holds."""
    fetched_all = held_all = 0
    for kind in (FULL, SLIDING):
        layers = sum(t == kind for t in spec["layer_types"])
        if not layers:
            continue
        rows = cache_rows(spec, kind)
        held = fetched = spec["slots"] * rows
        if _ragged(spec, kind):
            blocks = spec["slots"] - len(lengths)
            for n in lengths:
                seen, first = int(n), 0
                if kind == SLIDING:
                    seen = min(seen, spec["window"])
                    first = (int(n) - seen) % rows % DECODE_BLOCK
                blocks += -(-(first + seen) // DECODE_BLOCK)
            fetched = DECODE_BLOCK * blocks
        fetched_all += layers * fetched
        held_all += layers * held
    return fetched_all, held_all


def _fused(spec, chunk: int, kind: str = FULL) -> bool:
    """Whether a prefill chunk's attention over a layer of this type is
    the kernel that keeps the scores on the chip: the shapes decide."""
    return prefill_attention_fits(chunk, cache_rows(spec, kind),
                                  spec["head_dim"], spec["dtype"])


def prefill_blocks_read(spec, chunk: int, pos0: int,
                        n_valid: int) -> Tuple[int, int]:
    """(key blocks read, key blocks held) by the attention of ONE
    prefill chunk of ``chunk`` queries, ``n_valid`` of which count, onto
    a slot at ``pos0`` tokens: a block is the kernel's (``prefill_tiles``)
    of one key/value head of one layer, counted once a query tile, by
    layer type, over the layers whose attention a prefill program
    computes (the last layer's feeds nothing). The kernel reads what
    ``prefill_walk`` gives a tile; held is every block of the slot's
    cache a tile, and what the block walk in plain XLA reads."""
    read_all = held_all = 0
    group = spec["heads"] // _kv_heads(spec)
    for kind in (FULL, SLIDING):
        layers = sum(t == kind for t in spec["layer_types"][:-1])
        if not layers:
            continue
        rows = cache_rows(spec, kind)
        tq, bk = prefill_tiles(chunk, rows, group, spec["dtype"])
        held = read = chunk // tq * (rows // bk)
        if _fused(spec, chunk, kind):
            window = spec["window"] if kind == SLIDING else None
            read = sum(int(prefill_walk(np, qi, pos0, n_valid, tq, bk, rows,
                                        window)[1])
                       for qi in range(chunk // tq))
        read_all += layers * _kv_heads(spec) * read
        held_all += layers * _kv_heads(spec) * held
    return read_all, held_all


def step_counts(spec) -> Dict[str, int]:
    """{"names": what a step returns after its slots' ids
    (``STEP_COUNTS``), "experts_held": the held experts its expert
    layers walk a step}; {} for a model without expert layers."""
    if "moe" not in spec:
        return {}
    layers = len(spec["layer_types"]) - spec["dense_layers"]
    return {"names": STEP_COUNTS,
            "experts_held": layers * spec["moe"]["held"]}


def _conv_width(spec) -> int:
    return spec["lin_heads"] * (2 * spec["lin_dk"] + spec["lin_dv"])


def weight_shapes(spec) -> Dict[str, Tuple[Tuple[int, int], bool]]:
    """{set name: ((rows, cols), is_matrix)} of every weight set.
    Matrices take the spec's dtype; vectors are float32 row vectors."""
    d, f, v = spec["hidden"], spec["intermediate"], spec["vocab"]
    hq = spec["heads"] * spec["head_dim"]
    hkv = _kv_heads(spec) * spec["head_dim"]
    normed = spec["head_dim"] if spec.get("qk_norm") == "head" else None
    lh, dk, dv = spec["lin_heads"], spec["lin_dk"], spec["lin_dv"]
    out = {"embed": ((v, d), True), "lm_head": ((v, d), True),
           "final_norm": ((1, d), False)}
    for i, kind in enumerate(spec["layer_types"]):
        p = f"l{i:02d}."
        out.update({p + "norm_mix": ((1, d), False),
                    p + "norm_ffn": ((1, d), False)})
        if spec.get("pre_norms"):
            out.update({p + "norm_pre_mix": ((1, d), False),
                        p + "norm_pre_ffn": ((1, d), False)})
        if _is_expert_layer(spec, i):
            # an expert's gate over its up projection, expert after
            # expert; its down projection likewise
            m = spec["moe"]
            fe = m["intermediate"]
            out.update({p + "w_router": ((m["experts"], d), True),
                        p + "route_bias": ((1, m["experts"]), False),
                        p + "w_shared_gate_up": ((2 * fe, d), True),
                        p + "w_shared_down": ((d, fe), True),
                        p + "w_experts_gate_up": ((m["held"] * 2 * fe, d),
                                                  True),
                        p + "w_experts_down": ((m["held"] * d, fe), True)})
        else:
            out.update({p + "w_gate_up": ((2 * f, d), True),
                        p + "w_down": ((d, f), True)})
        if kind in (FULL, SLIDING):
            # rows: q, k, v, and the output gate's where there is one
            gate = hq if spec.get("attn_gate") else 0
            out.update({p + "w_qkv": ((hq + 2 * hkv + gate, d), True),
                        p + "wo": ((d, hq), True),
                        p + "q_norm": ((1, normed or hq), False),
                        p + "k_norm": ((1, normed or hkv), False)})
        elif kind == LINEAR:
            # rows: q (H dk), k (H dk), v (H dv), gate (H dv), beta (H),
            # alpha (H)
            out.update({p + "w_in": ((2 * lh * (dk + dv) + 2 * lh, d), True),
                        p + "wo": ((d, lh * dv), True),
                        p + "conv": ((spec["conv_k"], _conv_width(spec)),
                                     False),
                        p + "a_log": ((1, lh), False),
                        p + "dt_bias": ((1, lh), False),
                        p + "o_norm": ((1, dv), False)})
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
    return out


def block_for(shape) -> Tuple[int, int]:
    """A block shape that divides ``shape``, so that the set is stored
    unpadded and its dense view is the stored array itself: a dimension
    of at most 512 is one block, a larger one takes its largest
    power-of-two divisor up to 512."""
    def one(n):
        if n <= 512:
            return n
        b = 512
        while b > 1 and n % b:
            b //= 2
        return b if b >= 8 else n
    return one(int(shape[0])), one(int(shape[1]))


def state_layout(spec) -> Dict[str, Dict[str, Any]]:
    """The slab's arrays: shape with the slot axis in place, dtype, the
    slot axis, and whether a slot is zeroed when a session takes it (the
    cache is not: what lies beyond ``pos`` is never read)."""
    n_lin = sum(t == LINEAR for t in spec["layer_types"])
    attn = [t for t in spec["layer_types"] if t != LINEAR]
    s = spec["slots"]
    return {
        **{f"S{i}": {"shape": (s, spec["lin_dk"],
                               spec["lin_heads"] * spec["lin_dv"]),
                     "dtype": "float32", "slot_axis": 0, "reset": True}
           for i in range(n_lin)},
        **({"conv": {"shape": (n_lin, spec["conv_k"] - 1, s,
                               _conv_width(spec)),
                     "dtype": spec["dtype"], "slot_axis": 2, "reset": True}}
           if n_lin else {}),
        **{f"{kv}{i}": {"shape": (s, _kv_heads(spec), cache_rows(spec, kind),
                                   spec["head_dim"]),
                         "dtype": spec["dtype"], "slot_axis": 0,
                         "reset": False}
           for i, kind in enumerate(attn) for kv in "kv"},
        "pos": {"shape": (s,), "dtype": "int32", "slot_axis": 0,
                "reset": True},
        "tok": {"shape": (s,), "dtype": "int32", "slot_axis": 0,
                "reset": True},
    }


def plan_chunks(spec, n_tokens: int) -> List[Tuple[int, int]]:
    """[(chunk length, tokens of it that count)] for a prefill of
    ``n_tokens``: the largest length while more than the smallest is
    left, then the smallest length that holds the rest."""
    sizes = spec["prefill_chunks"]
    out, left = [], int(n_tokens)
    while left > 0:
        size = next((c for c in sizes if c >= left), sizes[-1])
        out.append((size, min(size, left)))
        left -= size
    return out


# --- the forward pass -------------------------------------------------

def _rms(x, gain, eps):
    import jax.numpy as jnp
    from jax import lax

    x = x.astype(jnp.float32)
    # the gain is a stored (1, n) row: it broadcasts as it is
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _dense(x, w):
    """``x @ w^T`` with operands in the weight's dtype, float32 out."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(x.astype(w.dtype), w,
                           (((x.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _gated(u, w_gate_up, w_down):
    """``W_down(silu(W_gate u) * W_up u)``, the gate stacked over up."""
    import jax

    gu = _dense(u, w_gate_up)
    f = gu.shape[-1] // 2
    return _dense(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_down)


def _feed_forward(spec, p, i, h, valid, tile):
    """A layer's second branch, ``h + N(F(h))`` (with ``pre_norms``
    ``h + N4(F(N3 h))``), ``F`` dense or, on an expert layer, the shared
    expert plus the held experts' weighted part. Returns it with the
    layer's ``STEP_COUNTS`` (None on a dense layer); ``valid`` (T,)
    says which rows are tokens that count."""
    pre = f"l{i:02d}."
    eps = spec["eps"]
    u = _rms(h, p[pre + "norm_pre_ffn"], eps) if spec.get("pre_norms") else h
    if not _is_expert_layer(spec, i):
        f, counts = _gated(u, p[pre + "w_gate_up"], p[pre + "w_down"]), None
    else:
        m = spec["moe"]
        d, fe = spec["hidden"], m["intermediate"]
        idx, weights = experts.route(u, p[pre + "w_router"],
                                     p[pre + "route_bias"], m["top_k"],
                                     m["route_scale"])
        routed, counts = experts.held_experts_ffn(
            u, idx, weights, valid,
            p[pre + "w_experts_gate_up"].reshape(m["held"], 2, fe, d),
            p[pre + "w_experts_down"].reshape(m["held"], d, fe),
            m["first"], tile)
        f = routed + _gated(u, p[pre + "w_shared_gate_up"],
                            p[pre + "w_shared_down"])
    return h + _rms(f, p[pre + "norm_ffn"], eps), counts


def _add_counts(total, counts):
    """Pairs and experts touched add up over the expert layers; the
    largest load is the largest of any."""
    import jax.numpy as jnp

    if counts is None:
        return total
    if total is None:
        return counts
    return jnp.concatenate([total[:2] + counts[:2],
                            jnp.maximum(total[2:], counts[2:])])


def _lin_in(spec, p, pre, x):
    """The linear layer's one input product, cut into the convolution's
    input (q, k, v side by side), the output gate's, and the delta
    rule's gates (log alpha, beta), float32."""
    import jax
    import jax.numpy as jnp

    lh, w = spec["lin_heads"], _conv_width(spec)
    proj = _dense(x, p[pre + "w_in"])
    gate_at = w + lh * spec["lin_dv"]
    beta = 2.0 * jax.nn.sigmoid(proj[..., gate_at:gate_at + lh])
    dt = jax.nn.softplus(proj[..., gate_at + lh:] + p[pre + "dt_bias"])
    log_alpha = -jnp.exp(p[pre + "a_log"]) * dt
    return proj[..., :w], proj[..., w:gate_at], log_alpha, beta


def _split_qkv(spec, c):
    """Convolved activations (..., H (2 dk + dv)) -> normalised q, k and
    v per head."""
    import jax.numpy as jnp

    lh, dk, dv = spec["lin_heads"], spec["lin_dk"], spec["lin_dv"]
    q = c[..., :lh * dk].reshape(c.shape[:-1] + (lh, dk))
    k = c[..., lh * dk:2 * lh * dk].reshape(c.shape[:-1] + (lh, dk))
    v = c[..., 2 * lh * dk:].reshape(c.shape[:-1] + (lh, dv))

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    return l2(q) * dk ** -0.5, l2(k), v


def _lin_out(spec, p, pre, o, gate):
    """Per-head RMSNorm of the rule's output, the output gate, W_o."""
    import jax

    o = _rms(o, p[pre + "o_norm"], spec["eps"])
    o = o.reshape(o.shape[:-2] + (-1,)) * jax.nn.silu(gate)
    return _dense(o, p[pre + "wo"])


def _rope(x, pos, theta):
    """Rotary positions on ``x`` (T, heads, head_dim) at ``pos`` (T,):
    the halves of a head rotated against each other by ``pos *
    theta^(-2i / head_dim)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = (float(theta) ** (-np.arange(half) / half)).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attn_in(spec, p, pre, kind, u, pos):
    """q (T, heads, head_dim), k and v (T, kv_heads, head_dim) float32
    of tokens at ``pos`` (T,), and the output gate's input (T, heads x
    head_dim) or None."""
    heads, hkv, hd = spec["heads"], _kv_heads(spec), spec["head_dim"]
    hq, hk = heads * hd, hkv * hd
    proj = _dense(u, p[pre + "w_qkv"])
    q, k = proj[..., :hq], proj[..., hq:hq + hk]
    v = proj[..., hq + hk:hq + 2 * hk].reshape(-1, hkv, hd)
    gate = proj[..., hq + 2 * hk:] if spec.get("attn_gate") else None
    if spec.get("qk_norm") == "head":
        q, k = q.reshape(-1, heads, hd), k.reshape(-1, hkv, hd)
    q = _rms(q, p[pre + "q_norm"], spec["eps"]).reshape(-1, heads, hd)
    k = _rms(k, p[pre + "k_norm"], spec["eps"]).reshape(-1, hkv, hd)
    if kind == SLIDING and "rope_theta" in spec:
        q, k = (_rope(q, pos, spec["rope_theta"]),
                _rope(k, pos, spec["rope_theta"]))
    return q, k, v, gate


def _attn_out(p, pre, o, gate):
    """``W_o o``, ``o`` (T, heads x head_dim) under its sigmoid gate
    where there is one."""
    import jax

    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    return _dense(o, p[pre + "wo"])


def _ring_write(cache, rows, slot, pos0):
    """``rows`` (heads, C, D) into slot ``slot`` of a ring cache
    (slots, heads, R, D), chunk row ``i`` into ring row ``(pos0 + i) %
    R``, around the ring's end: two windows of ``C`` ring rows, the one
    that starts at ``pos0 % R`` (moved down to fit) and the one at the
    ring's start, each read, the chunk's rows that fall into it put in
    (the chunk rolled into place), and written back. ``R >= 2 C``."""
    import jax.numpy as jnp
    from jax import lax

    heads, c, d = rows.shape
    r = cache.shape[2]
    start = pos0 % r
    rows = rows.astype(cache.dtype)
    w = jnp.arange(c)[None, :, None]
    low = jnp.minimum(start, r - c)
    # (the window's first ring row, the chunk row its ring row w takes,
    # how far the chunk is rolled so that row lies at w)
    for base, i, shift in ((low, low + w - start, start - low),
                           (jnp.zeros_like(start), w - start + r, start - r)):
        have = lax.dynamic_slice(cache, (slot, 0, base, 0),
                                 (1, heads, c, d))[0]
        want = jnp.roll(rows, shift % c, axis=1)
        cache = lax.dynamic_update_slice(
            cache, jnp.where((i >= 0) & (i < c), want, have)[None],
            (slot, 0, base, 0))
    return cache


def _conv_taps(p, pre):
    """(conv_k, width) float32; tap ``i`` multiplies ``u_{t-i}``."""
    import jax.numpy as jnp

    return p[pre + "conv"].astype(jnp.float32)


def _ring_pos(newest, rows: int):
    """The position of the token each row of a ring holds once
    ``newest`` (a scalar, or one a slot) is written: below 0 where none
    has been yet."""
    import jax.numpy as jnp

    newest = jnp.asarray(newest)[..., None]
    return newest - (newest - jnp.arange(rows)) % rows


def _prefill_attn_block(spec, chunk: int, rows: int):
    """Keys a pass of a prefill chunk's attention over a cache of
    ``rows``: all of them (None) while the float32 scores are small."""
    if 4 * spec["heads"] * chunk * rows <= PREFILL_ATTN_WHOLE:
        return None
    return next(b for b in (PREFILL_ATTN_BLOCK, DECODE_BLOCK)
                if rows % b == 0)


def build_step(spec):
    """``step(params, slab, active) -> (slab', ids, logits)``: one token
    for every slot whose ``active`` is set; row = slot. A model with
    expert layers returns ``STEP_COUNTS`` after the slots' ids."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = spec["eps"]
    types = spec["layer_types"]
    heads, hd = spec["heads"], spec["head_dim"]
    keep = spec["conv_k"] - 1
    fits = step_kernel_fits(spec["lin_dk"],
                            spec["lin_heads"] * spec["lin_dv"])
    attn = [t for t in types if t != LINEAR]

    def hybrid_lm_step(p, slab, active):
        # runs when the program is traced, once a compiled program
        obs.REGISTRY.gauge("decode.gdn_step.fused_layers").set(
            sum(t == LINEAR for t in types) if fits else 0)
        obs.REGISTRY.gauge("decode.attn.ragged_layers").set(
            sum(_ragged(spec, t) for t in attn))
        slab = dict(slab)
        conv = slab.get("conv")          # None: no linear layer
        pos, tok = slab["pos"], slab["tok"]
        cdt = jnp.dtype(spec["dtype"])
        x = p["embed"][jnp.clip(tok, 0)].astype(jnp.float32)
        if "embed_scale" in spec:
            x = x * spec["embed_scale"]
        counts = None
        li = fi = 0
        for i, kind in enumerate(types):
            pre = f"l{i:02d}."
            if spec.get("pre_norms"):
                x_in, x = x, _rms(x, p[pre + "norm_pre_mix"], eps)
            else:
                x_in = x
            if kind == LINEAR:
                u, gate, log_alpha, beta = _lin_in(spec, p, pre, x)
                u = u.astype(cdt)
                window = jnp.concatenate([conv[li], u[None]], axis=0)
                taps = _conv_taps(p, pre)
                win32 = window.astype(jnp.float32)   # oldest input first
                c = jax.nn.silu(sum(taps[j] * win32[keep - j]
                                    for j in range(keep + 1)))
                q, k, v = _split_qkv(spec, c)
                # an idle slot's token leaves the state as it is
                log_alpha = jnp.where(active[:, None], log_alpha, 0.0)
                beta = jnp.where(active[:, None], beta, 0.0)
                slab[f"S{li}"], o = gated_delta_step_flat(
                    slab[f"S{li}"], q, k, v, log_alpha, beta)
                conv = conv.at[li].set(jnp.where(
                    active[None, :, None], window[1:], conv[li]))
                mix = _lin_out(spec, p, pre, o, gate)
                li += 1
            else:
                q, k, v, gate = _attn_in(spec, p, pre, kind, x, pos)
                rows = cache_rows(spec, kind)
                window = spec["window"] if kind == SLIDING else None
                # a sliding layer's cache is a ring; an idle slot's row
                # lands where nothing it reads lies (below)
                at = pos % rows if window else pos
                kc = cache_write_rows(slab[f"k{fi}"], k, at)
                vc = cache_write_rows(slab[f"v{fi}"], v, at)
                slab[f"k{fi}"], slab[f"v{fi}"] = kc, vc
                if _ragged(spec, kind):
                    # each slot's cache is read up to the slot's own
                    # length; an idle slot sees nothing (nobody reads
                    # its row of o)
                    o = decode_attention(q[:, None], kc, vc,
                                         jnp.where(active, pos, -1),
                                         window=window)
                else:
                    o = cached_attention(
                        q[:, None], kc, vc, pos[:, None], window=window,
                        k_pos=_ring_pos(pos, rows) if window else None)
                mix = _attn_out(p, pre, o.reshape(-1, heads * hd), gate)
                fi += 1
            h = x_in + _rms(mix, p[pre + "norm_mix"], eps)
            x, c = _feed_forward(spec, p, i, h, active, STEP_TILE)
            counts = _add_counts(counts, c)
        logits = _dense(_rms(x, p["final_norm"], eps), p["lm_head"])
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if conv is not None:
            slab["conv"] = conv
        slab.update(pos=jnp.where(active, pos + 1, pos),
                    tok=jnp.where(active, ids, tok))
        if counts is not None:
            ids = jnp.concatenate([ids, counts])
        return slab, ids, logits

    return hybrid_lm_step


def build_prefill(spec, chunk: int):
    """``prefill(params, slab, slot, tokens, n_valid, next_tok) ->
    slab'``: one slot consumes ``n_valid`` of ``chunk`` token ids (an id
    below zero stands for the slot's own ``tok``); ``next_tok >= 0``
    becomes the slot's ``tok``. No logits."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = spec["eps"]
    types = spec["layer_types"]
    heads, hd = spec["heads"], spec["head_dim"]
    keep = spec["conv_k"] - 1
    fits = chunk_kernel_fits(spec["delta_chunk"], spec["lin_dk"],
                             spec["lin_dv"])

    def hybrid_lm_prefill(p, slab, slot, tokens, n_valid, next_tok):
        # runs when the program is traced, once a compiled program
        obs.REGISTRY.gauge("prefill.gdn_chunk.fused_layers").set(
            sum(t == LINEAR for t in types) if fits else 0)
        obs.REGISTRY.gauge("prefill.attn.fused_layers").set(
            sum(t != LINEAR and _fused(spec, chunk, t) for t in types))
        slab = dict(slab)
        conv = slab.get("conv")          # None: no linear layer
        pos, tok = slab["pos"], slab["tok"]
        cdt = jnp.dtype(spec["dtype"])
        pos0 = pos[slot]
        valid = jnp.arange(chunk) < n_valid
        tokens = jnp.where(tokens < 0, tok[slot], tokens)
        x = p["embed"][tokens].astype(jnp.float32)
        if "embed_scale" in spec:
            x = x * spec["embed_scale"]
        li = fi = 0
        last = len(types) - 1
        for i, kind in enumerate(types):
            pre = f"l{i:02d}."
            if spec.get("pre_norms"):
                x_in, x = x, _rms(x, p[pre + "norm_pre_mix"], eps)
            else:
                x_in = x
            if kind == LINEAR:
                u, gate, log_alpha, beta = _lin_in(spec, p, pre, x)
                u = u.astype(cdt)
                seq = jnp.concatenate([conv[li, :, slot], u], axis=0)
                taps = _conv_taps(p, pre)
                seq32 = seq.astype(jnp.float32)
                c = jax.nn.silu(sum(
                    taps[j] * lax.dynamic_slice_in_dim(seq32, keep - j,
                                                       chunk, axis=0)
                    for j in range(keep + 1)))
                q, k, v = _split_qkv(spec, c)
                # a padded token leaves the state as it is
                log_alpha = jnp.where(valid[:, None], log_alpha, 0.0)
                beta = jnp.where(valid[:, None], beta, 0.0)
                S = slab[f"S{li}"]
                S_new, o = gated_delta_chunked(
                    heads_first(S[slot], spec["lin_heads"]), q, k, v,
                    log_alpha, beta, spec["delta_chunk"])
                slab[f"S{li}"] = S.at[slot].set(heads_on_lanes(S_new))
                conv = conv.at[li, :, slot].set(
                    lax.dynamic_slice_in_dim(seq, n_valid, keep, axis=0))
                mix = _lin_out(spec, p, pre, o, gate)
                li += 1
            else:
                at = pos0 + jnp.arange(chunk)
                q, k, v, gate = _attn_in(spec, p, pre, kind, x, at)
                rows = cache_rows(spec, kind)
                window = spec["window"] if kind == SLIDING else None
                # the whole chunk is written from pos on; what it writes
                # past n_valid lies beyond the slot's length (the
                # cache's margin; in a ring, over keys that no token
                # from here on sees)

                def put(cache, new):
                    new = jnp.moveaxis(new, 0, 1)
                    if window:
                        return _ring_write(cache, new, slot, pos0)
                    return lax.dynamic_update_slice(
                        cache, new[None].astype(cache.dtype),
                        (slot, 0, pos0, 0))

                kc, vc = put(slab[f"k{fi}"], k), put(slab[f"v{fi}"], v)
                slab[f"k{fi}"], slab[f"v{fi}"] = kc, vc
                if i == last:
                    break     # the last layer's output feeds only the head
                if _fused(spec, chunk, kind):
                    # the scores stay on the chip, and of the slot's
                    # cache only the blocks some query of the chunk sees
                    # are read
                    o = prefill_attention(q, kc, vc, slot, pos0, n_valid,
                                          window=window)
                else:
                    o = cached_attention(
                        q[None], kc, vc, jnp.where(valid, at, -1)[None],
                        block_size=_prefill_attn_block(spec, chunk, rows),
                        row0=slot, window=window,
                        k_pos=(_ring_pos(pos0 + chunk - 1, rows) if window
                               else None))
                mix = _attn_out(p, pre, o.reshape(chunk, heads * hd), gate)
                fi += 1
            h = x_in + _rms(mix, p[pre + "norm_mix"], eps)
            if i == last:
                break     # the last layer's FFN feeds only the head
            x, _ = _feed_forward(spec, p, i, h, valid, PREFILL_TILE)
        if conv is not None:
            slab["conv"] = conv
        slab.update(pos=pos.at[slot].add(n_valid),
                    tok=tok.at[slot].set(
                        jnp.where(next_tok >= 0, next_tok, tok[slot])))
        return slab

    return hybrid_lm_prefill


# --- deployment -------------------------------------------------------

def deploy(client, db: str, spec: Dict[str, Any], weights) -> Dict[str, Any]:
    """Create ``db`` and fill its sets: ``weights(name, shape,
    is_matrix)`` returns each tensor (host or device array) already in
    its dtype. ``client`` is the daemon's in-process library (the
    wire's array codec carries no bfloat16; float32 weights may come
    through a ``RemoteClient`` too). Returns the spec."""
    client.create_database(db)
    client.create_set(db, SPEC_SET)
    client.send_data(db, SPEC_SET, [dict(spec)])
    for name, (shape, is_matrix) in weight_shapes(spec).items():
        client.create_set(db, name, type_name="matrix")
        client.send_matrix(db, name, weights(name, shape, is_matrix),
                           block_for(shape))
    return spec


def random_weights(spec, seed: int):
    """A ``weights`` callback for :func:`deploy`: small seeded normal
    weights on the host (tests and smokes; the benchmark makes its own
    on the device)."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    dtype = np.dtype(getattr(ml_dtypes, spec["dtype"], None)
                     or spec["dtype"])

    def make(name, shape, is_matrix):
        leaf = name.rsplit(".", 1)[-1]
        if is_matrix:
            w = rng.standard_normal(shape) / np.sqrt(shape[1])
            return w.astype(np.float32).astype(dtype)
        if leaf == "a_log":
            return rng.uniform(-3.0, 1.0, shape).astype(np.float32)
        if leaf == "dt_bias":
            return rng.uniform(-4.0, -2.0, shape).astype(np.float32)
        if leaf == "conv":
            return (rng.standard_normal(shape) * 0.5).astype(np.float32)
        if leaf == "route_bias":
            return rng.uniform(-1 / 64, 1 / 64, shape).astype(np.float32)
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    return make
