"""Operations and bytes that a session-served language model's work needs, from its shapes.

Like ``work.py``: none of these knows which kernel, fusion or padding implements
the work. Tokens are the tokens the turns asked for (``lm_sessions.Ops`` counts
them on the client's side), not the rows a batch or a chunk was padded to.
``cfg`` is the configuration's file: a model's published keys.
"""

LINEAR, FULL = "linear_attention", "full_attention"


def delta_chunk(cfg) -> int:
    """Tokens a chunk of the delta rule's chunked form: the algorithm's own parameter."""
    return int(cfg.get("delta_chunk", 64))


def _layers(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def counts(cfg):
    kinds = _layers(cfg)
    return sum(k == LINEAR for k in kinds), sum(k == FULL for k in kinds)


def layer_matrix_params(cfg, kind: str) -> int:
    """Parameters of one layer's matrices (norm gains, gates' biases and the convolution's taps
    are vectors and are left out)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    ffn = 3 * d * f
    if kind == FULL:
        return 4 * d * d + ffn
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return d * (2 * h * dk + 2 * h * dv) + h * dv * d + 2 * h * d + ffn


def matrix_params(cfg, head: bool = True) -> int:
    """All matrices a token's path multiplies by: every layer's, and the output head's.
    The embedding is a lookup."""
    total = sum(layer_matrix_params(cfg, k) for k in _layers(cfg))
    return total + (cfg["vocab_size"] * cfg["hidden_size"] if head else 0)


def state_bytes_per_slot_layer(cfg, itemsize: int = 2) -> int:
    """One linear layer's recurrent state (float32) and convolution window of one session."""
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return 4 * h * dk * dv + itemsize * (cfg["linear_conv_kernel_dim"] - 1) * h * (2 * dk + dv)


def cache_bytes_per_token_layer(cfg, itemsize: int = 2) -> int:
    """One full layer's key and value of one token."""
    return 2 * cfg["hidden_size"] * itemsize


def delta_rule_flops_per_token(cfg) -> float:
    """One token of the rule as written, one layer: decay, k^T S, the rank-one update, S^T q."""
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return 7.0 * h * dk * dv


def attention_flops(cfg, context_sum: float) -> float:
    """q k^T and p v of one full layer over ``context_sum`` (query, key) pairs."""
    return 4.0 * cfg["hidden_size"] * context_sum


def model_flops(cfg, prompt_tokens: float, new_tokens: float, prefill_context_sum: float,
                decode_context_sum: float) -> float:
    """What the window's tokens need: every token through every layer's matrices, the delta
    rule and attention over its context; the output head for the generated tokens only (a
    prompt token's logits are never asked for)."""
    n_lin, n_full = counts(cfg)
    tokens = prompt_tokens + new_tokens
    return (2.0 * matrix_params(cfg, head=False) * tokens
            + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * new_tokens
            + n_lin * delta_rule_flops_per_token(cfg) * tokens
            + n_full * attention_flops(cfg, prefill_context_sum + decode_context_sum))


def decode_steps_bytes(cfg, steps: float, new_tokens: float, decode_context_sum: float,
                       itemsize: int = 2) -> float:
    """Over ``steps`` decode steps that produced ``new_tokens`` tokens in all: the matrices read
    once a step, each live session's recurrent state and window read and written, its cache
    read up to its length and one token's keys and values written."""
    n_lin, n_full = counts(cfg)
    return (steps * matrix_params(cfg) * itemsize
            + new_tokens * n_lin * 2 * state_bytes_per_slot_layer(cfg, itemsize)
            + n_full * cache_bytes_per_token_layer(cfg, itemsize) * (decode_context_sum
                                                                     + new_tokens))


def decode_steps_flops(cfg, new_tokens: float, decode_context_sum: float) -> float:
    n_lin, n_full = counts(cfg)
    return (2.0 * matrix_params(cfg) * new_tokens
            + n_lin * delta_rule_flops_per_token(cfg) * new_tokens
            + n_full * attention_flops(cfg, decode_context_sum))


def gdn_step_bytes(cfg, new_tokens: float) -> float:
    """The rule's one-token update over all linear layers: the float32 state read and written."""
    n_lin, _ = counts(cfg)
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return new_tokens * n_lin * 2 * 4.0 * h * dk * dv


def gdn_step_flops(cfg, new_tokens: float) -> float:
    n_lin, _ = counts(cfg)
    return new_tokens * n_lin * delta_rule_flops_per_token(cfg)


def gdn_prefill_flops(cfg, prompt_tokens: float) -> float:
    """The chunked (WY) form over all linear layers, by the token: per chunk of C tokens and
    head, k k^T and q k^T (2 C^2 dk each), the unit-triangular solve for dk + dv right-hand
    sides (C^2 (dk + dv)), W S and Q S (2 C dk dv each), (q k^T) U (2 C^2 dv), and the state's
    update (2 C dk dv)."""
    n_lin, _ = counts(cfg)
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    c = delta_chunk(cfg)
    per_chunk = 4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv
    return prompt_tokens * n_lin * h * per_chunk / c


def gdn_prefill_bytes(cfg, prompt_tokens: float, chunks: float) -> float:
    """q, k, v read and o written in float32 a token; the state read and written a prefill chunk."""
    n_lin, _ = counts(cfg)
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return n_lin * 4.0 * h * (prompt_tokens * (2 * dk + 2 * dv) + chunks * 2 * dk * dv)


def touches_state(hlo_text: str, cfg) -> bool:
    """Whether an HLO instruction reads or writes an array of recurrent states: one whose last
    two dimensions are (dk, heads x dv), the layout the program keeps them in, or whose last
    three are (heads, dk, dv)."""
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return f"{dk},{h * dv}]" in hlo_text or f"{h},{dk},{dv}]" in hlo_text


def touches_chunk_solve(hlo_text: str, cfg) -> bool:
    """Whether an HLO instruction works on the chunked form's per-head chunk matrices or on its
    states: arrays with (heads, C, C), (heads, C, dk + dv), (heads, C, dk) or (heads, C, dv)
    last, or a state (``touches_state``)."""
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    c = delta_chunk(cfg)
    return touches_state(hlo_text, cfg) or any(
        f"{h},{a},{b}]" in hlo_text for a, b in ((c, c), (c, dk + dv), (c, dk), (c, dv)))
