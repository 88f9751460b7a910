"""Operations and bytes that a session-served softmax-routed sparse-expert language model's work
needs.

Like ``moe_work.py``, for a configuration of ``model_type`` mellum (``configs/mellum2-12b-a2.5b-
8l.json``): attention without an output gate, every built layer's feed-forward routed experts
(``mlp_layer_types`` "sparse") and no shared expert, every expert of a layer held on the chip.
None of these knows which kernel, fusion, sorting or padding implements the work. ``cfg`` is
the configuration's file: the model's published keys, the layers built (``first_layer``,
``num_hidden_layers``) and the serving sizes (``slots``, ``cache_tokens``, ``prefill_chunks``).
Tokens are the tokens the turns asked for, (token, expert) pairs those routed to experts,
experts touched the distinct experts that a step's pairs chose: the least that the routing makes
a step read.
"""

import moe_work

SLIDING, FULL = "sliding_attention", "full_attention"
touches_experts = moe_work.touches_experts


def layers(cfg):
    """[(layer type, whether its feed-forward is experts)] of the layers built."""
    first = cfg.get("first_layer", 0)
    end = first + cfg["num_hidden_layers"]
    return [(kind, mlp == "sparse") for kind, mlp in
            zip(cfg["layer_types"][first:end], cfg["mlp_layer_types"][first:end])]


def counts(cfg):
    """(sliding layers, full layers, expert layers)."""
    built = layers(cfg)
    return (sum(k == SLIDING for k, _ in built), sum(k == FULL for k, _ in built),
            sum(sparse for _, sparse in built))


def attention_params(cfg) -> int:
    """q, k, v and o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (hq + 2 * hkv) + hq * d


def expert_params(cfg) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg) -> int:
    return cfg["num_experts"] * cfg["hidden_size"]


def layer_params(cfg, experts: int) -> int:
    """One layer's matrices with ``experts`` of its experts (norm gains are vectors and are
    left out)."""
    return attention_params(cfg) + router_params(cfg) + experts * expert_params(cfg)


def head_params(cfg) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def resident_params(cfg) -> int:
    """Every matrix parameter this chip holds: its layers with all their experts, the embedding
    and the head."""
    return len(layers(cfg)) * layer_params(cfg, cfg["num_experts"]) + 2 * head_params(cfg)


def params_outside_experts(cfg, head: bool = True) -> int:
    """The matrices every token's path multiplies by whatever it is routed to: attention and the
    router, and the output head. The embedding is a lookup."""
    return len(layers(cfg)) * layer_params(cfg, 0) + (head_params(cfg) if head else 0)


def cache_bytes_per_token_layer(cfg, itemsize: int = 2) -> int:
    """One layer's key and value of one token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def slab_bytes(cfg, itemsize: int = 2) -> int:
    """The session state of all slots: a full layer's cache of ``cache_tokens`` and a sliding
    layer's ring of a window, each with a margin of the longest prefill chunk."""
    sliding, full, _ = counts(cfg)
    margin = max(cfg["prefill_chunks"])
    rows = full * (cfg["cache_tokens"] + margin) + sliding * (cfg["sliding_window"] + margin)
    return cfg["slots"] * rows * cache_bytes_per_token_layer(cfg, itemsize)


def attention_flops(cfg, context_sum: float) -> float:
    """q k^T and p v of one layer over ``context_sum`` (query, key) pairs, every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context_sum


def expected_pairs(cfg, tokens: float) -> float:
    """(token, expert) pairs that ``tokens`` tokens route over the expert layers: every expert
    is held, so ``top_k`` a token and layer."""
    return tokens * counts(cfg)[2] * cfg["num_experts_per_tok"]


def tokens_flops(cfg, tokens: float, pairs: float, full_context_sum: float,
                 window_context_sum: float) -> float:
    """``tokens`` tokens through attention's and the router's matrices (no head), ``pairs``
    (token, expert) pairs through an expert each, attention of a full layer over
    ``full_context_sum`` pairs of query and visible key and of a sliding layer over
    ``window_context_sum``."""
    sliding, full, _ = counts(cfg)
    return (2.0 * params_outside_experts(cfg, head=False) * tokens
            + 2.0 * expert_params(cfg) * pairs
            + full * attention_flops(cfg, full_context_sum)
            + sliding * attention_flops(cfg, window_context_sum))


def decode_flops(cfg, c, decode_pairs: float) -> float:
    return (tokens_flops(cfg, c["lm_new_tokens"], decode_pairs, c["lm_decode_context_sum"],
                         c["lm_decode_window_sum"])
            + 2.0 * head_params(cfg) * c["lm_new_tokens"])


def model_flops(cfg, c, decode_pairs: float) -> float:
    """What the window's tokens need. ``c``: the client's counters of the window's turns
    (``deployments/lm_sessions_moe.py``); ``decode_pairs``: the pairs the decode steps routed,
    as the program counted them; the prompt tokens' pairs are ``top_k`` a token and layer (a
    prefill chunk returns nothing to the host, and no pair is dropped). The head for the
    generated tokens only."""
    return (tokens_flops(cfg, c["lm_prompt_tokens"], expected_pairs(cfg, c["lm_prompt_tokens"]),
                         c["lm_prefill_context_sum"], c["lm_prefill_window_sum"])
            + decode_flops(cfg, c, decode_pairs))


def decode_bytes(cfg, c, steps: float, experts_touched: float, itemsize: int = 2) -> float:
    """Over ``steps`` decode steps: the matrices outside the experts once a step, each touched
    expert once (``experts_touched`` summed over steps and expert layers), each live session's
    caches up to what is visible by layer type, and one token's keys and values written."""
    sliding, full, _ = counts(cfg)
    seen = full * c["lm_decode_context_sum"] + sliding * c["lm_decode_window_sum"]
    return (itemsize * (steps * params_outside_experts(cfg)
                        + experts_touched * expert_params(cfg))
            + cache_bytes_per_token_layer(cfg, itemsize)
            * (seen + (sliding + full) * c["lm_new_tokens"]))


def experts_bytes(cfg, experts_touched: float, itemsize: int = 2) -> float:
    """The touched experts' matrices, each read once."""
    return float(itemsize) * experts_touched * expert_params(cfg)


def experts_flops(cfg, pairs: float) -> float:
    return 2.0 * expert_params(cfg) * pairs
