"""Operations and bytes that a session-served sparse-expert language model's work needs.

Like ``lm_work.py``, for a configuration of ``model_type`` afmoe (``configs/trinity-large-
ep8-5l.json``): none of these knows which kernel, fusion, sorting or padding implements the
work. ``cfg`` is the configuration's file: the model's published keys, the layers built
(``first_layer``, ``num_hidden_layers``, ``num_dense_layers``) and the chip's share
(``num_experts`` held of ``experts_routed``, ``vocab_size`` rows). Tokens are the tokens the
turns asked for, (token, expert) pairs those routed to experts held here, experts touched the
distinct held experts that a step's pairs chose: the least that the routing makes a step read.
"""

import re

SLIDING, FULL = "sliding_attention", "full_attention"


def layers(cfg):
    """[(layer type, whether its feed-forward is experts)] of the layers built."""
    first = cfg.get("first_layer", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    return [(kind, j >= cfg["num_dense_layers"]) for j, kind in enumerate(kinds)]


def counts(cfg):
    """(sliding layers, full layers, expert layers)."""
    built = layers(cfg)
    return (sum(k == SLIDING for k, _ in built), sum(k == FULL for k, _ in built),
            sum(sparse for _, sparse in built))


def attention_params(cfg) -> int:
    """q, the output gate, k, v and o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (2 * hq + 2 * hkv) + hq * d


def expert_params(cfg) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg) -> int:
    return cfg["experts_routed"] * cfg["hidden_size"]


def dense_ffn_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg, sparse: bool, experts: int = 0) -> int:
    """One layer's matrices with ``experts`` of its routed experts (norm gains and the
    selection bias are vectors and are left out)."""
    if not sparse:
        return attention_params(cfg) + dense_ffn_params(cfg)
    return attention_params(cfg) + router_params(cfg) + (1 + experts) * expert_params(cfg)


def head_params(cfg) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def resident_params(cfg) -> int:
    """Every matrix parameter this chip holds: its layers with the experts held, the embedding
    and the head over its rows of the vocabulary."""
    return sum(layer_params(cfg, sparse, cfg["num_experts"]) for _, sparse in layers(cfg)) \
        + 2 * head_params(cfg)


def params_outside_experts(cfg, head: bool = True) -> int:
    """The matrices every token's path multiplies by whatever it is routed to: attention, the
    dense feed-forward or the shared expert and the router, and the output head. The embedding
    is a lookup."""
    return sum(layer_params(cfg, sparse) for _, sparse in layers(cfg)) \
        + (head_params(cfg) if head else 0)


def cache_bytes_per_token_layer(cfg, itemsize: int = 2) -> int:
    """One layer's key and value of one token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def attention_flops(cfg, context_sum: float) -> float:
    """q k^T and p v of one layer over ``context_sum`` (query, key) pairs, every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context_sum


def expected_pairs(cfg, tokens: float) -> float:
    """(token, expert) pairs that ``tokens`` tokens route to the experts held, over the expert
    layers, if every expert is as likely as any other: what seeded weights and ids give."""
    _, _, sparse = counts(cfg)
    return tokens * sparse * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["experts_routed"]


def tokens_flops(cfg, tokens: float, pairs: float, full_context_sum: float,
                 window_context_sum: float) -> float:
    """``tokens`` tokens through the matrices outside the experts (no head), ``pairs`` (token,
    expert) pairs through an expert each, attention of a full layer over ``full_context_sum``
    pairs of query and visible key and of a sliding layer over ``window_context_sum``."""
    sliding, full, _ = counts(cfg)
    return (2.0 * params_outside_experts(cfg, head=False) * tokens
            + 2.0 * expert_params(cfg) * pairs
            + full * attention_flops(cfg, full_context_sum)
            + sliding * attention_flops(cfg, window_context_sum))


def model_flops(cfg, c, decode_pairs: float) -> float:
    """What the window's tokens need. ``c``: the client's counters of the window's turns
    (``deployments/lm_sessions_moe.py``); ``decode_pairs``: the pairs the decode steps routed
    to held experts, as the program counted them; the prompt tokens' pairs by expectation (a
    prefill chunk returns nothing to the host). The head for the generated tokens only."""
    return (tokens_flops(cfg, c["lm_prompt_tokens"], expected_pairs(cfg, c["lm_prompt_tokens"]),
                         c["lm_prefill_context_sum"], c["lm_prefill_window_sum"])
            + decode_flops(cfg, c, decode_pairs))


def decode_flops(cfg, c, decode_pairs: float) -> float:
    return (tokens_flops(cfg, c["lm_new_tokens"], decode_pairs, c["lm_decode_context_sum"],
                         c["lm_decode_window_sum"])
            + 2.0 * head_params(cfg) * c["lm_new_tokens"])


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


_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]+)\]")


def touches_experts(hlo_text: str, cfg) -> bool:
    """Whether an HLO instruction reads an array of routed experts' matrices: one of three
    dimensions or more whose last two are an expert matrix's (width x hidden or hidden x width:
    the held experts' stack, or a stack gathered from it, a tile an expert), or one with as
    many elements as all held experts' gate and up matrices, or as their down matrices, in
    whatever shape the program stores them."""
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    for dims in _ARRAY.findall(hlo_text):
        sizes = [int(n) for n in dims.split(",") if n]
        if len(sizes) >= 3 and tuple(sizes[-2:]) in ((fe, d), (d, fe)):
            return True
        n = 1
        for size in sizes:
            n *= size
        if sizes and n in (held * 2 * fe * d, held * d * fe):
            return True
    return False
