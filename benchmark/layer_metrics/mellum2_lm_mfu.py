"""The whole served softmax-routed sparse-expert model's share of the chip's bf16 peak, in
percent: the FLOP that the window's tokens need (``mellum2_work.model_flops``: every token
through the matrices it uses, attention, router, and the experts it chose; attention over the
visible context by layer type; the head for generated tokens) over the traced window's seconds.

The decode steps' (token, expert) pairs are the program's own count (registry counter
``decode.moe.pairs``, after the window less before); a prefill chunk returns nothing to the
host, so the prompt tokens' pairs are ``top_k`` (8) a token and expert layer: every expert is
held and no pair is dropped."""
import mellum2_work
import peaks
import spans


def read(run):
    t, c = run["trace"], run["client_counters"]
    pairs = spans.registry_delta(run, "counters", "decode.moe.pairs")
    if not t.devices or t.window_s <= 0 or run["rehearsal"] or pairs is None \
            or "lm_decode_window_sum" not in c:
        return None
    flops = mellum2_work.model_flops(run["cfg"], c, pairs)
    return 100.0 * flops / (t.window_s * peaks.peaks_for(run["device_kind"])["bf16_flops_per_s"])
