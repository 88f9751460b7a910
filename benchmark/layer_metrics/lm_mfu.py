"""The whole served model's share of the chip's bf16 peak: the FLOP that the window's tokens
need (``lm_work.model_flops``: prompt and generated tokens as the callers counted them) over the
traced window's seconds, in percent."""
import lm_work
import peaks


def read(run):
    t, c = run["trace"], run["client_counters"]
    if not t.devices or t.window_s <= 0 or run["rehearsal"] or "lm_new_tokens" not in c:
        return None
    flops = lm_work.model_flops(run["cfg"], c["lm_prompt_tokens"], c["lm_new_tokens"],
                                c["lm_prefill_context_sum"], c["lm_decode_context_sum"])
    return 100.0 * flops / (t.window_s * peaks.peaks_for(run["device_kind"])["bf16_flops_per_s"])
