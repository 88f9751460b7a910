"""The delta rule's chunked form's share of its roofline, in percent.

The operations counted are those of the ``hybrid_lm_prefill`` programs that work on the chunked
form's per-head chunk matrices or states (``lm_work.touches_chunk_solve``). Least time: the
form's operations (``lm_work.gdn_prefill_flops``) and bytes for the prompt tokens the window's
turns brought."""
import lm_trace
import lm_work
import peaks
import work


def read(run):
    c, cfg = run["client_counters"], run["cfg"]
    if "lm_prompt_tokens" not in c:
        return None
    seconds = lm_trace.op_seconds_in(run, "hybrid_lm_prefill",
                                     lambda text: lm_work.touches_chunk_solve(text, cfg))
    _, chunks = lm_trace.module_seconds(run, "hybrid_lm_prefill")
    if not seconds:
        return None
    least = work.roofline_seconds(lm_work.gdn_prefill_flops(cfg, c["lm_prompt_tokens"]),
                                  lm_work.gdn_prefill_bytes(cfg, c["lm_prompt_tokens"], chunks),
                                  peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
