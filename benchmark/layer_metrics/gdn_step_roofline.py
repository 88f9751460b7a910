"""The delta rule's one-token update's share of its roofline, in percent.

The operations counted are those of the ``hybrid_lm_step`` programs that read or write an
array of per-head recurrent states (``lm_work.touches_state``). Least time: the float32 state
of every live session and linear layer read and written once a token."""
import lm_trace
import lm_work
import peaks
import work


def read(run):
    c, cfg = run["client_counters"], run["cfg"]
    if "lm_new_tokens" not in c:
        return None
    seconds = lm_trace.op_seconds_in(run, "hybrid_lm_step",
                                     lambda text: lm_work.touches_state(text, cfg))
    if not seconds:
        return None
    least = work.roofline_seconds(lm_work.gdn_step_flops(cfg, c["lm_new_tokens"]),
                                  lm_work.gdn_step_bytes(cfg, c["lm_new_tokens"]),
                                  peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
