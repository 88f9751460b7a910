"""The decode step program's share of its roofline, in percent, from the device trace.

Least time: the steps' bytes (matrices once a step, live state read and written, cache read
up to each session's length; ``lm_work.decode_steps_bytes``) and operations against the chip's
peaks. Time: the device seconds of the ``hybrid_lm_step`` programs in the window."""
import lm_trace
import lm_work
import peaks
import work


def read(run):
    c = run["client_counters"]
    seconds, steps = lm_trace.module_seconds(run, "hybrid_lm_step")
    if not seconds or not steps or "lm_new_tokens" not in c:
        return None
    least = work.roofline_seconds(
        lm_work.decode_steps_flops(run["cfg"], c["lm_new_tokens"], c["lm_decode_context_sum"]),
        lm_work.decode_steps_bytes(run["cfg"], steps, c["lm_new_tokens"],
                                   c["lm_decode_context_sum"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
