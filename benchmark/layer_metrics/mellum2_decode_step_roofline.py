"""The softmax-routed sparse-expert model's decode step program's share of its roofline, in
percent.

Least time: the steps' bytes (``mellum2_work.decode_bytes``: the matrices outside the experts
once a step, the distinct experts that the step's tokens chose once, by the program's counter
``decode.moe.experts_touched``, each live session's caches up to what is visible by layer type)
and operations against the chip's peaks. Time: the device seconds of the ``hybrid_lm_step``
programs in the window."""
import lm_trace
import mellum2_work
import peaks
import spans
import work


def read(run):
    c = run["client_counters"]
    touched = spans.registry_delta(run, "counters", "decode.moe.experts_touched")
    pairs = spans.registry_delta(run, "counters", "decode.moe.pairs")
    seconds, steps = lm_trace.module_seconds(run, "hybrid_lm_step")
    if not seconds or not steps or touched is None or "lm_decode_window_sum" not in c:
        return None
    least = work.roofline_seconds(
        mellum2_work.decode_flops(run["cfg"], c, pairs),
        mellum2_work.decode_bytes(run["cfg"], c, steps, touched),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
