"""The routed experts' product's share of its roofline in the decode steps, in percent.

The operations counted are those of the ``hybrid_lm_step`` programs that read an array of
expert matrices (``moe_work.touches_experts``: a predicate on shapes, so a kernel and an XLA
form both match). Least time: each touched expert's matrices read once (the program's counter
``decode.moe.experts_touched``) and the routed pairs' operations (``decode.moe.pairs``): the
least the routing needs, so a product that reads all held experts reads low, never over 100."""
import lm_trace
import moe_work
import peaks
import spans
import work


def read(run):
    cfg = run["cfg"]
    touched = spans.registry_delta(run, "counters", "decode.moe.experts_touched")
    pairs = spans.registry_delta(run, "counters", "decode.moe.pairs")
    if not touched or "num_experts_per_tok" not in cfg:
        return None
    seconds = lm_trace.op_seconds_in(run, "hybrid_lm_step",
                                     lambda text: moe_work.touches_experts(text, cfg))
    if not seconds:
        return None
    least = work.roofline_seconds(moe_work.experts_flops(cfg, pairs),
                                  moe_work.experts_bytes(cfg, touched),
                                  peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
