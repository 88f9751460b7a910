"""The softmax-routed experts' product's share of its roofline in the decode steps, in percent.

The operations counted are those of the ``hybrid_lm_step`` programs that read an array of
expert matrices (``mellum2_work.touches_experts``: a predicate on shapes, so a kernel and an XLA
form both match). Least time: each touched expert's matrices read once (the program's counter
``decode.moe.experts_touched``) and the routed pairs' operations (``decode.moe.pairs``): the
least the routing needs, so a product that read an expert twice in a step would read low,
never over 100. At 64 rows a step (512 pairs over 64 experts) nearly every expert is touched."""
import lm_trace
import mellum2_work
import peaks
import spans
import work


def read(run):
    cfg = run["cfg"]
    touched = spans.registry_delta(run, "counters", "decode.moe.experts_touched")
    pairs = spans.registry_delta(run, "counters", "decode.moe.pairs")
    if not touched or "num_experts_per_tok" not in cfg:
        return None
    # a window holds some two million operations of a few thousand texts: each text is parsed once
    seen = {}

    def touches(text):
        if text not in seen:
            seen[text] = mellum2_work.touches_experts(text, cfg)
        return seen[text]

    seconds = lm_trace.op_seconds_in(run, "hybrid_lm_step", touches)
    if not seconds:
        return None
    least = work.roofline_seconds(mellum2_work.experts_flops(cfg, pairs),
                                  mellum2_work.experts_bytes(cfg, touched),
                                  peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
