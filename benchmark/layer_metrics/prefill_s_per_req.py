"""Device seconds per request of the prefill programs (``hybrid_lm_prefill``, every chunk length).

From the device trace's ``XLA Modules`` line: prefill is dispatched without the host waiting for
it (``session.prefill`` times the dispatch alone), so its time is the device's to give."""
import lm_trace
import spans


def read(run):
    seconds, _ = lm_trace.module_seconds(run, "hybrid_lm_prefill")
    return spans.per_request(run, seconds)
