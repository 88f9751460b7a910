"""Seconds per request spent waiting for admission by the scheduler (``server.sched.*`` spans)."""
import spans


def read(run):
    return spans.per_request(run, spans.span_seconds(
        spans.window_profiles(run), lambda n: n.startswith("server.sched.")))
