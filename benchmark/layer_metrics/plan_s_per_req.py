"""Seconds per request in the planner (``planner.plan`` span)."""
import spans


def read(run):
    return spans.per_request(run, spans.span_seconds(
        spans.window_profiles(run), lambda n: n == "planner.plan"))
