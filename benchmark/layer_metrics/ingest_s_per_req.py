"""Seconds per request in the set write of a shipped batch: the daemon's ``store.ingest`` spans
(blocking, pad, host-to-HBM dispatch, ``put_tensor``)."""
import spans


def read(run):
    return spans.per_request(run, spans.span_seconds(
        spans.window_profiles(run), lambda n: n == "store.ingest"))
