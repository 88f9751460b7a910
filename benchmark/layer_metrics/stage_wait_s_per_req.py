"""Seconds per request the executor waited for a staged host-to-device chunk (``stage.wait_s``)."""
import spans


def read(run):
    return spans.per_request(run, spans.counter_sum(spans.window_profiles(run), "stage.wait_s"))
