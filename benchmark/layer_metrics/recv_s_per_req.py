"""Seconds per request the daemon spent receiving frames: its ``server.recv`` spans, from the header
landed to the last segment's last byte. The client's ``client.send`` is the other end of the same
socket and overlaps it: the two are reported side by side and never added."""
import spans


def read(run):
    return spans.per_request(run, spans.span_seconds(
        spans.window_profiles(run), lambda n: n == "server.recv"))
