"""Bytes on the socket per request, both ways: the daemon's ``serve.wire.bytes_in`` and
``serve.wire.bytes_out`` (header, segment table, body and segments of every workload frame and of
what answered it), after the window less before."""
import spans


def read(run):
    got = [spans.registry_delta(run, "counters", name)
           for name in ("serve.wire.bytes_in", "serve.wire.bytes_out")]
    if None in got:
        return None
    return spans.per_request(run, sum(got))
