"""Seconds per request in the checksums of out-of-band segments: spans ``client.encode.checksum`` of
the client's profiles (before a frame's first byte is sent) and ``server.decode.checksum`` of the
daemon's (after its last byte has landed), each a child of a span that ``wire_s_per_req`` reads. None
on a program without the spans."""
import spans


def read(run):
    server = spans.span_seconds(spans.window_profiles(run),
                                lambda n: n == "server.decode.checksum")
    client = spans.span_seconds(run["client_profiles"], lambda n: n == "client.encode.checksum")
    if server is None and client is None:
        return None
    return spans.per_request(run, (server or 0.0) + (client or 0.0))
