"""Seconds per request on the wire and in the codec: the client's send spans, the daemon's decode and reply spans."""
import spans


def read(run):
    server = spans.span_seconds(spans.window_profiles(run),
                                lambda n: n in ("server.decode", "server.reply"))
    client = spans.span_seconds(run["client_profiles"], lambda n: n == "client.send")
    if server is None and client is None:
        return None
    return spans.per_request(run, (server or 0.0) + (client or 0.0))
