"""Seconds per request in ``RemoteClient.send_matrix``: the batch pickled, sent, decoded and written
into the input set, on the client's clock. The program traces no span for a SEND_MATRIX frame."""


def read(run):
    seconds = run["client_counters"].get("ship_s")
    if not seconds or not run["requests"]:
        return None
    return seconds / run["requests"]
