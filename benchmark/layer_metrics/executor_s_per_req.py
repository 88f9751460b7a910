"""Seconds per request of the executor's own host work: ``executor.*`` spans less their children."""
import spans


def read(run):
    return spans.per_request(run, spans.self_seconds(
        spans.window_profiles(run), lambda n: n.startswith("executor.")))
