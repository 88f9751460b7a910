"""Bytes of session state that crossed the host per decode step dispatched in the window:
``session.state_host_bytes`` over ``decode.batches``. 0 in a window with no eviction."""
import spans


def read(run):
    moved = spans.registry_delta(run, "counters", "session.state_host_bytes")
    steps = spans.registry_delta(run, "decode", "batches")
    if steps is None or steps <= 0:
        return None
    return (moved or 0.0) / steps
