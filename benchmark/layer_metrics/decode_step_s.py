"""Seconds a decode step, as the daemon's host sees it: the mean ``session.step`` span.

A step's span runs from when the device was free for it (the step before it was read, or its
own dispatch if that came later) to its outputs on the host, so consecutive steps tile the
timeline and a prefill chunk dispatched between two steps falls into the later one's span."""
import spans


def read(run):
    found = [s["duration_s"] for p in spans.window_profiles(run) for s in p.get("spans", ())
             if s["name"] == "session.step"]
    return sum(found) / len(found) if found else None
