"""Reductions over the program's query profiles (``obs/trace.py`` spans and counters).

A profile is one traced frame on one side of the wire: ``spans`` with ``name``,
``start_s`` (offset from the profile's start), ``duration_s`` and ``depth``, and
``counters``. The window's profiles are those whose query id a client of the
window minted. A reader that finds nothing to read gets ``None``.
"""

from __future__ import annotations


def window_profiles(run) -> list:
    """The daemon's profiles of the frames the window's clients sent."""
    qids = {p.get("qid") for p in run["client_profiles"]}
    return [p for p in run["profiles"] if p.get("qid") in qids]


def span_seconds(profiles, pred) -> float | None:
    """Sum of the durations of the spans ``pred(name)`` accepts; None where there is none."""
    found = [s["duration_s"] for p in profiles for s in p.get("spans", ()) if pred(s["name"])]
    return sum(found) if found else None


def self_seconds(profiles, pred) -> float | None:
    """Self time of the accepted spans: their duration less what their direct children cover."""
    total, seen = 0.0, False
    for p in profiles:
        spans = p.get("spans", ())
        for s in spans:
            if not pred(s["name"]):
                continue
            seen = True
            lo, hi = s["start_s"], s["start_s"] + s["duration_s"]
            children = sum(c["duration_s"] for c in spans
                           if c["depth"] == s["depth"] + 1 and lo <= c["start_s"]
                           and c["start_s"] + c["duration_s"] <= hi + 1e-9)
            total += max(0.0, s["duration_s"] - children)
    return total if seen else None


def counter_sum(profiles, name: str) -> float | None:
    found = [p["counters"][name] for p in profiles if name in p.get("counters", {})]
    return sum(found) if found else None


def per_request(run, value):
    """``value`` over the window's requests; None stays None."""
    if value is None or not run["requests"]:
        return None
    return value / run["requests"]


def registry_delta(run, *path) -> float | None:
    """after - before of one number under ``collect_stats()["metrics"]``."""
    def dig(stats):
        node = stats["metrics"]
        for key in path:
            if not isinstance(node, dict) or key not in node:
                return None
            node = node[key]
        return node if isinstance(node, (int, float)) else None
    a, b = dig(run["after"]), dig(run["before"])
    return None if a is None or b is None else a - b
