"""Idle seconds of the chip, put down to the program's spans: which layer each lies under.

The device trace says when the first chip ran nothing (``xplane.Reduction``:
the window less the union of its operations). The program's profiles say what
each request was doing meanwhile: every profile carries ``t0_unix_ns``, the wall
clock at which its trace opened, so a span begins at ``t0_unix_ns + start_s *
1e9`` on the host's wall clock, in the harness process and in the daemon alike,
and the window's own offset (``window.opened * 1e9 - trace.lo_ns``, from the
``bench_clock_sync`` marker) carries that onto the trace's clock.

Each idle interval is cut at the boundaries of the spans that cross it. A piece
belongs to the requests in flight over it (the query ids with a span that covers
it), in equal parts; within a request it goes to the deepest span that covers it
and that ``LAYERS`` knows, where any span of the daemon lies deeper than any of
the client (the daemon works inside the client's ``client.wait``). A piece that
no request covers, or only spans of no known layer, is unattributed. The parts
add up to the idle seconds of the window.

A program whose profiles carry no anchor gives nothing to read: ``by_layer`` is
then None, and so is every metric built on it.
"""

from __future__ import annotations

import bisect
import heapq

import spans

# span-name prefix -> layer key; a span of no listed prefix leaves its time to the next span up
LAYERS = (
    ("client.encode", "wire"), ("client.send", "wire"), ("client.wait", "wire"),
    ("server.recv", "wire"), ("server.decode", "wire"), ("server.reply", "wire"),
    ("store.ingest", "ingest"),
    ("server.dispatch:", "dispatch"), ("server.sched.", "dispatch"),
    ("planner.plan", "dispatch"), ("executor.", "dispatch"),
    ("models.score", "client"),
)
KEYS = ("wire", "ingest", "dispatch", "client", "unattributed")
DAEMON_RANK = 1000   # a daemon span outranks every client span of its request


def layer_of(name: str):
    for prefix, key in LAYERS:
        if name.startswith(prefix):
            return key
    return None


def idle_intervals(events, lo: float, hi: float) -> list:
    """[lo, hi] less the union of the (start, end) intervals in ``events``, as sorted intervals."""
    out, cursor = [], lo
    for s, e in sorted(events):
        if e <= cursor:
            continue
        if s >= hi:
            break
        if s > cursor:
            out.append((cursor, s))
        cursor = e
    if cursor < hi:
        out.append((cursor, hi))
    return out


def attribute(idle, covering) -> dict:
    """Seconds (in the unit of the inputs) of ``idle`` under each layer key.

    ``idle``: disjoint (lo, hi) intervals. ``covering``: spans, each as (lo, hi, rank, layer or
    None, request id).
    """
    totals = dict.fromkeys(KEYS, 0.0)
    ordered = sorted((s for s in covering if s[1] > s[0]), key=lambda s: s[0])
    cuts = sorted({t for s in ordered for t in s[:2]})
    nxt, ends, active = 0, [], {}      # active: index -> span, those that cover the piece at hand
    for lo, hi in sorted(idle):
        inner = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts, hi)]
        for a, b in zip([lo] + inner, inner + [hi]):
            while nxt < len(ordered) and ordered[nxt][0] <= a:
                active[nxt] = ordered[nxt]
                heapq.heappush(ends, (ordered[nxt][1], nxt))
                nxt += 1
            while ends and ends[0][0] <= a:
                del active[heapq.heappop(ends)[1]]
            best = {}                  # request -> (rank, layer) of its deepest known span
            for _, _, rank, layer, req in active.values():
                if layer is None:
                    best.setdefault(req, (-1, "unattributed"))
                elif rank > best.get(req, (-1, None))[0]:
                    best[req] = (rank, layer)
            if not best:
                totals["unattributed"] += b - a
            for _, layer in best.values():
                totals[layer] += (b - a) / len(best)
    return totals


def spans_on_trace_clock(run):
    """Every span of the run's profiles as ``attribute`` takes them; None without an anchor."""
    offset = run["window"].opened * 1e9 - run["trace"].lo_ns
    out, anchored = [], False
    for base, profiles in ((0, run["client_profiles"]), (DAEMON_RANK, run["profiles"])):
        for p in profiles:
            t0 = p.get("t0_unix_ns")
            if t0 is None:
                continue
            anchored = True
            for s in p.get("spans", ()):
                lo = t0 + s["start_s"] * 1e9 - offset
                out.append((lo, lo + s["duration_s"] * 1e9, base + s["depth"],
                            layer_of(s["name"]), p.get("qid")))
    return out if anchored else None


def by_layer(run):
    """Idle seconds of the window's first chip under each key of ``KEYS``, and ``idle``, their sum."""
    if "_idle_by_layer" not in run:
        t = run["trace"]
        covering = spans_on_trace_clock(run) if t.devices and t.synced else None
        if covering is None:
            run["_idle_by_layer"] = None
        else:
            ops = [(s, s + d) for _, s, d in next(iter(t.devices.values()))]
            idle = idle_intervals(ops, t.lo_ns, t.hi_ns)
            out = {k: v / 1e9 for k, v in attribute(idle, covering).items()}
            out["idle"] = sum(b - a for a, b in idle) / 1e9
            run["_idle_by_layer"] = out
    return run["_idle_by_layer"]


def per_request(run, key: str):
    """Idle seconds under ``key`` over the window's requests; None where there is nothing to read."""
    found = by_layer(run)
    return spans.per_request(run, None if found is None else found[key])
