"""From the profiler's ``.xplane.pb`` to device busy time, kernel times and idle gaps.

The trace is taken in the daemon (the process on the chip) around the whole
window. A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per executed HLO operation, with a start and a duration in
nanoseconds since the session began. Busy time is the union of those intervals,
clipped to the window; idle is the rest of the window.

The window's bounds are known in the host's wall clock (the load generator's).
They are carried into the trace's clock by a marker: right after the session
starts, the launcher records ``time.time_ns()`` and opens a ``TraceAnnotation``
named ``bench_clock_sync``; the difference between the two is the offset.

Reading uses ``jax.profiler.ProfileData`` alone, which parses the file and
touches no backend.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

SYNC = "bench_clock_sync"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def short_name(hlo_text: str) -> str:
    """``%fusion.4 = f32[1024,512]{1,0:T(8,128)} fusion(...)`` -> ``fusion.4 f32[1024,512]``."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[\d,]*\])?", hlo_text)
    if not m:
        return hlo_text[:60]
    return (m.group(1) + " " + (m.group(2) or "").lstrip("(")).strip()


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length, in seconds, of the union of (start_ns, end_ns) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


@dataclass
class Reduction:
    """What the readers in ``layer_metrics/`` see of the device trace."""

    devices: dict = field(default_factory=dict)   # plane name -> [(hlo text, start_ns, dur_ns)]
    host: list = field(default_factory=list)      # [(name, start_ns, dur_ns)] of the daemon's threads
    lo_ns: float = 0.0                            # the window in the trace's clock
    hi_ns: float = 0.0
    synced: bool = False
    sync_ns: float | None = None                  # the marker's start in the trace's clock
    requests: list = field(default_factory=list)  # (start_ns, end_ns) of client requests, trace clock

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips traced."""
        if not self.devices:
            return 0.0
        per_chip = [union_seconds([(s, s + d) for _, s, d in ev], self.lo_ns, self.hi_ns)
                    for ev in self.devices.values()]
        return sum(per_chip) / len(per_chip)

    def op_seconds(self, match) -> float:
        """Device seconds, inside the window and averaged over chips, of the ops ``match`` accepts."""
        if not self.devices:
            return 0.0
        total = 0.0
        for ev in self.devices.values():
            for text, s, d in ev:
                if match(text):
                    total += max(0.0, min(s + d, self.hi_ns) - max(s, self.lo_ns))
        return total / 1e9 / len(self.devices)

    def top_ops(self, n: int = 10):
        sums = {}
        for ev in self.devices.values():
            for text, s, d in ev:
                over = max(0.0, min(s + d, self.hi_ns) - max(s, self.lo_ns))
                if over:
                    key = short_name(text)
                    sums[key] = sums.get(key, 0.0) + over / 1e9 / len(self.devices)
        return sorted(sums.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10):
        """Idle seconds of the first chip, summed by what the host side was doing meanwhile."""
        if not self.devices:
            return []
        ev = sorted((s, s + d) for _, s, d in next(iter(self.devices.values())))
        gaps, cursor = [], self.lo_ns
        for s, e in ev:
            if s > cursor and s > self.lo_ns:
                gaps.append((max(cursor, self.lo_ns), min(s, self.hi_ns)))
            cursor = max(cursor, e)
            if cursor >= self.hi_ns:
                break
        if cursor < self.hi_ns:
            gaps.append((max(cursor, self.lo_ns), self.hi_ns))
        sums = {}
        name_of = self._gap_namer()
        for a, b in gaps:
            if b > a:
                name = name_of(a, b)
                sums[name] = sums.get(name, 0.0) + (b - a) / 1e9
        return sorted(sums.items(), key=lambda kv: -kv[1])[:n]

    def _gap_namer(self):
        """(a, b) -> whether a request was in flight at the gap's middle, and the host event
        of the daemon that covers most of the gap. Bisection, since a window has thousands of each."""
        req_starts = sorted(s for s, _ in self.requests)
        req_ends = sorted(e for _, e in self.requests)
        host = sorted(self.host, key=lambda e: e[1])
        starts = [s for _, s, _ in host]
        long_ns = 1e6
        long_events = [e for e in host if e[2] > long_ns]

        def name_of(a: float, b: float) -> str:
            mid = (a + b) / 2
            if not self.synced:
                who = "clock_not_synced"
            elif bisect.bisect_right(req_starts, mid) - bisect.bisect_right(req_ends, mid) > 0:
                who = "request_in_flight"
            else:
                who = "no_request_in_flight"
            near = host[bisect.bisect_left(starts, a - long_ns):bisect.bisect_right(starts, b)]
            best, covering = 0.0, None
            for name, s, d in long_events + [e for e in near if e[2] <= long_ns]:
                over = min(s + d, b) - max(s, a)
                if over > best:
                    best, covering = over, name
            return f"{who}|host:{covering or 'nothing_traced'}"

        return name_of

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.top_ops()],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()]}


def read_file(path: str, rehearsal: bool = False) -> Reduction:
    """Planes and lines of one ``.xplane.pb``: device ops, and the host's outermost events.

    A CPU rehearsal has no device plane; there the XLA CPU client's threads stand
    in for it, so that the same code runs. Their times are never reported as a
    device's.
    """
    from jax.profiler import ProfileData

    red = Reduction()
    sync_ns = None
    for plane in ProfileData.from_file(path).planes:
        if rehearsal and plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    red.devices.setdefault("cpu-rehearsal", []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
                        if not e.name.startswith(("ThreadpoolListener", "end: ")))
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    red.devices[plane.name] = [(e.name, float(e.start_ns), float(e.duration_ns))
                                               for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                end = -1.0
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    if e.name == SYNC and sync_ns is None:
                        sync_ns = float(e.start_ns)
                    if e.start_ns >= end:  # outermost events of this thread only
                        red.host.append((e.name, float(e.start_ns), float(e.duration_ns)))
                        end = e.start_ns + e.duration_ns
    red.sync_ns = sync_ns
    return red


def reduce_dir(trace_dir: str, sync_wall_ns, window, rehearsal: bool = False) -> Reduction:
    """The newest trace under ``trace_dir``, clipped to the load generator's window.

    ``sync_wall_ns`` is (wall clock at the sync marker, wall clock at stop), as
    the launcher reported them.
    """
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under {trace_dir}")
    red = read_file(paths[-1], rehearsal)
    if not red.devices:
        raise RuntimeError(f"{paths[-1]} has no device plane with an '{OPS_LINE}' line: "
                           f"no operation ran on the device while it was traced")
    red.synced = red.sync_ns is not None
    # without the marker, the session is taken to have begun when start_trace returned
    offset = sync_wall_ns[0] - (red.sync_ns if red.synced else 0.0)
    red.lo_ns = window.opened * 1e9 - offset
    red.hi_ns = window.closed * 1e9 - offset
    red.requests = [(s * 1e9 - offset, e * 1e9 - offset) for _, s, e, _ in window.requests]
    return red
