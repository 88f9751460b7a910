"""The device trace by program: which XLA module (jitted program) each device operation ran in.

``xplane.py`` reduces a trace to its ``XLA Ops`` line. A session-served model runs two programs
over one slab, a decode step and a prefill chunk, and their kernels are told apart by the
program they ran in: the device plane's ``XLA Modules`` line holds one event per executed
program, named ``jit_<function>(<fingerprint>)``. This reads that line from the run's own
``.xplane.pb`` (the newest under ``.benchmark_state/<cell>/trace``) and cuts the reduction's
operations by it. A CPU rehearsal has no device plane: everything here then finds nothing.
"""

from __future__ import annotations

import bisect
import glob
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULES_LINE = "XLA Modules"


def modules(run) -> list:
    """[(module name, start_ns, dur_ns)] of the first device plane, in the trace's clock,
    clipped to nothing: callers clip to the window. Cached on ``run``."""
    if "_lm_modules" in run:
        return run["_lm_modules"]
    out = []
    run["_lm_modules"] = out
    if run.get("rehearsal") or not run["trace"].devices:
        return out
    paths = sorted(glob.glob(os.path.join(ROOT, ".benchmark_state", run["cell"], "trace", "**",
                                          "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not paths:
        return out
    from jax.profiler import ProfileData

    first = sorted(run["trace"].devices)[0]
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != first:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out.extend((e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
    out.sort(key=lambda e: e[1])
    return out


def _in_window(run, start, dur):
    t = run["trace"]
    return max(0.0, min(start + dur, t.hi_ns) - max(start, t.lo_ns))


def module_seconds(run, name_part: str):
    """(device seconds inside the window, executions that began inside it) of the programs whose
    name contains ``name_part``; (None, 0) where the trace names none."""
    total, n, seen = 0.0, 0, False
    t = run["trace"]
    for name, start, dur in modules(run):
        if name_part in name:
            seen = True
            total += _in_window(run, start, dur)
            n += t.lo_ns <= start < t.hi_ns
    return (total / 1e9, n) if seen else (None, 0)


def op_seconds_in(run, name_part: str, match):
    """Device seconds, inside the window, of the operations ``match(hlo text)`` accepts that ran
    inside a program whose name contains ``name_part``; None where there is no such program."""
    mods = [(s, s + d) for name, s, d in modules(run) if name_part in name]
    if not mods:
        return None
    starts = [s for s, _ in mods]
    first = sorted(run["trace"].devices)[0]
    total = 0.0
    for text, start, dur in run["trace"].devices[first]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < mods[i][1] and match(text):
            total += _in_window(run, start, dur)
    return total / 1e9
