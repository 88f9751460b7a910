"""Device-throughput timing that cancels fixed per-dispatch overhead.

A per-dispatch wall time includes dispatch and sync overhead that can
exceed a sub-millisecond kernel, so steady-state device time is measured
as the SLOPE between a short and a long on-device loop: the caller
wraps its workload in a ``lax.scan`` whose carry depends on each
iteration's full output (so XLA can neither hoist the body nor
slice-push it down to a single element), and the fixed dispatch+sync
overhead cancels in the subtraction.

Used by the autotuner (``relational/tuning.py``) and
``workloads/la_tasks.py`` — one implementation so the protocol cannot
diverge between them.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional


# --- clock discipline (serve layer) ------------------------------------
# Deadlines and intervals MUST use the monotonic clock: time.time() can
# jump (NTP step, manual set), which once broke the serve layer's 30 s
# follower dial-retry loop. tests/test_static_checks.py enforces that
# serve/ never calls time.time(); the one legitimate wall-clock use —
# a human-readable timestamp in job records — goes through wall_now()
# here so the intent is explicit at every call site.

def wall_now() -> float:
    """Wall-clock seconds since the epoch — DISPLAY ONLY (job-record
    timestamps, logs). Never compare this against a deadline; use
    :func:`deadline_after`/:func:`seconds_left` instead."""
    return time.time()


def deadline_after(seconds: float) -> float:
    """A deadline ``seconds`` from now on the monotonic clock."""
    return time.monotonic() + seconds


def seconds_left(deadline: float) -> float:
    """Seconds remaining until a :func:`deadline_after` deadline
    (negative once expired)."""
    return deadline - time.monotonic()


def device_seconds(run: Callable[[int], None], lo: int = 4, hi: int = 20,
                   **kw) -> Optional[float]:
    """Seconds-per-iteration via :func:`scan_slope_seconds`, or None when
    the signal never clears controller noise (callers must then fall
    back to a wall-time upper bound, never a clamped denominator)."""
    res = scan_slope_seconds(run, lo=lo, hi=hi, **kw)
    return res["seconds_per_iter"] if not res["below_noise"] else None


def scan_slope_seconds(run: Callable[[int], None], lo: int, hi: int,
                       repeats: int = 3, max_escalations: int = 4,
                       min_delta_seconds: float = 0.2) -> Dict[str, object]:
    """Median seconds-per-iteration of ``run(n)`` (an n-iteration
    on-device loop that blocks until complete).

    The slope is only trustworthy when the long loop takes measurably
    longer than the short one RELATIVE TO controller noise (~tens of
    ms): if the median (t_hi - t_lo) delta is below
    ``min_delta_seconds`` — or non-positive — the loop lengths are
    escalated (``hi`` x4, recompiling) up to ``max_escalations`` times.
    Without this, a fast kernel measured with short loops reports
    noise as throughput (observed: an LSTM "measured" at 6x the chip's
    peak FLOP/s with hi=20). If escalation runs out,
    ``below_noise=True`` is returned and ``seconds_per_iter`` is None
    so callers fall back to a wall-time upper bound instead of
    reporting an astronomical number from a noise denominator.
    """
    for attempt in range(max_escalations + 1):
        for n in (lo, hi):
            run(n)  # compile + warm this pair of lengths
        deltas: List[float] = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            run(lo)
            t_lo = time.perf_counter() - t0
            t0 = time.perf_counter()
            run(hi)
            t_hi = time.perf_counter() - t0
            deltas.append(t_hi - t_lo)
        med_delta = sorted(deltas)[len(deltas) // 2]
        if med_delta >= min_delta_seconds:
            return {"seconds_per_iter": med_delta / (hi - lo),
                    "slopes": [d / (hi - lo) for d in deltas],
                    "below_noise": False, "lo": lo, "hi": hi}
        hi *= 4
    return {"seconds_per_iter": None,
            "slopes": [d / (hi // 4 - lo) for d in deltas],
            "below_noise": True, "lo": lo, "hi": hi // 4}
