"""The one traffic generator: it reads a mix's parameters and drives the deployment's clients.

A mix is a data file, ``traffic/<name>.json``:

    loop      "closed": each client sends its next request when the last has been answered
    clients   how many callers, each with a connection of its own
    request   the deployment's request kind that every client sends
    params    that kind's parameters (rows, sets, what is kept for the check)

The choices within a request kind (which stored set, which batch) are drawn
from the seed, every client from a stream of its own, as shuffled passes over
all the choices: each seed gives the same set of sizes in another order.

All times are the host's clock in this process, around calls that return only
when the daemon has finished the request.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Window:
    opened: float = 0.0           # time.time() when the first request was due
    closed: float = 0.0           # when the last request in flight was answered
    requests: list = field(default_factory=list)   # (client, start, end, rows)
    failed: list = field(default_factory=list)     # (client, start, message)

    @property
    def seconds(self) -> float:
        return self.closed - self.opened

    def latencies(self):
        return sorted(e - s for _, s, e, _ in self.requests)


def percentile(sorted_values, q: float) -> float:
    """Nearest-rank percentile of an ascending list (q in (0, 1])."""
    if not sorted_values:
        raise ValueError("no requests completed")
    return sorted_values[max(0, math.ceil(q * len(sorted_values)) - 1)]


def client_rng(seed: int, k: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, k])


def warm_up(ops, ctxs) -> int:
    """Every client sends the deployment's warm-up requests, one client after another."""
    n = 0
    for ctx in ctxs:
        for choice in ops.warm_requests(ctx["k"]):
            ops.issue(ctx, choice)
            n += 1
    return n


def run_window(ops, ctxs, traffic, seed: int, seconds: float) -> Window:
    """Closed loop for ``seconds``: no request starts after the deadline, all that started finish."""
    if traffic.get("loop") != "closed":
        raise ValueError(f"loop {traffic.get('loop')!r}: this generator drives closed loops")
    win = Window()
    lock = threading.Lock()
    go = threading.Barrier(len(ctxs) + 1)

    def client(ctx):
        schedule = ops.schedule(ctx["k"], client_rng(seed, ctx["k"]))
        go.wait()
        while True:
            start = time.time()
            if start >= win.opened + seconds:
                return
            choice = next(schedule)
            try:
                rows = ops.issue(ctx, choice)
            except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
                with lock:
                    win.failed.append((ctx["k"], start, f"{type(e).__name__}: {e}"))
                if len(win.failed) > 20:
                    return
                continue
            end = time.time()
            with lock:
                win.requests.append((ctx["k"], start, end, rows))

    threads = [threading.Thread(target=client, args=(ctx,), name=f"client-{ctx['k']}")
               for ctx in ctxs]
    for t in threads:
        t.start()
    win.opened = time.time()
    go.wait()
    for t in threads:
        t.join()
    win.closed = max((e for _, _, e, _ in win.requests), default=time.time())
    return win
