#!/usr/bin/env python3
"""Look at a cell's last trace by hand: planes, lines, and the device operations by time, full text.

    python3 benchmark/tools/trace_ops.py <cell> [--top 30]
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--top", type=int, default=30)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    pattern = os.path.join(os.path.dirname(BENCH), ".benchmark_state", args.cell, "trace",
                           "**", "*.xplane.pb")
    paths = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)
    if not paths:
        print(f"no trace under {pattern}", file=sys.stderr)
        return 1
    print(paths[-1], os.path.getsize(paths[-1]), "bytes")
    for plane in ProfileData.from_file(paths[-1]).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:"):
                continue
            sums, counts = {}, {}
            for e in events:
                sums[e.name] = sums.get(e.name, 0.0) + e.duration_ns / 1e9
                counts[e.name] = counts.get(e.name, 0) + 1
            for name, s in sorted(sums.items(), key=lambda kv: -kv[1])[:args.top]:
                print(f"    {s:.6f}s x{counts[name]}  {name[:1500]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
