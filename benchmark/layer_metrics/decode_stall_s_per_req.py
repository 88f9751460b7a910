"""Seconds per request that a turn's later tokens wait behind other turns' prompt chunks.

Span ``session.turn.decode`` counts, of a turn's steps after its first, ``chunk_steps``: those
whose ``session.step`` span held another turn's chunk, and ``chunk_step_s``: their seconds. A step
without a chunk takes *m*, the median of the window's ``session.step`` spans whose counter
``prefill_tokens`` is 0; so a turn's stall is max(0, ``chunk_step_s`` - ``chunk_steps`` x *m*),
summed over the window's turns and divided by its requests. Where the window has no chunk-free
step (only a short CPU rehearsal can), *m* is 0 and the stall is the chunk-carrying steps' whole
seconds. None where no profile holds ``session.turn.decode``, which is also what a program without
the turn's spans reads."""
import statistics

import spans


def read(run):
    profiles = spans.window_profiles(run)
    turns = [s["counters"] for p in profiles for s in p.get("spans", ())
             if s["name"] == "session.turn.decode"]
    if not turns:
        return None
    free = [s["duration_s"] for p in profiles for s in p.get("spans", ())
            if s["name"] == "session.step" and s.get("counters", {}).get("prefill_tokens") == 0]
    m = statistics.median(free) if free else 0.0
    return spans.per_request(run, sum(max(0.0, c["chunk_step_s"] - c["chunk_steps"] * m)
                                      for c in turns))
