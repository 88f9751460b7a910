"""Seconds from a turn's submit to its first generated id on the daemon's host, median over the
window's turns: spans ``server.sched.session_wait`` + ``session.admit`` + ``session.turn.prefill``
+ ``session.turn.first_token`` of one GENERATE frame's profile (a turn without a prompt chunk has
no ``session.turn.prefill``). None where no profile holds ``session.turn.first_token``, which is
also what a program without the turn's spans reads."""
import statistics

import spans

PHASES = ("server.sched.session_wait", "session.admit", "session.turn.prefill",
          "session.turn.first_token")


def read(run):
    found = [sum(s["duration_s"] for s in p["spans"] if s["name"] in PHASES)
             for p in spans.window_profiles(run)
             if any(s["name"] == "session.turn.first_token" for s in p.get("spans", ()))]
    return statistics.median(found) if found else None
