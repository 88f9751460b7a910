"""Idle seconds of the chip per request under the daemon's dispatch, scheduler, planner and executor
spans (``gaps.py``)."""
import gaps


def read(run):
    return gaps.per_request(run, "dispatch")
