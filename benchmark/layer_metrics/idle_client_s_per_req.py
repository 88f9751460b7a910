"""Idle seconds of the chip per request under the caller's own work between and after the frames of a
score: ``models.score`` less the spans inside it (``gaps.py``)."""
import gaps


def read(run):
    return gaps.per_request(run, "client")
