"""Idle seconds of the chip per request under the set write of a shipped batch, ``store.ingest``
(``gaps.py``)."""
import gaps


def read(run):
    return gaps.per_request(run, "ingest")
