"""Idle seconds of the chip per request under the wire and the codec: the client's encode, send and the
wait no daemon span covers; the daemon's receive, decode and reply (``gaps.py``)."""
import gaps


def read(run):
    return gaps.per_request(run, "wire")
