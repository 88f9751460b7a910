"""Compilations inside the window: the daemon's ``compile.misses`` after less before. Must be 0."""
import spans


def read(run):
    return spans.registry_delta(run, "compile", "misses")
