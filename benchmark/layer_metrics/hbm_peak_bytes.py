"""Peak bytes in use on the fullest chip, from the daemon's ``memory_stats()`` after the window."""


def read(run):
    return run["memory"]["peak_bytes"] or None
