"""Share of the daemon's pooled receives that found a recycled, already-faulted arena: registry
counters ``serve.wire.recv_pool.hits`` over hits + ``serve.wire.recv_pool.misses`` (out-of-band
segments of 1 MiB or more; a miss allocates), after the window less before. The registry makes a
counter at its first increment, so one that is missing reads 0: a warm-up of one request leaves a
miss and no ``hits`` behind. None where no segment of the window reached the pool, which is also
what a program without the pool reads."""


def read(run):
    def delta(name):
        after, before = (run[side]["metrics"].get("counters", {}).get("serve.wire.recv_pool." + name, 0)
                         for side in ("after", "before"))
        return after - before
    hits, misses = delta("hits"), delta("misses")
    return hits / (hits + misses) if hits + misses else None
