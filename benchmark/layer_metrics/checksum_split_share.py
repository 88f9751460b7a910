"""Share of the bytes the daemon checksummed that were split across the host's cores: registry
counters ``serve.wire.checksum.split_bytes`` over ``serve.wire.checksum.bytes`` (out-of-band segments
received and sent), after the window less before. The registry makes a counter at its first
increment, so one that is missing reads 0. None where the window checksummed nothing, which is also
what a program without the counters reads."""


def read(run):
    def delta(name):
        after, before = (run[side]["metrics"].get("counters", {}).get("serve.wire.checksum." + name, 0)
                         for side in ("after", "before"))
        return after - before
    total = delta("bytes")
    return delta("split_bytes") / total if total else None
