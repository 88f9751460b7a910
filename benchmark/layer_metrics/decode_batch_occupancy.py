"""Live rows over dispatched rows of the window's decode steps: ``decode.steps`` over
``decode.steps + decode.pad_rows`` (a step program runs over every slot of the slab)."""
import spans


def read(run):
    live = spans.registry_delta(run, "decode", "steps")
    idle = spans.registry_delta(run, "decode", "pad_rows")
    if live is None or idle is None or live + idle <= 0:
        return None
    return live / (live + idle)
