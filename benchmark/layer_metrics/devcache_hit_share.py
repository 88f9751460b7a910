"""Share of the blocks the window's scans consumed that the device cache served from HBM:
``devcache.partial_hits`` (blocks served resident) over those plus ``stage.chunks`` (blocks staged
from the host). The cache is block-granular: its run-level ``devcache.hits`` ticks only when a
whole scan is resident, and would read 0 beside a scan served half from HBM."""
import spans


def read(run):
    profiles = spans.window_profiles(run)
    served = spans.counter_sum(profiles, "devcache.partial_hits") or 0.0
    staged = spans.counter_sum(profiles, "stage.chunks") or 0.0
    if served + staged == 0:
        return None
    return served / (served + staged)
