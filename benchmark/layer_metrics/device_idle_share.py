"""1 - busy_s / window_s of the traced window: the number the driver works out from ``device``, as a fraction."""


def read(run):
    t = run["trace"]
    if not t.devices or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
