"""Device seconds per request: the union of device-operation intervals in the window, over its requests."""


def read(run):
    if not run["requests"] or not run["trace"].devices:
        return None
    return run["trace"].busy_s / run["requests"]
