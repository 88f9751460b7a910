"""Seconds ``jax.devices()`` took in the daemon's fresh process: libtpu's own start-up, the one part
of set-up that wanders from run to run (PERF.md, the set-up study). The launcher's clock."""


def read(run):
    return run["setup_parts"].get("daemon", {}).get("backend_start_s")
