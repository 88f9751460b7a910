#!/usr/bin/env python3
"""The daemon child: the one process that holds the chip.

Builds the same ``ServeController`` that ``python -m netsdb_tpu serve`` builds,
fills the deployment's sets from ``--seed`` through the controller's in-process
``Client`` (on the device where the deployment keeps them there), and only then
listens. From then on it is an ordinary daemon: the window reaches it through
``RemoteClient`` and the wire alone.

Beside the daemon's socket there is a control channel on stdin/stdout, one JSON
object per line, for what only the process that holds the chip can do: start
and stop a ``jax.profiler`` trace, read the device's memory statistics, apply a
test's fault. Nothing on it touches a request.

stdout carries the control replies, the first being the ``ready`` line with the
seconds of each part of start-up; the log goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

T_PROCESS = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from loading import load_json, load_module  # noqa: E402


def reply(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def control_loop(ctl, state) -> None:
    """Serve the control channel until stdin closes or ``quit``."""
    import jax

    for line in sys.stdin:
        try:
            cmd = json.loads(line)
        except ValueError:
            continue
        op = cmd.get("op")
        try:
            if op == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
                wall_ns = time.time_ns()
                with jax.profiler.TraceAnnotation("bench_clock_sync"):
                    pass  # carries the host's wall clock into the trace's clock (xplane.py)
                reply({"ok": True, "wall_ns": wall_ns})
            elif op == "trace_stop":
                jax.profiler.stop_trace()
                reply({"ok": True, "wall_ns": time.time_ns()})
            elif op == "memory":
                stats = [d.memory_stats() or {} for d in jax.local_devices()]
                reply({"ok": True,
                       "peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
                       "bytes_limit": max(int(s.get("bytes_limit", 0)) for s in stats)})
            elif op == "quit":
                reply({"ok": True})
                break
            else:
                reply({"ok": False, "error": f"unknown op {op!r}"})
        except Exception as e:  # noqa: BLE001 - reported to the harness, which fails the run
            reply({"ok": False, "error": f"{type(e).__name__}: {e}"})
    state["stop"] = True
    ctl.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="the configuration's JSON file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", required=True, help="the daemon's state directory")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--fault", default=None,
                    help="tests only: path.py:function applied to the program before it serves")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    parts = {}
    cfg = load_json(args.config)
    if args.rehearse_cpu:
        cfg.update(cfg.get("rehearsal", {}))
    daemon = dict(cfg.get("daemon", {}))
    if daemon.get("tpu_premapped_buffer_mb") is not None:
        # the host buffer libtpu pins at start-up: the deployment states its size (PERF.md, the
        # set-up study), and libtpu reads it when the backend starts, below
        os.environ["TPU_PREMAPPED_BUFFER_SIZE"] = str(int(daemon["tpu_premapped_buffer_mb"]) << 20)

    import jax

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    from netsdb_tpu.config import Configuration, enable_compilation_cache
    from netsdb_tpu.serve.server import ServeController

    enable_compilation_cache()
    parts["launcher_imports_s"] = time.time() - T_PROCESS
    t1 = time.time()
    dev = jax.devices()[0]
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev.platform != want:
        print(f"launcher: jax found platform {dev.platform!r}, not {want!r}", file=sys.stderr)
        return 3
    parts["backend_start_s"] = time.time() - t1

    overrides = {"root_dir": args.root}
    for key, field, shift in (("store_budget_mb", "shared_mem_bytes", 20),
                              ("device_cache_mb", "device_cache_bytes", 20),
                              ("page_pool_mb", "page_pool_bytes", 20),
                              ("page_kb", "page_size_bytes", 10)):
        if daemon.get(key) is not None:
            overrides[field] = int(daemon[key]) << shift
    if daemon.get("trace_ring") is not None:
        overrides["obs_trace_ring"] = int(daemon["trace_ring"])
    ctl = ServeController(Configuration(**overrides), host="127.0.0.1", port=0)

    deployment = load_module(
        os.path.join(HERE, "deployments", cfg["deployment"] + ".py"),
        "bench_deployment_" + cfg["deployment"])
    t2 = time.time()
    fill_parts = deployment.fill(ctl.library, cfg, args.seed)
    parts.update(fill_parts)
    parts["fill_s"] = time.time() - t2
    if args.fault:
        path, _, fn = args.fault.rpartition(":")
        getattr(load_module(path, "bench_fault"), fn)()

    t3 = time.time()
    port = ctl.start()
    parts["listen_s"] = time.time() - t3
    state = {"stop": False}
    reply({"ready": True, "port": port, "parts": parts, "pid": os.getpid(),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}})
    threading.Thread(target=control_loop, args=(ctl, state), daemon=True).start()
    ctl.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
