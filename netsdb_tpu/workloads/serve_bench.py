"""Served-inference benchmark — FF throughput through the RPC hop.

The reference's serving story: the master loads model weight sets once
and many ``PDBClient`` processes run inference queries against them
concurrently (``src/mainServer/source/MasterMain.cc:64-96``,
``src/queries/headers/QueryClient.h:160-224``). This benchmark measures
the same shape here: one resident daemon (owning the device + weight
sets + compiled-plan cache), N separate *client processes*, each sending
its private input set once and then running M inference jobs whose only
per-job wire traffic is the plan.

Reported: aggregate rows/s across clients (wall), per-job latency
percentiles, and the daemon's view (jobs done, cache stats).

One process per chip: ``run_serve_bench``'s daemon inherits the
caller's environment, so it reaches an accelerator only when the
calling process has not initialised a jax backend itself. The
data-plane, scale-out and rebalance arms pin their daemon subprocesses
to ``JAX_PLATFORMS=cpu`` — they are HOST-ONLY by construction (they
measure the wire, routing and slot moves) and their numbers are never
chip numbers.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

BATCH = 16384
FEATURES = 1024
HIDDEN = 4096
LABELS = 1024
BLOCK = (512, 512)


def _wait_port(host: str, port: int, timeout: float = 120.0) -> None:
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            with socket.create_connection((host, port), timeout=1):
                return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"daemon on {host}:{port} did not come up")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_model(address: str, db: str = "ffserve", seed: int = 0,
               features: int = FEATURES, hidden: int = HIDDEN,
               labels: int = LABELS) -> None:
    """Load the FF weight sets into the daemon ONCE (ref ff::setup +
    loadMatrix). Runs in whatever process calls it — only thin-client
    RPC, no device work here."""
    import numpy as np

    from netsdb_tpu.serve.client import RemoteClient

    c = RemoteClient(address)
    rng = np.random.default_rng(seed)
    c.create_database(db)
    for s in ("w1", "b1", "wo", "bo"):
        c.create_set(db, s)
    c.send_matrix(db, "w1",
                  rng.standard_normal((hidden, features)).astype(np.float32)
                  * np.sqrt(2.0 / features), BLOCK)
    c.send_matrix(db, "b1",
                  (rng.standard_normal((hidden, 1)) * 0.01).astype(np.float32),
                  (BLOCK[0], 1))
    c.send_matrix(db, "wo",
                  rng.standard_normal((labels, hidden)).astype(np.float32)
                  * np.sqrt(2.0 / hidden), BLOCK)
    c.send_matrix(db, "bo",
                  (rng.standard_normal((labels, 1)) * 0.01).astype(np.float32),
                  (BLOCK[0], 1))
    c.close()


def run_client_worker(address: str, client_id: int, jobs: int,
                      batch: int = BATCH, db: str = "ffserve",
                      features: int = FEATURES) -> Dict[str, Any]:
    """One client process: send a private input set once, then run
    ``jobs`` inference jobs against the RESIDENT weights. Returns
    timing; also printed as JSON when run via --worker."""
    import numpy as np

    from netsdb_tpu.models.ff import FFModel
    from netsdb_tpu.serve.client import RemoteClient

    c = RemoteClient(address)
    inp = f"inputs_c{client_id}"
    out = f"output_c{client_id}"
    rng = np.random.default_rng(client_id)
    c.create_set(db, inp)
    c.create_set(db, out)
    t_load0 = time.perf_counter()
    c.send_matrix(db, inp,
                  rng.standard_normal((batch, features)).astype(np.float32),
                  BLOCK)
    load_s = time.perf_counter() - t_load0

    model = FFModel(db=db, block=BLOCK)
    sink = model.build_inference_dag(input_set=inp, output_set=out)
    # warmup: first job compiles (cached thereafter — and shared across
    # clients, since the canonical plan signature is identical)
    c.execute_computations(sink, job_name="ff-serve",
                           fetch_results=False)
    lat: List[float] = []
    t_start = time.time()  # epoch: lets the parent compute the union
    t0 = time.perf_counter()
    for _ in range(jobs):
        t1 = time.perf_counter()
        c.execute_computations(sink, job_name="ff-serve",
                               fetch_results=False)
        lat.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    c.close()
    lat.sort()
    return {
        "client_id": client_id, "jobs": jobs, "batch": batch,
        "wall_s": wall, "input_load_s": load_s,
        "t_start": t_start, "t_end": t_start + wall,
        "job_p50_s": lat[len(lat) // 2],
        "job_p90_s": lat[int(len(lat) * 0.9)],
        "rows_per_sec": jobs * batch / wall,
    }


def run_serve_bench(clients: int = 2, jobs_per_client: int = 8,
                    batch: int = BATCH, port: int = 0,
                    platform: Optional[str] = None,
                    daemon_env: Optional[Dict[str, str]] = None,
                    ) -> Dict[str, Any]:
    """Spawn (or reuse) a daemon, load weights once, run N concurrent
    client PROCESSES, aggregate."""
    host = "127.0.0.1"
    daemon: Optional[subprocess.Popen] = None
    if port == 0:
        port = _free_port()
        env = dict(os.environ)
        env.update(daemon_env or {})
        argv = [sys.executable, "-m", "netsdb_tpu", "serve",
                "--port", str(port),
                "--root", f"/tmp/netsdb_serve_bench_{port}"]
        if platform:
            argv += ["--platform", platform]
        daemon = subprocess.Popen(
            argv, env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
        )
    address = f"{host}:{port}"
    try:
        _wait_port(host, port)
        load_model(address)

        procs = []
        t0 = time.perf_counter()
        for i in range(clients):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "netsdb_tpu.workloads.serve_bench",
                 "--worker", "--address", address, "--client-id", str(i),
                 "--jobs", str(jobs_per_client), "--batch", str(batch)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))),
            ))
        results = []
        for p in procs:
            out_text, err_text = p.communicate(timeout=1800)
            if p.returncode != 0:
                raise RuntimeError(
                    f"client worker failed rc={p.returncode}:\n{err_text[-4000:]}")
            results.append(json.loads(out_text.strip().splitlines()[-1]))
        wall = time.perf_counter() - t0

        from netsdb_tpu.serve.client import RemoteClient

        c = RemoteClient(address)
        stats = c.collect_stats()
        server_jobs = [j for j in c.list_jobs() if j["name"] == "ff-serve"]
        elapsed = sorted(j["elapsed"] for j in server_jobs
                         if j["elapsed"] is not None)
        c.close()
        total_rows = sum(r["jobs"] * r["batch"] for r in results)
        # measurement window = union of the clients' job loops (spawn +
        # import + warmup-compile time excluded: steady-state serving)
        window = max(r["t_end"] for r in results) - min(
            r["t_start"] for r in results)
        return {
            "clients": clients, "jobs_per_client": jobs_per_client,
            "batch": batch,
            "aggregate_rows_per_sec": total_rows / window,
            "measurement_window_s": window,
            "wall_s_incl_spawn": wall,
            "per_client": results,
            "server_jobs_done": sum(j["status"] == "done"
                                    for j in server_jobs),
            "server_job_elapsed_p50":
                elapsed[len(elapsed) // 2] if elapsed else None,
            "cache_stats": stats.get("cache"),
        }
    finally:
        if daemon is not None:
            daemon.terminate()
            try:
                daemon.wait(timeout=10)
            except subprocess.TimeoutExpired:
                daemon.kill()


def run_stream_bench(rows: int = 50_000, row_bytes: int = 2000,
                     tensor_mb: int = 128) -> Dict[str, Any]:
    """Transfer-path comparison on loopback: single-frame SCAN_SET /
    GET_TENSOR (whole payload held twice on each end) vs the round-3
    streamed forms (bounded continuation frames). Throughput should be
    comparable — the point of streaming is the MEMORY bound, reported
    here as the largest single frame each path holds."""
    import tempfile

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    ctl = ServeController(Configuration(root_dir=tempfile.mkdtemp(
        prefix="stream_bench_")), port=0)
    port = ctl.start()
    out: Dict[str, Any] = {}
    try:
        c = RemoteClient(f"127.0.0.1:{port}")
        c.create_database("b")
        c.create_set("b", "objs", type_name="object")
        pad = "x" * row_bytes
        c.send_data("b", "objs", [{"i": i, "p": pad} for i in range(rows)])
        obj_bytes = rows * (row_bytes + 50)

        t0 = time.perf_counter()
        n1 = len(list(c.get_set_iterator("b", "objs")))
        t_single = time.perf_counter() - t0
        t0 = time.perf_counter()
        n2 = sum(1 for _ in c.scan_stream("b", "objs",
                                          max_frame_bytes=4 << 20))
        t_stream = time.perf_counter() - t0
        assert n1 == n2 == rows
        out["scan"] = {
            "payload_mb": round(obj_bytes / 2**20, 1),
            "single_frame_s": round(t_single, 3),
            "streamed_s": round(t_stream, 3),
            "single_peak_frame_mb": round(obj_bytes / 2**20, 1),
            "streamed_peak_frame_mb": 4,
            "streamed_mb_per_s": round(obj_bytes / 2**20 / t_stream, 1),
        }

        side = int((tensor_mb * 2**20 / 4) ** 0.5) // 128 * 128
        dense = np.random.default_rng(0).standard_normal(
            (side, side)).astype(np.float32)
        c.create_set("b", "w")
        c.send_matrix("b", "w", dense, (512, 512))
        t0 = time.perf_counter()
        a1 = c.get_tensor("b", "w").to_dense()
        t_one = time.perf_counter() - t0
        t0 = time.perf_counter()
        a2 = c.get_tensor_chunked("b", "w", chunk_bytes=8 << 20).to_dense()
        t_chunk = time.perf_counter() - t0
        assert np.array_equal(a1, a2)
        out["tensor"] = {
            "payload_mb": round(dense.nbytes / 2**20, 1),
            "single_frame_s": round(t_one, 3),
            "chunked_s": round(t_chunk, 3),
            "chunked_peak_frame_mb": 8,
            "chunked_mb_per_s": round(dense.nbytes / 2**20 / t_chunk, 1),
        }
        c.close()
    finally:
        ctl.shutdown()
    return out


def run_data_plane_bench(table_mb: int = 64, chunk_mb: int = 8,
                         window: int = 4,
                         hedge_reads: int = 40) -> Dict[str, Any]:
    """v3 data-plane numbers on loopback: bulk-table ingest MB/s for
    the pre-change single-frame path (one pickled monolith) vs the
    streamed pipelined path (row-range column slices riding out-of-band
    segments, ``window`` chunks in flight), streamed scan MB/s, tensor
    push/pull MB/s over the zero-copy framing, and hedged-read p99
    against a tail-latency-injected primary.

    The daemon runs as a REAL subprocess (like ``run_serve_bench``):
    pipelining only overlaps client encode/send with server
    decode/apply when the two sides don't share a GIL. HOST-ONLY: the
    daemon is pinned to ``JAX_PLATFORMS=cpu`` (the caller may hold the
    chip, and the arm measures the wire, not the device)."""
    import tempfile

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.relational.table import ColumnTable
    from netsdb_tpu.serve.chaos import ChaosInjector
    from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
    from netsdb_tpu.serve.server import ServeController

    out: Dict[str, Any] = {"table_mb": table_mb, "chunk_mb": chunk_mb,
                           "window": window}
    nrows = table_mb * (1 << 20) // 8  # two f32/int32 columns per row
    cols = {"a": np.arange(nrows, dtype=np.int32),
            "b": np.random.default_rng(0).standard_normal(nrows)
            .astype(np.float32)}
    table = ColumnTable(dict(cols), {}, None)
    payload_mb = sum(c.nbytes for c in cols.values()) / 2**20

    host = "127.0.0.1"
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # host-only arm, see docstring
    daemon = subprocess.Popen(
        [sys.executable, "-m", "netsdb_tpu", "serve", "--port", str(port),
         "--root", tempfile.mkdtemp(prefix="dataplane_bench_")],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
    )
    try:
        _wait_port(host, port)
        c = RemoteClient(f"{host}:{port}", ingest_window=window,
                         ingest_chunk_bytes=chunk_mb << 20)
        c.create_database("b")

        def ingest(set_name: str, pipeline: bool, repeats: int = 2) -> float:
            """Best-of-N wall time of one full ingest (machine-load
            noise on shared hosts dwarfs run-to-run variance)."""
            best = None
            for r in range(repeats):
                name = f"{set_name}{r}"
                c.create_set("b", name, type_name="table")
                t0 = time.perf_counter()
                c.send_table("b", name, table, pipeline=pipeline)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best

        ingest("warm", True, repeats=1)  # compile/alloc warmup, excluded
        t_single = ingest("single", False)
        t_stream = ingest("streamed", True)
        out["ingest"] = {
            "payload_mb": round(payload_mb, 1),
            "single_frame_s": round(t_single, 3),
            "single_frame_mb_per_s": round(payload_mb / t_single, 1),
            "streamed_s": round(t_stream, 3),
            "streamed_mb_per_s": round(payload_mb / t_stream, 1),
            "speedup": round(t_single / t_stream, 2),
        }

        t0 = time.perf_counter()
        back = c.get_table_streamed("b", "streamed0",
                                    max_frame_bytes=chunk_mb << 20)
        t_scan = time.perf_counter() - t0
        assert back.num_rows == nrows
        out["scan"] = {"streamed_s": round(t_scan, 3),
                       "streamed_mb_per_s": round(payload_mb / t_scan, 1)}

        side = int((table_mb * (1 << 20) / 4) ** 0.5) // 128 * 128
        dense = np.random.default_rng(1).standard_normal(
            (side, side)).astype(np.float32)
        c.create_set("b", "w")
        t0 = time.perf_counter()
        c.send_matrix("b", "w", dense, (512, 512))
        t_push = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = c.get_tensor("b", "w").to_dense()
        t_pull = time.perf_counter() - t0
        assert got.shape == dense.shape
        mb = dense.nbytes / 2**20
        out["tensor"] = {
            "payload_mb": round(mb, 1),
            "push_mb_per_s": round(mb / t_push, 1),
            "pull_mb_per_s": round(mb / t_pull, 1),
        }
        c.close()

        # hedged reads: a replica daemon + a primary whose replies
        # stall with seeded probability — p99 with hedging should sit
        # near the replica RTT, not the injected stall
        pchaos = ChaosInjector(seed=7, delay=0.25, delay_s=0.15)
        slow = ServeController(Configuration(root_dir=tempfile.mkdtemp(
            prefix="dataplane_slow_")), port=0, chaos=pchaos)
        sport = slow.start()
        try:
            small = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
            for p in (sport, port):
                boot = RemoteClient(f"127.0.0.1:{p}")
                boot.create_database("h")
                boot.create_set("h", "w")
                boot.send_matrix("h", "w", small, (32, 32))
                boot.close()

            def read_p99(client) -> Dict[str, float]:
                lat = []
                for _ in range(hedge_reads):
                    t0 = time.perf_counter()
                    client.get_tensor("h", "w")
                    lat.append(time.perf_counter() - t0)
                lat.sort()
                return {"p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                        "p99_ms": round(lat[int(0.99 * (len(lat) - 1))]
                                        * 1e3, 2)}

            plain = RemoteClient(f"127.0.0.1:{sport}",
                                 retry=RetryPolicy(max_attempts=2))
            unhedged = read_p99(plain)
            plain.close()
            hedged_c = RemoteClient(f"127.0.0.1:{sport}",
                                    replicas=[f"127.0.0.1:{port}"],
                                    hedge_delay_s=0.02,
                                    retry=RetryPolicy(max_attempts=2))
            hedged = read_p99(hedged_c)
            out["hedged_reads"] = {
                "injected_stall_ms": 150, "stall_rate": 0.25,
                "unhedged": unhedged, "hedged": hedged,
                "hedges_issued": hedged_c.hedges_issued,
                "hedges_won": hedged_c.hedges_won,
            }
            hedged_c.close()
        finally:
            slow.shutdown()
    finally:
        daemon.terminate()
        try:
            daemon.wait(timeout=10)
        except subprocess.TimeoutExpired:
            daemon.kill()
    return out


def run_device_cache_bench(rows: int = 1_200_000, page_rows: int = 65_536,
                           pool_mb: int = 8, repeats: int = 4,
                           cache_mb: int = 256) -> Dict[str, Any]:
    """Cold vs warm EXECUTE latency for a q01-style query over a
    device-cache-resident paged set — the buffer-pool payoff measured
    at the serve surface (``--device-cache``).

    One in-process daemon owns a paged ``lineitem`` whose arena pool is
    far smaller than the table (cold streams re-read spilled pages).
    Phases:

    * **uncached** — device cache budget 0: every EXECUTE re-reads the
      arena, re-pads and re-uploads each chunk (first run additionally
      compiles; reported separately). Best-of-N steady state.
    * **warm** — cache on: one installing run, then best-of-N warm
      runs that replay device-resident blocks. The cache's miss
      counter is asserted FLAT across the warm runs — zero host→device
      transfers for the cached set blocks.

    ``speedup`` = uncached steady / warm. On CPU the "device" is host
    RAM, so the number understates real HBM transfer savings (same
    caveat as the PR 3 staging bench); the structural claims — miss
    counter flat, hit counters advancing — are platform-independent."""
    import tempfile

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.table import ColumnTable
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    cfg = Configuration(root_dir=tempfile.mkdtemp(prefix="devcache_bench_"),
                        page_size_bytes=page_rows * 4,
                        page_pool_bytes=pool_mb << 20,
                        device_cache_bytes=cache_mb << 20)
    ctl = ServeController(cfg, port=0)
    port = ctl.start()
    out: Dict[str, Any] = {"rows": rows, "pool_mb": pool_mb,
                           "cache_mb": cache_mb}
    try:
        c = RemoteClient(f"127.0.0.1:{port}")
        rng = np.random.default_rng(0)
        cols = {
            "l_shipdate": rng.integers(19920101, 19981231, rows,
                                       dtype=np.int32),
            "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
            "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
            "l_quantity": rng.integers(1, 51, rows,
                                       dtype=np.int32).astype(np.float32),
            "l_extendedprice": rng.uniform(1000, 100000,
                                           rows).astype(np.float32),
            "l_discount": rng.uniform(0, 0.1, rows).astype(np.float32),
            "l_tax": rng.uniform(0, 0.08, rows).astype(np.float32),
        }
        out["table_mb"] = round(sum(v.nbytes for v in cols.values())
                                / 2**20, 1)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table", storage="paged")
        c.send_table("d", "lineitem",
                     ColumnTable(cols, {"l_returnflag": ["A", "N", "R"],
                                        "l_linestatus": ["F", "O"]}))
        sink = rdag.q01_sink("d")
        cache = ctl.library.store.device_cache()

        def run_once() -> float:
            t0 = time.perf_counter()
            c.execute_computations(sink, job_name="q01-devcache",
                                   fetch_results=False)
            return time.perf_counter() - t0

        # phase 1: cache off — the pre-cache serve data path
        cache.resize(0)
        out["cold_first_s"] = round(run_once(), 4)  # includes compile
        out["uncached_steady_s"] = round(
            min(run_once() for _ in range(repeats)), 4)

        # phase 2: cache on — one installing run, then warm replays
        cache.resize(cache_mb << 20)
        out["install_run_s"] = round(run_once(), 4)
        m0 = cache.stats()["misses"]
        out["warm_s"] = round(min(run_once() for _ in range(repeats)), 4)
        st = cache.stats()
        out["warm_misses_flat"] = (st["misses"] == m0)
        out["speedup_warm_vs_uncached"] = round(
            out["uncached_steady_s"] / out["warm_s"], 2)
        out["cache_stats"] = st
        c.close()
    finally:
        ctl.shutdown()
    return out


def run_partial_cache_bench(rows: int = 1_200_000,
                            page_rows: int = 65_536,
                            pool_mb: int = 8, cache_mb: int = 256,
                            append_frac: float = 0.01,
                            cycles: int = 3) -> Dict[str, Any]:
    """Paired A/B for block-granular partial-run caching
    (``--partial-cache``): the WARM RE-QUERY AFTER A SMALL APPEND,
    partial dirty-range invalidation vs whole-run invalidation.

    Both arms run the identical protocol on a fresh in-process daemon:
    ingest a 1.2M-row paged q01 ``lineitem``, warm the device cache
    (install + one warm run), then ``cycles`` rounds of: append
    ``append_frac`` of the rows → time ONE warm re-query. Under
    whole-run invalidation the append unkeys the entire cached run, so
    the re-query re-reads/re-uploads every page; under partial
    invalidation only the appended tail range is dirty, so the
    re-query stitches every pre-append block from HBM and stages only
    the tail. Reported per arm: best-of-cycles warm-after-append
    seconds; plus the partial arm's structural proof — ZERO evictions
    of pre-append blocks across the appends and ``partial_hits`` > 0.

    ``devcache_partial_speedup`` = whole_run / partial (the bench.py
    ``--compare`` headline; acceptance floor 2×). CPU-container
    caveat: the "device" is host RAM, so re-upload savings understate
    real HBM numbers — the ratio is the claim, not the absolute
    seconds (same caveat as ``--device-cache``)."""
    import shutil
    import tempfile

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.table import ColumnTable
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    rng = np.random.default_rng(0)
    cols = {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows,
                                   dtype=np.int32).astype(np.float32),
        "l_extendedprice": rng.uniform(1000, 100000,
                                       rows).astype(np.float32),
        "l_discount": rng.uniform(0, 0.1, rows).astype(np.float32),
        "l_tax": rng.uniform(0, 0.08, rows).astype(np.float32),
    }
    dicts = {"l_returnflag": ["A", "N", "R"],
             "l_linestatus": ["F", "O"]}
    n_extra = max(int(rows * append_frac), 1)
    out: Dict[str, Any] = {"rows": rows, "pool_mb": pool_mb,
                           "cache_mb": cache_mb,
                           "append_rows": n_extra, "cycles": cycles}

    def arm(partial: bool) -> Dict[str, Any]:
        root = tempfile.mkdtemp(prefix="partial_bench_")
        cfg = Configuration(root_dir=root,
                            page_size_bytes=page_rows * 4,
                            page_pool_bytes=pool_mb << 20,
                            device_cache_bytes=cache_mb << 20,
                            device_cache_partial=partial)
        ctl = ServeController(cfg, port=0)
        port = ctl.start()
        try:
            c = RemoteClient(f"127.0.0.1:{port}")
            c.create_database("d")
            c.create_set("d", "lineitem", type_name="table",
                         storage="paged")
            c.send_table("d", "lineitem", ColumnTable(cols, dicts))
            sink = rdag.q01_sink("d")
            cache = ctl.library.store.device_cache()

            def run_once() -> float:
                t0 = time.perf_counter()
                c.execute_computations(sink, job_name="q01-partial",
                                       fetch_results=False)
                return time.perf_counter() - t0

            run_once()                      # cold (compile + install)
            warm_s = run_once()             # fully warm
            blocks0 = cache.stats()["entries"]
            ev0 = cache.stats()["evictions"]
            times = []
            for i in range(cycles):
                extra = {k: v[:n_extra] for k, v in cols.items()}
                c.send_table("d", "lineitem",
                             ColumnTable(extra, dicts), append=True)
                times.append(run_once())    # warm-after-append
            st = cache.stats()
            res = {"warm_s": round(warm_s, 4),
                   "warm_after_append_s": round(min(times), 4),
                   "warm_after_append_all": [round(t, 4)
                                             for t in times],
                   "blocks_before_appends": blocks0,
                   "cache_stats": st}
            if partial:
                res["pre_append_evictions"] = st["evictions"] - ev0
                res["partial_hits"] = st["partial_hits"]
            c.close()
            return res
        finally:
            ctl.shutdown()
            shutil.rmtree(root, ignore_errors=True)

    out["whole_run"] = arm(False)
    out["partial"] = arm(True)
    p, w = out["partial"], out["whole_run"]
    if p["warm_after_append_s"] > 0:
        out["devcache_partial_speedup"] = round(
            w["warm_after_append_s"] / p["warm_after_append_s"], 2)
    # the acceptance structure: appends evicted NOTHING and the warm
    # re-queries stitched resident blocks
    out["partial_zero_evictions"] = (p.get("pre_append_evictions") == 0)
    out["partial_hits_positive"] = (p.get("partial_hits", 0) > 0)
    return out


# --- horizontal scale-out (--scale) ----------------------------------

def scaleout_table(rows: int, seed: int = 0):
    """The q01-style paged workload with INTEGER measures: partial
    sums stay exactly representable, so the 4-daemon scatter-gather
    result must be BYTE-equal to the 1-daemon run (float q01 differs
    by merge-order reassociation in the last ulp — this workload is
    the acceptance oracle, the shape is identical)."""
    import numpy as np

    from netsdb_tpu.relational.table import ColumnTable

    rng = np.random.default_rng(seed)
    cols = {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows, dtype=np.int32),
        "l_price": rng.integers(1, 1000, rows, dtype=np.int32),
    }
    return ColumnTable(cols, {"l_returnflag": ["A", "N", "R"],
                              "l_linestatus": ["F", "O"]})


def scaleout_q01_sink(db: str, cutoff: int = 19980902,
                      lineitem_set: str = "lineitem",
                      output_set: str = "scale_q01_out"):
    """SCAN(lineitem) → APPLY(int group-by fold) → OUTPUT: per
    (returnflag, linestatus) group, int32 count + sum(qty) +
    sum(price) under a shipdate cutoff. Single-pass fold with a
    declared ``state_merge`` (tree add) — the scatterable q01 shape
    with exact integer accumulators."""
    import jax.numpy as jnp

    from netsdb_tpu.plan.computations import Apply, ScanSet, WriteSet
    from netsdb_tpu.plan.fold import single_pass, tree_add_states
    from netsdb_tpu.relational.table import ColumnTable

    n_groups = 6  # 3 returnflags x 2 linestatuses

    def init(prev, src):
        z = jnp.zeros((n_groups,), jnp.int32)
        return (z, z, z)

    def step(state, chunk):
        counts, qty, price = state
        ok = chunk.mask() & (chunk["l_shipdate"] <= cutoff)
        gid = jnp.where(ok, chunk["l_returnflag"] * 2
                        + chunk["l_linestatus"], 0)
        one = jnp.where(ok, 1, 0).astype(jnp.int32)
        return (counts.at[gid].add(one),
                qty.at[gid].add(jnp.where(ok, chunk["l_quantity"], 0)),
                price.at[gid].add(jnp.where(ok, chunk["l_price"], 0)))

    def fin(state, src):
        counts, qty, price = state
        gid = jnp.arange(n_groups, dtype=jnp.int32)
        return ColumnTable(
            cols={"l_returnflag": gid // 2, "l_linestatus": gid % 2,
                  "count": counts, "sum_qty": qty, "sum_price": price},
            dicts={"l_returnflag": src.dicts["l_returnflag"],
                   "l_linestatus": src.dicts["l_linestatus"]},
            valid=counts > 0)

    return WriteSet(Apply(ScanSet(db, lineitem_set),
                          fold=single_pass(init, step, fin,
                                           state_merge=tree_add_states),
                          label=f"scaleq01:{cutoff}"),
                    db, output_set)


def scaleout_join_sink(db: str, key_space: int,
                       lineitem_set: str = "lineitem",
                       orders_set: str = "orders",
                       output_set: str = "scale_join_out"):
    """Grace-hash-capable revenue join with INTEGER accumulators:
    per-order sum of lineitem prices via a LUT probe. Declared
    probe/build keys + an output merge make it a distributed-shuffle
    join over a sharded pool; every order's lineitems co-locate on its
    key's shuffle bucket, so the sharded result is byte-equal to the
    single-node run."""
    import jax.numpy as jnp

    from netsdb_tpu.plan.computations import Join, ScanSet, WriteSet
    from netsdb_tpu.plan.fold import single_pass
    from netsdb_tpu.relational.table import ColumnTable

    def init(prev, src, orders):
        return jnp.zeros((orders.num_rows,), jnp.int32)

    def step(acc, li, orders):
        lut = jnp.full((key_space,), -1, jnp.int32).at[
            orders["o_orderkey"]].set(
            jnp.arange(orders.num_rows, dtype=jnp.int32))
        oidx = lut[li["l_orderkey"]]
        ok = (oidx >= 0) & li.mask()
        return acc.at[jnp.where(ok, oidx, 0)].add(
            jnp.where(ok, li["l_price"], 0))

    def fin(acc, src, orders):
        return ColumnTable(cols={"okey": orders["o_orderkey"],
                                 "rev": acc},
                           valid=acc > 0)

    def merge(a, b):
        return ColumnTable(
            cols={"okey": jnp.concatenate([a["okey"], b["okey"]]),
                  "rev": jnp.concatenate([a["rev"], b["rev"]])},
            valid=jnp.concatenate([a.mask(), b.mask()]))

    return WriteSet(
        Join(ScanSet(db, lineitem_set), ScanSet(db, orders_set),
             fold=single_pass(init, step, fin, merge,
                              probe_key="l_orderkey",
                              build_key="o_orderkey",
                              probe_columns=("l_price",)),
             label=f"scalejoin:{key_space}"),
        db, output_set)


def _scale_rows(client, db: str, out_set: str):
    """Decoded, canonically-ordered result rows (the byte-equality
    probe)."""
    import numpy as np

    t = client.get_table(db, out_set)
    ok = np.asarray(t.mask()) if t.valid is not None \
        else np.ones(t.num_rows, bool)
    names = sorted(t.cols)
    rows = [tuple(int(np.asarray(t[n])[i]) for n in names)
            for i in range(t.num_rows) if ok[i]]
    return sorted(rows)


def run_scaleout_bench(rows: int = 6_000_000, daemons: int = 4,
                       queries: int = 6, page_rows: int = 65_536,
                       join_orders: int = 2048,
                       join_rows: int = 400_000) -> Dict[str, Any]:
    """Paired 1 vs N-daemon arm (``--scale``): aggregate ingest MB/s
    (client-routed partitions vs one daemon) and cold scatter-gather
    q01 QPS over the same paged workload, plus the byte-equality
    checks — the sharded q01 result AND a grace-hash join routed
    through the distributed shuffle must equal the single-node run
    exactly (integer accumulators).

    Daemons are real subprocesses (parallel apply needs separate
    GILs). The device cache is disabled daemon-side so every query
    re-streams its pages — the COLD query path is what capacity
    scaling is about. HOST-ONLY: every daemon is pinned to
    ``JAX_PLATFORMS=cpu`` (N subprocesses cannot share one chip, and
    the caller may hold it), and all daemons share one machine's
    cores, so the reported scale is a count of routing behaviour, not
    a chip number."""
    import tempfile

    import numpy as np

    from netsdb_tpu.serve.client import RemoteClient

    table = scaleout_table(rows)
    payload_mb = sum(np.asarray(v).nbytes
                     for v in table.cols.values()) / 2**20
    rng = np.random.default_rng(7)
    join_li_cols = {
        "l_orderkey": rng.integers(0, join_orders, join_rows,
                                   dtype=np.int32),
        "l_price": rng.integers(1, 1000, join_rows, dtype=np.int32)}
    from netsdb_tpu.relational.table import ColumnTable

    join_li = ColumnTable(join_li_cols, {}, None)
    join_orders_tbl = ColumnTable(
        {"o_orderkey": np.arange(join_orders, dtype=np.int32)}, {},
        None)

    def spawn(port: int, workers: Optional[List[str]] = None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # host-only arm, see docstring
        argv = [sys.executable, "-m", "netsdb_tpu", "serve",
                "--port", str(port),
                "--root", tempfile.mkdtemp(prefix=f"scale_{port}_"),
                "--device-cache-mb", "0",
                "--page-kb", str(page_rows * 4 // 1024)]
        if workers:
            argv += ["--workers", ",".join(workers)]
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))

    def run_arm(n: int) -> Dict[str, Any]:
        ports = [_free_port() for _ in range(n)]
        worker_addrs = [f"127.0.0.1:{p}" for p in ports[1:]]
        procs = [spawn(p) for p in ports[1:]]
        procs.insert(0, spawn(ports[0], workers=worker_addrs or None))
        out: Dict[str, Any] = {"daemons": n}
        try:
            for p in ports:
                _wait_port("127.0.0.1", p)
            c = RemoteClient(f"127.0.0.1:{ports[0]}")
            c.create_database("d")
            kw = {"placement": "range"} if n > 1 else {}
            # ingest warmup: every daemon's first ingest pays lazy
            # imports + arena setup once — both arms exclude it
            c.create_set("d", "warm", type_name="table",
                         storage="paged", **kw)
            c.send_table("d", "warm", scaleout_table(4096, seed=9))
            c.create_set("d", "lineitem", type_name="table",
                         storage="paged", **kw)
            t0 = time.perf_counter()
            c.send_table("d", "lineitem", table)
            ingest_s = time.perf_counter() - t0
            out["ingest_s"] = round(ingest_s, 3)
            out["ingest_mb_per_s"] = round(payload_mb / ingest_s, 1)

            sink = scaleout_q01_sink("d")
            # warmup compiles (both arms pay it once, excluded)
            c.execute_computations(sink, job_name="scale-q01-warm",
                                   fetch_results=False)
            t0 = time.perf_counter()
            for _ in range(queries):
                c.execute_computations(sink, job_name="scale-q01",
                                       fetch_results=False)
            q_s = time.perf_counter() - t0
            out["query_s_total"] = round(q_s, 3)
            out["cold_query_qps"] = round(queries / q_s, 3)
            out["q01_rows"] = _scale_rows(c, "d", "scale_q01_out")

            # the distributed-shuffle join leg
            jkw = {"placement": "hash"} if n > 1 else {}
            c.create_set("d", "jli", type_name="table", **jkw)
            c.create_set("d", "jorders", type_name="table", **jkw)
            c.send_table("d", "jli", join_li)
            c.send_table("d", "jorders", join_orders_tbl)
            jsink = scaleout_join_sink("d", join_orders,
                                       lineitem_set="jli",
                                       orders_set="jorders")
            t0 = time.perf_counter()
            c.execute_computations(jsink, job_name="scale-join",
                                   fetch_results=False)
            out["join_s"] = round(time.perf_counter() - t0, 3)
            out["join_rows"] = _scale_rows(c, "d", "scale_join_out")
            c.close()
        finally:
            for proc in procs:
                proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        return out

    single = run_arm(1)
    pool = run_arm(daemons)
    out: Dict[str, Any] = {
        "rows": rows, "payload_mb": round(payload_mb, 1),
        "daemons": daemons, "queries": queries,
        "single": {k: v for k, v in single.items()
                   if not k.endswith("_rows")},
        "pool": {k: v for k, v in pool.items()
                 if not k.endswith("_rows")},
        "ingest_scale_x": round(pool["ingest_mb_per_s"]
                                / single["ingest_mb_per_s"], 2),
        "query_scale_x": round(pool["cold_query_qps"]
                               / single["cold_query_qps"], 2),
        "q01_byte_equal": pool["q01_rows"] == single["q01_rows"],
        "join_byte_equal": pool["join_rows"] == single["join_rows"],
    }
    out["scaleout_throughput_x"] = round(
        min(out["ingest_scale_x"], out["query_scale_x"]), 2)
    return out


def run_scheduler_bench(clients: int = 8, rows: int = 600_000,
                        page_rows: int = 65_536, pool_mb: int = 8,
                        cache_mb: int = 256) -> Dict[str, Any]:
    """Paired A/B for the query scheduler (``--scheduler``): N
    concurrent byte-identical cold EXECUTEs over one paged set,
    scheduler on vs off. Reported per phase: executions actually run,
    devcache installs, coalesce hits, and client latency p50/p99.

    With the scheduler ON the N identical frames collapse into ONE
    execution (one devcache install, N−1 coalesce hits) and every
    client's latency ≈ the single execution; OFF, N cold streams race
    through one arena (N executions, up to N installs) and the p99 is
    the thrashed tail. Both phases run compile-warm (a separate warmup
    daemon pays the XLA trace once — the in-process jit cache is
    shared) and devcache-cold (fresh store per phase), so the delta
    isolates the scheduling policy."""
    import tempfile
    import threading

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.table import ColumnTable
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu import obs

    rng = np.random.default_rng(0)
    cols = {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows,
                                   dtype=np.int32).astype(np.float32),
        "l_extendedprice": rng.uniform(1000, 100000,
                                       rows).astype(np.float32),
        "l_discount": rng.uniform(0, 0.1, rows).astype(np.float32),
        "l_tax": rng.uniform(0, 0.08, rows).astype(np.float32),
    }
    table = ColumnTable(cols, {"l_returnflag": ["A", "N", "R"],
                               "l_linestatus": ["F", "O"]})
    sink = rdag.q01_sink("d")

    def make_ctl(sched_on: bool) -> ServeController:
        cfg = Configuration(
            root_dir=tempfile.mkdtemp(prefix="sched_bench_"),
            page_size_bytes=page_rows * 4,
            page_pool_bytes=pool_mb << 20,
            device_cache_bytes=cache_mb << 20,
            sched_coalesce=sched_on, sched_affinity=sched_on)
        ctl = ServeController(cfg, port=0, max_jobs=clients)
        ctl.start()
        return ctl

    def load(addr: str) -> None:
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "lineitem", type_name="table",
                     storage="paged")
        c.send_table("d", "lineitem", table)
        c.close()

    def phase(sched_on: bool) -> Dict[str, Any]:
        ctl = make_ctl(sched_on)
        addr = f"127.0.0.1:{ctl.port}"
        try:
            load(addr)
            cache = ctl.library.store.device_cache()
            installs0 = cache.stats()["installs"]
            hits0 = obs.REGISTRY.counter("sched.coalesce_hits").value
            barrier = threading.Barrier(clients)
            lat: List[Optional[float]] = [None] * clients

            def worker(i: int) -> None:
                c = RemoteClient(addr, client_id=f"tenant-{i}")
                try:
                    barrier.wait()
                    t0 = time.perf_counter()
                    c.execute_computations(sink, job_name="q01-sched",
                                           fetch_results=False)
                    lat[i] = time.perf_counter() - t0
                finally:
                    c.close()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            done = sorted(v for v in lat if v is not None)
            with ctl._jobs_lock:
                executions = sum(1 for j in ctl._jobs.values()
                                 if j["name"] == "q01-sched")
            return {
                "clients": clients,
                "executions_run": executions,
                "devcache_installs": cache.stats()["installs"]
                - installs0,
                "coalesce_hits":
                    obs.REGISTRY.counter("sched.coalesce_hits").value
                    - hits0,
                # nearest-rank (ceil) quantiles throughout: at N=8 the
                # p99 is the MAX — the thrashed single worst client is
                # exactly the tail this metric exists to measure,
                # never dropped
                "p50_s": round(
                    done[max(-(-50 * len(done) // 100) - 1, 0)], 4)
                if done else None,
                "p99_s": round(
                    done[min(len(done) - 1,
                             -(-99 * len(done) // 100) - 1)], 4)
                if done else None,
            }
        finally:
            ctl.shutdown()

    # compile warmup on a throwaway daemon (the jit cache is
    # process-wide; both measured phases then isolate the data path)
    warm = make_ctl(True)
    try:
        load(f"127.0.0.1:{warm.port}")
        c = RemoteClient(f"127.0.0.1:{warm.port}")
        c.execute_computations(sink, job_name="warmup",
                               fetch_results=False)
        c.close()
    finally:
        warm.shutdown()

    off = phase(False)
    on = phase(True)
    out: Dict[str, Any] = {"rows": rows, "clients": clients,
                           "scheduler_off": off, "scheduler_on": on}
    if on.get("p99_s") and off.get("p99_s"):
        out["p99_speedup"] = round(off["p99_s"] / on["p99_s"], 2)
        out["p50_speedup"] = round(off["p50_s"] / on["p50_s"], 2)
    return out


def run_serving_bench(daemons: int = 4, batch: int = 8192,
                      features: int = 256, hidden: int = 512,
                      labels: int = 64, frames: int = 6,
                      block=(128, 128)) -> Dict[str, Any]:
    """End-to-end model serving over the sharded pool (``--serving``):
    the ``ff_inference_rows_per_sec_per_chip`` headline measured the
    way the reference serves it — deploy once (weights replicated,
    inputs range-partitioned over leader + N−1 workers), then batched
    scoring frames through ``models.serving.ModelServing``: routed
    batch ingest, the tensor_chain scatter, ONE compiled program per
    shard, slot-order gather.

    The figure is only trusted when the structural gates hold on this
    run: (1) the pool output is byte-equal to a solo daemon scoring
    the same bytes (integer-valued f32 weights make it bit-exact);
    (2) every shard's EXPLAIN tree reports ``whole_plan_jit`` with
    every plan node fused — one program per shard; (3) no daemon holds
    more than ceil(B/N) input rows — the ≤1/N staged-bytes proof.
    CPU-container caveat: all daemons share one machine's cores, so
    rows/s/chip is a lower bound on a per-chip pool; the gates are
    platform-independent."""
    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.models.ff import FFModel
    from netsdb_tpu.models.serving import ff_serving
    from netsdb_tpu.serve import placement as PL
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu.storage.store import SetIdentifier
    import tempfile

    rng = np.random.default_rng(0)

    def ints(shape):
        return rng.integers(-3, 3, size=shape).astype(np.float32)

    weights = (ints((hidden, features)), ints((hidden,)),
               ints((labels, hidden)), ints((labels,)))
    batches = [ints((batch, features)) for _ in range(frames)]

    def make_ctl(tag, workers=None):
        ctl = ServeController(
            Configuration(root_dir=tempfile.mkdtemp(
                prefix=f"serving_{tag}_")),
            port=0, workers=workers)
        ctl.start()
        return ctl

    def solo_arm() -> Dict[str, Any]:
        ctl = make_ctl("solo")
        try:
            c = RemoteClient(f"127.0.0.1:{ctl.port}")
            m = FFModel(db="ffsolo", block=block)
            m.setup(c)
            m.load_weights(c, *weights)
            m.load_inputs(c, batches[0])
            res = c.execute_computations(m.build_inference_dag(),
                                         job_name="solo-warm")
            oracle = np.asarray(next(iter(res.values())).to_dense())
            t0 = time.perf_counter()
            for b in batches:
                m.load_inputs(c, b)
                c.execute_computations(m.build_inference_dag(),
                                       job_name="solo",
                                       fetch_results=False)
            dt = time.perf_counter() - t0
            c.close()
            return {"oracle": oracle,
                    "rows_per_sec": round(frames * batch / dt, 1)}
        finally:
            ctl.shutdown()

    solo = solo_arm()
    out: Dict[str, Any] = {
        "daemons": daemons, "batch": batch, "frames": frames,
        "shape": [features, hidden, labels],
        "solo_rows_per_sec": solo["rows_per_sec"],
    }

    workers = [make_ctl(f"w{i}") for i in range(daemons - 1)]
    leader = make_ctl("leader",
                      workers=[f"127.0.0.1:{w.port}" for w in workers])
    try:
        model = FFModel(db="ffserving", block=block)

        def load(c):
            model.setup(c)
            model.load_weights(c, *weights)

        srv = ff_serving(model, f"127.0.0.1:{leader.port}",
                         block=model.block)
        addrs = srv.deploy(load)
        out["slots"] = len(addrs)

        # cold frame carries the per-layer EXPLAIN decomposition and
        # the structural gates
        cold, forest = srv.score(batches[0], explain=True)
        out["byte_equal"] = bool(
            np.asarray(cold.to_dense()).tobytes()
            == solo["oracle"].tobytes())
        one_program = sorted(forest) == sorted(addrs)
        shard_trees = {}
        for daemon, tree in forest.items():
            nodes = [n for n in tree["nodes"]
                     if n.get("kind") != "WholePlanJit"]
            one_program &= tree["mode"] == "whole_plan_jit" \
                and bool(nodes) and all(n.get("fused") for n in nodes)
            shard_trees[daemon] = {
                "mode": tree["mode"],
                "layers": [f"{n['kind']}:{n.get('label', '')}"
                           for n in nodes]}
        out["one_program_per_shard"] = bool(one_program)
        out["explain_shard"] = shard_trees[addrs[0]]

        # <=1/N structural proof: no daemon holds more input rows
        # than its contiguous range slice
        bound = max(hi - lo
                    for lo, hi in PL.range_slices(batch, len(addrs)))
        max_rows, total_rows = 0, 0
        for ctl in [leader] + workers:
            for it in ctl.library.store.get_items(
                    SetIdentifier("ffserving", "inputs")):
                rows = int(np.asarray(it.to_dense()).shape[0]) \
                    if hasattr(it, "to_dense") else 0
                max_rows = max(max_rows, rows)
                total_rows += rows
        out["rows_bound_ok"] = bool(
            max_rows <= bound and total_rows == batch)
        out["per_shard_max_row_frac"] = round(max_rows / batch, 3)

        # warm frames: every shard rides its compiled program; each
        # frame is DIFFERENT bytes so no coalescing can shortcut it
        t0 = time.perf_counter()
        for b in batches:
            srv.score(b)
        dt = time.perf_counter() - t0
        srv.close()
        total = frames * batch
        out["pool_rows_per_sec"] = round(total / dt, 1)
        out["rows_per_sec_per_chip"] = round(total / dt / daemons, 1)
        out["gates_ok"] = bool(out["byte_equal"]
                               and out["one_program_per_shard"]
                               and out["rows_bound_ok"])
    finally:
        for d in [leader] + workers:
            d.shutdown()
    return out


def run_failover_bench(batches: int = 24, rows_each: int = 2000,
                       kill_after: int = 12,
                       election_s: float = 0.35) -> Dict[str, Any]:
    """Failover-under-traffic (``--failover``): the measured HA
    p99-blip bound the PR 16 acceptance left open. A client streams
    append batches against an armed leader+follower pair (every write
    log-shipped); mid-stream the leader is killed. Each logical
    request's latency INCLUDES its typed-retry failover rotation, so
    the post-kill maximum is the client-observed blip bound. The
    record is only trusted when the promotion happened and totals are
    exact — zero lost, zero doubled writes."""
    import tempfile

    from netsdb_tpu import obs
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.serve import ha as ha_mod
    from netsdb_tpu.serve.client import RemoteClient, RetryPolicy
    from netsdb_tpu.serve.errors import RetryableRemoteError
    from netsdb_tpu.serve.server import ServeController
    from netsdb_tpu.storage.store import SetIdentifier

    kw = dict(heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
              heartbeat_misses=2, mirror_ack_timeout_s=5.0,
              resync_grace_s=2.0)
    follower = ServeController(
        Configuration(root_dir=tempfile.mkdtemp(prefix="ha_f_")),
        port=0, **kw)
    follower.start()
    leader = ServeController(
        Configuration(root_dir=tempfile.mkdtemp(prefix="ha_l_")),
        port=0, followers=[follower.advertise_addr], **kw)
    leader.start()
    out: Dict[str, Any] = {"batches": batches, "rows_each": rows_each,
                           "election_s": election_s}
    try:
        peers = [leader.advertise_addr, follower.advertise_addr]
        for d in (leader, follower):
            d.arm_ha(peers, election_timeout_s=election_s)
        c = RemoteClient(leader.advertise_addr,
                         failover=[follower.advertise_addr],
                         retry=RetryPolicy(max_attempts=80,
                                           base_delay_s=0.05,
                                           max_delay_s=0.25))
        c.create_database("d")
        c.create_set("d", "t", type_name="table")
        table = scaleout_table(rows_each, seed=1)
        lat: List[float] = []
        promos0 = obs.REGISTRY.counter("ha.promotions").value
        done = 0
        for i in range(batches):
            if i == kill_after:
                leader.shutdown()  # mid-traffic kill
            t0 = time.perf_counter()
            deadline = time.monotonic() + 60.0
            while True:
                try:
                    c.send_table("d", "t",
                                 scaleout_table(rows_each, seed=i),
                                 append=True)
                    done += 1
                    break
                except RetryableRemoteError:
                    if time.monotonic() > deadline:
                        break
                    time.sleep(0.05)
            lat.append(time.perf_counter() - t0)
        del table

        def pctl(vals, p):
            vals = sorted(vals)
            return vals[min(len(vals) - 1, -(-p * len(vals) // 100) - 1)]

        steady = lat[:kill_after]
        after = lat[kill_after:]
        out["steady_p50_s"] = round(pctl(steady, 50), 4)
        out["steady_p99_s"] = round(pctl(steady, 99), 4)
        out["blip_p99_s"] = round(pctl(after, 99), 4)
        out["blip_max_s"] = round(max(after), 4)
        out["blip_x"] = round(out["blip_p99_s"]
                              / max(out["steady_p99_s"], 1e-9), 2)
        out["promoted"] = bool(
            follower._ha.role == ha_mod.LEADER
            and obs.REGISTRY.counter("ha.promotions").value
            == promos0 + 1)
        total = sum(
            int(getattr(it, "num_rows", 0) or 0)
            for it in follower.library.store.get_items(
                SetIdentifier("d", "t")))
        out["exact_totals"] = bool(done == batches
                                   and total == batches * rows_each)
        c.close()
    finally:
        for d in (leader, follower):
            d.shutdown()
    return out


# --- distributed fusion A/B (--fusion-distributed) --------------------

def run_fusion_distributed_bench(rows: int = 400_000, daemons: int = 4,
                                 queries: int = 6) -> Dict[str, Any]:
    """Paired mapper A/B over the distributed compilation path
    (``--fusion-distributed``): the 4-daemon scatter q01 plus a
    3-sink dashboard fan, run under three arms — ``optimal`` (the
    region path: ONE compiled partial-fold program per shard, ONE
    coordinator merge+finalize program, the fan shipped as one
    multi-sink subplan per shard), ``greedy`` (``fusion_mapper=
    greedy``: the pre-region scatter path) and ``off``
    (``plan_fusion=False``). Arms share nothing but the workload:
    each gets its own in-process pool, ingest and job names, so every
    arm cold-compiles its own programs.

    The headline ``plan_fusion_distributed_speedup`` is off-arm p50
    round latency over optimal-arm p50 across the warm timed rounds
    (each round = one q01 + one 3-cutoff fan), and is only trusted
    when the
    structural gates hold on THIS run: (1) the optimal arm's cold
    q01 minted exactly one ``fold::`` key and one
    ``region::…::merge`` key with ``shard.subplans`` advancing by
    ``daemons``; (2) the fan ran as ONE scatter query with one
    multi-sink subplan per daemon; (3) q01 rows and every fan sink
    are byte-equal across all three arms. CPU-container caveat: all
    daemons share one machine's cores and the q01 fold states are
    small, so the paired delta is a lower bound on a pool whose
    merge+finalize closes over real state width; the gates are
    platform-independent."""
    import tempfile

    from netsdb_tpu import obs
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.plan import executor
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    cuts = (19950101, 19970101, 19980902)

    def counter(name: str) -> int:
        return obs.REGISTRY.counter(name).value

    def pool(tag: str, **cfg_extra):
        cfg = dict({"page_size_bytes": 64 * 1024}, **cfg_extra)
        ctls = []
        for i in range(daemons - 1):
            w = ServeController(Configuration(
                root_dir=tempfile.mkdtemp(prefix=f"fzd_{tag}_w{i}_"),
                **cfg), port=0)
            w.start()
            ctls.append(w)
        leader = ServeController(Configuration(
            root_dir=tempfile.mkdtemp(prefix=f"fzd_{tag}_l_"), **cfg),
            port=0, workers=[f"127.0.0.1:{w.port}" for w in ctls])
        leader.start()
        return [leader] + ctls

    table = scaleout_table(rows)

    def run_arm(tag: str, **cfg_extra) -> Dict[str, Any]:
        ctls = pool(tag, **cfg_extra)
        try:
            c = RemoteClient(f"127.0.0.1:{ctls[0].port}")
            c.create_database("d")
            c.create_set("d", "lineitem", type_name="table",
                         storage="paged", placement="range")
            c.send_table("d", "lineitem", table)

            def fan_sinks(prefix: str):
                return [scaleout_q01_sink(
                    "d", cutoff=ct, output_set=f"{prefix}_{i}")
                    for i, ct in enumerate(cuts)]

            # cold round: compiles every program the warm rounds ride
            keys0 = set(executor.compiled_cache_keys())
            sp0 = counter("shard.subplans")
            sq0 = counter("shard.scatter_queries")
            c.execute_computations(scaleout_q01_sink("d"),
                                   job_name=f"fzd-{tag}-q01",
                                   fetch_results=False)
            q01_new = set(executor.compiled_cache_keys()) - keys0
            q01_subplans = counter("shard.subplans") - sp0
            sp1 = counter("shard.subplans")
            sq1 = counter("shard.scatter_queries")
            c.execute_computations(*fan_sinks("fan"),
                                   job_name=f"fzd-{tag}-fan",
                                   fetch_results=False)
            arm = {
                "q01_fold_keys": sum(
                    1 for k in q01_new if k.startswith("fold::")),
                "q01_merge_keys": sum(
                    1 for k in q01_new
                    if k.startswith(f"region::fzd-{tag}-q01::scatter::")
                    and f"::merge::k{daemons}::" in k),
                "q01_other_keys": sum(
                    1 for k in q01_new
                    if not k.startswith(("fold::", "region::"))),
                "q01_subplans": q01_subplans,
                "fan_scatter_queries":
                    counter("shard.scatter_queries") - sq1,
                "fan_subplans": counter("shard.subplans") - sp1,
                "q01_scatter_queries": sq1 - sq0,
            }

            # warm timed rounds: every program cached, so the paired
            # delta isolates the dispatch path (region executor +
            # compiled merge vs eager per-node + eager merge). Two
            # untimed warm rounds first — the jit dispatch path keeps
            # warming for a couple of calls after the cold compile,
            # and timing those would charge warmup to the fused arm.
            def round_once() -> float:
                t0 = time.perf_counter()
                c.execute_computations(scaleout_q01_sink("d"),
                                       job_name=f"fzd-{tag}-q01",
                                       fetch_results=False)
                c.execute_computations(*fan_sinks("fan"),
                                       job_name=f"fzd-{tag}-fan",
                                       fetch_results=False)
                return time.perf_counter() - t0

            for _ in range(2):
                round_once()
            lat = sorted(round_once() for _ in range(queries))
            arm["wall_s"] = round(sum(lat), 4)
            arm["round_p50_s"] = round(lat[len(lat) // 2], 4)
            arm["round_min_s"] = round(lat[0], 4)
            arm["rounds_per_sec"] = round(queries / max(
                arm["wall_s"], 1e-9), 2)
            arm["q01_rows"] = _scale_rows(c, "d", "scale_q01_out")
            arm["fan_rows"] = [_scale_rows(c, "d", f"fan_{i}")
                               for i in range(len(cuts))]
            c.close()
            return arm
        finally:
            for d in ctls:
                d.shutdown()

    opt = run_arm("opt")
    greedy = run_arm("greedy", fusion_mapper="greedy")
    off = run_arm("off", plan_fusion=False)

    rows_equal = bool(
        opt["q01_rows"] == greedy["q01_rows"] == off["q01_rows"]
        and opt["fan_rows"] == greedy["fan_rows"] == off["fan_rows"])
    one_program = bool(
        opt["q01_fold_keys"] == 1 and opt["q01_merge_keys"] == 1
        and opt["q01_other_keys"] == 0
        and opt["q01_subplans"] == daemons
        and opt["q01_scatter_queries"] == 1)
    fan_one_subplan = bool(opt["fan_scatter_queries"] == 1
                           and opt["fan_subplans"] == daemons)
    rollback_clean = bool(
        greedy["q01_merge_keys"] == 0 and off["q01_merge_keys"] == 0)

    def strip(arm):
        return {k: v for k, v in arm.items()
                if k not in ("q01_rows", "fan_rows")}

    out: Dict[str, Any] = {
        "rows": rows, "daemons": daemons, "queries": queries,
        "optimal": strip(opt), "greedy": strip(greedy),
        "off": strip(off),
        "byte_equal": rows_equal,
        "one_program_per_shard_plus_merge": one_program,
        "fan_one_subplan_per_shard": fan_one_subplan,
        "rollback_no_region_keys": rollback_clean,
        "gates_ok": bool(rows_equal and one_program
                         and fan_one_subplan and rollback_clean),
    }
    if opt["round_p50_s"] > 0:
        # p50 of per-round latency, not total wall: one straggler
        # round (GC, a page-cache miss) would otherwise decide a
        # paired A/B whose honest signal is the typical round
        out["plan_fusion_distributed_speedup"] = round(
            off["round_p50_s"] / opt["round_p50_s"], 3)
        out["speedup_vs_greedy"] = round(
            greedy["round_p50_s"] / opt["round_p50_s"], 3)
    return out


def run_rebalance_bench(rows: int = 400_000, daemons: int = 4,
                        clients: int = 4, measure_s: float = 6.0,
                        settle_s: float = 4.0) -> Dict[str, Any]:
    """Self-rebalancing paired A/B (``--rebalance``): a
    ``daemons``-strong pool serves an 80/20 hot/cold routed-read mix
    from ``clients`` concurrent threads; mid-run a fresh daemon
    registers (``RESHARD op=add_worker``). The **on** arm lets the
    rebalancer run its forced campaign — slot ownership moves onto
    the new member under live traffic — while the **frozen** arm
    leaves it slot-less. The headline is the RECOVERED throughput
    ratio (``serve_rebalance_recovery_x``): the recovery window opens
    ``settle_s`` after the campaign returns, so it measures the
    steady state the pool recovers TO, not the one-time transient of
    the move itself (the moved slot's first scans re-stage cold
    pages; that cost is the campaign's, not the recovered level's).
    The ratio is gated on the
    flagship exactness story: ZERO failed client requests in either
    arm (in-flight old-epoch frames absorb typed ``PlacementStale``/
    ``ShardUnavailable`` retries inside the client), and the
    post-campaign scan-back must be row- and checksum-exact against
    the ingested tables in BOTH arms.

    Daemons are real subprocesses (parallel scans need separate
    GILs). HOST-ONLY like ``--scale``: every daemon is pinned to
    ``JAX_PLATFORMS=cpu``, so the ratio is never a chip number."""
    import tempfile
    import threading

    import numpy as np

    from netsdb_tpu.serve.client import RemoteClient

    hot = scaleout_table(rows, seed=1)
    cold = scaleout_table(max(rows // 10, 64), seed=2)

    def checksum(t) -> int:
        return int(np.asarray(t["l_price"], dtype=np.int64).sum())

    want = {"hot": (hot.num_rows, checksum(hot)),
            "cold": (cold.num_rows, checksum(cold))}

    def spawn(port: int, on: bool,
              workers: Optional[List[str]] = None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # host-only arm, see docstring
        argv = [sys.executable, "-m", "netsdb_tpu", "serve",
                "--port", str(port),
                "--root", tempfile.mkdtemp(prefix=f"rebal_{port}_"),
                "--device-cache-mb", "0"]
        if on:
            argv.append("--rebalance")
        if workers:
            argv += ["--workers", ",".join(workers)]
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))

    def run_arm(on: bool) -> Dict[str, Any]:
        ports = [_free_port() for _ in range(daemons + 1)]
        worker_addrs = [f"127.0.0.1:{p}" for p in ports[1:daemons]]
        procs = [spawn(p, on) for p in ports[1:daemons]]
        procs.insert(0, spawn(ports[0], on,
                              workers=worker_addrs or None))
        leader_addr = f"127.0.0.1:{ports[0]}"
        out: Dict[str, Any] = {"rebalance": on}
        try:
            for p in ports[:daemons]:
                _wait_port("127.0.0.1", p)
            c = RemoteClient(leader_addr)
            c.create_database("d")
            c.create_set("d", "hot", type_name="table",
                         placement="range")
            c.create_set("d", "cold", type_name="table",
                         placement="range")
            c.send_table("d", "hot", hot)
            c.send_table("d", "cold", cold)
            c.get_table_streamed("d", "hot")  # warm the scan path

            stop = threading.Event()
            counts = [0] * clients
            failures: List[str] = []
            retries = [0] * clients

            def load(i: int) -> None:
                lc = RemoteClient(leader_addr)
                n = 0
                try:
                    while not stop.is_set():
                        name = "hot" if n % 5 else "cold"
                        try:
                            t = lc.get_table_streamed("d", name)
                            if t.num_rows != want[name][0]:
                                failures.append(
                                    f"{name}: {t.num_rows} rows")
                        except Exception as e:  # noqa: BLE001 — the
                            # gate: NOTHING typed-retryable may
                            # escape the client during the campaign
                            failures.append(f"{name}: {e!r}")
                        n += 1
                        counts[i] = n
                finally:
                    retries[i] = lc.total_retries
                    lc.close()

            threads = [threading.Thread(target=load, args=(i,),
                                        daemon=True)
                       for i in range(clients)]
            for t in threads:
                t.start()
            time.sleep(measure_s)
            baseline = sum(counts)
            out["baseline_qps"] = round(baseline / measure_s, 2)

            # the 5th daemon joins mid-run; on the on arm the forced
            # campaign moves slots under this very traffic
            w5 = spawn(ports[daemons], on)
            procs.append(w5)
            _wait_port("127.0.0.1", ports[daemons])
            t0 = time.perf_counter()
            reply = c.add_worker(f"127.0.0.1:{ports[daemons]}")
            out["campaign_s"] = round(time.perf_counter() - t0, 3)
            out["moves"] = [
                {k: m[k] for k in ("db", "set", "slot", "src", "dst",
                                   "ok") if k in m}
                for m in (reply.get("moves") or [])]
            # settle: let the moved slot's cold first scans drain so
            # the recovery window measures the steady state (both
            # arms wait, keeping the within-run warming symmetric)
            time.sleep(settle_s)
            at_join = sum(counts)
            time.sleep(measure_s)
            recovered = sum(counts) - at_join
            stop.set()
            for t in threads:
                t.join(timeout=30)
            out["recovery_qps"] = round(recovered / measure_s, 2)
            out["failed_requests"] = len(failures)
            out["failures"] = failures[:8]
            out["retries_absorbed"] = sum(retries)

            # exactness gates: the campaign must not lose or double
            # a single row
            totals = {}
            for name in ("hot", "cold"):
                t = c.get_table_streamed("d", name)
                totals[name] = (t.num_rows, checksum(t))
            out["totals"] = {k: list(v) for k, v in totals.items()}
            out["totals_exact"] = totals == want
            view = c.placement_view()
            out["placement_epoch"] = (view.get("status")
                                      or {}).get("epoch")
            out["member_slots"] = {m["addr"]: m["slots"]
                                   for m in view.get("members") or []}
            c.close()
        finally:
            for proc in procs:
                proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        return out

    frozen = run_arm(False)
    live = run_arm(True)
    out: Dict[str, Any] = {
        "rows": rows, "daemons": daemons, "clients": clients,
        "measure_s": measure_s, "settle_s": settle_s,
        "frozen": frozen, "on": live,
        "moved_slots": sum(1 for m in live.get("moves") or []
                           if m.get("ok")),
        "zero_failed_requests": frozen["failed_requests"] == 0
        and live["failed_requests"] == 0,
        "totals_exact": frozen["totals_exact"]
        and live["totals_exact"],
        "byte_equal": frozen["totals"] == live["totals"],
    }
    out["serve_rebalance_recovery_x"] = round(
        live["recovery_qps"] / max(frozen["recovery_qps"], 1e-9), 2)
    return out


def run_sessions_bench(sessions: int = 8, steps: int = 32,
                       hidden: int = 64, workers: int = 2,
                       kind: str = "lstm") -> Dict[str, Any]:
    """Stateful interactive serving (``--sessions``): ``sessions``
    concurrent decode loops over one model on a sharded pool (a
    leader routing sticky to ``workers`` session-owning shards), each
    driving ``steps`` GENERATE rounds from its own client thread.

    The headline is aggregate warm decode throughput
    (``serve_sessions_steps_per_sec``), but the number only records
    when the structural gates hold — a fast-but-wrong run must never
    snapshot:

    * **one compiled step program** for the whole timed phase: the
      bucket-rows padding ladder maps every coalesced batch size to
      one (kind, hidden, bucket) program, so the decode trace count
      is PINNED across the run (delta 0 after warmup);
    * **zero arena reads** on the warm path: session state stays
      devcache-resident between steps, never revived from the host
      spill arena;
    * **byte-equality**: every session's full output stream equals a
      solo unbatched replay of the same inputs — coalescing must be
      invisible to results.

    Daemons are in-process (the trace/arena gates read the
    process-global decode stats); on a CPU container the wall number
    measures GIL-shared host stepping, so treat the throughput as a
    lower bound and the gates as the point.
    """
    import tempfile
    import threading

    import numpy as np

    from netsdb_tpu.config import Configuration
    from netsdb_tpu.models import decode as decode_mod
    from netsdb_tpu.models.decode import deploy_decode_model
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    root = tempfile.mkdtemp(prefix="sessions_bench_")
    daemons: List[ServeController] = []
    out: Dict[str, Any] = {
        "sessions": sessions, "steps": steps, "hidden": hidden,
        "workers": workers, "kind": kind,
    }
    try:
        pool = []
        for i in range(workers):
            w = ServeController(
                Configuration(root_dir=os.path.join(root, f"w{i}")),
                port=0)
            w.start()
            daemons.append(w)
            pool.append(w)
        leader = ServeController(
            Configuration(root_dir=os.path.join(root, "leader")),
            port=0, workers=[w.advertise_addr for w in pool])
        leader.start()
        daemons.append(leader)

        deploy = RemoteClient(leader.advertise_addr)
        deploy_decode_model(deploy, "m", kind=kind, hidden=hidden,
                            seed=7)

        def x_row(i: int, s: int) -> np.ndarray:
            rng = np.random.default_rng(7000 + 1000 * i + s)
            return rng.standard_normal(hidden).astype(np.float32)

        clients = [RemoteClient(leader.advertise_addr)
                   for _ in range(sessions)]
        handles = [clients[i].open_session("m", kind=kind)
                   for i in range(sessions)]

        outputs: Dict[int, List[np.ndarray]] = {
            i: [] for i in range(sessions)}
        errors: List[str] = []
        barrier = threading.Barrier(sessions)

        def drive(i: int) -> None:
            try:
                barrier.wait()
                for s in range(steps):
                    outputs[i].append(np.asarray(
                        handles[i].generate(x_row(i, s),
                                            deadline_s=120.0)))
            except Exception as e:  # noqa: BLE001 — gate below
                errors.append(f"session {i}: {e!r}")

        # warmup OUTSIDE the timed window: first steps compile the
        # padded program and install per-session state
        for i in range(sessions):
            outputs[i].append(np.asarray(
                handles[i].generate(x_row(i, -1), deadline_s=120.0)))
            outputs[i].clear()

        def arena_reads() -> int:
            return sum(d.sessions.arena.stats()["reads"]
                       for d in daemons)

        traces0 = decode_mod.decode_stats()["traces"]
        reads0 = arena_reads()
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(sessions)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        stats = decode_mod.decode_stats()
        out["errors"] = errors
        out["wall_s"] = round(wall, 3)
        out["decode"] = dict(stats)
        out["traces_delta"] = stats["traces"] - traces0
        out["arena_reads_delta"] = arena_reads() - reads0
        out["batch_occupancy_avg"] = round(
            stats["steps"] / stats["batches"], 2) \
            if stats.get("batches") else None

        # byte-equality: every session vs a solo unbatched replay on
        # a fresh runtime over the same library (same weights)
        byte_equal = not errors
        rt = decode_mod.DecodeRuntime(leader.library)
        rt.register_model("m", kind)
        for i in range(sessions):
            solo = rt.solo_session("m")
            for s in range(-1, steps):
                y = solo.step(x_row(i, s if s >= 0 else -1))
                if s >= 0 and not np.array_equal(y, outputs[i][s]):
                    byte_equal = False
        out["byte_equal"] = byte_equal
        out["one_program"] = out["traces_delta"] == 0
        out["zero_warm_arena_reads"] = out["arena_reads_delta"] == 0
        if not errors and wall > 0:
            out["serve_sessions_steps_per_sec"] = round(
                sessions * steps / wall, 1)
        for h in handles:
            h.close()
        for c in clients:
            c.close()
        deploy.close()
    finally:
        for d in daemons:
            d.shutdown()
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="serve_bench")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--address", default="127.0.0.1:8108")
    ap.add_argument("--client-id", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--batch", type=int, default=BATCH)
    # None = per-mode default (2 for the FF bench, 8 for --scheduler);
    # an explicit value — however small — is always respected
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="transfer-path comparison: single-frame vs "
                         "streamed scan / chunked tensor")
    ap.add_argument("--data-plane", action="store_true",
                    help="v3 data-plane numbers: single-frame vs "
                         "streamed pipelined ingest MB/s, scan MB/s, "
                         "zero-copy tensor push/pull, hedged-read p99")
    ap.add_argument("--device-cache", action="store_true",
                    help="cold vs warm EXECUTE latency over a "
                         "device-cache-resident paged set, plus "
                         "hit/miss counters")
    ap.add_argument("--partial-cache", action="store_true",
                    help="paired A/B: warm re-query after a 1%% "
                         "append, partial dirty-range invalidation "
                         "vs whole-run invalidation")
    ap.add_argument("--scheduler", action="store_true",
                    help="paired A/B: N concurrent identical cold "
                         "EXECUTEs with the query scheduler on vs "
                         "off — executions run, devcache installs, "
                         "coalesce hits, client p50/p99")
    ap.add_argument("--scale", action="store_true",
                    help="horizontal scale-out: paired 1 vs N-daemon "
                         "arm — aggregate routed-ingest MB/s, cold "
                         "scatter-gather q01 QPS, byte-equality incl. "
                         "a distributed-shuffle join")
    ap.add_argument("--serving", action="store_true",
                    help="end-to-end model serving over the sharded "
                         "pool: deploy + batched scoring frames via "
                         "ModelServing, with byte-equality / one-"
                         "program-per-shard / <=1-N structural gates")
    ap.add_argument("--failover", action="store_true",
                    help="failover-under-traffic: client-observed "
                         "p99 blip across a leader kill on an armed "
                         "HA pair, exact-totals gated")
    ap.add_argument("--fusion-distributed", action="store_true",
                    help="distributed fusion paired A/B: 4-daemon "
                         "scatter q01 + 3-sink fan under the optimal "
                         "mapper vs greedy vs plan_fusion=off, with "
                         "one-program-per-shard + byte-equality gates")
    ap.add_argument("--sessions", action="store_true",
                    help="stateful serving: N concurrent decode "
                         "sessions over a sharded pool — aggregate "
                         "steps/s gated on one-compiled-program, "
                         "zero warm arena reads, byte-equality vs "
                         "solo replay")
    ap.add_argument("--rebalance", action="store_true",
                    help="self-rebalancing paired A/B: 80/20 skewed "
                         "mix over a 4-daemon pool, a 5th daemon "
                         "registers mid-run — rebalance on vs "
                         "frozen, recovery throughput + exactness "
                         "gates")
    ap.add_argument("--daemons", type=int, default=4,
                    help="pool size for --scale (leader + N-1 shards)")
    ap.add_argument("--rows", type=int, default=6_000_000,
                    help="lineitem rows for --scale")
    ap.add_argument("--table-mb", type=int, default=64)
    args = ap.parse_args(argv)
    if args.worker:
        out = run_client_worker(args.address, args.client_id, args.jobs,
                                args.batch)
    elif args.serving:
        out = run_serving_bench(daemons=args.daemons)
    elif args.failover:
        out = run_failover_bench()
    elif args.fusion_distributed:
        out = run_fusion_distributed_bench(daemons=args.daemons)
    elif args.sessions:
        out = run_sessions_bench()
    elif args.rebalance:
        out = run_rebalance_bench(daemons=args.daemons)
    elif args.scale:
        out = run_scaleout_bench(rows=args.rows, daemons=args.daemons)
    elif args.scheduler:
        out = run_scheduler_bench(
            clients=args.clients if args.clients is not None else 8)
    elif args.partial_cache:
        out = run_partial_cache_bench()
    elif args.device_cache:
        out = run_device_cache_bench()
    elif args.data_plane:
        out = run_data_plane_bench(table_mb=args.table_mb)
    elif args.stream:
        out = run_stream_bench()
    else:
        out = run_serve_bench(clients=args.clients
                              if args.clients is not None else 2,
                              jobs_per_client=args.jobs, batch=args.batch,
                              port=args.port)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
