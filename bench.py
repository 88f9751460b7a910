"""Benchmark harness — north-star metric from BASELINE.md: in-database
FFNN inference rows/sec/chip (the reference's flagship workload,
``src/FF/source/SimpleFF.cc`` inference_unit, run through our full
client→store→plan→jit path, not a bare matmul).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference publishes no FF numbers (BASELINE.json
published={}), so we measure the reference-equivalent ourselves: the same
blocked FF inference computed the way netsDB does it per worker thread —
per-block f64 GEMMs on CPU (Eigen ≈ numpy BLAS here), measured on this
host with --cpu-baseline and recorded below.
"""

import json
import os
import sys
import time

try:
    import numpy as np
except ModuleNotFoundError:  # pragma: no cover
    # the image's PATH python has an empty site-packages; the real
    # environment lives in /opt/venv — re-exec there via the shared
    # helper, loaded by FILE PATH (importing the package here would
    # re-trigger the very error being handled)
    import importlib.util

    _p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "netsdb_tpu", "_reexec.py")
    _spec = importlib.util.spec_from_file_location("_netsdb_reexec", _p)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    _mod.maybe_reexec("NETSDB_BENCH_REEXEC")
    raise

# FFTest-style workload: batch x features -> hidden -> labels
BATCH = 16384
FEATURES = 1024
HIDDEN = 4096
LABELS = 1024
BLOCK = (512, 512)

# Measured on this container with `python bench.py --cpu-baseline`
# (numpy/OpenBLAS f64 blocked FF inference, the reference's per-node
# compute model). Updated whenever the workload shape changes.
CPU_BASELINE_ROWS_PER_SEC = None  # filled after first measurement; see below
_CPU_BASELINE_FILE = "BASELINE_CPU.json"


def _cpu_reference_rows_per_sec() -> float:
    """netsDB-equivalent CPU path: f64 block GEMMs + bias/relu/softmax
    over the same blocked layout (one pseudo-cluster worker's work)."""
    rng = np.random.default_rng(0)
    batch = 2048  # smaller sample, extrapolates linearly in batch
    x = rng.standard_normal((batch, FEATURES))
    w1 = rng.standard_normal((HIDDEN, FEATURES))
    b1 = rng.standard_normal((HIDDEN, 1))
    wo = rng.standard_normal((LABELS, HIDDEN))
    bo = rng.standard_normal((LABELS, 1))

    def block_mm(a, b, blk=BLOCK[0]):
        m, k = a.shape
        n = b.shape[1]
        out = np.zeros((m, n))
        for i0 in range(0, m, blk):
            for j0 in range(0, n, blk):
                acc = np.zeros((min(blk, m - i0), min(blk, n - j0)))
                for k0 in range(0, k, blk):
                    acc += a[i0:i0 + blk, k0:k0 + blk] @ b[k0:k0 + blk, j0:j0 + blk]
                out[i0:i0 + blk, j0:j0 + blk] = acc
        return out

    t0 = time.perf_counter()
    h = np.maximum(block_mm(w1, x.T) + b1, 0)
    z = block_mm(wo, h) + bo
    e = np.exp(z - z.max(0, keepdims=True))
    _ = e / e.sum(0, keepdims=True)
    dt = time.perf_counter() - t0
    return batch / dt


# headline metrics and which direction is good — the --compare gate
# fails on a >REGRESSION_PCT move the WRONG way for any of these.
# serve_sched_p99_speedup (the --sched section: N concurrent identical
# cold EXECUTEs, query scheduler on vs off) is only present in
# snapshots taken with --sched; absent-in-one-run metrics are never
# gated (compare_runs reports "not compared").
HEADLINE_METRICS = {"ff_inference_rows_per_sec_per_chip": "higher",
                    "serve_sched_p99_speedup": "higher",
                    "plan_fusion_speedup": "higher",
                    "plan_fusion_distributed_speedup": "higher",
                    "serve_scaleout_throughput_x": "higher",
                    "serve_rebalance_recovery_x": "higher",
                    "serve_sessions_steps_per_sec": "higher",
                    "devcache_partial_speedup": "higher",
                    "summa_staging_reduction_x": "higher",
                    "reshard_collective_speedup": "higher",
                    "ha_failover_p99_blip_s": "lower"}
REGRESSION_PCT = 15.0


def _normalize_snapshot(obj):
    """{metric: record} from any BENCH snapshot shape: the raw
    one-line result dict, the BENCH_rNN.json wrapper (its ``parsed``
    field), or a list of result dicts."""
    if isinstance(obj, dict) and "parsed" in obj:
        obj = obj["parsed"]
    records = obj if isinstance(obj, list) else [obj]
    out = {}
    for rec in records:
        if isinstance(rec, dict) and "metric" in rec and "value" in rec:
            out[rec["metric"]] = rec
    return out


def compare_runs(current, prior, threshold_pct: float = REGRESSION_PCT):
    """Diff two bench results metric by metric. Returns ``(lines,
    regressed)``: human-readable per-metric deltas, and True when any
    HEADLINE metric moved more than ``threshold_pct`` the wrong way —
    the exit-nonzero gate that turns the BENCH trajectory from an
    archive into a regression fence."""
    cur = _normalize_snapshot(current)
    pri = _normalize_snapshot(prior)
    lines, regressed = [], False
    for metric in sorted(set(cur) | set(pri)):
        c, p = cur.get(metric), pri.get(metric)
        if c is None or p is None:
            lines.append(f"{metric}: only in the "
                         f"{'prior' if c is None else 'current'} run "
                         f"— not compared")
            continue
        cv, pv = float(c["value"]), float(p["value"])
        if pv == 0:
            lines.append(f"{metric}: prior value 0 — not compared")
            continue
        delta_pct = 100.0 * (cv - pv) / pv
        direction = HEADLINE_METRICS.get(metric, "higher")
        bad = (delta_pct < -threshold_pct if direction == "higher"
               else delta_pct > threshold_pct)
        verdict = "REGRESSION" if bad and metric in HEADLINE_METRICS \
            else ("regressed (non-headline)" if bad else "ok")
        lines.append(f"{metric}: {pv:.6g} -> {cv:.6g} "
                     f"({delta_pct:+.1f}%, {direction} is better) "
                     f"[{verdict}]")
        if bad and metric in HEADLINE_METRICS:
            regressed = True
    return lines, regressed


def main():
    if "--cpu-baseline" in sys.argv:
        rps = _cpu_reference_rows_per_sec()
        with open(_CPU_BASELINE_FILE, "w") as f:
            json.dump({"cpu_ff_rows_per_sec": rps}, f)
        print(json.dumps({"metric": "cpu_ff_rows_per_sec", "value": rps}))
        return

    if "--summa" in sys.argv:
        # the SUMMA A/B needs a mesh: on a single-accelerator (or
        # CPU-only) box, force the virtual host-platform mesh BEFORE
        # jax initializes its backends (jax reads XLA_FLAGS at backend
        # init, not import — the `import jax` below is the first use)
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=4"
            ).strip()

    compare_path = None
    if "--compare" in sys.argv:
        idx = sys.argv.index("--compare")
        if idx + 1 >= len(sys.argv):
            print("--compare needs a prior BENCH_rNN.json path",
                  file=sys.stderr)
            raise SystemExit(2)
        compare_path = sys.argv[idx + 1]

    import jax

    from netsdb_tpu.client import Client
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.core.blocked import BlockedTensor
    from netsdb_tpu.models.ff import FFModel

    rng = np.random.default_rng(0)
    config = Configuration(root_dir="/tmp/netsdb_bench",
                           default_block_shape=BLOCK)
    client = Client(config)
    from netsdb_tpu.ops.common import on_tpu

    # bfloat16 compute on TPU MXU; f32 on CPU for a fair functional run
    model = FFModel(db="bench", block=BLOCK,
                    compute_dtype="bfloat16" if on_tpu() else None)
    model.setup(client)
    model.load_random_weights(client, FEATURES, HIDDEN, LABELS, seed=1)
    x = rng.standard_normal((BATCH, FEATURES)).astype(np.float32)
    model.load_inputs(client, x)

    params = model.params_from_store(client)
    xb = BlockedTensor.from_dense(x, BLOCK)
    fwd = jax.jit(model.forward)

    import jax.numpy as jnp

    # warmup (compile)
    out = fwd(params, xb)
    float(jnp.sum(out.data))

    # Timing protocol: the iteration loop runs ON DEVICE via lax.scan —
    # each iteration's input depends on the previous
    # output (a +0-sized scalar perturbation), so XLA can neither hoist
    # the forward pass out of the loop nor elide iterations — and
    # throughput is the slope between a short and a long scan, which
    # cancels the fixed dispatch+sync overhead exactly. Median of 3.
    from functools import partial

    @partial(jax.jit, static_argnums=2)
    def loop(p, x0, n):
        def step(carry, _):
            x = x0.with_data(x0.data + carry)
            o = model.forward(p, x)
            # reduce over the WHOLE output so no slice-pushdown can
            # shrink the per-iteration work
            return jnp.sum(o.data).astype(jnp.float32) * 1e-20, None
        c, _ = jax.lax.scan(step, jnp.float32(0.0), None, length=n)
        return c

    from netsdb_tpu.utils.timing import scan_slope_seconds

    # best of two slope measurements: the metric is a CAPABILITY
    # (rows/s the chip sustains), so transient host interference in one
    # window must not understate it — min seconds wins
    res = min((scan_slope_seconds(lambda n: float(loop(params, xb, n)),
                                  lo=4, hi=36) for _ in range(2)),
              key=lambda r: (r["below_noise"],
                             r["seconds_per_iter"] or 0.0))
    if res["below_noise"]:
        # device time unresolvable: report the single-dispatch wall
        # time as an upper bound rather than a clamped-denominator lie
        t0 = time.perf_counter()
        out = fwd(params, xb)
        float(jnp.sum(out.data))
        dt = time.perf_counter() - t0
    else:
        dt = res["seconds_per_iter"]
    rows_per_sec = BATCH / dt

    # baseline: measured reference-equivalent CPU number
    try:
        with open(_CPU_BASELINE_FILE) as f:
            cpu_rps = json.load(f)["cpu_ff_rows_per_sec"]
    except (OSError, KeyError):
        cpu_rps = _cpu_reference_rows_per_sec()
        with open(_CPU_BASELINE_FILE, "w") as f:
            json.dump({"cpu_ff_rows_per_sec": cpu_rps}, f)

    result = {
        "metric": "ff_inference_rows_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / cpu_rps, 2),
    }
    records = [result]
    if "--serving" in sys.argv:
        # end-to-end serving (serve_bench --serving): the SAME
        # ff_inference headline re-measured the way the reference
        # serves it — ModelServing deploy + batched scoring frames
        # over a leader + N−1 worker pool (routed batch ingest,
        # tensor_chain scatter, ONE compiled program per shard,
        # slot-order gather). The record only switches to the
        # end-to-end figure when ALL structural gates hold on this
        # run: byte-equality vs the solo-daemon engine, one-program-
        # per-shard EXPLAIN proof, and per-shard input rows ≤ 1/N.
        # The single-chip capability figure (the historical scan-
        # slope methodology) rides in detail — the two are NOT
        # comparable (end-to-end includes the wire and the gather).
        from netsdb_tpu.workloads.serve_bench import run_serving_bench

        sv = run_serving_bench()
        if sv.get("gates_ok"):
            result = {
                "metric": "ff_inference_rows_per_sec_per_chip",
                "value": sv["rows_per_sec_per_chip"],
                "unit": "rows/s (end-to-end over %d-daemon pool, "
                        "per daemon; byte-equal + one-program + "
                        "<=1/N gates held)" % sv["daemons"],
                "vs_baseline": round(
                    sv["rows_per_sec_per_chip"] / cpu_rps, 2),
                "detail": {
                    "device_capability_rows_per_sec": rows_per_sec,
                    "pool_rows_per_sec": sv["pool_rows_per_sec"],
                    "solo_rows_per_sec": sv["solo_rows_per_sec"],
                    "per_shard_max_row_frac":
                        sv["per_shard_max_row_frac"],
                    "explain_shard": sv["explain_shard"],
                    "batch": sv["batch"], "frames": sv["frames"],
                    "shape": sv["shape"],
                },
            }
            records[0] = result
        else:
            # a gate failure is a BUG (byte-inequality / unfused
            # shard / over-staged slot) — keep the capability figure
            # and surface the failed arm instead of snapshotting it
            print(f"-- serving arm gates failed; end-to-end figure "
                  f"omitted: {json.dumps(sv, default=str)}",
                  file=sys.stderr)
    if "--failover" in sys.argv:
        # HA failover-under-traffic (serve_bench --failover): the
        # client-observed p99 latency blip across a leader kill on an
        # armed leader+follower pair — the PR 16 acceptance leftover.
        # Only recorded when the promotion happened and totals are
        # exact (zero lost, zero doubled writes).
        from netsdb_tpu.workloads.serve_bench import run_failover_bench

        fo = run_failover_bench()
        if fo.get("blip_p99_s") and fo.get("promoted") \
                and fo.get("exact_totals"):
            records.append({
                "metric": "ha_failover_p99_blip_s",
                "value": fo["blip_p99_s"],
                "unit": "s (client-observed p99 across a leader kill "
                        "under append traffic, incl. typed-retry "
                        "rotation; election window %.2fs)"
                        % fo["election_s"],
                "detail": {
                    "steady_p50_s": fo.get("steady_p50_s"),
                    "steady_p99_s": fo.get("steady_p99_s"),
                    "blip_max_s": fo.get("blip_max_s"),
                    "blip_x": fo.get("blip_x"),
                    "batches": fo.get("batches"),
                    "rows_each": fo.get("rows_each"),
                },
            })
        else:
            print(f"-- failover arm unusable (promotion/totals gate "
                  f"failed?); metric omitted: "
                  f"{json.dumps(fo, default=str)}", file=sys.stderr)
    if "--sched" in sys.argv:
        # query-scheduler A/B (serve_bench --scheduler): 8 concurrent
        # byte-identical cold EXECUTEs over one paged set, scheduler
        # on vs off — the serve-concurrency headline
        from netsdb_tpu.workloads.serve_bench import run_scheduler_bench

        sched = run_scheduler_bench()
        if sched.get("p99_speedup"):
            records.append({
                "metric": "serve_sched_p99_speedup",
                "value": sched["p99_speedup"],
                "unit": "x (p99, 8 identical cold EXECUTEs on vs off)",
                "detail": {
                    "on": sched.get("scheduler_on"),
                    "off": sched.get("scheduler_off"),
                },
            })
        else:
            # a broken A/B phase must OMIT the record (absent metrics
            # are never gated), not poison the snapshot with a 0.0
            # that reads as a -100% regression
            print(f"-- sched A/B produced no speedup figure; metric "
                  f"omitted: {json.dumps(sched)}", file=sys.stderr)
    if "--fusion" in sys.argv:
        # fusion-aware plan compilation A/B (micro_bench --fusion):
        # a mixed paged/resident plan with a 12-node resident spine,
        # plan_fusion on vs off through the real executor — the
        # raw-dispatch headline (the fold-stream arm rides along as
        # detail; its CPU number reflects no transfer overlap to hide)
        from netsdb_tpu.workloads.micro_bench import bench_fusion

        fz = bench_fusion()
        if fz.get("plan_fusion_speedup"):
            records.append({
                "metric": "plan_fusion_speedup",
                "value": fz["plan_fusion_speedup"],
                "unit": "x (resident-spine mixed plan, plan_fusion "
                        "on vs off)",
                "detail": {
                    "spine": fz.get("spine"),
                    "fold_stream": fz.get("fold_stream"),
                },
            })
        else:
            print(f"-- fusion A/B produced no speedup figure; metric "
                  f"omitted: {json.dumps(fz)}", file=sys.stderr)
    if "--fusion-distributed" in sys.argv:
        # distributed fusion A/B (serve_bench --fusion-distributed):
        # the 4-daemon scatter q01 + 3-sink fan under the optimal
        # mapper vs plan_fusion=off, gated on the structural proofs
        # (one compiled partial-fold program per shard + one
        # coordinator merge+finalize program, fan shipped as one
        # multi-sink subplan per daemon, byte-equality across all
        # three arms). CPU-container caveat: tiny q01 fold states
        # make the paired delta a lower bound — the gates are the
        # platform-independent part.
        from netsdb_tpu.workloads.serve_bench import (
            run_fusion_distributed_bench)

        fd = run_fusion_distributed_bench()
        if fd.get("plan_fusion_distributed_speedup") \
                and fd.get("gates_ok"):
            records.append({
                "metric": "plan_fusion_distributed_speedup",
                "value": fd["plan_fusion_distributed_speedup"],
                "unit": "x (4-daemon scatter q01 + 3-sink fan, warm "
                        "rounds, optimal mapper vs plan_fusion=off; "
                        "one-program-per-shard + byte-equal gates "
                        "held)",
                "detail": dict(fd),
            })
        else:
            # a broken arm or a failed gate (which is a BUG, not
            # noise) must omit the record, not snapshot it
            print(f"-- fusion-distributed arm unusable; metric "
                  f"omitted: {json.dumps(fd)}", file=sys.stderr)
    if "--scale" in sys.argv:
        # horizontal scale-out (serve_bench --scale): paired 1 vs
        # 4-daemon arm over the q01-style paged workload — aggregate
        # routed-ingest MB/s and cold scatter-gather QPS; the headline
        # is the MIN of the two scale factors (both must scale), and
        # the byte-equality checks ride as detail. CPU-container
        # caveat: all daemons share one machine's cores, so the number
        # is a lower bound on a real multi-host pool. HOST-ONLY arm:
        # its daemon subprocesses are pinned to JAX_PLATFORMS=cpu
        # (this process holds the device), never a chip number.
        from netsdb_tpu.workloads.serve_bench import run_scaleout_bench

        sc = run_scaleout_bench()
        if sc.get("scaleout_throughput_x") \
                and sc.get("q01_byte_equal") \
                and sc.get("join_byte_equal"):
            records.append({
                "metric": "serve_scaleout_throughput_x",
                "value": sc["scaleout_throughput_x"],
                "unit": "x (min of ingest MB/s and cold-query QPS "
                        "scale, 4 daemons vs 1)",
                "detail": dict(sc),
            })
        else:
            # a broken arm (or an equality failure — which is a BUG,
            # not noise) omits the record rather than snapshotting it
            print(f"-- scale arm unusable; metric omitted: "
                  f"{json.dumps(sc)}", file=sys.stderr)
    if "--rebalance" in sys.argv:
        # self-rebalancing placement (serve_bench --rebalance): a
        # 4-daemon pool under a live 80/20 skewed read mix registers
        # a 5th daemon mid-run — rebalance-on (the forced campaign
        # moves slot ownership under traffic) vs frozen. The headline
        # is the recovery-window throughput ratio; it only records
        # when the flagship gates hold: zero failed client requests
        # in EITHER arm (typed retries absorbed inside the client),
        # exact row/checksum totals post-campaign, and byte-equal
        # results across arms. Same single-machine caveat as --scale,
        # and HOST-ONLY like it (daemon subprocesses pinned to the CPU).
        from netsdb_tpu.workloads.serve_bench import run_rebalance_bench

        rb = run_rebalance_bench()
        if rb.get("serve_rebalance_recovery_x") \
                and rb.get("zero_failed_requests") \
                and rb.get("totals_exact") \
                and rb.get("byte_equal"):
            records.append({
                "metric": "serve_rebalance_recovery_x",
                "value": rb["serve_rebalance_recovery_x"],
                "unit": "x (recovery-window routed QPS after a 5th "
                        "daemon joins, rebalance on vs frozen)",
                "detail": dict(rb),
            })
        else:
            # a failed exactness gate is a BUG, not noise — omit the
            # record rather than snapshotting it
            print(f"-- rebalance arm unusable; metric omitted: "
                  f"{json.dumps(rb)}", file=sys.stderr)
    if "--sessions" in sys.argv:
        # stateful interactive serving (serve_bench --sessions): 8
        # concurrent decode sessions over one model on a sharded pool,
        # batched into one padded step program. The headline is
        # aggregate warm steps/s; it only records when the structural
        # gates hold: ONE compiled step program across the whole timed
        # phase (trace count pinned by the bucket ladder), zero arena
        # reads on the warm path (state stays devcache-resident), and
        # every session's stream byte-equal to a solo unbatched
        # replay. CPU-container caveat: in-process daemons share the
        # GIL, so the steps/s is a lower bound; the gates are exact.
        from netsdb_tpu.workloads.serve_bench import run_sessions_bench

        ss = run_sessions_bench()
        if ss.get("serve_sessions_steps_per_sec") \
                and ss.get("one_program") \
                and ss.get("zero_warm_arena_reads") \
                and ss.get("byte_equal") \
                and not ss.get("errors"):
            records.append({
                "metric": "serve_sessions_steps_per_sec",
                "value": ss["serve_sessions_steps_per_sec"],
                "unit": "steps/s (%s concurrent sessions x %s warm "
                        "decode steps, sharded pool, batched into "
                        "one compiled program)"
                        % (ss.get("sessions"), ss.get("steps")),
                "detail": {
                    "wall_s": ss.get("wall_s"),
                    "batch_occupancy_avg":
                        ss.get("batch_occupancy_avg"),
                    "decode": ss.get("decode"),
                    "workers": ss.get("workers"),
                },
            })
        else:
            # a failed structural gate is a BUG, not noise — omit the
            # record rather than snapshotting it
            print(f"-- sessions arm unusable; metric omitted: "
                  f"{json.dumps(ss, default=str)}", file=sys.stderr)
    if "--partial-cache" in sys.argv:
        # block-granular partial-run caching A/B (serve_bench
        # --partial-cache): warm re-query after a 1% append under
        # dirty-range vs whole-run invalidation. The record is only
        # taken when the structural proof holds (zero evictions of
        # pre-append blocks, partial hits advancing) — a fast-but-
        # wrong arm must not snapshot. CPU-container caveat: the
        # "device" is host RAM, the ratio understates HBM savings.
        from netsdb_tpu.workloads.serve_bench import run_partial_cache_bench

        pc = run_partial_cache_bench()
        if pc.get("devcache_partial_speedup") \
                and pc.get("partial_zero_evictions") \
                and pc.get("partial_hits_positive"):
            records.append({
                "metric": "devcache_partial_speedup",
                "value": pc["devcache_partial_speedup"],
                "unit": "x (warm re-query after 1% append, partial "
                        "vs whole-run invalidation)",
                "detail": {
                    "partial": pc.get("partial"),
                    "whole_run": pc.get("whole_run"),
                    "rows": pc.get("rows"),
                    "append_rows": pc.get("append_rows"),
                },
            })
        else:
            print(f"-- partial-cache A/B unusable; metric omitted: "
                  f"{json.dumps(pc)}", file=sys.stderr)
    if "--summa" in sys.argv:
        # distributed linear algebra (micro_bench --summa): SUMMA
        # panel staging vs replicated operands on the virtual mesh
        # (the per-host staged-byte reduction is the headline — it is
        # exact on any container; wall times on a CPU container
        # measure core contention, not a pod) plus reshard-via-
        # collectives vs re-stage-from-arena. Records are gated on
        # the structural proofs: byte-equality between arms and zero
        # arena reads during the reshard — a fast-but-wrong arm must
        # not snapshot.
        from netsdb_tpu.workloads.micro_bench import bench_summa

        sm = bench_summa()
        if sm.get("summa_staging_reduction_x") and sm.get("byte_equal"):
            records.append({
                "metric": "summa_staging_reduction_x",
                "value": sm["summa_staging_reduction_x"],
                "unit": "x (per-host staged bytes, replicated "
                        "operands vs SUMMA panels, N=%s)"
                        % sm.get("participants"),
                "detail": {
                    "per_host_staged_frac":
                        sm.get("per_host_staged_frac"),
                    "summa_s": sm.get("summa_s"),
                    "replicated_s": sm.get("replicated_s"),
                },
            })
        else:
            print(f"-- summa arm unusable; metric omitted: "
                  f"{json.dumps(sm, default=str)}", file=sys.stderr)
        if sm.get("reshard_collective_speedup") \
                and sm.get("reshard_zero_arena_reads"):
            records.append({
                "metric": "reshard_collective_speedup",
                "value": sm["reshard_collective_speedup"],
                "unit": "x (layout change + warm re-query: collective "
                        "steps vs re-stage from arena; CPU container "
                        "understates — the 'device' is host RAM)",
                "detail": {
                    "blocks_moved": sm.get("reshard_blocks_moved"),
                    "steps": sm.get("reshard_steps"),
                    "reshard_s": sm.get("reshard_s"),
                    "restage_s": sm.get("restage_s"),
                },
            })
        else:
            print(f"-- reshard arm unusable (zero-arena proof "
                  f"failed?); metric omitted", file=sys.stderr)
    # one JSON line: a single record stays the historical shape; with
    # --sched the line is a list (compare_runs accepts both)
    print(json.dumps(records if len(records) > 1 else result))

    if compare_path is not None:
        with open(compare_path) as f:
            prior = json.load(f)
        lines, regressed = compare_runs(
            records if len(records) > 1 else result, prior)
        print(f"-- compare vs {compare_path} "
              f"(gate: >{REGRESSION_PCT:.0f}% headline regression):",
              file=sys.stderr)
        for line in lines:
            print(f"   {line}", file=sys.stderr)
        if regressed:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
