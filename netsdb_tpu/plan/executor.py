"""Query executor — QueryScheduler + PipelineStage, single-controller.

The reference ships JobStages to every worker, whose backend builds
pipelines from TCAP and runs them threaded over pages
(``QuerySchedulerServer.cc:216-330``, ``PipelineStage.cc:933-1213``);
shuffles/broadcasts move bytes over TCP. Here one controller process
evaluates the DAG: tensor subgraphs are composed into a single traced
function and jit-compiled (XLA fuses the whole stage and, when inputs
are sharded over a mesh, inserts the collectives the reference's
shuffle threads implemented by hand); host-object nodes (relational
workloads) run eagerly.

The per-job compiled-function cache replaces the master's
``materializedWorkloads`` precompiled-plan cache
(``QuerySchedulerServer.cc:1242-1264``,
``src/queryPlanning/headers/PreCompiledWorkload.h``).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax

from netsdb_tpu import obs
from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.plan.computations import (
    Aggregate,
    Apply,
    Computation,
    Filter,
    Join,
    MultiApply,
    ScanSet,
    WriteSet,
)
from netsdb_tpu.plan.planner import LogicalPlan, plan_from_sinks
from netsdb_tpu.storage.paged import PagedObjects
from netsdb_tpu.storage.store import SetIdentifier, _PagedMatrix

# job_name+canonical-plan → compiled callable (the PreCompiledWorkload
# analogue, QuerySchedulerServer.cc:1242-1264). LRU-bounded: a serving
# loop rebuilding DAGs must not grow this without bound.
from collections import OrderedDict
import threading

_COMPILED_CACHE_CAP = 64
_compiled_cache: "OrderedDict[str, Any]" = OrderedDict()
# serve-layer jobs run on concurrent handler threads; the LRU
# reorder/insert/evict sequence must not interleave
_cache_lock = threading.Lock()

# observability for the shape-bucketing contract: ``traces`` counts XLA
# (re)traces across every cached wrapper — with bucketed chunk shapes
# it must stay CONSTANT across repeated executions over differing
# ragged tails (the recompile-churn regression the buckets absorb)
_compile_stats = {"hits": 0, "misses": 0, "traces": 0}
# per-FUSION-REGION trace counters ("job:fingerprint" → XLA traces of
# that region's one compiled program) — the fused-path analogue of
# ``traces``: flat across ragged-tail re-executions, one tick per
# region program per bucketed shape (plan/fusion.py). Bounded: a
# serving loop rebuilding distinct plans must not grow this without
# limit (oldest-inserted entries drop past the cap — dict preserves
# insertion order), and ``clear_compiled_cache`` resets it with the
# LRU it shadows.
_REGION_TRACES_CAP = 1024
_region_traces: Dict[str, int] = {}


def compile_stats() -> Dict[str, Any]:
    """Snapshot of the compiled-cache counters (hits/misses at the LRU,
    traces at XLA, plus the per-fusion-region trace map under
    ``region_traces``). The staging tests assert ``traces`` is flat
    across re-executions with different ragged tail sizes; the fusion
    tests assert the same of every ``region_traces`` entry."""
    with _cache_lock:
        out: Dict[str, Any] = dict(_compile_stats)
        out["region_traces"] = dict(_region_traces)
        return out


# the central registry reports these SAME counters under "compile"
# (obs/metrics.py absorption hook) — the accessor above keeps its
# shape and callers; the registry snapshot never double-books
obs.REGISTRY.register_collector("compile", compile_stats)


def compiled_cache_keys() -> List[str]:
    """Snapshot of the compiled-program cache's keys (LRU order). The
    rollback-parity tests pin that ``plan_fusion=off`` and
    ``fusion_mapper="greedy"`` produce byte-for-byte the same key sets
    (``fold::``/``eager::``/``region::``) as the paths they roll back
    to — a key drift here is a silent recompile in production."""
    with _cache_lock:
        return list(_compiled_cache)


def _cached_jit(key: str, fn, donate_argnums: tuple = (),
                region: Optional[str] = None,
                name: Optional[str] = None,
                compiler_options: Optional[Dict[str, Any]] = None) -> Any:
    """compiled-cache get-or-insert with the ONE LRU discipline (all
    call sites: fold steps, eager traceable nodes, fusion-region
    programs, whole-plan programs). The wrapper is published BEFORE
    its first call, so concurrent serve-layer threads racing the same
    cold key all call ONE jitted wrapper (jax dedups the trace/compile
    internally) instead of compiling N identical programs.

    ``donate_argnums`` marks arguments XLA may consume in place — the
    fold loops donate argument 0 (the carried accumulator) so each
    step updates its state buffer instead of allocating a fresh one
    per block (gated by ``staging.fold_donate_argnums``).

    ``region`` names the fusion region this program compiles
    (``"job:fingerprint"``) — its retraces tick the per-region map
    ``compile_stats()["region_traces"]`` alongside the global
    ``traces`` counter.

    ``name`` names the jitted wrapper, and with it the XLA module a
    device trace shows (``jit_<name>``); unnamed programs keep the
    wrapper's own name. ``compiler_options`` are this one program's
    XLA options (``jax.jit``'s own argument)."""
    with _cache_lock:
        cached = _compiled_cache.get(key)
        if cached is not None:
            _compiled_cache.move_to_end(key)
            _compile_stats["hits"] += 1
            return cached

    def counted(*args, **kwargs):
        # body runs only when jax (re)traces — the recompile counter;
        # the active query trace (if any) gets the same tick so a
        # profile shows WHICH query paid a compile, and the current
        # operator (if any) so the explain tree shows WHICH NODE did
        with _cache_lock:
            _compile_stats["traces"] += 1
            if region is not None:
                _region_traces[region] = \
                    _region_traces.get(region, 0) + 1
                while len(_region_traces) > _REGION_TRACES_CAP:
                    _region_traces.pop(next(iter(_region_traces)))
        obs.add("executor.traces")
        obs.operators.op_add("traces")
        return fn(*args, **kwargs)

    if name:
        counted.__name__ = counted.__qualname__ = name
    jfn = jax.jit(counted, donate_argnums=tuple(donate_argnums),
                  compiler_options=compiler_options or None)
    with _cache_lock:
        _compile_stats["misses"] += 1
        jfn = _compiled_cache.setdefault(key, jfn)
        _compiled_cache.move_to_end(key)
        while len(_compiled_cache) > _COMPILED_CACHE_CAP:
            _compiled_cache.popitem(last=False)
    return jfn


def _is_traceable(node: Computation) -> bool:
    """Host-object nodes can't go under jit: equi-joins/group-bys over
    Python records and predicate filters stay eager."""
    if isinstance(node, Filter):
        return False
    if isinstance(node, Join) and node.fn is None:
        return False
    if isinstance(node, Aggregate) and node.fn is None:
        return False
    return getattr(node, "traceable", True)


def _eval_node(node: Computation, in_vals: List[Any]) -> Any:
    """``node.evaluate`` with :class:`PagedObjects` inputs iterated
    under ``contextlib.closing`` for the node kinds that CONSUME
    record iterables (eager Filter / Flatten / key-based Join /
    key-based Aggregate): ``PagedObjects.__iter__`` holds the
    relation's read lock for the generator's lifetime, and a predicate
    raising mid-iteration — with the traceback frames retaining the
    generator — would otherwise hold that lock until GC, blocking
    appends and drops indefinitely (ADVICE round 5). Forwarding nodes
    (WriteSet, passthrough gathers, fn-bearing Apply/Aggregate) keep
    the raw handle — they may legitimately pass it downstream."""
    consumes = (isinstance(node, (Filter, MultiApply))
                or (isinstance(node, Join) and node.fn is None)
                or (isinstance(node, Aggregate) and node.fn is None))
    if not consumes or not any(isinstance(v, PagedObjects)
                               for v in in_vals):
        return node.evaluate(*in_vals)
    with contextlib.ExitStack() as stack:
        safe = [stack.enter_context(contextlib.closing(iter(v)))
                if isinstance(v, PagedObjects) else v
                for v in in_vals]
        return node.evaluate(*safe)


def _evaluate(plan: LogicalPlan, scan_values: Dict[int, Any],
              recorder=None) -> Dict[int, Any]:
    """Replay the DAG in topo order, memoizing shared subgraphs (the
    reference would materialize these as intermediate per-job sets).

    ``recorder`` (an :class:`obs.operators.OperatorRecorder`) times
    each node into the per-operator explain tree — passed ONLY by the
    eager execution branch: inside the whole-plan jit this function
    runs under trace (node values are tracers, wall times would be
    trace-time lies), so that caller leaves it None and the fused
    program records via ``mark_fused`` instead."""
    values: Dict[int, Any] = dict(scan_values)
    if recorder is None:
        for node in plan.topo:
            if node.node_id in values:
                continue
            args = [values[i.node_id] for i in node.inputs]
            values[node.node_id] = _eval_node(node, args)
        return values
    base = recorder.reserve(len(plan.topo))
    recorder.mode = "eager" if base == 0 else "mixed"
    pos = {n.node_id: base + i for i, n in enumerate(plan.topo)}
    for node in plan.topo:
        if node.node_id in values:
            # scans (and memoized shared subgraphs): register the node
            # so the tree keeps the plan's shape, no time attributed
            opr = recorder.node(pos[node.node_id], node,
                                [pos[i.node_id] for i in node.inputs])
            opr.rows_out = obs.operators.rows_of(values[node.node_id])
            continue
        args = [values[i.node_id] for i in node.inputs]
        with recorder.op(pos[node.node_id], node,
                         [pos[i.node_id] for i in node.inputs],
                         args) as opr:
            out = _eval_node(node, args)
            opr.rows_out = obs.operators.rows_of(out)
        values[node.node_id] = out
    return values


def _run_fold_once(fold, pc, resident, placement, step_jit):
    """One (possibly multi-pass) fold of a node over a page stream —
    the PageScanner loop: every pass re-streams the source, each chunk
    runs through ONE compiled step (static shapes; the chunk validity
    mask carries the ragged tail), and ``placement`` mesh-shards every
    chunk before the step so the fold executes distributed per chunk
    (ref ``PipelineStage.cc:228-265`` — workers stream local
    partitions through the same pipeline)."""
    state = None
    for pidx, (init, step) in enumerate(fold.passes):
        jstep = step_jit(pidx, step)
        state = init(state, pc, *resident)
        # closing(): a step raising mid-stream must release the page
        # stream's read lock NOW, not at GC (a retained traceback would
        # otherwise hold the lock and block appends/drops indefinitely)
        with obs.span("executor.fold_stream", "executor") as sp, \
                contextlib.closing(
                    pc.stream_tables(placement=placement)) as chunks:
            n = 0
            dev_s = 0.0
            for chunk in chunks:
                t0 = time.perf_counter()
                state = jstep(state, chunk, *resident)
                dev_s += time.perf_counter() - t0
                n += 1
            if sp is not None:
                # per-span device-time estimate (dispatch-inclusive
                # wall around the jitted step) — the host-vs-device
                # split the profile derives (obs/trace.profile)
                sp.counters["chunks"] = n
                sp.counters["device_est_s"] = dev_s
            obs.operators.op_add("device_est_s", dev_s)
            obs.operators.op_add("chunks", n)
            obs.attrib.account("executor.chunks", n,
                               scope=getattr(pc, "cache_scope", None))
    return fold.finalize(state, pc, *resident)


def _pad_table_rows(t, rows: int):
    """Pad a ColumnTable with invalid rows up to ``rows`` — build
    partitions pad to ONE uniform size so every partition reuses a
    single compiled step (the same static-shape discipline as the
    chunk stream)."""
    import jax.numpy as jnp

    from netsdb_tpu.relational.table import ColumnTable

    pad = rows - t.num_rows
    if pad <= 0:
        return t
    cols = {k: jnp.concatenate(
        [jnp.asarray(v), jnp.zeros((pad,) + v.shape[1:], v.dtype)])
        for k, v in t.cols.items()}
    valid = jnp.concatenate([t.mask(), jnp.zeros((pad,), jnp.bool_)])
    return ColumnTable(cols, t.dicts, valid)


def _part_chunks(ppc, placement):
    """Stream one probe partition, restoring the ORIGINAL global
    ``_rowid`` saved by the partitioner (folds arbitrate ties on it).
    Prefetch/staging depth come from the store's config knobs (the old
    hardwired ``prefetch=0`` defeated the overlap end-to-end)."""
    from netsdb_tpu.relational.table import ColumnTable

    if ppc is None:
        return
    with contextlib.closing(
            ppc.stream_tables(placement=placement)) as cs:
        for t in cs:
            if "_rowid0" in t.cols:
                cols = dict(t.cols)
                cols["_rowid"] = cols.pop("_rowid0")
                t = ColumnTable(cols, t.dicts, t.valid)
            yield t


def _run_fold_grace(fold, pc, rest, bi, build_pc, placement, step_jit):
    """ONE-PASS grace hash for a paged build side: hash-partition BOTH
    streams by the declared join keys into arena spill partitions (one
    pass each), then loop partition PAIRS — build partition + its probe
    partition resident together, outputs merged. Every probe page is
    read once for partitioning and each repartitioned row once for
    probing, instead of the whole probe stream once per build block
    (the reference partitions both sides the same way,
    ``PipelineStage.cc:1652-1728`` + ``HashSetManager.h``).

    Partition pairs OVERLAP: while pair *i* probes, pair *i+1*'s build
    block assembles and uploads on a bounded
    :class:`~netsdb_tpu.plan.staging.StagedStream` (depth =
    ``config.stage_depth``, same shutdown/leak discipline as every
    other staged stream), so the device no longer idles between pairs
    waiting for the next build side's host→device copy — the ROADMAP
    "staged multi-stream joins" item."""
    from netsdb_tpu.plan import staging
    from netsdb_tpu.relational.outofcore import partition_by_key

    nparts = build_pc.num_pages()
    build_parts: list = []
    probe_parts: list = []
    out = None
    try:
        # inside the try: a failure partitioning the SECOND side must
        # still reclaim the first side's spill partitions
        build_parts = partition_by_key(build_pc, fold.build_key, nparts)
        # partition pages carry only the columns the fold's step reads
        # (the reference's pipelines carry only listed tuple attrs)
        probe_parts = partition_by_key(pc, fold.probe_key, nparts,
                                       keep_rowid=True,
                                       columns=fold.probe_columns)
        maxr = max((bp.num_rows for bp in build_parts
                    if bp is not None), default=0)

        def pairs():
            for p in range(nparts):
                if build_parts[p] is not None:
                    yield p
                # no build rows: probes there can only miss

        def stage_build(p):
            # runs on the staging thread: pair p's build block pads to
            # ONE uniform size (one compiled step for all pairs) and
            # uploads while the previous pair still probes
            return p, _pad_table_rows(build_parts[p].to_table(), maxr)

        depth = getattr(build_pc.store.config, "stage_depth", 2)
        with obs.span("executor.grace_pairs", "executor") as gsp, \
                contextlib.closing(staging.stage_stream(
                    pairs(), stage_build, depth=depth,
                    name=f"grace-build:{build_pc.name}")) as staged_builds:
            npairs = 0
            nchunks = 0
            dev_s = 0.0
            for p, btab in staged_builds:
                part_res = list(rest)
                part_res[bi] = btab
                state = None
                for pidx, (init, step) in enumerate(fold.passes):
                    jstep = step_jit(pidx, step)
                    state = init(state, pc, *part_res)
                    for chunk in _part_chunks(probe_parts[p], placement):
                        t0 = time.perf_counter()
                        state = jstep(state, chunk, *part_res)
                        dev_s += time.perf_counter() - t0
                        nchunks += 1
                part = fold.finalize(state, pc, *part_res)
                out = part if out is None else fold.merge(out, part)
                npairs += 1
            if gsp is not None:
                gsp.counters["pairs"] = npairs
                gsp.counters["chunks"] = nchunks
                gsp.counters["device_est_s"] = dev_s
            # same device-estimate + attribution feed as every other
            # executor loop — grace joins must not read as 100% host
            # time, and a join-heavy tenant's executor.chunks must book
            obs.operators.op_add("device_est_s", dev_s)
            obs.operators.op_add("chunks", nchunks)
            obs.operators.op_add("pairs", npairs)
            obs.attrib.account("executor.chunks", nchunks,
                               scope=getattr(pc, "cache_scope", None))
    finally:
        # after the closing() above joined the build stager — spill
        # partitions must not be reclaimed under a live upload
        for lst in (build_parts, probe_parts):
            for prt in lst:
                if prt is not None:
                    prt.drop()
    return out


def _run_fold(node, fold, pc, resident, placement, step_jit):
    """Dispatch a fold, handling a paged BUILD side: when a resident
    input is itself paged and the fold declares ``merge``, the join
    runs grace-hash style (ref partitioned hash sets,
    ``src/queryExecution/headers/HashSetManager.h``) — ONE-PASS
    (both sides hash-partitioned, partition pairs joined) when the
    fold declares its join keys, else the legacy per-build-block
    re-stream. Other paged residents assemble HOST-side (never a
    silent device materialization of a set that was paged because it
    does not fit)."""
    from netsdb_tpu.relational.outofcore import PagedColumns

    builds = [i for i, v in enumerate(resident)
              if isinstance(v, PagedColumns)]
    bi = None
    keyed = False  # bi really holds the declared build_key column
    if builds and fold.merge is not None:
        if fold.build_key is not None:
            # a declared build_key scopes the merge rule: it is only
            # correct for partitions of THAT side (key-disjoint blocks;
            # e.g. q02's per-part winner merge is wrong for partitions
            # of supplier) — other paged residents assemble host-side
            for i in builds:
                v = resident[i]
                if (fold.build_key in v.int_names
                        or fold.build_key in v.float_names):
                    bi = i
                    keyed = True
                    break
        else:
            # no declared key (q03-style folds whose merge is written
            # for arbitrary row partitions of their one build side)
            bi = builds[0]
    if bi is not None:
        build_pc = resident[bi]
        rest = [v.to_host_table() if isinstance(v, PagedColumns)
                and i != bi else v for i, v in enumerate(resident)]
        if (keyed and fold.probe_key is not None
                and build_pc.num_pages() > 1):
            return _run_fold_grace(fold, pc, rest, bi, build_pc,
                                   placement, step_jit)
        # legacy discipline (no declared keys): outer loop over build
        # blocks, full probe re-stream per block (prefetch depth from
        # the config knob, not hardwired off)
        out = None
        with contextlib.closing(
                build_pc.stream_tables()) as btabs:
            for btab in btabs:
                part_res = list(rest)
                part_res[bi] = btab
                part = _run_fold_once(fold, pc, tuple(part_res),
                                      placement, step_jit)
                out = part if out is None else fold.merge(out, part)
        return out
    if builds:  # no merge rule: assemble the build side HOST-side
        resident = tuple(v.to_host_table() if isinstance(v, PagedColumns)
                         else v for v in resident)
    return _run_fold_once(fold, pc, resident, placement, step_jit)


def _summa_tensor_route(tfold, pt, others):
    """Route a MATMUL-SHAPED tensor-fold stream through the SUMMA
    engine — the ``config.distributed_matmul`` plan leg: a node whose
    :class:`~netsdb_tpu.plan.fold.TensorFold` declares ``summa_rhs``
    (``fn(block, *others) == block @ summa_rhs(*others)``) skips the
    per-block loop entirely; each mesh participant stages only its
    panel of the paged operand (1/N staged bytes per host, 1/(pr·pc)
    under a ``config.summa_grid`` 2-d mesh) and one compiled round
    program does the contraction (``parallel/summa.py``). Returns the
    assembled BlockedTensor, or None when the route does not apply
    (knob off, no declaration, or the declared RHS does not match
    these inputs) — the caller then takes the per-block path,
    byte-for-byte as before. Device/grid selection lives in ONE place
    — ``PagedTensorStore.matmul_streamed`` — so the plan leg and the
    set-property leg (``store.paged_matmul``) can never route
    differently; with fewer than 2 devices that router falls back to
    the single-device blocked stream, which is byte-equal anyway."""
    import jax.numpy as jnp
    import numpy as np

    rhs_fn = getattr(tfold, "summa_rhs", None)
    cfg = pt.store.config
    if rhs_fn is None or not getattr(cfg, "distributed_matmul", False):
        return None
    rhs = rhs_fn(*others)
    if rhs is None:
        return None
    rhs = np.asarray(rhs)
    (rows, k), _blk, _dtype = pt.store.meta(pt.name)
    if rhs.ndim != 2 or rhs.shape[0] != k:
        return None  # declaration does not fit these inputs
    cache = getattr(pt, "devcache", None)
    scope = getattr(pt, "cache_scope", None)
    cache_scope = None if scope is None else str(scope[0])
    stats = {}
    with obs.span("executor.tensor_summa", "executor") as sp, \
            pt.rw.read():
        out = pt.store.matmul_streamed(pt.name, rhs, devcache=cache,
                                       cache_scope=cache_scope,
                                       stats_out=stats)
        if sp is not None:
            sp.counters["summa.participants"] = stats.get(
                "participants", 0)
            sp.counters["summa.rounds"] = stats.get("rounds", 0)
    obs.operators.op_add("summa.participants",
                         stats.get("participants", 0))
    obs.attrib.account("executor.chunks", stats.get("rounds", 0),
                       scope=cache_scope)
    dense = jnp.asarray(out)
    if tfold.out_block is not None:
        return BlockedTensor.from_dense(dense, tfold.out_block)
    return BlockedTensor.from_dense(dense, tuple(dense.shape))


def _run_tensor_stream(node, tfold, in_vals, src, step_jit):
    """Stream a paged TENSOR input through a node — in-DB inference
    over storage-managed weights (ref ``SimpleFF.cc:94-290``: FF
    scans its weight sets page-fed via ``FFMatrixBlockScanner`` +
    ``PageScanner.h:25-34``). Only one weight page (plus the node's
    resident inputs, the staged next block, and the assembled output)
    is device-resident at a time; the upload of the NEXT block runs on
    the staging thread while the current step computes
    (``plan/staging.stage_stream`` — the host page readers feed the
    device stage).

    mode "rows": evaluate the node's fn once per row block (the block
    substituted for the paged input) and concatenate output rows.
    Blocks pad up to the row-block's shape bucket (zero rows — fn is
    row-decomposable by the mode's contract, so padded output rows are
    sliced back off before assembly) so a ragged tail reuses the
    full-block compiled step's bucket instead of compiling per tail
    size; ``out_block`` re-blocks the assembly so its meta — and
    downstream padded shapes — match the resident path exactly.
    mode "reduce": blocks are contraction slices; ``partial``
    accumulates (donated carry — in-place accumulator updates),
    ``finalize`` applies the epilogue. Reduce blocks are staged but
    NEVER bucket-padded: partials slice their co-factor by
    ``start``+``block.shape[0]``, so padded rows would misalign the
    contraction, not just waste it."""
    import jax.numpy as jnp
    import numpy as np

    from netsdb_tpu.plan import staging

    pt = in_vals[src]
    others = [v for i, v in enumerate(in_vals) if i != src]
    placement = pt.placement
    cfg = pt.store.config
    depth = getattr(cfg, "stage_depth", 2)
    rb = pt.store.meta(pt.name)[1][0]  # nominal rows per block
    bucketing = getattr(cfg, "shape_bucketing", True)
    density = getattr(cfg, "bucket_density", 2)

    # cross-query device cache for the weight stream: store-owned
    # handles carry (ident, write version) — a warm scan replays the
    # staged blocks already in HBM (storage/devcache.py); cached
    # blocks are never donated (the reduce carry is the only donated
    # argument)
    cache = getattr(pt, "devcache", None)
    scope = getattr(pt, "cache_scope", None)
    version_fn = getattr(pt, "cache_version_fn", None)

    def cache_key(kind):
        if cache is None or scope is None:
            return None
        pl = placement.label() if placement is not None else None
        return (scope[0], scope[1], kind, rb, bucketing, density, pl)

    def still_current():
        # install-time currentness: a write racing the scan must not
        # leave a dead (old-version) entry squatting on the budget
        return version_fn is None or version_fn() == scope[1]

    def partial_plan(kind):
        # block-granular caching for the weight stream (partial mode):
        # base key drops the write VERSION — put_tensor/restore are
        # whole-set writes, so dirty-range invalidation drops every
        # block anyway — and keeps the layout/sharding components
        if (cache is None or scope is None
                or not getattr(cache, "partial", False)
                or not cache.enabled):
            return None
        pl = placement.label() if placement is not None else None
        ranges = pt.store.block_ranges(pt.name)
        if not ranges:
            return None
        return staging.PartialPlan(
            cache, (scope[0], kind, rb, bucketing, density, pl), ranges,
            lambda idxs: pt.stream_blocks(blocks=idxs))

    def to_device(block):
        b = jnp.asarray(block)
        if placement is not None:
            b = placement.apply(b)
        return b

    if tfold.mode == "rows":
        routed = _summa_tensor_route(tfold, pt, others)
        if routed is not None:
            return routed

        def place(item):
            _start, block = item
            n = block.shape[0]
            target = staging.pad_rows_target(max(n, rb), bucketing,
                                             density=density)
            if target > n:
                block = np.pad(block, ((0, target - n), (0, 0)))
            return n, to_device(block)

        def step(block, *os):
            bt = BlockedTensor.from_dense(block, tuple(block.shape))
            args = list(os)
            args.insert(src, bt)
            return node.fn(*args)

        jstep = step_jit(0, step, donate=())
        outs = []
        was_blocked = False
        dev_s = 0.0
        with obs.span("executor.tensor_rows", "executor") as sp, \
                contextlib.closing(staging.stage_stream(
                    pt.stream_blocks(), place, depth,
                    name=f"trows:{pt.name}",
                    cache=cache, cache_key=cache_key("trows"),
                    cache_validator=still_current,
                    partial=partial_plan("trows"),
                    scope=None if scope is None else str(scope[0])
                    )) as blocks:
            for n, block in blocks:
                t0 = time.perf_counter()
                out = jstep(block, *others)
                dev_s += time.perf_counter() - t0
                if isinstance(out, BlockedTensor):
                    was_blocked = True
                    out = out.to_dense()
                if out.shape[0] != n:  # drop the bucket's padded rows
                    out = out[:n]
                outs.append(out)
            if sp is not None:
                sp.counters["blocks"] = len(outs)
                sp.counters["device_est_s"] = dev_s
            obs.operators.op_add("device_est_s", dev_s)
            obs.operators.op_add("blocks", len(outs))
            obs.attrib.account("executor.chunks", len(outs),
                               scope=scope if scope is None
                               else str(scope[0]))
        dense = jnp.concatenate(outs, axis=0)
        if tfold.out_block is not None:
            return BlockedTensor.from_dense(dense, tfold.out_block)
        if was_blocked:
            return BlockedTensor.from_dense(dense, tuple(dense.shape))
        return dense

    # mode "reduce": carry accumulation over contraction slices
    def place(item):
        start, block = item
        return jnp.asarray(start, jnp.int32), to_device(block)

    def step(carry, start, block, *os):
        return tfold.partial(carry, start, block, *os)

    jstep = step_jit(1, step)
    carry = None
    dev_s = 0.0
    with obs.span("executor.tensor_reduce", "executor") as sp, \
            contextlib.closing(staging.stage_stream(
                pt.stream_blocks(), place, depth,
                name=f"treduce:{pt.name}",
                cache=cache, cache_key=cache_key("treduce"),
                cache_validator=still_current,
                partial=partial_plan("treduce"),
                scope=None if scope is None else str(scope[0])
                )) as blocks:
        nblk = 0
        for start, block in blocks:
            t0 = time.perf_counter()
            carry = jstep(carry, start, block, *others)
            dev_s += time.perf_counter() - t0
            nblk += 1
        if sp is not None:
            sp.counters["blocks"] = nblk
            sp.counters["device_est_s"] = dev_s
        obs.operators.op_add("device_est_s", dev_s)
        obs.operators.op_add("blocks", nblk)
        obs.attrib.account("executor.chunks", nblk,
                           scope=scope if scope is None
                           else str(scope[0]))
    if tfold.finalize is not None:
        return tfold.finalize(carry, *others)
    return carry


def _execute_streamed(client, plan: LogicalPlan, scan_values: Dict[int, Any],
                      job_name: str) -> Dict[int, Any]:
    """Topo-evaluate a plan with paged scans: fold-bearing consumers of
    a paged set stream it page-by-page (``_run_fold``); everything else
    evaluates eagerly on resident values. Fold-less consumers of a
    paged set materialize it (correct, not streamed — the documented
    fallback, like the reference pinning a set that fits RAM).

    A job mixing paged-reachable and resident-only SINKS never reaches
    here whole: ``execute_computations`` auto-splits it and routes the
    resident-only component through the fused whole-plan jit (round
    5). This path sees only components that genuinely touch paged
    sets; their non-fold resident consumers stay correct but unfused."""
    from netsdb_tpu.plan.fold import flatten_resident
    from netsdb_tpu.relational.outofcore import PagedColumns
    from netsdb_tpu.storage.paged import PagedTensor

    placements = {
        n.node_id: client.store.placement_of(
            SetIdentifier(n.db, n.set_name))
        for n in plan.topo if isinstance(n, ScanSet)
        and isinstance(scan_values.get(n.node_id), PagedColumns)
    }
    plan_key = plan.cache_key()
    # nodes are keyed by topo POSITION, not label alone: two fold-bearing
    # nodes sharing a label in one plan must not reuse each other's
    # jitted steps (plan_key renumbers nodes n0..nN, so structurally
    # identical plans still share cache entries)
    topo_pos = {n.node_id: i for i, n in enumerate(plan.topo)}

    # fusion-aware region mapping (plan/fusion.py): spine regions
    # compile as ONE program each, graft regions weave rowwise
    # pre-chains into fold steps and traceable epilogues onto fold
    # outputs. plan_fusion=False takes the per-node paths byte-for-byte
    # (same keys, same trace counts — the rollback contract).
    from netsdb_tpu.plan import fusion

    cfg = client.store.config
    regions = None
    graft_at: Dict[int, Any] = {}
    consumers: Dict[int, Any] = {}
    if getattr(cfg, "plan_fusion", True):
        consumers = plan.consumers()  # ONE reverse-edge build, shared
        rmap = fusion.map_regions(plan, scan_values, cfg, job_name,
                                  traceable=_is_traceable,
                                  consumers=consumers)
        if rmap.regions:
            regions = rmap
            graft_at = {r.anchor: r for r in rmap.regions
                        if r.kind == "graft"}
    node_by_id = {n.node_id: n for n in plan.topo}
    skip = set(regions.fused_away) if regions is not None else set()

    # fold-step accumulators (argument 0 of every step) are donated so
    # XLA updates the per-stream state in place instead of allocating a
    # fresh HBM buffer every block; auto-gated to backends that
    # implement donation (staging.fold_donate_argnums). ONLY the
    # carried state is ever donated: chunk and resident arguments may
    # be device-cache-owned blocks reused by the next query, and a
    # donated cache block would be freed out from under it — donation
    # applies exclusively to buffers the cache does not own.
    from netsdb_tpu.plan.staging import fold_donate_argnums

    donate_default = fold_donate_argnums(client.store.config)

    def step_jit_for(node, fz: str = ""):
        # ``fz`` carries the graft region's fingerprint when the fold's
        # steps were rewritten with a fused pre-chain: the wrapped step
        # is a DIFFERENT program and must never share a cache entry
        # with the bare fold's (plan_fusion=off keys stay unchanged)
        def step_jit(pidx, step, donate=None):
            key = (f"fold::{job_name}::{plan_key}::"
                   f"n{topo_pos[node.node_id]}::{node.label}::{pidx}{fz}")
            return _cached_jit(
                key, step,
                donate_argnums=donate_default if donate is None else donate)
        return step_jit

    values: Dict[int, Any] = dict(scan_values)
    materialized: Dict[int, Any] = {}  # per-relation memo: N fold-less
    # consumers of one paged set must not stream it N times

    def table_of(pc: PagedColumns):
        # HOST-side assembly (numpy columns): the fold-less fallback
        # must not materialize a paged set in device memory — consumers
        # that compute on it upload transiently as jit arguments.
        # The per-EXECUTION memo consults the CROSS-QUERY cache first
        # (same budget as the device blocks): a warm serve EXECUTE
        # skips the re-assembly stream entirely, and any write bumps
        # the version out from under the entry.
        if id(pc) not in materialized:
            cache, key = pc._cache_ref("host-table", None)
            if cache is not None:
                hit = cache.get(key)
                if hit is not None:
                    materialized[id(pc)] = hit[0]
                    return hit[0]
            t = pc.to_host_table()
            if cache is not None:
                # currentness re-checked INSIDE install's lock — a
                # racing write must not leave a dead entry on the budget
                cache.install(
                    key, [t],
                    validator=lambda: pc._cache_ref(
                        "host-table", None)[1] == key)
            materialized[id(pc)] = t
        return materialized[id(pc)]

    def demote(v):
        """Replace paged handles (possibly inside gather tuples) with
        host-assembled tables for non-streaming consumers."""
        if isinstance(v, PagedColumns):
            return table_of(v)
        if isinstance(v, tuple):
            return tuple(demote(x) for x in v)
        return v

    def graft_epilogue(greg, out):
        """Apply a graft region's fused downstream chain to the fold's
        merged output as ONE compiled program (fold→materialize→
        per-node dispatch becomes fold→one program). Non-jit-safe fold
        outputs run the chain eagerly — a counted fallback, never a
        failure."""
        if greg is None or not greg.post_ids:
            return out
        chain = fusion.compose_chain(
            [node_by_id[i].fn for i in greg.post_ids])
        if not _jit_safe_values([out]):
            fusion.fallback("graft epilogue input not jit-safe")
            return chain(out)
        key = (f"region::{job_name}::{plan_key}::r{greg.rid}"
               f"::{greg.fingerprint}::epi")
        return _cached_jit(key, chain,
                           region=f"{job_name}:{greg.fingerprint}")(out)

    def dispatch(node, in_vals):
        """One node's streamed-path evaluation — extracted so the
        per-operator recorder can time it inclusively. A graft
        anchor's fused epilogue applies to EVERY return path (the
        anchor may dispatch off the streaming branch — e.g. a
        demoted-at-runtime stream input — and its skipped post-chain
        nodes must still run)."""
        greg = graft_at.get(node.node_id)
        return graft_epilogue(greg, _dispatch_inner(node, in_vals,
                                                    greg))

    def _dispatch_inner(node, in_vals, greg):
        fold = getattr(node, "fold", None)
        src = getattr(node, "fold_src", 0)
        if greg is not None and greg.pre_ids:
            # the fused pre-chain was skipped by the topo loop: its
            # paged SCAN handle replaces the chain's (never computed)
            # output, and the chunk transforms run inside the fold's
            # compiled step instead
            in_vals = list(in_vals)
            in_vals[src] = values[greg.stream_src]
        if (fold is not None and len(in_vals) > src
                and isinstance(in_vals[src], PagedColumns)):
            resident = flatten_resident(
                tuple(v for i, v in enumerate(in_vals) if i != src))
            if greg is not None and greg.pre_ids:
                placement = placements.get(greg.stream_src)
                run_fold = fusion.wrap_fold_prechain(
                    fold, [node_by_id[i].fn for i in greg.pre_ids])
                sj = step_jit_for(node, fz=f"::fz{greg.fingerprint}")
            else:
                placement = placements.get(node.inputs[src].node_id)
                run_fold = fold
                sj = step_jit_for(node)
            return _run_fold(node, run_fold, in_vals[src], resident,
                             placement, sj)
        tsrcs = [i for i, v in enumerate(in_vals)
                 if isinstance(v, PagedTensor)]
        if tsrcs:
            tfold = getattr(node, "tensor_fold", None)
            if tfold is None or len(tsrcs) > 1:
                # NO silent materialization: a paged weight exists
                # because it does not fit — a fold-less consumer would
                # defeat that by construction

                def set_of(i):
                    inp = node.inputs[i]
                    return (f"{inp.db}:{inp.set_name}"
                            if isinstance(inp, ScanSet) else in_vals[i].name)

                raise ValueError(
                    f"node "
                    f"{getattr(node, 'label', node.op_kind)!r} "
                    f"consumes paged tensor set(s) "
                    f"{[set_of(i) for i in tsrcs]} but "
                    + ("declares no tensor_fold" if tfold is None else
                       "only one input may stream")
                    + "; give the node a plan.fold.TensorFold, or store "
                      "the set with storage='memory'")
            # a co-input that is a paged RELATION materializes (the
            # documented fold-less fallback) — it cannot ride into the
            # jitted tensor step as a raw stream handle
            in_vals = [demote(v) for v in in_vals]
            return _run_tensor_stream(node, tfold, in_vals, tsrcs[0],
                                      step_jit_for(node))
        if not getattr(node, "passthrough", False):
            # gather-chain nodes forward paged handles untouched so a
            # downstream fold can stream them; real consumers get the
            # host-assembled fallback (tuples from gathers included)
            in_vals = [demote(v) for v in in_vals]
        fn = getattr(node, "fn", None)
        if (fn is not None and _is_traceable(node)
                and isinstance(node, (Apply, Join, Aggregate))
                and not getattr(node, "passthrough", False)
                and _jit_safe_values(in_vals)):
            # traceable fn over table/tensor values: compile it like
            # the resident whole-plan path would, instead of eager
            # per-op dispatch (each unjitted op costs a device RTT —
            # a 15M-row q03 build filter measured minutes eager vs
            # seconds compiled). Passthrough/gather nodes are EXCLUDED:
            # jitting a pure restructuring fn would device-copy (and,
            # for host-assembled tables, device-UPLOAD) everything it
            # forwards — defeating the bounded-device-memory
            # discipline the host fallbacks exist for.
            key = (f"eager::{job_name}::{plan_key}::"
                   f"n{topo_pos[node.node_id]}")
            return _cached_jit(key, fn)(*in_vals)
        return _eval_node(node, in_vals)

    # per-operator explain recording (obs/operators.py): op ids are
    # RESERVED per plan component so auto-split jobs record every
    # component into one collision-free tree; scans register untimed
    # so the rendered tree keeps the plan's full shape
    recorder = obs.operators.current_recorder()
    op_base = recorder.reserve(len(plan.topo)) if recorder else 0
    if recorder is not None and op_base != 0:
        recorder.mode = "mixed"  # an auto-split job's later component
    op_pos = {n.node_id: op_base + i for i, n in enumerate(plan.topo)}

    def run_spine(reg) -> bool:
        """Execute one spine region as ONE compiled program (all its
        nodes replayed under a single trace — the region analogue of
        the whole-plan jit). False = runtime fallback: the caller
        un-skips the region's nodes and they dispatch per-node
        exactly as with fusion off (counted, never an error)."""
        nodes = [node_by_id[i] for i in reg.node_ids]
        rset = set(reg.node_ids)
        in_ids: List[int] = []
        for n in nodes:
            for i in n.inputs:
                if i.node_id not in rset and i.node_id not in in_ids:
                    in_ids.append(i.node_id)
        args = [values[i] for i in in_ids]
        if not _jit_safe_values(args):
            fusion.fallback("spine inputs not jit-safe")
            return False
        out_ids = [nid for nid in reg.node_ids
                   if not consumers.get(nid)
                   or any(c.node_id not in rset
                          for c in consumers.get(nid, ()))]

        def region_fn(*fargs, _nodes=tuple(nodes), _in=tuple(in_ids),
                      _out=tuple(out_ids)):
            vals = dict(zip(_in, fargs))
            for n in _nodes:
                vals[n.node_id] = n.evaluate(
                    *[vals[i.node_id] for i in n.inputs])
            return tuple(vals[o] for o in _out)

        key = (f"region::{job_name}::{plan_key}::r{reg.rid}"
               f"::{reg.fingerprint}")
        jfn = _cached_jit(key, region_fn,
                          region=f"{job_name}:{reg.fingerprint}")
        tail = nodes[-1]
        ctx = (recorder.op(op_pos[tail.node_id], tail,
                           [op_pos[i.node_id] for i in tail.inputs],
                           args)
               if recorder is not None else contextlib.nullcontext())
        with obs.span("executor.fusion_region", "executor") as sp, \
                ctx as opr:
            t0 = time.perf_counter()
            outs = jfn(*args)
            dev_s = time.perf_counter() - t0
            if sp is not None:
                sp.counters["nodes"] = len(nodes)
                sp.counters["device_est_s"] = dev_s
            if opr is not None:
                opr.add("device_est_s", dev_s)
                opr.add("region_nodes", len(nodes))
        for nid, v in zip(out_ids, outs):
            values[nid] = v
        if recorder is not None:
            # the whole region executed as one program: every member
            # keeps its place in the tree, marked fused with its
            # region id; the tail carries the measured wall time
            for n in nodes:
                rec = recorder.node(op_pos[n.node_id], n,
                                    [op_pos[i.node_id]
                                     for i in n.inputs])
                rec.fused = True
                rec.region = reg.rid
                if n.node_id in values:
                    rec.rows_out = obs.operators.rows_of(
                        values[n.node_id])
        return True

    for node in plan.topo:
        if node.node_id in skip:
            # subsumed by a fusion region (spine body or graft
            # pre/post chain): no evaluation here — register the node
            # so the explain tree keeps the plan's full shape
            if recorder is not None:
                opr = recorder.node(
                    op_pos[node.node_id], node,
                    [op_pos[i.node_id] for i in node.inputs])
                opr.fused = True
                opr.region = regions.region_of(node.node_id)
                if node.node_id in values:
                    opr.rows_out = obs.operators.rows_of(
                        values[node.node_id])
            continue
        if node.node_id in values:
            if recorder is not None:
                opr = recorder.node(
                    op_pos[node.node_id], node,
                    [op_pos[i.node_id] for i in node.inputs])
                opr.rows_out = obs.operators.rows_of(
                    values[node.node_id])
            continue
        sreg = (regions.spine_at.get(node.node_id)
                if regions is not None else None)
        if sreg is not None:
            if run_spine(sreg):
                continue
            skip.difference_update(sreg.node_ids)  # per-node fallback
        # a fused-away input (a graft pre-chain member) has no value —
        # dispatch substitutes the chain's paged scan handle; every
        # other input must exist (KeyError here would be a real bug)
        in_vals = [values.get(i.node_id) if i.node_id in skip
                   else values[i.node_id] for i in node.inputs]
        greg = graft_at.get(node.node_id)
        if recorder is None:
            out_val = dispatch(node, in_vals)
        else:
            with recorder.op(op_pos[node.node_id], node,
                             [op_pos[i.node_id] for i in node.inputs],
                             in_vals) as opr:
                out_val = dispatch(node, in_vals)
                opr.rows_out = obs.operators.rows_of(out_val)
                if regions is not None:
                    rid = regions.region_of(node.node_id)
                    if rid is not None:
                        opr.region = rid
        values[node.node_id] = out_val
        if greg is not None and greg.post_ids:
            # the graft epilogue already ran inside dispatch: the
            # chain's tail carries the fused result (its members were
            # skipped above)
            values[greg.post_ids[-1]] = out_val
    return values


def _jit_safe_values(vals) -> bool:
    """True when every value is a table/tensor/array (or a gather tuple
    of them) — the kinds the resident whole-plan jit already traces;
    host-object lists stay on the eager interpreter."""
    import numpy as _np

    from netsdb_tpu.relational.table import ColumnTable

    def ok(v) -> bool:
        if isinstance(v, tuple):
            return all(ok(x) for x in v)
        return isinstance(v, (ColumnTable, BlockedTensor, jax.Array,
                              _np.ndarray))

    return all(ok(v) for v in vals)


def execute_computations(
    client,
    sinks: List[WriteSet],
    job_name: str = "job",
    materialize: bool = True,
) -> Dict[SetIdentifier, Any]:
    """Plan and run; returns {output set ident: value} and (by default)
    materializes results into the store — the reference's OUTPUT sets.

    Recorded per operator when the query is traced (or an
    ``obs.operators.explain_capture`` is active): every node's wall
    time, device estimate, chunk/row counts and cache/compile ticks
    land in the explain tree (``obs/operators.py``). The recursion for
    auto-split jobs joins the outer recording — one tree per logical
    job."""
    with obs.operators.recording(job_name, client.store.config):
        return _execute_computations(client, sinks, job_name,
                                     materialize)


def _execute_computations(
    client,
    sinks: List[WriteSet],
    job_name: str = "job",
    materialize: bool = True,
) -> Dict[SetIdentifier, Any]:
    with obs.span("planner.plan", "planner"):
        plan = plan_from_sinks(sinks)
    t0 = time.perf_counter()

    from netsdb_tpu.relational.outofcore import PagedColumns
    from netsdb_tpu.relational.table import ColumnTable

    if len(plan.sinks) > 1:
        # AUTO-SPLIT (round 5), decided from the CHEAP storage peek
        # BEFORE any scan set is fetched: sinks whose transitive inputs
        # touch no paged set must not lose the fused whole-plan jit
        # because an unrelated sink in the same job went paged (the
        # reference plans stages per source, not per job —
        # ``TCAPAnalyzer.h:20-40``). Recursion re-plans each component;
        # the compiled cache keys on the component's own canonical plan.
        paged_scan_ids = {
            n.node_id for n in plan.topo if isinstance(n, ScanSet)
            and client.store.storage_of(
                SetIdentifier(n.db, n.set_name)) == "paged"}

        def touches_paged(sink) -> bool:
            stack, seen = [sink], set()
            while stack:
                n = stack.pop()
                if n.node_id in seen:
                    continue
                seen.add(n.node_id)
                if n.node_id in paged_scan_ids:
                    return True
                stack.extend(n.inputs)
            return False

        if paged_scan_ids:
            resident_sinks = [s for s in sinks if not touches_paged(s)]
            if resident_sinks and len(resident_sinks) < len(sinks):
                paged_sinks = [s for s in sinks if touches_paged(s)]
                out = execute_computations(client, resident_sinks,
                                           job_name, materialize)
                out.update(execute_computations(client, paged_sinks,
                                                job_name, materialize))
                return out

    scan_values: Dict[int, Any] = {}
    tensor_scans: List[ScanSet] = []
    for node in plan.topo:
        if isinstance(node, ScanSet):
            ident = SetIdentifier(node.db, node.set_name)
            items = client.store.get_items(ident)
            # single-tensor, single-table and single-array sets become
            # traced jit arguments; when their arrays carry a
            # NamedSharding from the set's placement, XLA partitions
            # the whole stage and inserts the cross-device collectives
            # (the reference's per-stage shuffle/broadcast threads,
            # QuerySchedulerServer.cc:216-330)
            # NOTE: np.ndarray single items deliberately stay on the
            # host-object path — conv staged pipelines store numpy
            # images/patches as object items and iterate them
            if len(items) == 1 and isinstance(items[0],
                                              (BlockedTensor, ColumnTable,
                                               jax.Array)):
                scan_values[node.node_id] = items[0]
                tensor_scans.append(node)
            elif len(items) == 1 and isinstance(items[0], PagedColumns):
                # paged set: the value IS the page stream handle; the
                # streamed evaluator folds consumers over it
                scan_values[node.node_id] = items[0]
            elif len(items) == 1 and isinstance(items[0], _PagedMatrix):
                # paged TENSOR set (weights in the arena): the value is
                # a streaming handle; TensorFold-bearing consumers
                # stream it, everything else errors (never materialize)
                scan_values[node.node_id] = client.store.paged_tensor(
                    ident)
            elif len(items) == 1 and isinstance(items[0], PagedObjects):
                # paged OBJECT set: the handle IS an iterable of
                # records, so the eager Filter/Join/Aggregate
                # interpreter consumes it page-streamed unchanged
                scan_values[node.node_id] = items[0]
            else:
                scan_values[node.node_id] = items

    from netsdb_tpu.storage.paged import PagedTensor

    any_paged = any(isinstance(v, (PagedColumns, PagedTensor,
                                   PagedObjects))
                    for v in scan_values.values())
    all_traceable = all(_is_traceable(n) for n in plan.topo)

    num_scans = sum(isinstance(n, ScanSet) for n in plan.topo)

    if any_paged:
        with obs.span("executor.streamed", "executor"):
            values = _execute_streamed(client, plan, scan_values, job_name)
        sink_vals = {s.node_id: values[s.inputs[0].node_id]
                     for s in plan.sinks}
    elif all_traceable and tensor_scans:
        # Cache only pure-tensor jobs: host-object scan values are traced
        # as constants, so a cached callable would pin stale data.
        cacheable = len(tensor_scans) == num_scans
        cache_key = f"{job_name}::{plan.cache_key()}"
        # canonical arg keys (topo position) so independently built
        # DAGs of the same shape hit one traced signature; host-object
        # scan values are closed over (non-cacheable jobs only)
        canon = {n.node_id: i for i, n in enumerate(plan.topo)}
        host_values = {k: v for k, v in scan_values.items()
                       if not isinstance(v, (BlockedTensor, ColumnTable,
                                             jax.Array))}

        def run(tensor_args: Dict[int, BlockedTensor],
                _plan=plan, _canon=canon, _host=host_values):
            merged = dict(_host)
            for n in _plan.topo:
                if isinstance(n, ScanSet) and _canon[n.node_id] in tensor_args:
                    merged[n.node_id] = tensor_args[_canon[n.node_id]]
            values = _evaluate(_plan, merged)
            return [values[s.inputs[0].node_id] for s in _plan.sinks]

        # _cached_jit publishes the wrapper BEFORE its first call, so
        # concurrent serve-layer threads racing the same cold plan all
        # call ONE jitted wrapper (non-cacheable jobs close over host
        # data and must not be shared)
        fn = _cached_jit(cache_key, run) if cacheable else jax.jit(run)
        topo_pos = {n.node_id: i for i, n in enumerate(plan.topo)}
        canon_args = {topo_pos[n.node_id]: scan_values[n.node_id]
                      for n in tensor_scans}
        with obs.span("executor.whole_plan_jit", "executor") as sp:
            t0_jit = time.perf_counter()
            out_list = fn(canon_args)
            dev_s = time.perf_counter() - t0_jit
            if sp is not None:
                sp.counters["device_est_s"] = dev_s
        rec = obs.operators.current_recorder()
        if rec is not None:
            # XLA fused the whole component: the tree keeps the plan's
            # SHAPE (nodes marked fused) with one root carrying the
            # program's measured wall/device time
            rec.mark_fused(plan.topo, dev_s, dev_s)
        sink_vals = {s.node_id: out_list[i] for i, s in enumerate(plan.sinks)}
    else:
        with obs.span("executor.eager", "executor"):
            values = _evaluate(plan, scan_values,
                               recorder=obs.operators.current_recorder())
        sink_vals = {s.node_id: values[s.inputs[0].node_id] for s in plan.sinks}

    results: Dict[SetIdentifier, Any] = {}
    with obs.span("executor.materialize", "executor"):
        for sink in plan.sinks:
            out = sink_vals[sink.node_id]
            ident = SetIdentifier(sink.db, sink.set_name)
            results[ident] = out
            if materialize:
                client.store.create_set(ident)
                if isinstance(out, BlockedTensor):
                    client.store.put_tensor(ident, out)
                elif isinstance(out, (ColumnTable, jax.Array)):
                    # one relation / one raw array IS the set's content
                    # (iterating a jax.Array into rows would be wrong)
                    client.store.clear_set(ident)
                    client.store.add_data(ident, [out])
                elif isinstance(out, dict):
                    client.store.clear_set(ident)
                    client.store.add_data(ident, list(out.items()))
                else:
                    client.store.clear_set(ident)
                    client.store.add_data(ident, list(out))

    elapsed = time.perf_counter() - t0
    # stage timing record — feeds the Lachesis-lite advisor (§2.4)
    try:
        from netsdb_tpu.learning.history import record_job

        record_job(job_name, plan, elapsed)
    except ImportError:
        pass
    return results


def clear_compiled_cache() -> None:
    with _cache_lock:
        _compiled_cache.clear()
        _region_traces.clear()


def drop_compiled(prefix: str) -> int:
    """Drop the cached programs whose key starts with ``prefix`` (one
    tenant of the LRU clearing its own programs, e.g. the decode
    programs of ``models/decode.py``). Returns how many went."""
    with _cache_lock:
        keys = [k for k in _compiled_cache if k.startswith(prefix)]
        for k in keys:
            del _compiled_cache[k]
    return len(keys)


#: the compiled-program LRU for programs built outside this module
#: (the session decode programs): same get-or-insert, same counters.
cached_jit = _cached_jit
