"""SUMMA-streamed distributed blocked matmul — pod-scale linear algebra.

Per *Large Scale Distributed Linear Algebra With TPUs* (arxiv
2112.09017), a matmul whose operands exceed one chip's HBM scales by
keeping each mesh participant's PANEL local and moving only one
broadcast panel per step over the interconnect. The reference netsDB
expresses the same algorithm as join-on-block-index + cluster
aggregation shuffled over TCP; ``ops/matmul.py`` collapses it to one
``dot_general`` when the operands fit — this module is the form for
when they DON'T: the left operand lives as arena pages
(``storage/paged.py``) and each participant stages ONLY its own panel
through the bounded ``plan/staging.stage_stream`` pipeline.

Algorithm (1-d mesh of N participants, C = A·B):

* A's row blocks are dealt round-robin to participants (block *i* →
  participant ``i % N``): each stages 1/N of A, host→device, through
  the existing prefetch→upload pipeline.
* B is split into N contraction PANELS (k-slices); participant *d*
  stages only panel *d* (1/N of B).
* Each round dispatches ONE compiled program over the mesh: a scan of
  N SUMMA steps, each broadcasting one participant's B panel over the
  mesh axis (a ``psum`` of the masked panel — the netsDB per-stage
  broadcast, as one collective) and accumulating
  ``A_local[:, panel] @ B_panel`` into the carried C tile. The
  accumulator lives in the scan carry, so XLA updates it in place
  (donation discipline: staged A blocks may be device-CACHE entries
  and are never donated; only the carried C tile is).
* Output C rows land row-sharded like A; each participant's tile is
  pulled per shard and stitched into the host result in block order.

Staged bytes per participant ≈ (|A| + |B|) / N, against the
replicated-operand baseline in which every participant stages
everything.

Device-cache integration: staged A blocks ride the SAME block-granular
:class:`~netsdb_tpu.storage.devcache.DeviceBlockCache` entries as every
other stream — base key ``(scope, "summa", bucket, mesh-label)`` with
the mesh label carrying the participant count and axis, so a warm
re-run under the same mesh serves every panel from HBM with zero arena
reads, and a different mesh shape can never alias.

Runs unchanged on the virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) — the tier-1
fixture (``tests/conftest.py`` ``mesh4``) — and on a real TPU pod.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from netsdb_tpu import obs

#: stream kind for device-cache keys (a SUMMA panel block is placed on
#: ONE owner device — never interchangeable with a "trows" block)
CACHE_KIND = "summa"


def mesh_label(axis: str, devices) -> str:
    """The sharding component of SUMMA cache keys: axis name AND the
    participant device ids — cached panel blocks are committed to
    specific physical devices, so two device sets of the same SIZE
    must still key apart (a warm run over a different quartet would
    otherwise stitch blocks resident on the wrong devices)."""
    ids = ",".join(str(getattr(d, "id", d)) for d in devices)
    return f"summa[{axis}={ids}]"


def _mesh_over(devices: Sequence, axis: str):
    from jax.sharding import Mesh

    return Mesh(np.asarray(list(devices)), (axis,))


@functools.lru_cache(maxsize=32)
def _round_program(mesh, axis: str, n: int, kp: int):
    """ONE compiled SUMMA round: a scan of ``n`` panel-broadcast +
    accumulate steps under ``shard_map``. Cached per (mesh, shapes)
    so every round of every stream with the same bucket reuses one
    XLA program."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(a_blk, b_blk):
        # a_blk: (bucket, n*kp) — this participant's A block, all
        # panel columns; b_blk: (kp, cols) — this participant's B panel
        idx = jax.lax.axis_index(axis)

        def step(c, s):
            # the SUMMA broadcast: participant s's panel to everyone,
            # as one psum of the masked panel (netsDB's per-stage
            # broadcast-to-all-nodes, QuerySchedulerServer.cc:216-330,
            # collapsed to a single collective)
            panel = jax.lax.psum(
                jnp.where(s == idx, b_blk, jnp.zeros_like(b_blk)), axis)
            a_cols = jax.lax.dynamic_slice_in_dim(a_blk, s * kp, kp, 1)
            part = jax.lax.dot_general(
                a_cols, panel, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            # the C tile accumulates IN PLACE: the carry is dead after
            # each step (immediately rebound), so XLA reuses its buffer
            return c + part, None

        c0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        c, _ = jax.lax.scan(step, c0, jnp.arange(n))
        return c

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis, None), P(axis, None)),
                   out_specs=P(axis, None), check_vma=False)
    return jax.jit(fn)


def _stage_b_panels(rhs: np.ndarray, devices: Sequence, axis: str,
                    mesh, staged_bytes: Dict[int, int]):
    """Split B into N contraction panels and stage panel *d* onto
    participant *d* ONLY (1/N of B per host), then assemble the
    k-sharded global — the multi-host
    ``make_array_from_single_device_arrays`` idiom from 2112.09017
    (each process contributes just its addressable shard)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from netsdb_tpu.storage.devcache import to_device

    n = len(devices)
    k = rhs.shape[0]
    kp = -(-k // n)  # panel rows (ceil)
    k_pad = kp * n
    if k_pad > k:
        rhs = np.pad(rhs, ((0, k_pad - k), (0, 0)))
    parts = []
    for d in range(n):
        panel = np.ascontiguousarray(rhs[d * kp:(d + 1) * kp])
        parts.append(to_device(panel, SingleDeviceSharding(devices[d])))
        staged_bytes[d] = staged_bytes.get(d, 0) + panel.nbytes
    b_global = jax.make_array_from_single_device_arrays(
        (k_pad, rhs.shape[1]), NamedSharding(mesh, P(axis, None)), parts)
    return b_global, kp, k_pad


def summa_matmul_streamed(store, name: str, rhs: np.ndarray,
                          devices: Optional[Sequence] = None,
                          axis: str = "data",
                          stage_depth: Optional[int] = None,
                          cache=None, cache_scope: Optional[str] = None,
                          stats_out: Optional[Dict[str, Any]] = None
                          ) -> np.ndarray:
    """``out = M @ rhs`` with M streamed from the page arena and the
    compute SUMMA-distributed over ``devices`` (default: every device).

    ``store`` is a :class:`~netsdb_tpu.storage.paged.PagedTensorStore`
    holding matrix ``name``. Each participant stages only its own
    panel (see module docstring); the whole stream runs ONE compiled
    round program. ``cache``/``cache_scope`` opt the staged A blocks
    into the block-granular device cache (partial mode) under the
    SUMMA mesh label; ``stats_out`` (a dict) receives the run's
    per-participant staged-byte table and round/broadcast counts."""
    import contextlib

    import jax
    from jax.sharding import SingleDeviceSharding

    from netsdb_tpu.plan import staging
    from netsdb_tpu.storage.devcache import to_device

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n < 2:
        raise ValueError("SUMMA needs >= 2 mesh participants; "
                         "use matmul_streamed on one device")
    rhs = np.asarray(rhs)
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    (rows, k), (rb, _), _dtype = store.meta(name)
    if rhs.shape[0] != k:
        raise ValueError(f"matmul contraction mismatch: {name} is "
                         f"{rows}x{k}, rhs {rhs.shape}")
    mesh = _mesh_over(devices, axis)
    cfg = store.config
    depth = getattr(cfg, "stage_depth", 2) if stage_depth is None \
        else stage_depth
    bucketing = getattr(cfg, "shape_bucketing", True)
    density = getattr(cfg, "bucket_density", 2)
    bucket = staging.pad_rows_target(rb, bucketing, density=density)

    staged_bytes: Dict[int, int] = {}
    b_global, kp, k_pad = _stage_b_panels(rhs, devices, axis, mesh,
                                          staged_bytes)
    program = _round_program(mesh, axis, n, kp)

    ranges = store.block_ranges(name)
    start_to_idx = {s: i for i, (s, _e) in enumerate(ranges)}

    def place(item):
        """Pad one host block to (bucket, k_pad) and upload it to its
        PANEL OWNER's device only — the per-shard upload leg. Runs on
        the staging thread (bounded pipeline)."""
        s0, block = item
        i = start_to_idx[s0]
        d = i % n
        nrows = block.shape[0]
        pad_r = bucket - nrows
        pad_c = k_pad - block.shape[1]
        if pad_r or pad_c:
            block = np.pad(block, ((0, max(pad_r, 0)), (0, pad_c)))
        placed = to_device(block, SingleDeviceSharding(devices[d]))
        staged_bytes[d] = staged_bytes.get(d, 0) + block.nbytes
        return i, nrows, placed

    partial = None
    if cache is not None and cache_scope is not None \
            and getattr(cache, "partial", False) and cache.enabled \
            and ranges:
        partial = staging.PartialPlan(
            cache, (str(cache_scope), CACHE_KIND, bucket,
                    mesh_label(axis, devices)), ranges,
            lambda idxs: store.stream_blocks(name, blocks=idxs))

    out = np.zeros((rows, rhs.shape[1]), np.float32)
    zeros_for: Dict[int, Any] = {}  # tail-round filler, one per device

    def filler(d):
        if d not in zeros_for:
            zeros_for[d] = to_device(
                np.zeros((bucket, k_pad), np.float32),
                SingleDeviceSharding(devices[d]))
        return zeros_for[d]

    rounds = bcasts = 0
    compute_s = 0.0
    stream = staging.stage_stream(
        store.stream_blocks(name) if partial is None else None,
        place, depth=depth, name=f"summa:{name}", partial=partial,
        scope=str(cache_scope) if cache_scope is not None else None)
    with contextlib.closing(stream):
        batch: List[Tuple[int, int, Any]] = []

        def run_round(batch):
            nonlocal rounds, bcasts, compute_s
            import jax as _jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            per_dev = {i % n: (i, nv, arr) for i, nv, arr in batch}
            parts = [per_dev[d][2] if d in per_dev else filler(d)
                     for d in range(n)]
            a_global = _jax.make_array_from_single_device_arrays(
                (n * bucket, k_pad), NamedSharding(mesh, P(axis, None)),
                parts)
            t0 = time.perf_counter()
            c = program(a_global, b_global)
            shards = {sh.index[0].start // bucket: sh
                      for sh in c.addressable_shards}
            for d, (i, nv, _arr) in per_dev.items():
                s0, _e0 = ranges[i]
                out[s0:s0 + nv] = np.asarray(shards[d].data)[:nv]
            compute_s += time.perf_counter() - t0
            rounds += 1
            bcasts += n
            obs.REGISTRY.counter("summa.rounds").inc()
            obs.REGISTRY.counter("summa.panel_bcasts").inc(n)
            obs.REGISTRY.counter("summa.panel_bytes").inc(
                n * int(b_global.nbytes // n))
            # the per-step operator record: EXPLAIN decomposes a SUMMA
            # node into panel broadcasts vs compute
            obs.operators.op_add("summa.rounds")
            obs.operators.op_add("summa.panel_bcasts", n)
            obs.operators.op_add("summa.compute_s",
                                 time.perf_counter() - t0)

        for item in stream:
            batch.append(item)
            if len(batch) == n:
                run_round(batch)
                batch = []
        if batch:
            run_round(batch)

    total_staged = sum(staged_bytes.values())
    obs.REGISTRY.counter("summa.staged_bytes").inc(total_staged)
    if stats_out is not None:
        stats_out.update({
            "participants": n, "rounds": rounds,
            "panel_bcasts": bcasts, "compute_s": compute_s,
            "staged_bytes_per_participant": dict(staged_bytes),
            "staged_bytes_total": total_staged,
            "operand_bytes": int(rows * k * 4 + k * rhs.shape[1] * 4),
        })
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------
# 2-d grid SUMMA (arxiv 2112.09017 §III: the true processor-grid form)
# ---------------------------------------------------------------------

#: mesh axis names of the 2-d grid (rows × columns of the processor
#: grid — NOT matrix rows/cols; each device owns one (row, col) tile)
GRID_AXES = ("gr", "gc")


def grid_shape(config, num_devices: int) -> Optional[Tuple[int, int]]:
    """Parse the ``config.summa_grid`` knob ("PRxPC" string or a
    (pr, pc) pair) into a processor-grid shape, or None when the knob
    is unset / the device set cannot fill the grid. A malformed value
    raises — a typo'd grid silently running 1-d would invalidate every
    staging-fraction expectation downstream."""
    raw = getattr(config, "summa_grid", None)
    if not raw:
        return None
    if isinstance(raw, str):
        try:
            pr, pc = (int(p) for p in raw.lower().split("x"))
        except ValueError:
            raise ValueError(f"summa_grid must be 'PRxPC', got {raw!r}")
    else:
        pr, pc = (int(p) for p in raw)
    if pr < 1 or pc < 1 or pr * pc < 2:
        raise ValueError(f"summa_grid needs >= 2 participants, got "
                         f"{pr}x{pc}")
    if pr * pc > num_devices:
        return None  # grid does not fit this host's device set
    return pr, pc


def grid_label(devices, pr: int, pc: int) -> str:
    """Cache-key sharding component for grid layouts — carries the grid
    SHAPE and the participant device ids, so a 2x2 layout can never
    alias a 1x4 (or a different quartet's 2x2): each caches blocks
    split and committed to different physical devices."""
    ids = ",".join(str(getattr(d, "id", d)) for d in devices)
    return f"summa[{pr}x{pc}={ids}]"


def _grid_mesh(devices: Sequence, pr: int, pc: int):
    from jax.sharding import Mesh

    return Mesh(np.asarray(list(devices)[:pr * pc]).reshape(pr, pc),
                GRID_AXES)


@functools.lru_cache(maxsize=32)
def _grid_program(mesh, pr: int, pc: int, kp: int):
    """ONE compiled 2-d SUMMA round: a scan of ``pr*pc`` steps, each
    broadcasting one kp-slice of A along the grid's COLUMN axis and
    one kp-slice of B along its ROW axis (two masked psums — the dual
    of the 1-d panel broadcast), then accumulating the local C tile.
    Per 2112.09017 both matrix dimensions distribute: a device holds
    1/(pr·pc) of A, of B and of C."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    steps = pr * pc

    def local(a_blk, b_blk):
        # a_blk: (rows_local, pr*kp) — this device's tile of A (grid
        # column c owns contraction panels c*pr .. c*pr+pr-1);
        # b_blk: (pc*kp, cols_local) — its tile of B (grid row r owns
        # panels r*pc .. r*pc+pc-1)
        r = jax.lax.axis_index(GRID_AXES[0])
        c = jax.lax.axis_index(GRID_AXES[1])

        def step(acc, s):
            # panel s of A lives on grid column s//pr at local column
            # offset (s%pr)*kp: broadcast it across the column axis
            a_sl = jax.lax.psum(
                jnp.where(s // pr == c,
                          jax.lax.dynamic_slice_in_dim(
                              a_blk, (s % pr) * kp, kp, 1),
                          jnp.zeros((a_blk.shape[0], kp), a_blk.dtype)),
                GRID_AXES[1])
            # panel s of B lives on grid row s//pc at local row offset
            # (s%pc)*kp: broadcast it across the row axis
            b_sl = jax.lax.psum(
                jnp.where(s // pc == r,
                          jax.lax.dynamic_slice_in_dim(
                              b_blk, (s % pc) * kp, kp, 0),
                          jnp.zeros((kp, b_blk.shape[1]), b_blk.dtype)),
                GRID_AXES[0])
            part = jax.lax.dot_general(
                a_sl, b_sl, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return acc + part, None

        acc0 = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        acc, _ = jax.lax.scan(step, acc0, jnp.arange(steps))
        return acc

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(*GRID_AXES), P(*GRID_AXES)),
                   out_specs=P(*GRID_AXES), check_vma=False)
    return jax.jit(fn)


def _stage_b_grid(rhs: np.ndarray, devices: Sequence, mesh,
                  pr: int, pc: int, kp: int,
                  staged_bytes: Dict[int, int]):
    """Tile B over the full grid: device (r, c) stages only rows
    ``[r·pc·kp, (r+1)·pc·kp)`` × its 1/pc column slice — 1/(pr·pc) of
    B per device, the both-dims-exceed-one-host layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from netsdb_tpu.storage.devcache import to_device

    k_pad = pr * pc * kp
    cols = rhs.shape[1]
    cpc = -(-cols // pc)
    cols_pad = cpc * pc
    pad = ((0, k_pad - rhs.shape[0]), (0, cols_pad - cols))
    if any(p for _s, p in pad):
        rhs = np.pad(rhs, pad)
    parts = []
    rows_per = pc * kp
    for r in range(pr):
        for c in range(pc):
            tile = np.ascontiguousarray(
                rhs[r * rows_per:(r + 1) * rows_per,
                    c * cpc:(c + 1) * cpc])
            d = r * pc + c
            parts.append(to_device(tile,
                                   SingleDeviceSharding(devices[d])))
            staged_bytes[d] = staged_bytes.get(d, 0) + tile.nbytes
    b_global = jax.make_array_from_single_device_arrays(
        (k_pad, cols_pad), NamedSharding(mesh, P(*GRID_AXES)), parts)
    return b_global, cols_pad, cpc


def summa_grid_matmul_streamed(store, name: str, rhs: np.ndarray,
                               devices: Optional[Sequence] = None,
                               grid: Tuple[int, int] = (2, 2),
                               stage_depth: Optional[int] = None,
                               cache=None,
                               cache_scope: Optional[str] = None,
                               stats_out: Optional[Dict[str, Any]] = None
                               ) -> np.ndarray:
    """``out = M @ rhs`` over a true 2-d processor grid (2112.09017
    §III): A's row blocks deal round-robin over GRID ROWS and split
    column-wise over GRID COLUMNS, B tiles over the whole grid — every
    device stages ~1/(pr·pc) of each operand, the layout for operands
    whose BOTH dims exceed one host. Each round runs ONE compiled scan
    of pr·pc dual-broadcast steps (``_grid_program``). Staged A tiles
    ride the block-granular device cache under the grid label — a
    layout change (1-d ↔ 2-d) re-keys, and ``parallel/reshard.py``
    moves the cached blocks between layouts instead of re-staging."""
    import contextlib

    import jax
    from jax.sharding import SingleDeviceSharding

    from netsdb_tpu.plan import staging
    from netsdb_tpu.storage.devcache import to_device

    pr, pc = int(grid[0]), int(grid[1])
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < pr * pc:
        raise ValueError(f"summa grid {pr}x{pc} needs {pr * pc} "
                         f"devices, have {len(devices)}")
    devices = devices[:pr * pc]
    rhs = np.asarray(rhs)
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    (rows, k), (rb, _), _dtype = store.meta(name)
    if rhs.shape[0] != k:
        raise ValueError(f"matmul contraction mismatch: {name} is "
                         f"{rows}x{k}, rhs {rhs.shape}")
    mesh = _grid_mesh(devices, pr, pc)
    cfg = store.config
    depth = getattr(cfg, "stage_depth", 2) if stage_depth is None \
        else stage_depth
    bucketing = getattr(cfg, "shape_bucketing", True)
    density = getattr(cfg, "bucket_density", 2)
    bucket = staging.pad_rows_target(rb, bucketing, density=density)

    steps = pr * pc
    kp = -(-k // steps)
    k_pad = steps * kp
    apc = pr * kp  # A columns per grid column
    staged_bytes: Dict[int, int] = {}
    b_global, cols_pad, cpc = _stage_b_grid(rhs, devices, mesh, pr, pc,
                                            kp, staged_bytes)
    program = _grid_program(mesh, pr, pc, kp)

    ranges = store.block_ranges(name)
    start_to_idx = {s: i for i, (s, _e) in enumerate(ranges)}

    def place(item):
        """Pad one host block to (bucket, k_pad), split it into pc
        column tiles and upload tile c to grid device (i % pr, c) —
        each device receives 1/(pr·pc) of A. Runs on the staging
        thread; the tuple of placed tiles is what the partial cache
        records per block range."""
        s0, block = item
        i = start_to_idx[s0]
        r = i % pr
        nrows = block.shape[0]
        pad_r = bucket - nrows
        pad_c = k_pad - block.shape[1]
        if pad_r > 0 or pad_c:
            block = np.pad(block, ((0, max(pad_r, 0)), (0, pad_c)))
        tiles = []
        for c in range(pc):
            tile = np.ascontiguousarray(block[:, c * apc:(c + 1) * apc])
            d = r * pc + c
            tiles.append(to_device(tile,
                                   SingleDeviceSharding(devices[d])))
            staged_bytes[d] = staged_bytes.get(d, 0) + tile.nbytes
        return i, nrows, tuple(tiles)

    partial = None
    if cache is not None and cache_scope is not None \
            and getattr(cache, "partial", False) and cache.enabled \
            and ranges:
        partial = staging.PartialPlan(
            cache, (str(cache_scope), CACHE_KIND, bucket,
                    grid_label(devices, pr, pc)), ranges,
            lambda idxs: store.stream_blocks(name, blocks=idxs))

    out = np.zeros((rows, rhs.shape[1]), np.float32)
    zeros_for: Dict[int, Any] = {}

    def filler(d):
        if d not in zeros_for:
            zeros_for[d] = to_device(
                np.zeros((bucket, apc), np.float32),
                SingleDeviceSharding(devices[d]))
        return zeros_for[d]

    rounds = nsteps = 0
    compute_s = 0.0
    out_cols = rhs.shape[1]

    def run_round(batch):
        nonlocal rounds, nsteps, compute_s
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        per_row = {i % pr: (i, nv, tiles) for i, nv, tiles in batch}
        parts = []
        for r in range(pr):
            for c in range(pc):
                if r in per_row:
                    parts.append(per_row[r][2][c])
                else:
                    parts.append(filler(r * pc + c))
        a_global = _jax.make_array_from_single_device_arrays(
            (pr * bucket, k_pad),
            NamedSharding(mesh, P(*GRID_AXES)), parts)
        t0 = time.perf_counter()
        cg = program(a_global, b_global)
        # stitch: row block i owns grid-row i%pr's pc column shards
        by_tile = {(sh.index[0].start // bucket,
                    sh.index[1].start // cpc): sh
                   for sh in cg.addressable_shards}
        for r, (i, nv, _tiles) in per_row.items():
            s0, _e0 = ranges[i]
            row = np.concatenate(
                [np.asarray(by_tile[(r, c)].data) for c in range(pc)],
                axis=1)
            out[s0:s0 + nv] = row[:nv, :out_cols]
        compute_s += time.perf_counter() - t0
        rounds += 1
        nsteps += steps
        obs.REGISTRY.counter("summa.grid_rounds").inc()
        obs.REGISTRY.counter("summa.grid_steps").inc(steps)
        # each step broadcasts one A slice (column axis) and one B
        # slice (row axis): the dual of the 1-d panel broadcast
        obs.REGISTRY.counter("summa.grid_panel_bcasts").inc(2 * steps)
        obs.operators.op_add("summa.grid_rounds")
        obs.operators.op_add("summa.grid_panel_bcasts", 2 * steps)
        obs.operators.op_add("summa.compute_s",
                             time.perf_counter() - t0)

    stream = staging.stage_stream(
        store.stream_blocks(name) if partial is None else None,
        place, depth=depth, name=f"summa2d:{name}", partial=partial,
        scope=str(cache_scope) if cache_scope is not None else None)
    with contextlib.closing(stream):
        batch: List[Tuple[int, int, Any]] = []
        for item in stream:
            batch.append(item)
            if len(batch) == pr:
                run_round(batch)
                batch = []
        if batch:
            run_round(batch)

    total_staged = sum(staged_bytes.values())
    obs.REGISTRY.counter("summa.grid_staged_bytes").inc(total_staged)
    if stats_out is not None:
        stats_out.update({
            "participants": pr * pc, "grid": (pr, pc),
            "rounds": rounds, "steps": nsteps,
            "panel_bcasts": 2 * nsteps, "compute_s": compute_s,
            "staged_bytes_per_participant": dict(staged_bytes),
            "staged_bytes_total": total_staged,
            "operand_bytes": int(rows * k * 4 + k * rhs.shape[1] * 4),
        })
    return out[:, 0] if squeeze else out


def summa_matmul_resident(a, b, devices: Optional[Sequence] = None,
                          axis: str = "data"):
    """C = A·B for RESIDENT arrays through one SUMMA round — the
    ``ops/matmul.py`` leg of the ``distributed_matmul`` knob: A's rows
    shard over the mesh, B splits into contraction panels, one scan of
    panel broadcasts accumulates each participant's C tile in place.
    Returns a row-sharded global jax array of logical shape
    ``(A.rows, B.cols)`` (f32 accumulation, like the blocked engine)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    mesh = _mesh_over(devices, axis)
    m, k = a.shape
    k2, cols = b.shape
    if k != k2:
        raise ValueError(f"matmul contraction mismatch {a.shape} x "
                         f"{b.shape}")
    kp = -(-k // n)
    mp = -(-m // n)
    a = jnp.pad(jnp.asarray(a), ((0, mp * n - m), (0, kp * n - k)))
    b = jnp.pad(jnp.asarray(b), ((0, kp * n - k2), (0, 0)))
    a = jax.device_put(a, NamedSharding(mesh, P(axis, None)))
    b = jax.device_put(b, NamedSharding(mesh, P(axis, None)))
    program = _round_program(mesh, axis, n, kp)
    obs.REGISTRY.counter("summa.rounds").inc()
    obs.REGISTRY.counter("summa.panel_bcasts").inc(n)
    obs.operators.op_add("summa.rounds")
    obs.operators.op_add("summa.panel_bcasts", n)
    return program(a, b)[:m, :cols]
