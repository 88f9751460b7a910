"""Collective-step resharding — move a placed set between layouts
WITHOUT a host round-trip.

Per *Memory-efficient array redistribution* (arxiv 2112.01075), any
layout change decomposes into a bounded sequence of collective steps —
all-to-alls, all-gathers, local slices — each moving at most
shard-sized (or, for a gather, array-sized) messages device-to-device.
Before this module, changing a placed set's sharding meant re-staging
every page from the host arena (``SetStore.create_set(placement=...)``
re-places and ``_touch`` drops every cached device block); now
:func:`reshard_set` PLANS the minimal step schedule, executes it over
the device-resident blocks the partial-run cache already holds, and
installs the moved blocks under the NEW layout's cache key — the warm
re-query under the new sharding performs ZERO arena reads.

The planner (:func:`plan_steps`) covers the redistribution lattice a
1-axis mesh needs:

* same spec → no steps;
* sharded → replicated → one ``all_gather`` (tiled — each device
  receives N-1 shard-sized messages);
* replicated → sharded → one ``local_slice`` (zero communication:
  every device already holds its piece);
* sharded(dim i) → sharded(dim j) over the SAME axis → one
  ``all_to_all`` (shard-sized messages, never a full replica — the
  paper's memory-efficient case);
* anything else (axis/mesh changes) → ``all_gather`` then
  ``local_slice``/``replace`` — the bounded two-step fallback (one
  transient replica, noted in the step's ``peak`` estimate).

Devcache integration rides the PR 14 dirty-range path: the moved
ranges are invalidated under the old layout (bumping the scope epoch,
so racing installs of the old layout are refused) and the transformed
blocks install under the new layout's base key as they land — block by
block, bounded memory.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

from netsdb_tpu import obs

# ---------------------------------------------------------------------
# the step schedule
# ---------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Step:
    """One collective step of a reshard schedule.

    ``kind``: ``all_gather`` | ``local_slice`` | ``all_to_all`` |
    ``replace``. ``dim``/``dim_to`` are tensor dims, ``axis`` the mesh
    axis, ``peak`` the per-device transient-bytes FACTOR relative to
    one shard — the bounded-memory annotation from 2112.01075:
    1 = shard-sized messages (the memory-efficient case), the axis
    SIZE = a full replica (all_gather / replace; 0 when the planner
    was not given the mesh sizes and cannot resolve it)."""

    kind: str
    dim: int = 0
    dim_to: int = 0
    axis: str = ""
    peak: int = 1

    def label(self) -> str:
        if self.kind == "all_to_all":
            return f"all_to_all[{self.axis}:{self.dim}->{self.dim_to}]"
        if self.kind in ("all_gather", "local_slice"):
            return f"{self.kind}[{self.axis}:{self.dim}]"
        return self.kind


def _sharded_dims(spec: Tuple, ndim: int) -> List[Tuple[int, Any]]:
    out = []
    for i in range(ndim):
        entry = spec[i] if i < len(spec) else None
        if entry is not None:
            out.append((i, entry))
    return out


def plan_steps(src_spec: Tuple, dst_spec: Tuple, ndim: int,
               same_mesh: bool = True,
               axis_sizes: Optional[Dict[str, int]] = None
               ) -> List[Step]:
    """The minimal collective-step schedule turning ``src_spec`` into
    ``dst_spec`` over ``ndim``-rank values (specs are PartitionSpec
    tuples; missing trailing entries mean replicated). ``same_mesh``
    False (the two placements resolve different device sets) forces
    the gather → replace fallback — cross-mesh single collectives
    don't exist. ``axis_sizes`` (axis name → mesh size) resolves the
    full-replica ``peak`` annotation on gather steps; without it
    those report ``peak=0`` (unknown — a full replica)."""
    src_spec = tuple(src_spec or ())
    dst_spec = tuple(dst_spec or ())
    norm = lambda sp: tuple((sp[i] if i < len(sp) else None)  # noqa: E731
                            for i in range(ndim))
    s, d = norm(src_spec), norm(dst_spec)
    if s == d and same_mesh:
        return []  # an identical spec on a DIFFERENT mesh still moves
    ssh, dsh = _sharded_dims(s, ndim), _sharded_dims(d, ndim)
    if same_mesh and len(ssh) == 1 and len(dsh) == 1 \
            and ssh[0][1] == dsh[0][1] and ssh[0][0] != dsh[0][0]:
        # the paper's headline case: one tiled all-to-all, shard-sized
        # messages, no transient replica
        axis = ssh[0][1]
        axis = axis if isinstance(axis, str) else "+".join(axis)
        return [Step("all_to_all", dim=ssh[0][0], dim_to=dsh[0][0],
                     axis=axis, peak=1)]
    steps: List[Step] = []
    for i, axis in ssh:  # undo the source sharding
        a = axis if isinstance(axis, str) else "+".join(axis)
        # a gather materializes a full replica per device: peak = the
        # axis size (the bounded-memory worst case the planner admits)
        steps.append(Step("all_gather", dim=i, axis=a,
                          peak=(axis_sizes or {}).get(a, 0)))
    if dsh:
        if same_mesh and len(dsh) == 1:
            i, axis = dsh[0]
            a = axis if isinstance(axis, str) else "+".join(axis)
            steps.append(Step("local_slice", dim=i, axis=a, peak=1))
        else:
            # different mesh (or multi-axis target): one device-to-
            # device re-place — still no host round-trip
            steps.append(Step("replace", peak=1))
    elif not same_mesh:
        steps.append(Step("replace", peak=1))
    return steps


# ---------------------------------------------------------------------
# step execution
# ---------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _step_program(kind: str, mesh, axis: str, dim: int, dim_to: int,
                  ndim: int, shard: int):
    """ONE jitted collective program per (step shape, mesh) — a
    reshard applies its schedule to every column of every block, so
    building the shard_map per call would retrace per block (the
    difference between a collective move and a compile storm)."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def spec_at(d):
        entries = [None] * ndim
        entries[d] = axis
        return P(*entries)

    if kind == "all_gather":
        fn = shard_map(
            lambda v: jax.lax.all_gather(v, axis, axis=dim, tiled=True),
            mesh=mesh, in_specs=(spec_at(dim),),
            out_specs=P(*([None] * ndim)), check_vma=False)
    elif kind == "local_slice":
        def slice_local(v):
            idx = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(v, idx * shard, shard,
                                                dim)

        fn = shard_map(slice_local, mesh=mesh,
                       in_specs=(P(*([None] * ndim)),),
                       out_specs=spec_at(dim), check_vma=False)
    else:  # all_to_all
        fn = shard_map(
            lambda v: jax.lax.all_to_all(v, axis, split_axis=dim_to,
                                         concat_axis=dim, tiled=True),
            mesh=mesh, in_specs=(spec_at(dim),),
            out_specs=spec_at(dim_to), check_vma=False)
    return jax.jit(fn)


def _run_step(x, step: Step, src_mesh, dst_mesh, dst_sharding):
    import jax

    ndim = x.ndim
    if step.kind == "all_gather":
        return _step_program("all_gather", src_mesh, step.axis,
                             step.dim, 0, ndim, 0)(x)
    if step.kind == "local_slice":
        size = dst_mesh.shape[step.axis]
        if x.shape[step.dim] % size:
            # indivisible: fall through to the re-place fallback (the
            # planner's divisibility assumption broke on a ragged tail)
            return jax.device_put(x, dst_sharding)
        shard = x.shape[step.dim] // size
        # the value must be addressable on the DESTINATION mesh's
        # devices first (device-to-device broadcast, no host trip)
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(
            x, NamedSharding(dst_mesh, P(*([None] * ndim))))
        return _step_program("local_slice", dst_mesh, step.axis,
                             step.dim, 0, ndim, shard)(x)
    if step.kind == "all_to_all":
        return _step_program("all_to_all", src_mesh, step.axis,
                             step.dim, step.dim_to, ndim, 0)(x)
    # "replace": one device-to-device re-place under the target
    # sharding (jax moves shards directly; the host never sees bytes)
    return jax.device_put(x, dst_sharding)


def execute_steps(x, steps: List[Step], src_placement, dst_placement):
    """Run one value through a schedule, finishing with a normalizing
    re-place under the destination sharding (ensures the result's
    committed sharding compares EQUAL to what a fresh placement would
    produce — the jit-cache-hit requirement)."""
    import jax

    src_mesh = src_placement.mesh() if src_placement is not None else None
    dst_mesh = dst_placement.mesh() if dst_placement is not None else None
    nd = getattr(x, "ndim", 0)
    dst_sharding = None
    if dst_placement is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = tuple(dst_placement.spec)[:nd]
        spec = spec + (None,) * (nd - len(spec))
        dst_sharding = NamedSharding(dst_mesh, P(*spec))
    for step in steps:
        x = _run_step(x, step, src_mesh, dst_mesh, dst_sharding)
        obs.REGISTRY.counter("reshard.steps").inc()
        obs.operators.op_add("reshard.steps")
    if dst_sharding is not None:
        sh = getattr(x, "sharding", None)
        if sh is None or not sh.is_equivalent_to(dst_sharding, nd):
            x = jax.device_put(x, dst_sharding)
    return x


def _spec_for(placement, ndim: int) -> Tuple:
    if placement is None:
        return (None,) * ndim
    spec = tuple(placement.spec)
    return spec[:ndim] + (None,) * max(ndim - len(spec), 0)


def _axis_sizes(placement) -> Optional[Dict[str, int]]:
    """axis name → mesh size for the peak annotation (None when the
    placement cannot resolve a mesh on this process)."""
    if placement is None:
        return None
    try:
        return {name: int(size)
                for name, size in placement.mesh().shape.items()}
    except Exception:  # noqa: BLE001 — degraded hardware: no mesh
        return None


def _same_mesh(src, dst) -> bool:
    if src is None or dst is None:
        return False
    try:
        return src.mesh() is dst.mesh() or src.mesh() == dst.mesh()
    except Exception:  # degraded-hardware collapse etc.
        return False


def move_table(table, steps: List[Step], src_placement, dst_placement):
    """Apply a schedule to one cached chunk ColumnTable — every column
    plus the validity mask, column by column (bounded memory)."""
    from netsdb_tpu.relational.table import ColumnTable

    cols = {k: execute_steps(v, steps, src_placement, dst_placement)
            for k, v in table.cols.items()}
    valid = table.valid
    if valid is not None:
        valid = execute_steps(valid, steps, src_placement, dst_placement)
    return ColumnTable(cols, dict(table.dicts), valid)


# ---------------------------------------------------------------------
# the set-level primitive
# ---------------------------------------------------------------------


@dataclasses.dataclass
class ReshardReport:
    """What one :func:`reshard_set` did — steps planned, blocks moved
    device-to-device, bytes that never touched the host arena."""

    steps: List[Step]
    blocks_moved: int = 0
    bytes_moved: int = 0
    items_moved: int = 0
    elapsed_s: float = 0.0

    def labels(self) -> List[str]:
        return [s.label() for s in self.steps]


def _move_tensor_entry(val, steps: List[Step], src_placement,
                       dst_placement):
    """Run one cached tensor-stream entry through a schedule. Entries
    are the tuples the stream's ``place`` produced — ``(n, block)``
    for "trows", ``(start, block)`` for "treduce" — so only the 2-d
    array element moves; the bookkeeping scalars ride along."""
    out = []
    for el in (val if isinstance(val, (tuple, list)) else (val,)):
        if getattr(el, "ndim", None) == 2:
            el = execute_steps(el, steps, src_placement, dst_placement)
        out.append(el)
    if isinstance(val, (tuple, list)):
        return tuple(out)
    return out[0]


def _reshard_paged_tensor(store, ident, pm, src_placement,
                          dst_placement, report: ReshardReport) -> None:
    """The paged-TENSOR leg of :func:`reshard_set` (the ROADMAP
    carry-over: tables-only before this): the set's device-cached
    stream blocks — "trows"/"treduce" entries under the OLD
    placement's label — are invalidated through the dirty-range path,
    run through the collective schedule one block at a time, and
    installed under the NEW placement's key, so a warm re-stream under
    the new sharding performs zero arena reads. SUMMA panel entries
    (mesh-labelled, device-committed) move via
    :func:`reshard_summa_layout` instead — a placement change does not
    touch them."""
    from netsdb_tpu.storage.devcache import _value_nbytes

    ps = store.page_store()
    name = f"{pm.ident}.mat"
    steps = plan_steps(_spec_for(src_placement, 2),
                       _spec_for(dst_placement, 2), 2,
                       same_mesh=_same_mesh(src_placement, dst_placement),
                       axis_sizes=_axis_sizes(src_placement))
    report.steps = steps
    cache = store.device_cache()
    if cache is None or not getattr(cache, "partial", False) \
            or not cache.enabled:
        return
    cfg = store.config
    rb = ps.meta(name)[1][0]
    bucketing = getattr(cfg, "shape_bucketing", True)
    density = getattr(cfg, "bucket_density", 2)
    scope = str(ident)
    src_pl = src_placement.label() if src_placement is not None else None
    dst_pl = dst_placement.label() if dst_placement is not None else None
    ranges = ps.block_ranges(name)
    if not ranges:
        return
    # collect EVERY kind's covered map BEFORE invalidating: the
    # dirty-range drop is scope-wide, so reading after it would see
    # nothing to move
    covered_by = {}
    for kind in ("trows", "treduce"):
        src_key = (scope, kind, rb, bucketing, density, src_pl)
        _epoch, covered = cache.plan_ranges(src_key, ranges)
        if covered:
            covered_by[kind] = covered
    if not covered_by:
        return
    lo = min(r[0] for cov in covered_by.values() for r in cov)
    hi = max(r[1] for cov in covered_by.values() for r in cov)
    cache.invalidate_range(scope, lo, hi)
    epoch = cache.scope_epoch(scope)
    for kind, covered in covered_by.items():
        dst_key = (scope, kind, rb, bucketing, density, dst_pl)
        for rng in ranges:
            val = covered.get((int(rng[0]), int(rng[1])))
            if val is None:
                continue
            moved = _move_tensor_entry(val, steps, src_placement,
                                       dst_placement)
            if cache.install_block(dst_key, rng, moved, epoch=epoch):
                report.blocks_moved += 1
                report.bytes_moved += _value_nbytes(moved)


def reshard_summa_layout(store, ident, src_devices, dst_devices,
                         src_grid: Optional[Tuple[int, int]] = None,
                         dst_grid: Optional[Tuple[int, int]] = None,
                         axis: str = "data") -> ReshardReport:
    """Move a paged TENSOR set's cached SUMMA panel blocks between
    mesh LAYOUTS — 1-d row-dealt (``src_grid``/``dst_grid`` None) and
    2-d processor grids — without re-staging from the arena: each
    cached block re-places device-to-device (splitting into per-grid-
    column tiles or concatenating them as the layouts require) and
    installs under the destination layout's label, so the next
    distributed matmul under the new mesh serves every A panel from
    HBM. Both layouts must have the SAME participant count (the
    contraction padding ``k_pad`` is participant-derived; differing
    counts would need a host re-pad — callers re-stage instead)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from netsdb_tpu.parallel import summa as _summa
    from netsdb_tpu.plan import staging
    from netsdb_tpu.storage.devcache import _value_nbytes

    t0 = time.perf_counter()
    report = ReshardReport(steps=[Step("replace", peak=1)])
    obs.REGISTRY.counter("reshard.plans").inc()
    items = store.get_items(ident)
    pm = next((i for i in items
               if type(i).__name__ == "_PagedMatrix"), None)
    if pm is None:
        raise ValueError(f"reshard_summa_layout: {ident} holds no "
                         f"paged matrix")
    src_devices = list(src_devices)
    dst_devices = list(dst_devices)
    n_src = (src_grid[0] * src_grid[1] if src_grid is not None
             else len(src_devices))
    n_dst = (dst_grid[0] * dst_grid[1] if dst_grid is not None
             else len(dst_devices))
    if n_src != n_dst:
        raise ValueError(f"summa layout move needs equal participant "
                         f"counts (k padding), got {n_src} -> {n_dst}")
    src_devices = src_devices[:n_src]
    dst_devices = dst_devices[:n_dst]
    src_label = (_summa.grid_label(src_devices, *src_grid)
                 if src_grid is not None
                 else _summa.mesh_label(axis, src_devices))
    dst_label = (_summa.grid_label(dst_devices, *dst_grid)
                 if dst_grid is not None
                 else _summa.mesh_label(axis, dst_devices))
    cache = store.device_cache()
    if cache is None or not getattr(cache, "partial", False) \
            or not cache.enabled:
        report.elapsed_s = time.perf_counter() - t0
        return report
    ps = store.page_store()
    name = f"{pm.ident}.mat"
    cfg = store.config
    rb = ps.meta(name)[1][0]
    bucket = staging.pad_rows_target(
        rb, getattr(cfg, "shape_bucketing", True),
        density=getattr(cfg, "bucket_density", 2))
    scope = str(ident)
    src_key = (scope, _summa.CACHE_KIND, bucket, src_label)
    dst_key = (scope, _summa.CACHE_KIND, bucket, dst_label)
    ranges = ps.block_ranges(name)
    _epoch, covered = cache.plan_ranges(src_key, ranges)
    if not covered:
        report.elapsed_s = time.perf_counter() - t0
        return report
    lo = min(r[0] for r in covered)
    hi = max(r[1] for r in covered)
    cache.invalidate_range(scope, lo, hi)
    epoch = cache.scope_epoch(scope)
    import jax.numpy as jnp

    for rng in ranges:
        val = covered.get((int(rng[0]), int(rng[1])))
        if val is None:
            continue
        i, nrows, payload = val
        # normalize to the full (bucket, k_pad) block on ONE device —
        # grid tiles concatenate on their destination (device-side
        # concat, no host trip), 1-d panels are already whole
        if isinstance(payload, tuple):
            anchor = (dst_devices[(i % dst_grid[0]) * dst_grid[1]]
                      if dst_grid is not None
                      else dst_devices[i % n_dst])
            full = jnp.concatenate(
                [jax.device_put(t, SingleDeviceSharding(anchor))
                 for t in payload], axis=1)
        else:
            full = payload
        if dst_grid is not None:
            pr, pc = dst_grid
            r = i % pr
            apc = full.shape[1] // pc
            moved_payload = tuple(
                jax.device_put(full[:, c * apc:(c + 1) * apc],
                               SingleDeviceSharding(
                                   dst_devices[r * pc + c]))
                for c in range(pc))
        else:
            moved_payload = jax.device_put(
                full, SingleDeviceSharding(dst_devices[i % n_dst]))
        moved = (i, nrows, moved_payload)
        obs.REGISTRY.counter("reshard.steps").inc()
        if cache.install_block(dst_key, rng, moved, epoch=epoch):
            report.blocks_moved += 1
            report.bytes_moved += _value_nbytes(moved)
    report.elapsed_s = time.perf_counter() - t0
    obs.REGISTRY.counter("reshard.blocks_moved").inc(report.blocks_moved)
    obs.REGISTRY.counter("reshard.bytes_moved").inc(report.bytes_moved)
    obs.operators.op_add("reshard.blocks_moved", report.blocks_moved)
    return report


def reshard_set(store, ident, dst_placement,
                kind: str = "tables") -> ReshardReport:
    """Move set ``ident`` from its current placement to
    ``dst_placement`` through collective steps.

    * **memory sets** — every resident item's arrays run the schedule
      device-to-device and the set's declared placement swaps; the
      host never re-touches the data.
    * **paged sets** — the set's device-CACHED blocks (partial-run
      entries under the old layout's sharding-keyed base key) are
      invalidated via the dirty-range path, transformed through the
      schedule one block at a time, and installed under the NEW
      layout's key — a warm re-query under the new sharding serves
      entirely from HBM with zero arena reads. Blocks that were not
      resident simply stream (and install) cold on the next query, as
      always.

    Not safe against CONCURRENT streams of the same set — callers
    serialize like any other mutation (the serve layer's per-set
    locks); content is unchanged, so no dirty range is logged and the
    set's write version does not move."""
    from netsdb_tpu.relational.outofcore import PagedColumns

    t0 = time.perf_counter()
    src_placement = store.placement_of(ident)
    report = ReshardReport(steps=[])
    obs.REGISTRY.counter("reshard.plans").inc()

    if store.storage_of(ident) == "paged":
        items = store.get_items(ident)
        pc = next((i for i in items if isinstance(i, PagedColumns)), None)
        if pc is None:
            pm = next((i for i in items
                       if type(i).__name__ == "_PagedMatrix"), None)
            if pm is None:
                raise ValueError(f"reshard_set: {ident} holds no paged "
                                 f"relation or matrix")
            _reshard_paged_tensor(store, ident, pm, src_placement,
                                  dst_placement, report)
            store.set_placement(ident, dst_placement)
            report.elapsed_s = time.perf_counter() - t0
            obs.REGISTRY.counter("reshard.blocks_moved").inc(
                report.blocks_moved)
            obs.REGISTRY.counter("reshard.bytes_moved").inc(
                report.bytes_moved)
            obs.operators.op_add("reshard.blocks_moved",
                                 report.blocks_moved)
            return report
        steps = plan_steps(_spec_for(src_placement, 1),
                           _spec_for(dst_placement, 1), 1,
                           same_mesh=_same_mesh(src_placement,
                                                dst_placement),
                           axis_sizes=_axis_sizes(src_placement))
        report.steps = steps
        cache = pc.devcache
        scope = pc.cache_scope
        if cache is not None and scope is not None \
                and getattr(cache, "partial", False) and cache.enabled:
            ranges = pc.block_ranges()
            src_key = pc.partial_base_key(kind, src_placement)
            dst_key = pc.partial_base_key(kind, dst_placement)
            _epoch, covered = cache.plan_ranges(src_key, ranges)
            if covered:
                lo = min(r[0] for r in covered)
                hi = max(r[1] for r in covered)
                # PR 14 dirty-range invalidation: drops the old
                # layout's entries and bumps the scope epoch, so any
                # racing install planned under the old layout refuses
                cache.invalidate_range(scope, lo, hi)
                epoch = cache.scope_epoch(scope)
                for rng in ranges:
                    blk = covered.get((int(rng[0]), int(rng[1])))
                    if blk is None:
                        continue
                    moved = move_table(blk, steps, src_placement,
                                       dst_placement)
                    if cache.install_block(dst_key, rng, moved,
                                           epoch=epoch):
                        report.blocks_moved += 1
                        from netsdb_tpu.storage.devcache import \
                            _value_nbytes

                        report.bytes_moved += _value_nbytes(moved)
        store.set_placement(ident, dst_placement)
    else:
        moved_items = []
        same = _same_mesh(src_placement, dst_placement)

        def steps_for(nd):
            steps = plan_steps(_spec_for(src_placement, nd),
                               _spec_for(dst_placement, nd), nd,
                               same_mesh=same,
                               axis_sizes=_axis_sizes(src_placement))
            if not report.steps:
                report.steps = steps
            return steps

        for item in store.get_items(ident):
            if hasattr(item, "cols"):  # resident ColumnTable
                moved_items.append(
                    move_table(item, steps_for(1), src_placement,
                               dst_placement))
                report.items_moved += 1
                continue
            nd = getattr(item, "ndim", None)
            data = item
            is_blocked = hasattr(item, "meta") and hasattr(item, "data")
            if is_blocked:
                data = item.data
                nd = data.ndim
            if nd is None:  # host records: nothing device-resident
                moved_items.append(item)
                continue
            out = execute_steps(data, steps_for(nd), src_placement,
                                dst_placement)
            moved_items.append(item.with_data(out) if is_blocked
                               else out)
            report.items_moved += 1
        store.set_placement(ident, dst_placement, items=moved_items)

    report.elapsed_s = time.perf_counter() - t0
    obs.REGISTRY.counter("reshard.blocks_moved").inc(
        report.blocks_moved or report.items_moved)
    obs.REGISTRY.counter("reshard.bytes_moved").inc(report.bytes_moved)
    obs.operators.op_add("reshard.blocks_moved",
                         report.blocks_moved or report.items_moved)
    return report
