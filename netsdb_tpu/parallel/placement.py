"""Declarative set placement — distribution through the database API.

In the reference, distribution is a property of the *set*: ingest
partitions every set across workers by a PartitionPolicy chosen at
``createSet`` (``src/dispatcher/headers/PartitionPolicy.h:27-50``), and
every scheduled stage then runs against local partitions on all nodes
(``src/serverFunctionalities/source/QuerySchedulerServer.cc:216-330``).
The TPU-native equivalent of "which worker holds which partition" is a
``jax.sharding.NamedSharding``: this module gives sets a *declarative*,
catalog-serializable placement — mesh axes + a PartitionSpec — that
``Client.create_set(placement=...)`` records and the data path applies,
so every downstream jit (the query executor) sees committed shardings
and XLA inserts the collectives the reference's shuffle threads
implemented by hand.

Why declarative rather than a live ``Mesh`` object: placements live in
the catalog (sqlite JSON meta) and travel over the serve protocol
(msgpack), so they must be data, not device handles. ``mesh()``
materializes the same ``Mesh`` for equal axis descriptions (cached), so
NamedShardings built from one Placement compare equal across calls —
a requirement for jit cache hits.

Fewer devices than the declared mesh: on the CPU backend (tests, whose
virtual mesh has 8 devices) the placement collapses to the trivial
single-device mesh — the fallback the reference dispatcher makes when a
set cannot be partitioned by the preferred policy
(``PartitionPolicy.h:40``, DefaultPolicy). On an accelerator it raises:
a set declared over four chips must not quietly compute on one. Size 0
("all devices on this axis") is how a placement adapts to the machine.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _canon_axis(entry: Any) -> Any:
    """Spec entry → hashable canonical form (None | str | tuple[str])."""
    if entry is None or isinstance(entry, str):
        return entry
    return tuple(entry)


@dataclasses.dataclass(frozen=True)
class Placement:
    """Mesh axes + per-dimension PartitionSpec for one set.

    ``axes``: ((name, size), ...) — size 0 means "all devices on this
    axis" (resolved at ``mesh()`` time, like the dispatcher's
    round-robin over however many workers are registered).
    ``spec``: one entry per tensor dimension: ``None`` (replicated),
    an axis name, or a tuple of axis names. For a :class:`ColumnTable`
    set the spec has one entry — the row dimension.
    """

    axes: Tuple[Tuple[str, int], ...]
    spec: Tuple[Any, ...]

    # --- constructors -------------------------------------------------
    @staticmethod
    def data_parallel(ndim: int = 1, n_devices: int = 0,
                      axis: str = "data") -> "Placement":
        """Rows over ``axis``, everything else replicated — the
        reference's RoundRobin/Hash partitioning of a set's pages."""
        return Placement(((axis, n_devices),),
                         (axis,) + (None,) * (ndim - 1))

    @staticmethod
    def replicated(ndim: int = 2, n_devices: int = 0,
                   axis: str = "data") -> "Placement":
        """Whole copy on every device — the broadcast-join placement
        (small dim tables / model weights on every node)."""
        return Placement(((axis, n_devices),), (None,) * ndim)

    # --- catalog round-trip -------------------------------------------
    def to_meta(self) -> Dict[str, Any]:
        spec = [list(s) if isinstance(s, tuple) else s for s in self.spec]
        return {"axes": [list(a) for a in self.axes], "spec": spec}

    @staticmethod
    def from_meta(meta: Optional[Dict[str, Any]]) -> Optional["Placement"]:
        if not meta:
            return None
        axes = tuple((str(n), int(s)) for n, s in meta["axes"])
        spec = tuple(_canon_axis(s) for s in meta["spec"])
        return Placement(axes, spec)

    # --- materialization ----------------------------------------------
    def resolved_axes(self,
                      n_devices: Optional[int] = None) -> Tuple[Tuple[str, int], ...]:
        """Axis sizes with 0 resolved to "the remaining devices". When
        the process can't supply enough devices the whole shape collapses
        to 1s on the CPU backend and raises on any other (module
        docstring)."""
        n = n_devices if n_devices is not None else len(jax.devices())
        fixed = int(np.prod([s for _, s in self.axes if s > 0] or [1]))
        free = sum(1 for _, s in self.axes if s == 0)
        if free > 1:
            # "all remaining devices" on two axes is ambiguous — there is
            # no canonical factorization of the remainder. The reference
            # dispatcher has the same rule: a set either names its
            # partition counts or takes the single DEFAULT policy
            # (PartitionPolicy.h:29); it never guesses a 2-d split.
            raise ValueError(
                f"placement axes {self.axes}: at most one axis may have "
                f"size 0 (= all remaining devices); {free} do")
        remaining = n // fixed if fixed <= n else 0
        out = []
        for name, size in self.axes:
            if size == 0:
                size = max(1, remaining)
            out.append((name, size))
        if int(np.prod([s for _, s in out])) > n:
            if jax.default_backend() != "cpu":
                raise ValueError(
                    f"placement {self.label()} needs "
                    f"{int(np.prod([s for _, s in out]))} devices; this "
                    f"process has {n} on backend "
                    f"{jax.default_backend()!r} (size 0 = all devices)")
            return tuple((name, 1) for name, _ in self.axes)
        return tuple(out)

    def mesh(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        devices = tuple(devices if devices is not None else jax.devices())
        axes = self.resolved_axes(len(devices))
        return _cached_mesh(axes, devices)

    def sharding(self, devices: Optional[Sequence[jax.Device]] = None
                 ) -> NamedSharding:
        return NamedSharding(self.mesh(devices), P(*self.spec))

    def axis_size(self, devices: Optional[Sequence[jax.Device]] = None) -> int:
        """Total number of shards along the sharded dimensions — the
        row-padding granularity for ColumnTables."""
        mesh = self.mesh(devices)
        total = 1
        for entry in self.spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                total *= mesh.shape[ax]
        return total

    def label(self) -> str:
        """Human/history-DB form, e.g. ``data=8:P('data',None)``."""
        ax = ",".join(f"{n}={s}" for n, s in self.axes)
        sp = ",".join("None" if s is None else str(s) for s in self.spec)
        return f"mesh[{ax}]:P({sp})"

    # --- data placement ----------------------------------------------
    def apply(self, value: Any) -> Any:
        """Place a stored value on this placement's mesh. Dispatches on
        value kind: BlockedTensor (block-grid divisibility fallback,
        like the dispatcher's DEFAULT policy), ColumnTable (rows padded
        to the shard granularity with ``valid=False`` — filters never
        shrink arrays, so padding rides the existing mask algebra),
        bare arrays."""
        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.parallel.mesh import shard_blocked
        from netsdb_tpu.relational.table import ColumnTable

        if isinstance(value, BlockedTensor):
            return shard_blocked(value, self.mesh(), P(*self.spec))
        if isinstance(value, ColumnTable):
            return shard_table(value, self)
        if isinstance(value, (jax.Array, np.ndarray)):
            return jax.device_put(value, self.sharding())
        return value


def shard_table(table, placement: Placement):
    """Mesh-shard a ColumnTable's rows: pad to the shard granularity
    with invalid rows (``device_put`` requires even division), then
    place every column and the validity mask with the placement's
    sharding. The padded rows are masked out of every aggregate by the
    table's existing validity algebra (``table.py`` design rule:
    filters never shrink arrays)."""
    import jax.numpy as jnp

    from netsdb_tpu.relational.table import ColumnTable

    if len(placement.spec) != 1:
        raise ValueError(
            f"table placement needs a 1-d spec (rows); got {placement.spec}")
    n = table.num_rows
    div = placement.axis_size()
    pad = (-n) % div
    sharding = placement.sharding()
    cols = {}
    for name, col in table.cols.items():
        if pad:
            col = jnp.concatenate(
                [col, jnp.zeros((pad,) + col.shape[1:], col.dtype)])
        cols[name] = jax.device_put(col, sharding)
    valid = table.mask()
    if pad:
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
    elif table.valid is None and div == 1:
        valid = None  # unpadded single-shard: keep the fast no-mask path
    if valid is not None:
        valid = jax.device_put(valid, sharding)
    return ColumnTable(cols, table.dicts, valid)


# --- mesh cache -------------------------------------------------------
# Same axes + same devices must yield the SAME Mesh object so that
# NamedShardings compare equal and jit caches hit across jobs.
_mesh_cache: Dict[Tuple, Mesh] = {}
_mesh_lock = threading.Lock()


def _cached_mesh(axes: Tuple[Tuple[str, int], ...],
                 devices: Tuple[jax.Device, ...]) -> Mesh:
    need = int(np.prod([s for _, s in axes]))
    if need > len(devices):
        raise ValueError(f"placement axes {axes} need {need} devices, "
                         f"have {len(devices)}")
    key = (axes, tuple(d.id for d in devices[:need]))
    with _mesh_lock:
        mesh = _mesh_cache.get(key)
        if mesh is None:
            arr = np.asarray(devices[:need]).reshape([s for _, s in axes])
            mesh = Mesh(arr, tuple(n for n, _ in axes))
            _mesh_cache[key] = mesh
        return mesh
