"""Host-side set store — the Pangea storage engine, TPU-shaped.

The reference's worker-frontend ``PangeaStorageServer`` owns
databases→sets→64 MB shared-memory pages with a pin/unpin ``PageCache``
and flush threads spilling to ``PartitionedFile``s on disk (reference
``src/serverFunctionalities/headers/PangeaStorageServer.h:31-52``,
``src/storage/headers/PDBPage.h:17-33``, ``PageCache.h:106-118``,
``PartitionedFile.h``). Its job: keep hot sets in RAM, stream pages to
the execution pipelines, survive restarts.

On TPU the equivalent capability is: keep sets on host (numpy) or device
(jax.Array) with an LRU spill-to-disk cache, stream blocks into HBM on
demand, and persist sets as files. Sets hold either tensors
(:class:`BlockedTensor`) or arbitrary host objects (relational rows for
the TPCH-style workloads). Cache accounting mirrors ``CacheStats``
(``src/storage/headers/CacheStats.h:8-60``); eviction policy per set
mirrors ``LocalitySet`` {LRU, MRU, Random}
(``src/storage/headers/LocalitySet.h:16-24``).
"""

from __future__ import annotations

import dataclasses
import functools
import io
import os
import pickle
import random
import threading
import time
from collections import OrderedDict
from typing import (Any, Dict, Iterator, List, NamedTuple, Optional,
                    Tuple)

import jax
import numpy as np

from netsdb_tpu.config import Configuration, DEFAULT_CONFIG
from netsdb_tpu.core.blocked import BlockedTensor, BlockMeta
from netsdb_tpu.utils.locks import TrackedLock, TrackedRLock


class SetIdentifier(NamedTuple):
    """(database, set) pair — reference ``SetIdentifier`` builtin object."""

    db: str
    set: str

    def __str__(self) -> str:
        return f"{self.db}:{self.set}"


@dataclasses.dataclass
class CacheStats:
    """Hit/miss/eviction counters (ref ``CacheStats.h:8-60``)."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    spills: int = 0
    loads: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _StoredSet:
    """One set's in-memory state."""

    ident: SetIdentifier
    items: Optional[List[Any]]  # None => spilled to disk
    # serializes PAGED appends per set OUTSIDE the global store lock
    # (an append must wait for in-flight streams to drain — rw.write —
    # and that wait must not freeze every unrelated store operation)
    append_mu: Any = dataclasses.field(
        default_factory=lambda: TrackedLock("_StoredSet.append_mu"))
    persistence: str = "transient"  # ref PersistenceType (DataTypes.h:53)
    eviction: str = "lru"  # ref LocalitySet replacement policy
    last_access: float = 0.0
    nbytes: int = 0
    # dedup: set whose physical storage this set aliases
    # (ref SharedTensorBlockSet, src/deduplication/headers/SharedTensorBlockSet.h:25)
    alias_of: Optional[SetIdentifier] = None
    shared_mapping: Optional[Dict] = None
    # declarative sharding applied by the data path (ref: the
    # PartitionPolicy chosen at createSet — distribution is a property
    # of the set, netsdb_tpu.parallel.placement)
    placement: Optional[Any] = None
    # "memory" (resident items) or "paged" (relation lives as row-chunk
    # pages in the shared PagedTensorStore; queries stream it — the
    # reference's PageScanner-fed sets, ``PageScanner.h:25-34``)
    storage: str = "memory"
    # monotonic write version, drawn from the store-wide counter by
    # EVERY mutating path (ingest, append, clear, resync restore,
    # spill reload, …) — the freshness token the device block cache
    # keys on (storage/devcache.py): a bumped version means no stale
    # cached block can ever match again. Store-wide numbering means a
    # removed-and-recreated set can never reuse an old version.
    version: int = 0
    # bounded per-set dirty-range log (partial-run device caching):
    # every _touch appends (start, end) — end=None for whole-scope
    # writes (replace/clear/restore), the appended tail for appends —
    # and the cache drops only intersecting block entries. Beyond
    # config.device_cache_dirty_log un-collapsed entries the log folds
    # to one whole-scope range (bounded memory, conservative cache).
    dirty_log: list = dataclasses.field(default_factory=list)


def _item_nbytes(item: Any) -> int:
    if isinstance(item, BlockedTensor):
        return int(np.prod(item.meta.padded_shape)) * item.data.dtype.itemsize
    if isinstance(item, (np.ndarray, jax.Array)):
        return int(item.nbytes)
    resident = getattr(item, "nbytes_resident", None)  # PooledTensor:
    if resident is not None:  # counts only its slot grid; the shared
        return int(resident)  # pool is accounted once, by its owner
    return 256  # rough per-object estimate for host records


@dataclasses.dataclass
class _PagedMatrix:
    """Handle for a matrix living as arena pages (a paged TENSOR set):
    identity only — shape/dtype's authoritative copies live in the
    page store's meta; the data streams through
    ``SetStore.paged_matmul`` or a :class:`PagedTensor` scan handle,
    never materializing densely (ref: pipelines over pinned weight
    pages). ``rw`` guards streams vs drop/replace."""

    ident: str
    rw: Any = None

    def __post_init__(self):
        if self.rw is None:
            from netsdb_tpu.utils.locks import RWLock

            self.rw = RWLock(name="_PagedMatrix.rw")


def _locked(method):
    """Run a public store method under the store's reentrant lock."""

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return method(self, *args, **kwargs)

    return wrapper


class SetStore:
    """All sets of all databases on this host.

    Single-controller JAX means one store per process plays the role of
    every worker's Pangea instance at once; sharded device placement of a
    set's tensor is handled by ``netsdb_tpu.parallel``.
    """

    def __init__(self, config: Configuration = DEFAULT_CONFIG,
                 max_host_bytes: Optional[int] = None):
        self.config = config
        self.config.ensure_dirs()
        self._sets: "OrderedDict[SetIdentifier, _StoredSet]" = OrderedDict()
        self.stats = CacheStats()
        self.max_host_bytes = max_host_bytes or config.shared_mem_bytes
        # serve-layer handler threads mutate sets concurrently (the
        # reference guards Pangea's set maps with pthread mutexes);
        # reentrant because e.g. add_data -> _maybe_evict -> flush
        self._lock = TrackedRLock("SetStore._lock")
        # the runtime lock-order witness (utils/locks.py): config-
        # gated so a production daemon can run lockdep-style checks
        if getattr(config, "lock_witness", False):
            from netsdb_tpu.utils.locks import enable_witness

            enable_witness()
        # sets whose items include a shared-pool tensor (dedup/pool.py)
        # — keeps pool-bytes accounting O(pooled sets)
        self._pooled: set = set()
        # ONE shared page arena for every paged set (the reference has
        # one shared-memory pool per worker); lazy — most processes
        # never create a paged set
        self._page_store = None
        # arena names are GENERATION-unique (ident#gN): a deferred
        # unlocked drop after remove_set must never free the pages of a
        # same-named set re-created in the window
        import itertools

        self._gen = itertools.count()
        # store-wide set-version counter + the cross-query device block
        # cache (the buffer-pool role, storage/devcache.py) — lazy like
        # the page store; most short-lived stores never touch it
        self._version_ctr = itertools.count(1)
        self._device_cache = None

    def page_store(self):
        """The shared :class:`PagedTensorStore` backing every
        ``storage="paged"`` set, created on first use with the
        configured pool cap (``config.page_pool_bytes``)."""
        with self._lock:
            if self._page_store is None:
                from netsdb_tpu.storage.paged import PagedTensorStore

                self._page_store = PagedTensorStore(
                    self.config,
                    pool_bytes=self.config.page_pool_bytes)
            return self._page_store

    def page_store_stats(self) -> Optional[Dict[str, Any]]:
        """The paged arena's counters (hits/misses/evictions/spills/
        loads/bytes) plus whether the native backend holds it; None
        while no ``storage="paged"`` set has created the arena."""
        with self._lock:
            ps = self._page_store
        if ps is None:
            return None
        return dict(ps.stats(), native=ps.native)

    def device_cache(self):
        """The cross-query device block cache (``storage/devcache.py``)
        backing warm repeat queries — one per store, budgeted by
        ``config.device_cache_bytes``."""
        with self._lock:
            if self._device_cache is None:
                from netsdb_tpu.storage.devcache import DeviceBlockCache

                self._device_cache = DeviceBlockCache(
                    getattr(self.config, "device_cache_bytes", 0) or 0,
                    partial=bool(getattr(self.config,
                                         "device_cache_partial", False)),
                    pin_bytes=getattr(self.config,
                                      "device_cache_pin_bytes", 0) or 0)
            return self._device_cache

    def _touch(self, s: _StoredSet,
               rows: Optional[Tuple[int, int]] = None,
               columns: Optional[Tuple[str, ...]] = None) -> None:
        """Advance a set's write version, log the dirty row range and
        drop the intersecting cached device blocks NOW. Called by EVERY
        path that can change the set's content — direct ingest,
        appends, BULK COMMIT (which lands through these same mutators),
        mirrored frames, resync restore, checkpoint/spill reload.

        ``rows=(start, end)`` names the dirty row range (an append
        passes its tail); None means the whole scope changed
        (replace/clear/restore — today's behavior). In whole-run cache
        mode the range is advisory only and invalidation stays
        whole-scope, byte-for-byte as before. In partial mode a
        ranged touch is only LOGGED here: the per-range cache
        invalidation already happened inside ``PagedColumns.append``
        (the one mutator every ranged caller routes through — it owns
        the range invalidation so store-bypassing direct appends stay
        coherent, and doing it again here would double-bump the scope
        epoch and refuse installs of streams planned between the two
        bumps). A caller adding a NEW ``rows=...`` site that does not
        route through ``pc.append`` must invalidate the range itself.

        ``columns=(name, ...)`` additionally names the touched COLUMNS
        (an update-in-place write): the dirty log entry is keyed by
        column — ``(start, end, cols)`` — and the per-range cache
        invalidation (owned by ``PagedColumns.update_column``, same
        contract as ``pc.append`` above) drops only block entries
        whose stream PROJECTED one of those columns, so a
        single-column update keeps every other column's cached blocks
        resident.

        When the bounded log overflows it folds to one whole-scope
        entry AND the cache degrades to a whole-scope invalidation —
        a pathological writer gets today's invalidate-everything
        behavior, never unbounded memory or silent fidelity loss."""
        s.version = next(self._version_ctr)
        bound = max(int(getattr(self.config, "device_cache_dirty_log",
                                64) or 64), 1)
        folded = len(s.dirty_log) >= bound
        if folded:
            s.dirty_log[:] = [(0, None)]  # fold to whole-scope
        elif rows is None:
            s.dirty_log.append((0, None))
        elif columns is not None:
            s.dirty_log.append((int(rows[0]), int(rows[1]),
                                tuple(sorted(columns))))
        else:
            s.dirty_log.append((int(rows[0]), int(rows[1])))
        if self._device_cache is not None:
            if rows is not None and self._device_cache.partial \
                    and not folded:
                pass  # range already invalidated by pc.append (above)
            else:
                self._device_cache.invalidate(str(s.ident))

    def version_of(self, ident: SetIdentifier) -> int:
        """The set's current write version (0 = unknown set) — the
        freshness token device-cache keys carry."""
        s = self._sets.get(ident)
        return s.version if s is not None else 0

    def _bind_cache(self, pc, ident: SetIdentifier) -> None:
        """Attach the device cache to a store-owned paged relation
        handle so its streams consult/install cached runs. Direct
        ``PagedColumns.ingest`` callers (grace-hash spill partitions)
        never get a binding — temporaries stay uncached."""
        pc.devcache = self.device_cache()
        pc.cache_scope = str(ident)
        pc.cache_version_fn = functools.partial(self.version_of, ident)

    # --- set lifecycle ------------------------------------------------
    @_locked
    def create_set(
        self,
        ident: SetIdentifier,
        persistence: str = "transient",
        eviction: str = "lru",
        placement: Optional[Any] = None,
        storage: str = "memory",
    ) -> None:
        if storage not in ("memory", "paged"):
            raise ValueError(f"storage must be 'memory' or 'paged', "
                             f"got {storage!r}")
        if ident not in self._sets:
            self._sets[ident] = _StoredSet(
                ident=ident, items=[], persistence=persistence, eviction=eviction,
                last_access=time.time(), placement=placement, storage=storage,
            )
            self._touch(self._sets[ident])
        elif placement is not None:
            s = self._sets[ident]
            s.placement = placement
            if s.items:  # re-place already-stored data under the new policy
                s.items = [placement.apply(i) for i in s.items]
            self._touch(s)  # resharded items: cached runs are stale

    def placement_of(self, ident: SetIdentifier) -> Optional[Any]:
        s = self._sets.get(ident)
        return s.placement if s is not None else None

    @_locked
    def set_placement(self, ident: SetIdentifier, placement,
                      items: Optional[List[Any]] = None) -> None:
        """Swap a set's DECLARED placement without re-staging its data
        — the commit step of ``parallel/reshard.reshard_set``, which
        has already moved the device-resident blocks (or resident
        ``items``, passed here) through collective steps. Content is
        unchanged, so no write version moves and no dirty range is
        logged: cached blocks installed under the NEW layout's key
        stay matchable, which is the whole point. NOT the path for
        re-placing from host — ``create_set(placement=...)`` keeps
        that behavior (re-place + whole-scope invalidation)."""
        s = self._require(ident)
        s.placement = placement
        if items is not None:
            s.items = items
            s.nbytes = sum(_item_nbytes(i) for i in items)
        s.last_access = time.time()

    def storage_of(self, ident: SetIdentifier) -> str:
        s = self._sets.get(ident)
        return s.storage if s is not None else "memory"

    def exists(self, ident: SetIdentifier) -> bool:
        return ident in self._sets or os.path.exists(self._spill_path(ident))

    def remove_set(self, ident: SetIdentifier) -> None:
        with self._lock:
            s = self._sets.pop(ident, None)
            detached = list(s.items or []) if s is not None else []
            if s is not None:
                s.items = []
            if self._device_cache is not None:
                self._device_cache.invalidate(str(ident))
            path = self._spill_path(ident)
            if os.path.exists(path):
                os.remove(path)
        # page reclaim happens OUTSIDE the store lock: dropping a paged
        # relation waits for in-flight streams (its write lock) and must
        # not freeze every unrelated store operation while it waits
        self._drop_detached(detached)

    def clear_set(self, ident: SetIdentifier) -> None:
        with self._lock:
            s = self._sets.get(ident)
            detached = list(s.items or []) if s is not None else []
            if s is not None:
                s.items = []
                s.nbytes = 0
                self._touch(s)
        self._drop_detached(detached)

    def _drop_paged_items(self, s: Optional[_StoredSet]) -> None:
        """Return a dropped paged relation's (or paged matrix's) pages
        to the shared capped arena — without this, remove/clear of
        paged sets would leak dead pages against ``page_pool_bytes``
        until process restart. Called with the store lock held (ingest
        replace); remove/clear detach first and drop unlocked."""
        if s is None or not s.items:
            return
        self._drop_detached(s.items)

    def _drop_detached(self, items: List[Any]) -> None:
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.storage.paged import PagedObjects

        for item in items:
            if isinstance(item, (PagedColumns, PagedObjects)):
                item.drop()
            elif isinstance(item, _PagedMatrix) and \
                    self._page_store is not None:
                with item.rw.write():  # drain in-flight weight streams
                    self._page_store.drop(f"{item.ident}.mat")

    @_locked
    def list_sets(self) -> List[SetIdentifier]:
        return list(self._sets.keys())

    # --- data path (ref: StorageAddData / UserSet::addObject) ---------
    def add_data(self, ident: SetIdentifier, items: List[Any]) -> None:
        """Append/ingest ``items``. Paged OBJECT-set appends follow the
        same lock discipline as paged-table appends (advisor round 5):
        the store lock only LOCATES and pins the existing
        :class:`PagedObjects`; ``po.append`` runs OUTSIDE it under the
        set's ``append_mu`` — the append may wait on the relation's own
        locks (a concurrent drop), and that wait must never freeze
        every unrelated store operation. A concurrent remove/replace
        drops the pinned handle, making ``po.append`` fail loudly
        instead of resurrecting freed pages."""
        dead = []
        po = None
        with self._lock:
            s = self._require(ident)
            if s.alias_of is not None:
                raise ValueError(f"set {ident} aliases {s.alias_of}; "
                                 f"it is read-only")
            if s.storage == "paged":
                po = self._pin_paged_objects(s, items)
                if po is None:
                    # lint: disable=lock-blocking-call -- fresh ingest: the relation doesn't exist yet, so no stream can hold its rw lock and the append wait cannot occur
                    dead = self._ingest_paged(s, items)
                    self._touch(s)
            else:
                if s.items is None:  # evicted: reload before appending
                    # lint: disable=lock-blocking-call -- reload of an evicted set: its relation was spilled with no live streams, so the rebuild's appends cannot wait
                    self._load_from_spill(s)
                if s.placement is not None:
                    items = [s.placement.apply(i) for i in items]
                s.items.extend(items)
                s.nbytes += sum(_item_nbytes(i) for i in items)
                s.last_access = time.time()
                self._maybe_evict(exclude=ident)
                self._touch(s)
        if po is not None:
            with s.append_mu:  # per-set order among concurrent appends
                # lint: disable=lock-blocking-call -- append_mu exists to order THIS set's appends behind the relation locks; the global store lock stays released
                po.append(items)
            with self._lock:
                if self._sets.get(ident) is s:
                    s.last_access = time.time()
                    self._touch(s)
        self._drop_detached(dead)  # replaced pages reclaim UNLOCKED

    @staticmethod
    def _pin_paged_objects(s: _StoredSet, items: List[Any]):
        """The existing :class:`PagedObjects` of ``s`` when ``items``
        are host-object records appending to it, else None (fresh
        ingest / relation-replace — handled under the store lock,
        where no streams can exist on a relation that doesn't).
        Caller holds the store lock."""
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.relational.table import ColumnTable
        from netsdb_tpu.storage.paged import PagedObjects

        if not items or isinstance(
                items[0], (PagedColumns, np.ndarray, BlockedTensor,
                           ColumnTable)):
            return None
        return next((i for i in (s.items or [])
                     if isinstance(i, PagedObjects)), None)

    def _ingest_paged(self, s: _StoredSet, items: List[Any],
                      append: bool = False) -> List[Any]:
        """Route a relation into the page arena instead of RAM — the set
        property the reference expresses by EVERY set living in pages
        (``PangeaStorageServer.h:31-52``); here only sets that opt into
        streaming pay the page granularity. One relation per paged set
        (matching ``send_table`` semantics); re-ingest replaces, or
        APPENDS new pages when asked (the reference's addData flow) —
        dictionary-encoded batch columns remap into the stored
        dictionaries first.

        Returns the REPLACED paged items: arena names are generation-
        unique, so the caller reclaims the old pages OUTSIDE the store
        lock (``_drop_detached`` waits for in-flight streams; that wait
        must not freeze unrelated store operations)."""
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.relational.table import ColumnTable

        if not items:
            return []
        item = items[0]
        if isinstance(item, (PagedColumns, np.ndarray, BlockedTensor,
                             ColumnTable)) and len(items) != 1:
            raise ValueError(f"paged set {s.ident} holds exactly one "
                             f"relation; got {len(items)} items")
        if isinstance(item, PagedColumns):
            # replacing with a new handle: the OLD relation's arena
            # pages go back to the caller for reclaim (cross-type-leak
            # rule) — unless the "new" handle IS the stored one
            dead = []
            if not (s.items and len(s.items) == 1 and s.items[0] is item):
                dead = list(s.items or [])
            s.items = [item]
            self._bind_cache(item, s.ident)
            return dead
        if isinstance(item, (np.ndarray, BlockedTensor)):
            if append:
                raise ValueError(f"append is not supported for paged "
                                 f"matrices ({s.ident}); re-send the "
                                 f"full matrix")
            # paged TENSOR set: a matrix larger than HBM pages into the
            # arena; consumers stream it (``paged_matmul`` — the r1
            # matmul_streamed capability, now a property of the set).
            # Replace semantics: the old contents are returned for
            # unlocked reclaim (cross-type replaces must not leak)
            dead = list(s.items or [])
            dense = (np.asarray(item.to_dense()) if
                     isinstance(item, BlockedTensor) else
                     np.ascontiguousarray(item))
            arena_name = f"{s.ident}#g{next(self._gen)}"
            self.page_store().put(f"{arena_name}.mat", dense)
            s.items = [_PagedMatrix(arena_name)]
            s.nbytes = 0
            s.last_access = time.time()
            return dead
        if not isinstance(item, ColumnTable):
            # HOST-OBJECT records: pickled-batch pages (the reference's
            # pages hold arbitrary pdb::Objects, PDBPage.h:17-33).
            # Object add_data APPENDS, matching the memory object
            # path's extend semantics (relations replace; see above) —
            # but the append to an EXISTING PagedObjects never reaches
            # here: add_data pins it under the store lock and runs
            # po.append outside it (the round-5 lock-inversion fix),
            # so this branch only ever does the fresh first ingest
            from netsdb_tpu.storage.paged import PagedObjects

            dead = list(s.items or [])
            po = PagedObjects.ingest(
                self.page_store(), f"{s.ident}#g{next(self._gen)}",
                items)
            s.items = [po]
            s.nbytes = 0
            s.last_access = time.time()
            return dead
        existing = [i for i in (s.items or [])
                    if isinstance(i, PagedColumns)]
        if append and existing:
            self._append_paged_existing(s, existing[0], item)
            return []
        # fresh/replace table ingest: whatever the set held (table pages
        # or a matrix) is returned for unlocked reclaim — generation-
        # unique arena names make new-before-drop ordering safe
        dead = list(s.items or [])
        # page row count sized to the configured page bytes (floor 64 so
        # tiny test pages still hold whole rows); for placed sets,
        # rounded to the shard granularity so streamed chunks mesh-shard
        # with no second padding round
        width = max(len(item.cols), 1)
        row_block = max(self.config.page_size_bytes // (4 * width), 64)
        if s.placement is not None:
            div = s.placement.axis_size()
            row_block = -(-row_block // div) * div
        cols = {n: np.asarray(item[n]) for n in item.cols if n != "_rowid"}
        if item.valid is not None:
            keep = np.asarray(item.mask())
            cols = {n: c[keep] for n, c in cols.items()}
        pc = PagedColumns.ingest(self.page_store(),
                                 f"{s.ident}#g{next(self._gen)}", cols,
                                 row_block=row_block, dicts=dict(item.dicts))
        self._bind_cache(pc, s.ident)
        s.items = [pc]
        s.nbytes = 0  # pages are accounted (and capped) by the arena
        s.last_access = time.time()
        return dead

    def _append_paged_existing(self, s: _StoredSet, pc, item) -> None:
        """Append a batch to a LIVE paged relation (never a fresh
        ingest — the pc is pinned by the caller, so a concurrent
        remove cannot silently turn this into an orphaned re-create;
        ``pc.append`` raises if the relation was dropped). Safe to run
        outside the store lock under the set's append lock."""
        from netsdb_tpu.relational.autojoin import merge_dicts

        cols = {n: np.asarray(item[n]) for n in item.cols
                if n != "_rowid"}
        if item.valid is not None:
            keep = np.asarray(item.mask())
            cols = {n: c[keep] for n, c in cols.items()}
        # validate EVERYTHING before mutating any stored state — a
        # rejected batch must leave the set (dictionaries included)
        # exactly as it was
        expected = set(pc.int_names) | set(pc.float_names)
        if set(cols) != expected:
            raise ValueError(
                f"append to {s.ident}: schema mismatch — stored "
                f"{sorted(expected)}, batch {sorted(cols)}")
        missing = [n for n in pc.dicts
                   if n in cols and n not in item.dicts]
        if missing:
            raise ValueError(
                f"append to {s.ident}: columns {missing} are "
                f"dict-encoded in the stored set but arrive as raw "
                f"ints — codes would be meaningless")
        staged_dicts = {}
        for name, d_new in item.dicts.items():
            d_old = pc.dicts.get(name)
            if d_old is None:
                raise ValueError(f"append to {s.ident}: column "
                                 f"{name!r} is dict-encoded in the "
                                 f"batch but not in the stored set")
            merged, remap = merge_dicts(d_old, d_new)
            staged_dicts[name] = merged
            cols[name] = remap[cols[name]]
        pc.append(cols)  # atomic (rolls back its pages on failure)
        pc.dicts.update(staged_dicts)  # commit only after success
        s.last_access = time.time()

    @_locked
    def update_set(self, ident: SetIdentifier, fn) -> None:
        """Atomic read-modify-write of a set's items: ``fn(items) ->
        new_items`` runs UNDER the store lock, so concurrent updaters
        (e.g. two daemon handlers appending to one objects set) cannot
        interleave their read-concat-replace sequences and lose
        batches. Placement applies to the result like any ingest."""
        s = self._require(ident)
        if s.alias_of is not None:
            raise ValueError(f"set {ident} aliases {s.alias_of}; it is read-only")
        if s.items is None:
            self._load_from_spill(s)
        items = fn(list(s.items))
        if s.placement is not None:
            items = [s.placement.apply(i) for i in items]
        s.items = items
        s.nbytes = sum(_item_nbytes(i) for i in items)
        s.last_access = time.time()
        self._touch(s)
        self._maybe_evict(exclude=ident)

    def paged_matmul(self, ident: SetIdentifier, rhs) -> np.ndarray:
        """``stored_matrix @ rhs`` with the left side STREAMED page by
        page through the device — the larger-than-HBM weight pattern
        (only one page + rhs resident at a time; r1's matmul_streamed,
        reachable as a set property since the matrix lives in a
        ``storage="paged"`` set). The stream runs OUTSIDE the store
        lock under the item's read lock (the arena pin): a concurrent
        remove/re-ingest waits for the stream instead of the stream
        freezing every other store operation."""
        with self._lock:
            s = self._require(ident)
            pm = next((i for i in (s.items or [])
                       if isinstance(i, _PagedMatrix)), None)
            if pm is None:
                raise ValueError(f"set {ident} holds no paged matrix")
            s.last_access = time.time()
            ps = self.page_store()
        with pm.rw.read():
            # the devcache binding lets the SUMMA route (config.
            # distributed_matmul) install its per-participant panels
            # as block entries keyed by the mesh label — a warm
            # distributed matmul re-run stages zero bytes
            return ps.matmul_streamed(f"{pm.ident}.mat", np.asarray(rhs),
                                      devcache=self.device_cache(),
                                      cache_scope=str(ident))

    @_locked
    def paged_tensor(self, ident: SetIdentifier):
        """Streaming read handle for a paged TENSOR set — the ScanSet
        value the executor feeds to :class:`~netsdb_tpu.plan.fold.
        TensorFold`-bearing nodes (in-DB inference over storage-managed
        weights, ref ``SimpleFF.cc:94-290``). Never materializes."""
        from netsdb_tpu.storage.paged import PagedTensor

        s = self._require(ident)
        pm = next((i for i in (s.items or [])
                   if isinstance(i, _PagedMatrix)), None)
        if pm is None:
            raise ValueError(f"set {ident} holds no paged matrix")
        s.last_access = time.time()
        pt = PagedTensor(self.page_store(), f"{pm.ident}.mat",
                         rw=pm.rw, placement=s.placement)
        # version-scoped device-cache binding: the tensor stream's
        # staged uploads install under (ident, version) and a warm
        # consumer replays them without touching the arena; the
        # version_fn lets the install re-check currentness (a racing
        # write must not strand a dead entry on the budget)
        pt.devcache = self.device_cache()
        pt.cache_scope = (str(ident), s.version)
        pt.cache_version_fn = functools.partial(self.version_of, ident)
        return pt

    def restore_paged_matrix(self, ident: SetIdentifier, blocks,
                             row_block: int) -> None:
        """Rebuild a paged TENSOR set from its arena pages — the
        RESYNC_FOLLOWER replay path (the PR 2 leftover: a paged MATRIX
        used to resync as an empty set). ``blocks`` are the leader's
        row-blocks in order; each is written as its own arena page
        (ragged blocks fine — readers derive per-page row counts from
        actual page sizes), so the matrix NEVER materializes densely on
        the follower."""
        dead = []
        with self._lock:
            s = self._require(ident)
            dead = list(s.items or [])
            if not blocks:
                s.items = []
                s.nbytes = 0
                self._touch(s)
            else:
                arena_name = f"{s.ident}#g{next(self._gen)}"
                ps = self.page_store()
                first = True
                for b in blocks:
                    ps.put(f"{arena_name}.mat", np.ascontiguousarray(b),
                           row_block=max(int(row_block), 1),
                           append=not first)
                    first = False
                s.items = [_PagedMatrix(arena_name)]
                s.nbytes = 0
                s.last_access = time.time()
                self._touch(s)
        self._drop_detached(dead)

    def append_table(self, ident: SetIdentifier, table) -> None:
        """Append a batch of rows to a table set (the reference's
        addData flow, ``StorageAddData``): paged sets write additional
        arena pages (no rewrite); memory sets concat on device with
        dictionary remap.

        Paged appends serialize on the SET's append lock outside the
        global store lock: the page write must wait for in-flight
        streams of the same relation (rw.write), and that wait must not
        freeze unrelated store operations. The store lock is re-taken
        only to verify the set wasn't removed/replaced in between."""
        from netsdb_tpu.relational.autojoin import concat_tables
        from netsdb_tpu.relational.table import ColumnTable

        with self._lock:
            s = self._require(ident)
            if s.alias_of is not None:
                raise ValueError(f"set {ident} aliases {s.alias_of}; "
                                 f"it is read-only")
            paged = s.storage == "paged"
        if paged:
            from netsdb_tpu.relational.outofcore import PagedColumns

            with s.append_mu:  # concurrent appends: dict remaps must
                # not interleave (per-set, not global)
                with self._lock:
                    if self._sets.get(ident) is not s:
                        raise KeyError(f"set {ident} was removed "
                                       f"during append")
                    pc = next((i for i in (s.items or [])
                               if isinstance(i, PagedColumns)), None)
                    if pc is None:
                        # FIRST batch = a fresh ingest, done under the
                        # store lock: no streams can exist on a
                        # relation that doesn't, so no rw wait — and a
                        # concurrent replace can no longer interleave
                        # and orphan one relation's pages
                        # lint: disable=lock-blocking-call -- first batch of a fresh relation (comment above): no streams exist, the append wait cannot occur
                        dead = self._ingest_paged(s, [table],
                                                  append=True)
                rows = None
                if pc is not None:
                    # live relation: append outside the store lock
                    # (waits for in-flight streams via pc.rw; a
                    # concurrent remove/replace drops pc, making
                    # pc.append fail loudly instead of resurrecting)
                    before = pc.num_rows
                    self._append_paged_existing(s, pc, table)
                    # the appended tail is the ONLY dirty range: the
                    # partial device cache keeps every pre-append
                    # block resident (whole-run mode ignores it)
                    rows = (before, pc.num_rows)
                    dead = []
                with self._lock:
                    self._touch(s, rows=rows)
            self._drop_detached(dead)
            return
        self._append_table_memory(ident, table)

    def update_columns(self, ident: SetIdentifier,
                       cols: Dict[str, Any]) -> None:
        """Overwrite whole COLUMNS of a paged table set in place —
        the update-in-place write path (netsDB's UpdateSet over one
        attribute). Pages are rewritten where they live (same shape,
        no layout change); the device cache drops ONLY block entries
        whose stream projected a touched column (per-column dirty
        ranges — an untouched column's cached blocks keep serving
        with zero re-stages).

        Lock discipline mirrors ``append_table``: the store lock only
        locates and pins the relation; the page rewrites run outside
        it under the set's ``append_mu`` (they wait on the relation's
        own rw lock for in-flight streams)."""
        from netsdb_tpu.relational.outofcore import PagedColumns

        with self._lock:
            s = self._require(ident)
            if s.alias_of is not None:
                raise ValueError(f"set {ident} aliases {s.alias_of}; "
                                 f"it is read-only")
            if s.storage != "paged":
                raise ValueError(f"update_columns needs a paged table "
                                 f"set; {ident} is {s.storage!r}")
            pc = next((i for i in (s.items or [])
                       if isinstance(i, PagedColumns)), None)
            if pc is None:
                raise ValueError(f"set {ident} holds no paged relation")
        with s.append_mu:
            with self._lock:
                if self._sets.get(ident) is not s:
                    raise KeyError(f"set {ident} was removed during "
                                   f"update")
            for name, values in cols.items():
                # pc.update_column owns the per-range, per-column
                # cache invalidation (the pc.append contract)
                pc.update_column(name, values)
            with self._lock:
                self._touch(s, rows=(0, pc.num_rows),
                            columns=tuple(sorted(cols)))

    @_locked
    def _append_table_memory(self, ident: SetIdentifier, table) -> None:
        from netsdb_tpu.relational.autojoin import concat_tables
        from netsdb_tpu.relational.table import ColumnTable

        s = self._require(ident)
        if s.alias_of is not None:
            raise ValueError(f"set {ident} aliases {s.alias_of}; it is read-only")
        if s.items is None:
            self._load_from_spill(s)
        tables = [i for i in s.items if isinstance(i, ColumnTable)]
        if len(s.items) != len(tables) or len(tables) > 1:
            raise ValueError(
                f"append_table needs a single-relation table set; "
                f"{ident} holds {len(s.items)} items "
                f"({len(tables)} tables) — appending would drop the rest")
        new = concat_tables(tables[0], table) if tables else table
        if s.placement is not None:
            new = s.placement.apply(new)
        s.items = [new]
        s.nbytes = _item_nbytes(new)
        s.last_access = time.time()
        self._touch(s)
        self._maybe_evict(exclude=ident)

    def put_tensor(self, ident: SetIdentifier, tensor: BlockedTensor) -> None:
        """Replace a set's contents with one tensor — the dominant pattern
        for model-weight sets (each netsDB weight set is exactly one
        blocked matrix)."""
        dead = []
        with self._lock:
            s = self._require(ident)
            if s.alias_of is not None:
                raise ValueError(f"set {ident} aliases {s.alias_of}; "
                                 f"it is read-only")
            if s.storage == "paged":
                # lint: disable=lock-blocking-call -- replace builds a FRESH relation (the old one is dropped after the swap); no stream can hold the new relation's rw lock yet
                dead = self._ingest_paged(s, [tensor])
            else:
                if s.placement is not None:
                    tensor = s.placement.apply(tensor)
                s.items = [tensor]
                s.nbytes = _item_nbytes(tensor)
                s.last_access = time.time()
                self._maybe_evict(exclude=ident)
            self._touch(s)
        self._drop_detached(dead)  # replaced pages reclaim UNLOCKED

    def get_tensor(self, ident: SetIdentifier) -> BlockedTensor:
        items = self.get_items(ident)
        if any(isinstance(i, _PagedMatrix) for i in items):
            raise ValueError(
                f"set {ident} holds a PAGED matrix — it streams, it is "
                f"never device-resident; consume it with paged_matmul")
        tensors = [i for i in items if isinstance(i, BlockedTensor)]
        if len(tensors) != 1:
            raise ValueError(
                f"set {ident} holds {len(tensors)} tensors; expected exactly 1"
            )
        return tensors[0]

    @_locked
    def get_items(self, ident: SetIdentifier) -> List[Any]:
        s = self._require(ident)
        if s.alias_of is not None:
            # Shared-storage set: physical pages live in another set
            # (ref PartitionTensorBlockSharedPageIterator).
            return self.get_items(s.alias_of)
        if s.items is None:
            self._load_from_spill(s)
        else:
            self.stats.hits += 1
        s.last_access = time.time()
        from netsdb_tpu.dedup.pool import PooledTensor

        if any(isinstance(i, PooledTensor) for i in s.items):
            # dedup'd model set: resident HBM holds the shared pool +
            # slot grid; consumers get an eagerly-assembled TRANSIENT
            # BlockedTensor (freed when the consuming job drops it) —
            # the shared-page read path (SharedTensorBlockSet.h:25).
            # Per-read gather cost and the transient's peak-HBM are the
            # price of keeping consumers pooling-agnostic (dedup/pool.py
            # module docstring).
            return [i.assemble() if isinstance(i, PooledTensor) else i
                    for i in s.items]
        return s.items

    def scan(self, ident: SetIdentifier) -> Iterator[Any]:
        """Stream a set's items — reference ``SetScan`` / ``SetIterator``
        (``src/queries/headers/SetIterator.h``)."""
        yield from self.get_items(ident)

    @_locked
    def add_shared_mapping(
        self, private: SetIdentifier, shared: SetIdentifier, mapping: Optional[Dict] = None
    ) -> None:
        """Point ``private`` at ``shared``'s physical storage — model-dedup
        client API ``addSharedPage``/``addSharedMapping`` (reference
        ``src/mainClient/headers/PDBClient.h:113-138``)."""
        s = self._require(private)
        s.alias_of = shared
        s.shared_mapping = mapping or {}
        s.items = []
        s.nbytes = 0
        self._touch(s)

    @_locked
    def set_pooled(self, ident: SetIdentifier, pooled: Any) -> None:
        """Swap a weight set's dense tensor for its pooled form (the
        shared-block dedup flow, ``dedup/pool.py``) — the original
        device buffer is released once no set references it."""
        s = self._require(ident)
        s.items = [pooled]
        s.nbytes = _item_nbytes(pooled)
        self._touch(s)
        self._pooled.add(ident)  # pool-bytes accounting registry

    # --- persistence (ref: flush threads → PartitionedFile) -----------
    def _spill_path(self, ident: SetIdentifier) -> str:
        safe = f"{ident.db}__{ident.set}".replace("/", "_")
        return os.path.join(self.config.data_dir, f"{safe}.pdbset")

    @_locked
    def flush(self, ident: SetIdentifier) -> str:
        """Write a set durably to disk (keeps it in RAM). A PAGED set
        snapshots as its materialized relation tagged ``paged`` — on
        reload it re-ingests into the arena, so paged sets survive
        restart like any other (the reference's PartitionedFile +
        soft-reboot story; the snapshot holds the full relation on
        host once, the same peak as the original ingest). The arena's
        own spill files remain capacity, not durability."""
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.storage.paged import PagedObjects

        s = self._require(ident)
        items = self.get_items(ident)
        path = self._spill_path(ident)
        payload = []
        for item in items:
            if isinstance(item, BlockedTensor):
                payload.append(
                    ("tensor", np.asarray(item.data), item.meta.shape,
                     item.meta.block_shape)
                )
            elif isinstance(item, PagedColumns):
                # HOST-side snapshot (numpy columns): the flush path
                # must never materialize the relation in device memory
                payload.append(("paged", item.to_host_table(), None, None))
            elif isinstance(item, _PagedMatrix):
                # paged matrix: host-side block concat (never device)
                blocks = [b for _, b in self.page_store().stream_blocks(
                    f"{item.ident}.mat")]
                payload.append(("paged_mat", np.concatenate(blocks),
                                None, None))
            elif isinstance(item, PagedObjects):
                # object pages snapshot as the record list (host-side)
                payload.append(("paged_objs", item.to_list(), None,
                                None))
            else:
                payload.append(("object", item, None, None))
        record = {"ident": tuple(s.ident), "persistence": s.persistence,
                  "storage": s.storage,
                  "placement": (s.placement.to_meta()
                                if s.placement is not None else None),
                  "items": payload}
        with open(path, "wb") as f:
            if self.config.enable_compression:
                # reference -DENABLE_COMPRESSION snappy-compresses its
                # shuffle/page byte streams (PipelineStage.cc:179-196);
                # level 1 = the same speed-over-ratio tradeoff. Streamed
                # (compressobj wrapper) because flush runs from
                # _maybe_evict under memory pressure — materializing
                # pickle+compressed copies of a multi-GB set there
                # would spike RAM exactly when it is scarce.
                import zlib

                f.write(b"NZ01")
                comp = zlib.compressobj(1)

                class _W:
                    def write(self, chunk):
                        f.write(comp.compress(chunk))

                pickle.dump(record, _W(), protocol=pickle.HIGHEST_PROTOCOL)
                f.write(comp.flush())
            else:
                pickle.dump(record, f, protocol=pickle.HIGHEST_PROTOCOL)
        self.stats.spills += 1
        return path

    def _load_from_spill(self, s: _StoredSet) -> None:
        path = self._spill_path(s.ident)
        if not os.path.exists(path):
            raise KeyError(f"set {s.ident} has no data in RAM or on disk")
        with open(path, "rb") as f:
            magic = f.read(4)
            if magic == b"NZ01":  # compressed spill (see flush)
                # streamed, mirroring flush: never hold compressed +
                # decompressed + deserialized copies at once
                import zlib

                decomp = zlib.decompressobj()

                class _R:
                    """Minimal file-like over the decompressed stream.
                    Buffer is a bytearray: in-place append, so a large
                    pickle frame read stays linear, not quadratic."""

                    def __init__(self):
                        self.buf = bytearray()

                    def read(self, n=-1):
                        while (n < 0 or len(self.buf) < n):
                            chunk = f.read(1 << 20)
                            if not chunk:
                                self.buf += decomp.flush()
                                break
                            self.buf += decomp.decompress(chunk)
                        if n < 0:
                            out, self.buf = bytes(self.buf), bytearray()
                        else:
                            out = bytes(self.buf[:n])
                            del self.buf[:n]
                        return out

                    def readline(self):  # pickle protocol 2+ never calls
                        raise io.UnsupportedOperation("readline")

                blob = pickle.load(_R())
            else:
                f.seek(0)
                blob = pickle.load(f)
        # restore the set-level attributes the record carries: a fresh
        # load_set builds a bare _StoredSet, and paged-ness/placement
        # must come back BEFORE ingest (placement rounds the page row
        # count to the shard granularity)
        if blob.get("storage"):
            s.storage = blob["storage"]
        if s.placement is None and blob.get("placement"):
            from netsdb_tpu.parallel.placement import Placement

            s.placement = Placement.from_meta(blob["placement"])
        paged_objs = [data for kind, data, _, _ in blob["items"]
                      if kind == "paged_objs"]
        if paged_objs:
            # object-set snapshot: records re-page into the arena
            self._drop_detached(self._ingest_paged(s, paged_objs[0]))
            self._touch(s)
            self.stats.misses += 1
            self.stats.loads += 1
            return
        paged_tables = [data for kind, data, _, _ in blob["items"]
                        if kind in ("paged", "paged_mat")]
        if paged_tables:
            # snapshot of a paged set: re-ingest the relation into the
            # arena — the set comes back PAGED, placement and all.
            # (Reload happens under the store lock; a reload never
            # replaces live paged items, so the dead list is empty —
            # still reclaimed for belt-and-braces.)
            self._drop_detached(self._ingest_paged(s, paged_tables))
            self._touch(s)
            self.stats.misses += 1
            self.stats.loads += 1
            return
        if s.storage == "paged":
            # empty paged snapshot: nothing to ingest, but the set must
            # NOT silently demote to resident storage
            s.items = []
            s.nbytes = 0
            self.stats.loads += 1
            return
        items: List[Any] = []
        for kind, data, shape, block_shape in blob["items"]:
            if kind == "tensor":
                meta = BlockMeta(tuple(shape), tuple(block_shape))
                import jax.numpy as jnp

                items.append(BlockedTensor(jnp.asarray(data), meta))
            else:
                items.append(data)
        if s.placement is not None:
            # distribution is a property of the set: an eviction round-trip
            # must not silently demote a placed set to single-device
            items = [s.placement.apply(i) for i in items]
        s.items = items
        s.nbytes = sum(_item_nbytes(i) for i in items)
        self._touch(s)  # fresh objects: cached runs of the old
        # incarnation must never match (checkpoint-restore freshness)
        self.stats.misses += 1
        self.stats.loads += 1

    @_locked
    def load_set(self, ident: SetIdentifier) -> None:
        """Recover a persisted set after restart (ref: sets survive soft
        reboot, README.md:101-113)."""
        if ident not in self._sets:
            self._sets[ident] = _StoredSet(ident=ident, items=None,
                                           persistence="persistent")
        self.get_items(ident)

    @_locked
    def live_pool_bytes(self) -> int:
        """Bytes of every distinct shared block pool referenced by at
        least one resident set (``dedup/pool.py``) — counted ONCE per
        pool regardless of how many sets share it, and dropping out
        automatically when the last referencing set goes away. Scans
        only the sets registered by ``set_pooled`` (O(pooled sets), not
        O(all items))."""
        return self._live_pool_bytes()

    def _live_pool_bytes(self) -> int:
        seen: Dict[int, int] = {}
        dead = []
        for ident in self._pooled:
            s = self._sets.get(ident)
            if s is None:
                dead.append(ident)
                continue
            for item in (s.items or []):
                p = getattr(item, "pool", None)
                if p is not None and hasattr(p, "nbytes"):
                    seen[id(p)] = int(p.nbytes)
        for ident in dead:
            self._pooled.discard(ident)
        return sum(seen.values())

    @_locked
    def drop_pool_caches(self) -> int:
        """Release every pooled set's cached assembly (dedup/pool.py) —
        the cheapest memory to give back under pressure (re-creatable
        by one gather). Returns bytes released."""
        from netsdb_tpu.dedup.pool import PooledTensor

        released = 0
        for ident in list(self._pooled):
            s = self._sets.get(ident)
            for item in (s.items or []) if s is not None else []:
                if isinstance(item, PooledTensor):
                    released += item.drop_cache()
        return released

    def _live_pool_cache_bytes(self) -> int:
        """Bytes currently held by pooled sets' cached assemblies —
        counted into the pressure total (the caches themselves can BE
        the pressure; invisible bytes would defeat the cap)."""
        from netsdb_tpu.dedup.pool import PooledTensor

        total = 0
        for ident in self._pooled:
            s = self._sets.get(ident)
            for item in (s.items or []) if s is not None else []:
                if isinstance(item, PooledTensor) and item._cache is not None:
                    total += int(item._cache.data.nbytes)
        return total

    # --- eviction (ref: PageCache::evict + LocalitySet policies) ------
    def _maybe_evict(self, exclude: Optional[SetIdentifier] = None) -> None:
        total = sum(s.nbytes for s in self._sets.values() if s.items is not None)
        total += self._live_pool_bytes()
        total += self._live_pool_cache_bytes()
        if total <= self.max_host_bytes:
            return
        # pressure: cached pool assemblies go first — dropping them is
        # free (one gather re-creates), spilling a set is not
        total -= self.drop_pool_caches()
        if total <= self.max_host_bytes:
            return
        candidates = [
            s for s in self._sets.values()
            if s.items is not None and s.ident != exclude and s.nbytes > 0
            and s.alias_of is None and s.storage != "paged"
        ]
        # Policy per set; mixed policies resolved by sorting key.
        def key(s: _StoredSet):
            if s.eviction == "mru":
                return -s.last_access
            if s.eviction == "random":
                return random.random()
            return s.last_access  # lru

        pool_before = self._live_pool_bytes()
        for s in sorted(candidates, key=key):
            if total <= self.max_host_bytes:
                break
            self.flush(s.ident)
            total -= s.nbytes
            s.items = None
            s.nbytes = 0
            self.stats.evictions += 1
            if s.ident in self._pooled:
                # evicting a pooled set may release its shared pool
                # (when it was the last referencing set) — credit the
                # released bytes or the loop over-evicts everyone else
                pool_now = self._live_pool_bytes()
                total -= pool_before - pool_now
                pool_before = pool_now

    def _require(self, ident: SetIdentifier) -> _StoredSet:
        if ident not in self._sets:
            if os.path.exists(self._spill_path(ident)):
                self._sets[ident] = _StoredSet(ident=ident, items=None,
                                               persistence="persistent")
                return self._sets[ident]
            raise KeyError(f"unknown set {ident}; create_set first")
        return self._sets[ident]

    # --- stats (ref: StorageCollectStats → Statistics) ----------------
    @_locked
    def set_stats(self, ident: SetIdentifier) -> Dict[str, Any]:
        s = self._require(ident)
        items = s.items if s.items is not None else []
        return {
            "ident": str(ident),
            "num_items": len(items),
            "nbytes": s.nbytes,
            "in_memory": s.items is not None,
            "persistence": s.persistence,
            "alias_of": str(s.alias_of) if s.alias_of else None,
            "placement": s.placement.label() if s.placement is not None else None,
            "storage": s.storage,
            "version": s.version,
            # the bounded dirty-range log (partial-run device caching):
            # (start, end) per write, end=None for whole-scope writes
            "dirty_ranges": list(s.dirty_log),
        }
