"""Out-of-core relational execution: TPC-H through the paged store.

The reference's PageScanner streams sets bigger than RAM through every
pipeline — 64 MB pages pinned one at a time, fed to the pipeline
threads, evicted behind them (``src/storage/headers/PageScanner.h``,
``PageCircularBuffer.h``). Round 1 wired that streaming to matmul only;
this module runs the COLUMNAR QUERY ENGINE the same way: fact-table
columns live as row-chunk pages in the native page store (whose arena
cap forces spill-to-disk for cold pages), and a query is one compiled
chunk-step folded over the stream.

The chunk step IS the distributed engine's combiner: a masked partial
aggregate with a fixed-shape output (``sharded.py`` runs the same
kernels over shards in SPACE and merges with psum; here the "shards"
arrive in TIME and merge by accumulation — the same math either way,
so out-of-core answers match in-memory ones to float summation order).

Chunks are padded to the fixed page row count, so every chunk reuses
ONE compiled XLA program (static shapes; the ragged tail rides the
validity mask like everywhere else in this framework).
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.relational.table import ColumnTable, date_to_int, int_to_date
from netsdb_tpu.storage.paged import PagedTensorStore
from netsdb_tpu.utils.locks import RWLock

_INT_KINDS = "ib"


class PagedColumns:
    """A relation's columns paged as row-chunks in a PagedTensorStore.

    Integer and float columns pack into two page matrices with a SHARED
    row blocking, so one stream step yields every column for the same
    row range (the reference's page layout holds whole objects per page
    for the same reason). Dictionaries and host metadata stay resident
    — only bulk column data pages."""

    def __init__(self, store: PagedTensorStore, name: str,
                 int_names: List[str], float_names: List[str],
                 num_rows: int, row_block: int,
                 dicts: Optional[Dict[str, List[str]]] = None,
                 stats: Optional[Dict[str, object]] = None):
        self.store = store
        self.name = name
        self.int_names = int_names
        self.float_names = float_names
        self.num_rows = num_rows
        self.row_block = row_block
        self.dicts = dicts or {}
        # stream-vs-mutation guard: streams (executor folds, snapshots)
        # run OUTSIDE the SetStore lock, so a concurrent append/drop
        # could free or grow pages mid-stream; streams hold read, the
        # mutators hold write (the arena pin, Python-side)
        self.rw = RWLock(name="PagedColumns.rw")
        self.dropped = False  # set by drop(); appends must not
        # resurrect freed arena names (a fresh put under a dead name
        # would leak unreferenced pages)
        # chunks yielded over this relation's lifetime — the per-
        # relation page-load diagnostic the grace-hash tests assert on
        # (one-pass discipline: probe chunks read ONCE, not once per
        # build block)
        self.pages_streamed = 0
        # ingest-time ColumnStats per int column — collected in the one
        # pass that already touches every row, so the planner never has
        # to re-stream the set (the reference's StorageCollectStats
        # moment, ``PangeaStorageServer.h:48``)
        self.stats = stats or {}
        # device-cache binding (storage/devcache.py), set by
        # ``SetStore._bind_cache`` for store-owned relations only —
        # grace-hash spill partitions stay uncached. ``_mutations`` is this handle's own append/drop
        # counter: it rides every cache key so even direct
        # ``pc.append`` callers (bypassing the store's version bump)
        # can never leave a stale cached run matchable.
        self.devcache = None
        self.cache_scope = None
        self.cache_version_fn = None
        self._mutations = 0

    # ------------------------------------------------------------ ingest
    @staticmethod
    def _pack(cols: Dict[str, np.ndarray], int_names: List[str],
              float_names: List[str]):
        """Columns → (int32 matrix, float32 matrix, row count), the ONE
        packing used by ingest and append (divergent packing would make
        appended pages unreadable against ingested ones)."""
        lengths = {n: len(np.asarray(c)) for n, c in cols.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"ragged columns cannot page together: "
                             f"{lengths}")
        n = next(iter(lengths.values()))
        imat = (np.stack([np.asarray(cols[c]).astype(np.int32)
                          for c in int_names], axis=1)
                if int_names else None)
        fmat = (np.stack([np.asarray(cols[c]).astype(np.float32)
                          for c in float_names], axis=1)
                if float_names else None)
        return imat, fmat, n

    @staticmethod
    def ingest(store: PagedTensorStore, name: str,
               cols: Dict[str, np.ndarray],
               row_block: Optional[int] = None,
               dicts: Optional[Dict[str, List[str]]] = None,
               ) -> "PagedColumns":
        """Page a dict of host columns. ``row_block`` defaults so that
        one int-matrix page is ~the configured page size."""
        int_names = sorted(n for n, c in cols.items()
                           if np.asarray(c).dtype.kind in _INT_KINDS)
        float_names = sorted(n for n, c in cols.items()
                             if n not in int_names)
        imat, fmat, num_rows = PagedColumns._pack(cols, int_names,
                                                  float_names)
        if row_block is None:
            width = max(len(int_names) + len(float_names), 1)
            row_block = max(store.config.page_size_bytes // (4 * width),
                            1024)
        row_block = min(row_block, num_rows)
        from netsdb_tpu.relational.stats import analyze_array

        stats = {}
        if imat is not None:
            stats = {n: analyze_array(imat[:, j])
                     for j, n in enumerate(int_names)}
            store.put(f"{name}.int", imat, row_block=row_block)
        if fmat is not None:
            store.put(f"{name}.float", fmat, row_block=row_block)
        return PagedColumns(store, name, int_names, float_names,
                            num_rows, row_block, dicts, stats)

    @staticmethod
    def from_table(store: PagedTensorStore, name: str, table: ColumnTable,
                   columns: List[str],
                   row_block: Optional[int] = None) -> "PagedColumns":
        cols = {n: np.asarray(table[n]) for n in columns}
        return PagedColumns.ingest(store, name, cols, row_block,
                                   dicts={n: d for n, d in
                                          table.dicts.items()
                                          if n in columns})

    # ------------------------------------------------------------ append
    def append(self, cols: Dict[str, np.ndarray]) -> None:
        """Append a batch of rows as ADDITIONAL pages (the reference's
        addData continuously appending to a set) — no rewrite of
        existing pages. ATOMIC at the relation level: a failure while
        writing either matrix rolls both back to the pre-append page
        count (a half-written batch would otherwise desynchronize the
        co-paged int/float streams and brick the whole set). Stats and
        ``num_rows`` update only after both writes succeed."""
        from netsdb_tpu.relational.stats import ColumnStats, analyze_array

        if set(cols) != set(self.int_names) | set(self.float_names):
            raise ValueError(
                f"append schema mismatch: have "
                f"{sorted(set(self.int_names) | set(self.float_names))}, "
                f"got {sorted(cols)}")
        # _pack re-casts by the STORED classification, so a float batch
        # column landing on an int-classified stored column would
        # silently truncate via astype(int32) — reject it (int→float
        # widens losslessly and stays allowed)
        for n in self.int_names:
            if np.asarray(cols[n]).dtype.kind not in _INT_KINDS:
                raise TypeError(
                    f"append column {n!r} is float-valued but the "
                    f"stored column is int-classified; casting would "
                    f"truncate — convert explicitly first")
        imat, fmat, n_new = self._pack(cols, self.int_names,
                                       self.float_names)
        if n_new == 0:
            return  # all-masked/empty batch: a no-op, not a stats merge
        with self.rw.write():  # drain in-flight streams before growing
            if self.dropped:
                raise KeyError(f"paged relation {self.name!r} was "
                               f"dropped; cannot append")
            undo = []
            for suffix, mat in ((".int", imat), (".float", fmat)):
                if mat is None:
                    continue
                full = self.name + suffix
                undo.append((full, self.store.num_blocks(full),
                             self.num_rows))
                try:
                    self.store.put(full, mat, append=True)
                except Exception:
                    for uname, npages, rows in undo:
                        self.store.truncate_to(uname, npages, rows)
                    raise
            for j, name in enumerate(self.int_names):
                new = analyze_array(imat[:, j])
                old = self.stats.get(name)
                self.stats[name] = (new if old is None else ColumnStats(
                    old.n_rows + new.n_rows, min(old.min_val, new.min_val),
                    max(old.max_val, new.max_val), -1))
            n_before = self.num_rows
            self.num_rows += n_new
            self._mutations += 1  # cached whole RUNS of the old rows
            # are dead (their key carries this counter); cached BLOCKS
            # are range-keyed and survive — only the appended tail is
            # dirty. Invalidating here (not just in SetStore._touch)
            # covers direct pc.append callers that bypass the store.
        if (self.devcache is not None and self.cache_scope is not None
                and getattr(self.devcache, "partial", False)):
            self.devcache.invalidate_range(self.cache_scope, n_before,
                                           self.num_rows)

    def update_column(self, name: str, values) -> None:
        """Overwrite ONE column's values in place (same row count) —
        the update-in-place write. Each page the column lives in is
        rewritten where it sits (``PagedTensorStore.rewrite_block``,
        same shape — no layout change, no page movement), and the
        device cache drops only block entries whose stream PROJECTED
        this column (per-column dirty ranges): a query over the other
        columns keeps serving its cached blocks with zero re-stages.
        Column-projected streams key their blocks by their projection
        (``_partial_plan(columns=...)``); full-table streams carry no
        projection marker and always drop — they contain this column."""
        values = np.asarray(values)
        if name in self.dicts:
            raise ValueError(f"update_column: {name!r} is dict-encoded"
                             f" — update through re-ingest (codes would"
                             f" be meaningless)")
        if name in self.int_names:
            if values.dtype.kind not in _INT_KINDS:
                raise TypeError(
                    f"update_column {name!r}: stored column is "
                    f"int-classified; casting floats would truncate")
            suffix, names = ".int", self.int_names
        elif name in self.float_names:
            suffix, names = ".float", self.float_names
        else:
            raise KeyError(f"no column {name!r} in {self.name!r}")
        if len(values) != self.num_rows:
            raise ValueError(
                f"update_column {name!r}: {len(values)} values for "
                f"{self.num_rows} rows (in-place updates replace the "
                f"whole column)")
        full = self.name + suffix
        j = names.index(name)
        with self.rw.write():  # drain in-flight streams first
            if self.dropped:
                raise KeyError(f"paged relation {self.name!r} was "
                               f"dropped; cannot update")
            for idx, (s0, e0) in enumerate(self.store.block_ranges(full)):
                _start, blk = self.store.read_block(full, idx)
                arr = np.array(blk)  # read_block views are read-only
                arr[:, j] = values[s0:e0]
                self.store.rewrite_block(full, idx, arr)
            if name in self.int_names:
                from netsdb_tpu.relational.stats import analyze_array

                self.stats[name] = analyze_array(values.astype(np.int32))
            self._mutations += 1  # whole-run keys of old content die
        if (self.devcache is not None and self.cache_scope is not None
                and getattr(self.devcache, "partial", False)):
            self.devcache.invalidate_range(self.cache_scope, 0,
                                           self.num_rows,
                                           columns=(name,))

    # ------------------------------------------------------------ stream
    def pad_rows(self) -> int:
        """Row count every streamed chunk pads to: ``row_block``'s
        shape BUCKET when the config enables bucketing (so ragged
        tails and differing ingest sizes reuse one compiled chunk step
        per bucket — ``plan/staging.bucket_rows``), else ``row_block``
        exactly. Padded rows ride the validity mask either way."""
        from netsdb_tpu.plan.staging import pad_rows_target

        return pad_rows_target(
            self.row_block,
            getattr(self.store.config, "shape_bucketing", True),
            density=getattr(self.store.config, "bucket_density", 2))

    def stream(self, prefetch: Optional[int] = None, device: bool = True):
        """Chunk stream of (cols, valid, start_row), every chunk padded
        to :meth:`pad_rows` rows — the PageScanner loop feeding the
        compiled chunk step. Ragged blocks (appended batches' tails)
        are masked, never reshaped; ``start_row`` is the chunk's global
        row offset (exact even for ragged streams).

        ``device=False`` keeps the chunks as NUMPY columns (the serve
        wire streams pages to a client — the device must never see
        them) and returns a plain generator.  ``device=True`` returns a
        :class:`~netsdb_tpu.plan.staging.StagedStream`: the device
        upload runs ``config.stage_depth`` chunks ahead on a background
        thread, so the next chunk lands in HBM while the consumer's
        step computes.  ``prefetch`` (None = the
        ``config.stream_prefetch_pages`` knob) is the HOST read-ahead
        depth underneath.  Either way the relation's read lock is held
        for the stream's lifetime — on the staging thread for the
        device path — so a concurrent append/drop (write lock) cannot
        free or grow pages mid-stream; close() abandoned streams."""
        if not device:
            return self._host_stream(prefetch)
        from netsdb_tpu.plan.staging import stage_stream

        def place(item):
            cols, valid, start = item
            return ({k: jnp.asarray(v) for k, v in cols.items()},
                    jnp.asarray(valid), start)

        return stage_stream(
            self._host_stream(prefetch), place,
            depth=getattr(self.store.config, "stage_depth", 2),
            name=f"cols:{self.name}")

    def _host_stream(self, prefetch: Optional[int] = None,
                     blocks: Optional[List[int]] = None,
                     columns: Optional[List[str]] = None
                     ) -> Iterator[Tuple[Dict[str, np.ndarray],
                                         np.ndarray, int]]:
        """Locked host-side chunk generator (numpy columns). Runs —
        lock acquisition included — on whichever thread iterates it:
        the consumer directly (``device=False``) or the staging thread
        (``device=True``). ``blocks`` restricts to those page indices
        (the stitched gap feed — cached pages never touch the arena);
        ``columns`` projects: a matrix none of whose columns are
        requested is never read at all."""
        with self.rw.read():
            if self.dropped:
                raise KeyError(f"paged relation {self.name!r} was "
                               f"dropped; cannot stream")
            yield from self._stream_unlocked(prefetch, blocks, columns)

    def _stream_unlocked(self, prefetch: Optional[int] = None,
                         blocks: Optional[List[int]] = None,
                         columns: Optional[List[str]] = None
                         ) -> Iterator[Tuple[Dict[str, np.ndarray],
                                             np.ndarray, int]]:
        if columns is not None:
            missing = set(columns) - (set(self.int_names)
                                      | set(self.float_names))
            if missing:
                raise KeyError(f"no columns {sorted(missing)} in "
                               f"{self.name!r}")
        want = (lambda n: columns is None or n in columns)
        streams = []
        if self.int_names and any(want(n) for n in self.int_names):
            streams.append((self.int_names,
                            self.store.stream_blocks(f"{self.name}.int",
                                                     prefetch,
                                                     blocks=blocks)))
        if self.float_names and any(want(n) for n in self.float_names):
            streams.append((self.float_names,
                            self.store.stream_blocks(
                                f"{self.name}.float", prefetch,
                                blocks=blocks)))
        while True:
            chunk: Dict[str, np.ndarray] = {}
            start = n = None
            exhausted, yielded = [], []
            for names, it in streams:
                try:
                    s0, block = next(it)
                except StopIteration:
                    exhausted.append(names)
                    continue
                yielded.append(names)
                if start is None:
                    start, n = s0, block.shape[0]
                elif s0 != start or block.shape[0] != n:
                    raise RuntimeError(
                        "int/float page streams desynchronized "
                        f"({s0},{block.shape[0]}) vs ({start},{n})")
                for j, name in enumerate(names):
                    if want(name):
                        chunk[name] = block[:, j]
            if exhausted:
                # both streams must end on the same round — one ending
                # early would otherwise silently truncate the other's
                # remaining rows out of the query result
                if yielded:
                    raise RuntimeError(
                        "int/float page streams desynchronized: "
                        f"{exhausted} ended while {yielded} still had "
                        f"blocks")
                return
            pad = self.pad_rows() - n
            if pad > 0:
                chunk = {k: np.pad(v, (0, pad)) for k, v in chunk.items()}
            valid = np.arange(n + max(pad, 0)) < n
            self.pages_streamed += 1
            yield chunk, valid, start

    def num_pages(self) -> int:
        """Row-chunk page count (the co-paged int/float streams share
        one blocking, so either matrix's count is THE count)."""
        suffix = ".int" if self.int_names else ".float"
        return self.store.num_blocks(self.name + suffix)

    def _cache_ref(self, kind: str, placement, columns=None):
        """(cache, key) when this relation is store-owned and the
        device cache is on, else (None, None). The key is the
        tentpole's ``(db:set, version, bucket, sharding)`` — plus this
        handle's own mutation counter, the stream kind and any column
        PROJECTION — so a warm stream of the SAME content/shape/
        sharding replays device-resident blocks and any write anywhere
        unkeys every old run."""
        cache = self.devcache
        if (cache is None or not cache.enabled
                or self.cache_scope is None or self.dropped):
            return None, None
        ver = (self.cache_version_fn()
               if self.cache_version_fn is not None else 0)
        key = (self.cache_scope, ver, self._mutations, kind,
               self.pad_rows(),
               placement.label() if placement is not None else None)
        if columns is not None:
            key = key + (("cols",) + tuple(sorted(columns)),)
        return cache, key

    def partial_base_key(self, kind: str, placement, columns=None):
        """The block-entry base key for one stream shape of this
        relation: ``(scope, kind, bucket, sharding)`` — NO write
        version and NO mutation counter (block freshness is
        dirty-range invalidation's job) — plus, for column-PROJECTED
        streams, a trailing ``frozenset`` of the projected columns:
        the marker per-column invalidation matches against (an entry
        whose projection is disjoint from an updated column survives;
        unmarked entries contain every column and always drop). Also
        the key ``parallel/reshard.reshard_set`` moves entries
        between: same shape, different sharding label."""
        base = (self.cache_scope, kind, self.pad_rows(),
                placement.label() if placement is not None else None)
        if columns is not None:
            base = base + (frozenset(columns),)
        return base

    def _partial_plan(self, kind: str, placement, prefetch,
                      columns=None):
        """A :class:`~netsdb_tpu.plan.staging.PartialPlan` for one
        stream of this relation under the block-granular cache, or
        None (cache off / whole-run mode / unbound temporary)."""
        from netsdb_tpu.plan.staging import PartialPlan

        cache = self.devcache
        if (cache is None or not cache.enabled
                or not getattr(cache, "partial", False)
                or self.cache_scope is None or self.dropped):
            return None
        base_key = self.partial_base_key(kind, placement, columns)
        ranges = self.block_ranges()
        if not ranges:
            return None
        return PartialPlan(
            cache, base_key, ranges,
            lambda idxs: self._host_stream(prefetch, blocks=idxs,
                                           columns=columns))

    def block_ranges(self) -> List[Tuple[int, int]]:
        """The relation's [(start_row, end_row)] block layout —
        metadata only (the co-paged int/float matrices share one
        blocking, so either matrix's layout is THE layout)."""
        suffix = ".int" if self.int_names else ".float"
        return self.store.block_ranges(self.name + suffix)

    def drop(self) -> None:
        """Free this relation's pages from the shared arena (both the
        int and float matrices). After this the PagedColumns is dead.
        Waits for in-flight streams (read lock holders) to drain."""
        with self.rw.write():
            self.dropped = True
            self._mutations += 1
            for suffix in (".int", ".float"):
                self.store.drop(self.name + suffix)
        if self.devcache is not None and self.cache_scope is not None:
            self.devcache.invalidate(self.cache_scope)

    def stream_tables(self, prefetch: Optional[int] = None,
                      placement=None,
                      columns: Optional[List[str]] = None):
        """The PageScanner feed for the set/DAG API: a
        :class:`~netsdb_tpu.plan.staging.StagedStream` of chunk
        ColumnTables (validity-masked, plus a ``_rowid`` global-row-
        index column so key-range folds can recover absolute rows).
        The whole device leg — pad, upload, mesh-shard — runs
        ``config.stage_depth`` chunks ahead on the staging thread, so
        the next chunk is HBM-resident while the consumer's fold step
        computes; ``prefetch`` (None = the config knob) is the host
        page read-ahead underneath.

        ``placement`` mesh-shards every chunk's rows before yielding —
        the streamed-pages-onto-mesh-shards path (each device folds its
        shard of every page; XLA inserts the per-chunk collectives the
        reference's workers-stream-local-partitions model implies,
        ``PipelineStage.cc:228-265``). Ingest rounds ``row_block`` to
        the shard granularity (and buckets ≥ 16 are multiples of 8),
        so placed chunks usually shard without a second padding round —
        when a bucket doesn't divide, ``shard_table`` pads the
        remainder (one deterministic final shape per bucket either
        way).

        Store-owned relations consult the cross-query DEVICE CACHE
        first (``storage/devcache.py``). Whole-run mode
        (``device_cache_partial=off``): a warm stream replays the
        placed chunk run already in device memory and a cold stream
        installs the completed run on the way through. Partial mode
        (the default): each cached BLOCK range serves from HBM — zero
        arena reads — stitched in row order with gap ranges streaming
        through the normal pipeline, and every placed gap block
        installs as it goes (early exit keeps the consumed prefix).
        Cached chunks are owned by the cache, never donation targets
        (fold steps donate only their carried accumulator).

        ``columns`` projects the stream to just those columns: a
        packed matrix none of whose columns are requested is never
        read from the arena, and the cached blocks key on the
        projection — a per-column dirty range from ``update_column``
        drops only the streams that contained the touched column."""
        from netsdb_tpu.plan.staging import stage_stream

        cache, cache_key = self._cache_ref("tables", placement, columns)
        base_rowid = np.arange(self.pad_rows(), dtype=np.int32)
        dicts = self.dicts
        if columns is not None:
            dicts = {k: v for k, v in dicts.items() if k in columns}

        def place(item):
            cols, valid, start = item
            cols = dict(cols)
            # the stream's own start is exact even for ragged
            # (appended) block sequences; invalid tail rows get bogus
            # ids, masked like everything else
            cols["_rowid"] = base_rowid[:len(valid)] + start
            if placement is not None:
                from netsdb_tpu.parallel.placement import shard_table

                # shard_table pads to the shard granularity and
                # device_puts every column with the mesh sharding
                return shard_table(ColumnTable(cols, dicts, valid),
                                   placement)
            return ColumnTable({k: jnp.asarray(v) for k, v in cols.items()},
                               dicts, jnp.asarray(valid))

        partial = self._partial_plan("tables", placement, prefetch,
                                     columns)
        if partial is not None:
            return stage_stream(
                None, place,
                depth=getattr(self.store.config, "stage_depth", 2),
                name=f"tables:{self.name}", partial=partial,
                scope=str(self.cache_scope))
        return stage_stream(
            self._host_stream(prefetch, columns=columns), place,
            depth=getattr(self.store.config, "stage_depth", 2),
            name=f"tables:{self.name}",
            cache=cache, cache_key=cache_key,
            cache_validator=(
                None if cache is None else
                lambda: self._cache_ref("tables", placement,
                                        columns)[1] == cache_key))

    def stream_host_tables(self, prefetch: Optional[int] = None
                           ) -> Iterator[ColumnTable]:
        """Yield each chunk as a COMPACT host-side ColumnTable (numpy
        columns, padding stripped, no ``_rowid``) — the serve wire's
        page feed (``FrontendQueryTestServer.cc:785-890`` streams each
        node's local pages to the client page by page): per-frame bytes
        bounded by one page, and the device never sees the data."""
        # closing: an abandoned OUTER iterator (the serve wire loop
        # stops early / errors) must close the inner locked stream NOW,
        # not at GC — GeneratorExit propagates through the with
        with contextlib.closing(
                self.stream(prefetch, device=False)) as chunks:
            for cols, valid, _start in chunks:
                n = int(np.asarray(valid).sum())
                yield ColumnTable({k: v[:n] for k, v in cols.items()},
                                  dict(self.dicts), None)

    def to_host_table(self) -> ColumnTable:
        """Materialize the relation as one HOST-resident ColumnTable
        (numpy columns, nothing touches the device) — the snapshot path
        (``SetStore.flush``): device memory stays bounded no matter how
        large the paged relation is."""
        with obs.span(f"ooc.host_assemble:{self.name}", "storage"):
            return self._to_host_table()

    def _to_host_table(self) -> ColumnTable:
        parts: Dict[str, List[np.ndarray]] = {}
        n_done = 0
        # the consistency check compares against num_rows AS OF the
        # snapshot (read under the same lock the stream holds): a
        # concurrent append landing after the stream drains must not
        # turn a perfectly consistent pre-append snapshot into an error
        with self.rw.read():
            expected = self.num_rows
            for cols, valid, _start in self._stream_unlocked():
                n = int(np.asarray(valid).sum())
                for k, v in cols.items():
                    parts.setdefault(k, []).append(np.asarray(v)[:n])
                n_done += n
        if n_done != expected:
            raise RuntimeError(f"paged set {self.name!r}: streamed "
                               f"{n_done} rows, expected {expected}")
        from netsdb_tpu.relational.stats import inject_stats

        out = ColumnTable({k: np.concatenate(v)
                           for k, v in parts.items()}, self.dicts, None)
        return inject_stats(out, self.stats)

    def to_table(self) -> ColumnTable:
        """Materialize the whole relation as one DEVICE-resident
        ColumnTable — the compatibility escape hatch (``get_table`` on
        a paged set, fold-less query fallback). Defeats paging by
        construction; the streamed path is ``stream_tables``."""
        host = self.to_host_table()
        from netsdb_tpu.relational.stats import inject_stats

        out = ColumnTable({k: jnp.asarray(v) for k, v in host.cols.items()},
                          host.dicts, None)
        return inject_stats(out, self.stats)


# ----------------------------------------------- grace-hash partitioning
_grace_ids = itertools.count()

#: Fibonacci-multiply constant (golden-ratio reciprocal in 64 bits) —
#: the splitmix64 first-stage multiplier
_KEY_MIX_MULT = np.uint64(0x9E3779B97F4A7C15)


def mix_partition_key(kv: np.ndarray) -> np.ndarray:
    """Avalanche a key column before the partition modulus (uint64).

    Bare ``key % nparts`` collapses clustered/strided key sets: keys
    sharing a factor with ``nparts`` (every ``k*nparts``-strided id
    column does) land in a handful of partitions, re-inflating the
    per-partition build table that must be device-resident — the
    grace-hash memory bound degrades toward the full build side. A
    Fibonacci multiply + xor-shift (splitmix-style finalizer) spreads
    any key structure uniformly; applied identically on BOTH the build
    and the probe side (both stream through
    :func:`partition_by_key`), so matching keys still meet in the same
    partition — the reference hash-partitions both sides the same way
    (``PipelineStage.cc`` partition stage)."""
    h = np.asarray(kv).astype(np.int64).view(np.uint64) * _KEY_MIX_MULT
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return h


def partition_by_key(pc: PagedColumns, key: str, nparts: int,
                     keep_rowid: bool = False,
                     columns: Optional[Tuple[str, ...]] = None
                     ) -> List[Optional[PagedColumns]]:
    """ONE streaming pass over ``pc``, hash-partitioning its valid rows
    by ``mix(key) % nparts`` (:func:`mix_partition_key` — both join
    sides mix identically, so clustered/strided keys keep the
    per-partition memory bound) into ``nparts`` spill relations in the
    SAME arena — the reference's partition stage writing both join
    sides through the partitioned hash-set manager
    (``src/queryExecution/source/PipelineStage.cc:1652-1728``,
    ``HashSetManager.h``). Per-partition output buffers flush to arena
    pages at the relation's row_block (bounded host memory: nparts ×
    row_block rows), so partitions spill like any other paged data.

    ``keep_rowid=True`` stores the original global ``_rowid`` as a
    ``_rowid0`` column (the partition stream renumbers ``_rowid``;
    folds that arbitrate on global row order need the original).
    Negative keys (orphans/invalid) route to partition 0, where the
    kernels' orphan-key rule drops them. Returns None for partitions
    that received no rows."""
    parts: List[Optional[PagedColumns]] = [None] * nparts
    bufs: List[Dict[str, List[np.ndarray]]] = [{} for _ in range(nparts)]
    buf_rows = [0] * nparts
    uid = next(_grace_ids)

    def flush(p: int) -> None:
        if buf_rows[p] == 0:
            return
        cols = {k: np.concatenate(v) for k, v in bufs[p].items()}
        if parts[p] is None:
            parts[p] = PagedColumns.ingest(
                pc.store, f"{pc.name}#gr{uid}p{p}", cols,
                row_block=pc.row_block, dicts=dict(pc.dicts))
        else:
            parts[p].append(cols)
        bufs[p] = {}
        buf_rows[p] = 0

    # pure HOST pass: hashing/routing never touches the device (the
    # chunks would only round-trip H2D→D2H for numpy bucketing)
    with obs.span(f"ooc.partition:{pc.name}", "storage"), \
            contextlib.closing(pc.stream(prefetch=2,
                                         device=False)) as chunks:
        for ccols, valid, start in chunks:
            n = int(np.asarray(valid).sum())
            cols = {k: v[:n] for k, v in ccols.items()
                    if columns is None or k in columns or k == key}
            if keep_rowid:
                cols["_rowid0"] = np.arange(
                    start, start + n, dtype=np.int32)
            kv = cols[key]
            pid = np.where(kv >= 0,
                           (mix_partition_key(kv)
                            % np.uint64(nparts)).astype(np.int64), 0)
            for p in np.unique(pid):
                sel = pid == p
                for name, c in cols.items():
                    bufs[p].setdefault(name, []).append(c[sel])
                buf_rows[p] += int(sel.sum())
                if buf_rows[p] >= pc.row_block:
                    flush(p)
    for p in range(nparts):
        flush(p)
    return parts


# --------------------------------------------------------- fold runner
def run_fold(fold, pc: PagedColumns, *resident, placement=None):
    """Thin standalone driver for a FoldSpec over one paged relation —
    delegates to the SAME loop the plan executor runs for paged
    ScanSets, exposed for direct use without a Client. One jit
    per pass per call; call-site loops should go through the executor,
    whose compiled-step cache amortizes across jobs."""
    from netsdb_tpu.plan.executor import _run_fold_once
    from netsdb_tpu.plan.staging import fold_donate_argnums

    donate_default = fold_donate_argnums(pc.store.config)

    def step_jit(pidx, step, donate=None):
        return jax.jit(step, donate_argnums=(
            donate_default if donate is None else donate))

    return _run_fold_once(fold, pc, resident, placement, step_jit)


# ---------------------------------------------------------------- Q01
def ooc_q01(pc: PagedColumns, delta_date: str = "1998-09-02"):
    """Q01 over a paged lineitem — same result structure as
    ``queries.cq01``. Thin wrapper: the math lives in
    ``relational.folds.fold_q01`` (the SAME fold the set-API DAG
    streams); only the host-side row decoding is local."""
    from netsdb_tpu.relational.folds import fold_q01

    n_ls = len(pc.dicts["l_linestatus"])
    n_groups = len(pc.dicts["l_returnflag"]) * n_ls
    sums, counts = jax.device_get(
        run_fold(fold_q01({}, {}, {}, delta_date=delta_date), pc))
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "sum_disc")
    out = []
    for g in range(n_groups):
        cnt = int(counts[g])
        if cnt == 0:
            continue
        key = (pc.dicts["l_returnflag"][g // n_ls],
               pc.dicts["l_linestatus"][g % n_ls])
        v = {names[i]: float(sums[i, g]) for i in range(5)}
        v["count"] = cnt
        v["avg_qty"] = v["sum_qty"] / cnt
        v["avg_price"] = v["sum_base_price"] / cnt
        v["avg_disc"] = v["sum_disc"] / cnt
        out.append((key, v))
    out.sort(key=lambda kv: kv[0])
    return out


# ---------------------------------------------------------------- Q06
def ooc_q06(pc: PagedColumns, d0: str = "1994-01-01",
            d1: str = "1995-01-01", disc: float = 0.06, qty: int = 24):
    """Q06 over a paged lineitem — same result as ``queries.cq06``.
    Thin wrapper over ``relational.folds.fold_q06``."""
    from netsdb_tpu.relational.folds import fold_q06

    (acc,) = run_fold(fold_q06({}, {}, {}, d0=d0, d1=d1, disc=disc,
                               qty=qty), pc)
    return [("revenue", float(acc))]


# ---------------------------------------------- Q03: out-of-core JOIN
# The reference joins out of core by making the hash table itself a
# partitioned, spillable object: build stages write a PartitionedHashSet
# through HashSetManager, probe stages stream pages against it
# (``src/queryExecution/headers/HashSetManager.h``,
# ``HermesExecutionServer.cc:901``). The columnar equivalent here:
#
# - BUILD: customer ⋈ orders collapses to a dense per-orderkey LUT
#   [qualifies, o_orderdate, o_shippriority], paged into the SAME
#   spillable store as the data (row_block = partition size, so
#   partition p is exactly block p — resident only while probed).
# - PROBE/MERGE: ``ooc_q03`` is now a thin wrapper over the SAME
#   grace-hash machinery the set-API DAG uses for a paged build side
#   (``relational.dag.q03_probe_fold`` — outer loop over build blocks,
#   inner fold over the lineitem stream, per-partition top-k merged).

def build_q03_side(store: PagedTensorStore,
                   orders: Dict[str, np.ndarray],
                   customer: Dict[str, np.ndarray],
                   segment_code: int, date_int: int,
                   key_cap: int, name: str = "q03.build") -> int:
    """Build the resident side of the Q03 join: filter customers by
    segment, join to orders (host-side build, the small tables), and
    page the per-orderkey LUT into ``store`` partitioned by key range.
    Returns the number of partitions."""
    c_key = np.asarray(customer["c_custkey"])
    c_ok = np.asarray(customer["c_mktsegment"]) == segment_code
    cust_lut = np.zeros(int(c_key.max()) + 1, np.bool_)
    cust_lut[c_key] = c_ok

    o_key = np.asarray(orders["o_orderkey"])
    o_cust = np.asarray(orders["o_custkey"])
    o_date = np.asarray(orders["o_orderdate"])
    o_prio = np.asarray(orders["o_shippriority"])
    o_ok = (o_date < date_int) & cust_lut[o_cust]

    n_keys = int(o_key.max()) + 1
    build = np.zeros((n_keys, 3), np.int32)
    build[o_key, 0] = o_ok
    build[o_key, 1] = o_date
    build[o_key, 2] = o_prio
    store.put(name, build, row_block=key_cap)
    return store.num_blocks(name)


def ooc_q03(pc: PagedColumns, store: PagedTensorStore,
            date: str = "1995-03-15", k: int = 10,
            build_name: str = "q03.build") -> List[Dict[str, object]]:
    """Q03 with lineitem streamed from pages and the join LUT loaded one
    partition at a time — same result structure as ``queries.cq03``.
    Peak device state: one partition's build columns + one per-row
    revenue accumulator + one page of probe columns, independent of
    table or key-space size.

    Thin wrapper: each LUT block becomes a build-side ColumnTable
    (non-qualifying keys → -1, dropped by the orphan-key rule) and the
    grace-hash loop runs the SAME fold + merge the set-API DAG uses for
    a paged build side (``relational.dag.q03_probe_fold``). This
    driver keeps the LEGACY per-block discipline (full probe re-stream
    per LUT block — its build lives in a raw block store, not a
    relation); the canonical ONE-PASS grace hash is the set-API path
    (``q03_build_sink``/``q03_probe_sink``, both sides
    hash-partitioned, probe pages read once)."""
    from netsdb_tpu.relational.dag import q03_probe_fold, q03_rows
    from netsdb_tpu.relational.planner import JoinPlan

    if "l_orderkey" not in pc.stats:
        raise KeyError(
            "ooc_q03 needs ingest-time stats for 'l_orderkey' (the join "
            "key-space bound); this PagedColumns has none — re-ingest "
            "via PagedColumns.ingest/from_table")
    ks = pc.stats["l_orderkey"].key_space
    fold = q03_probe_fold(date_to_int(date), k, JoinPlan("lut", max(ks, 1)))
    jstep = jax.jit(fold.passes[0][1])
    out = None
    for p in range(store.num_blocks(build_name)):
        start, bmat = store.read_block(build_name, p)
        keys = np.where(bmat[:, 0] > 0,
                        np.arange(bmat.shape[0], dtype=np.int32) + start,
                        -1).astype(np.int32)
        btab = ColumnTable({"o_orderkey": jnp.asarray(keys),
                            "o_orderdate": jnp.asarray(bmat[:, 1])})
        state = fold.passes[0][0](None, pc, btab)
        with contextlib.closing(pc.stream_tables()) as chunks:
            for chunk in chunks:
                state = jstep(state, chunk, btab)
        part = fold.finalize(state, pc, btab)
        out = part if out is None else fold.merge(out, part)
    return q03_rows(out) if out is not None else []


Q01_COLUMNS = ["l_shipdate", "l_returnflag", "l_linestatus",
               "l_quantity", "l_extendedprice", "l_discount", "l_tax"]
Q06_COLUMNS = ["l_shipdate", "l_discount", "l_quantity",
               "l_extendedprice"]
Q03_COLUMNS = ["l_orderkey", "l_shipdate", "l_extendedprice",
               "l_discount"]
