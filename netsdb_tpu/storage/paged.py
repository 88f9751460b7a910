"""Paged tensor streaming — PageCache → pipeline feeding, TPU-shaped.

In the reference, a backend scan pins 64 MB pages one by one and feeds
them through ``PageCircularBuffer`` to the pipeline threads
(``src/storage/headers/PageScanner.h``, ``PageCircularBuffer.h``), so a
set larger than RAM streams from ``PartitionedFile`` through the
``PageCache``. Here the same role: a large matrix is stored row-block-
wise as pages in the native C++ page store (``native/pagestore.cpp``) —
which caches hot pages in its arena and spills cold ones — and is
streamed block-by-block into device HBM (``jax.device_put`` per chunk),
so working sets larger than host RAM or HBM flow through without ever
materializing densely.

A failed native build is an error carrying the compiler's message;
the pure-Python page dict below exists for ``force_python=True`` only.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

import numpy as np

from netsdb_tpu.config import Configuration, DEFAULT_CONFIG
from netsdb_tpu.utils.locks import TrackedLock


class _PyPageBackend:
    """In-process backend with the same surface as NativePageStore
    (explicit ``force_python=True`` only — no spill, no arena cap).

    Thread-safe like the native store (its C++ side is mutex-guarded):
    concurrent writers — two object-set appends no longer serialized by
    the store-wide lock — must not race the page-id allocation or the
    per-set page lists."""

    def __init__(self):
        self._mu = TrackedLock("_PyPageBackend._mu")
        self._pages: Dict[int, bytes] = {}
        self._sets: Dict[int, list] = {}
        self._next = 1

    def create_set(self, set_id, policy="lru"):
        with self._mu:
            self._sets.setdefault(set_id, [])

    def write_page(self, set_id, payload) -> int:
        data = payload if isinstance(payload, bytes) else \
            np.ascontiguousarray(payload).tobytes()
        with self._mu:
            pid = self._next
            self._next += 1
            self._pages[pid] = data
            self._sets[set_id].append(pid)
        return pid

    def read_page(self, page_id) -> bytes:
        with self._mu:
            return self._pages[page_id]

    def free_page(self, page_id) -> None:
        with self._mu:
            self._pages.pop(page_id, None)
            for pages in self._sets.values():
                if page_id in pages:
                    pages.remove(page_id)

    def overwrite_page(self, page_id, payload) -> None:
        """Replace one page's bytes IN PLACE (same size — the
        update-a-column-in-its-page path; a size change would shift
        every derived block layout)."""
        data = payload if isinstance(payload, bytes) else \
            np.ascontiguousarray(payload).tobytes()
        with self._mu:
            old = self._pages.get(page_id)
            if old is None:
                raise KeyError(f"unknown page {page_id}")
            if len(old) != len(data):
                raise ValueError(
                    f"overwrite_page: size change {len(old)} -> "
                    f"{len(data)} not allowed")
            self._pages[page_id] = data

    def set_pages(self, set_id):
        with self._mu:
            return list(self._sets[set_id])

    def page_size(self, page_id) -> int:
        with self._mu:
            return len(self._pages[page_id])

    def flush_set(self, set_id):
        pass

    def stats(self):
        with self._mu:
            nbytes = sum(len(v) for v in self._pages.values())
        return {"hits": 0, "misses": 0, "evictions": 0, "spills": 0,
                "loads": 0, "bytes_allocated": nbytes,
                "bytes_in_use": nbytes}

    def close(self):
        pass


class PagedTensor:
    """Streaming read handle for a matrix living as arena pages — the
    value a ``ScanSet`` of a paged TENSOR set produces in the executor.

    Never materializes: consumers stream row blocks (the reference's
    FFMatrixBlockScanner feeding weight pages into the inference
    pipeline, ``src/FF/headers/FFMatrixBlockScanner.h`` +
    ``src/storage/headers/PageScanner.h:25-34``). ``rw`` is the owning
    set item's stream-vs-mutation lock; ``placement`` the owning set's
    declared distribution (applied per block by the executor).
    """

    def __init__(self, store: "PagedTensorStore", name: str,
                 rw=None, placement=None):
        from netsdb_tpu.utils.locks import RWLock

        self.store = store
        self.name = name
        self.rw = rw if rw is not None else RWLock()
        self.placement = placement
        # device-cache binding (set by SetStore.paged_tensor for
        # store-owned handles): scope = (ident str, write version) —
        # the executor's tensor stream keys cached runs on it, and
        # cache_version_fn re-checks currentness at install time
        self.devcache = None
        self.cache_scope = None
        self.cache_version_fn = None

    @property
    def shape(self) -> Tuple[int, int]:
        return self.store.meta(self.name)[0]

    @property
    def dtype(self) -> np.dtype:
        return self.store.meta(self.name)[2]

    def num_blocks(self) -> int:
        return self.store.num_blocks(self.name)

    def stream_blocks(self, prefetch: Optional[int] = None,
                      blocks: Optional[list] = None
                      ) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield (start_row, block) holding the read lock for the
        generator's lifetime (a concurrent drop/replace must not free
        pages mid-stream); consumers should close() abandoned streams.
        ``prefetch=None`` takes the ``config.stream_prefetch_pages``
        read-ahead knob; ``blocks`` restricts to those page indices
        (the stitched gap feed — see ``PagedTensorStore.stream_blocks``)."""
        with self.rw.read():
            yield from self.store.stream_blocks(self.name, prefetch,
                                                blocks=blocks)

    def block_ranges(self) -> list:
        """[(start_row, end_row)] per page block, metadata only."""
        return self.store.block_ranges(self.name)


class PagedObjects:
    """Arbitrary host records paged as PICKLED BATCHES in the shared
    arena — the reference's pages hold arbitrary ``pdb::Object``s
    (``src/storage/headers/PDBPage.h:17-33``), so record workloads
    (reddit-style Filter/Join/Aggregate over Python objects) are
    out-of-core for free there; this is the TPU-native equivalent for
    the EAGER interpreter path. Iterating the handle streams records
    page by page (pin one batch, yield, move on), so the eager
    Filter/Join/Aggregate nodes consume it unchanged.

    Batches target ~the configured page size of pickled payload; the
    arena caps/spills these pages exactly like column pages.
    """

    def __init__(self, store: "PagedTensorStore", name: str,
                 num_items: int = 0):
        from netsdb_tpu.utils.locks import RWLock

        self.store = store
        self.name = name
        self.num_items = num_items
        self.rw = RWLock(name="PagedObjects.rw")
        # serializes concurrent appends against each other; appends
        # hold rw.READ (not write — see append()) so they never wait
        # for in-flight record streams to drain. Store-routed appends
        # additionally hold the set's ``_StoredSet.append_mu`` — that
        # one orders appends against the store's OTHER per-set
        # mutations; this one is the handle's own guarantee, so a
        # direct ``po.append`` (no store in sight) is still safe.
        self._append_mu = TrackedLock("PagedObjects._append_mu")
        self.dropped = False
        store.backend.create_set(store._set_id(name))

    @staticmethod
    def ingest(store: "PagedTensorStore", name: str,
               items: list) -> "PagedObjects":
        po = PagedObjects(store, name)
        po.append(items)
        return po

    def append(self, items: list) -> None:
        """Write records as additional pickled-batch pages (the
        reference's addData continuously appending objects).

        LOCKING (advisor round 5): appends only ADD pages — they never
        touch pages a live record stream is reading (``__iter__``
        snapshots the page list at its start, and freeing pages is
        ``drop``'s job, which does take the write lock). So append
        holds the relation's READ lock (drop exclusion only) plus a
        per-handle append mutex (order among concurrent appenders),
        NOT the write lock: it never waits for in-flight streams to
        drain — a slow wire scan cannot stall ingest, and a consumer
        appending while iterating the same set no longer
        self-deadlocks (its own read lock would make ``rw.write()``
        wait forever). A reader that starts mid-append may observe a
        prefix of the batch's pages — the same visibility a reader
        starting between two appends always had."""
        import io
        import pickle

        if not items:
            return
        with self._append_mu, self.rw.read():
            if self.dropped:
                raise KeyError(f"paged object set {self.name!r} was "
                               f"dropped; cannot append")
            sid = self.store._set_id(self.name)
            target = max(self.store.config.page_size_bytes, 4096)
            # page packing tracks CUMULATIVE PICKLED BYTES as the batch
            # fills: an incremental Pickler measures each record on
            # append, and the batch flushes the moment the measured
            # payload reaches the page target. The old scheme sized the
            # FIRST batch from a 256-byte seed estimate with an
            # 8-record floor, so eight multi-MB records landed on one
            # page — transiently blowing the arena far past
            # page_size_bytes before the estimate could adapt (ADVICE
            # round 5). The measuring stream shares the batch's object
            # graph, so its tell() tracks the real page size; each
            # flushed page stays ONE pickled list — the format
            # ``__iter__`` and the resync replay expect.
            batch: list = []
            buf = io.BytesIO()
            measurer = pickle.Pickler(buf,
                                      protocol=pickle.HIGHEST_PROTOCOL)

            def flush():
                nonlocal buf, measurer
                if not batch:
                    return
                self.store.backend.write_page(
                    sid, pickle.dumps(batch,
                                      protocol=pickle.HIGHEST_PROTOCOL))
                batch.clear()
                buf = io.BytesIO()
                measurer = pickle.Pickler(
                    buf, protocol=pickle.HIGHEST_PROTOCOL)

            for it in items:
                batch.append(it)
                try:
                    measurer.dump(it)
                    full = buf.tell() >= target
                except Exception:  # noqa: BLE001 — an unpicklable
                    # record must surface on the REAL dumps in flush()
                    # with the whole batch's context, not here
                    full = True
                if full:
                    flush()
            flush()
            self.num_items += len(items)

    def __iter__(self):
        """Stream records page by page under the read lock — the
        PageScanner feed for the eager interpreter."""
        import pickle

        with self.rw.read():
            if self.dropped:
                raise KeyError(f"paged object set {self.name!r} was "
                               f"dropped; cannot stream")
            sid = self.store._set_id(self.name)
            for pid in self.store.backend.set_pages(sid):
                yield from pickle.loads(self.store.backend.read_page(pid))

    def __len__(self) -> int:
        return self.num_items

    def to_list(self) -> list:
        return list(self)

    def drop(self) -> None:
        with self.rw.write():
            self.dropped = True
            sid = self.store._ids.pop(self.name, None)
            if sid is None:
                return
            for pid in self.store.backend.set_pages(sid):
                self.store.backend.free_page(pid)


class PagedTensorStore:
    """Row-block paged storage for large matrices."""

    def __init__(self, config: Configuration = DEFAULT_CONFIG,
                 pool_bytes: Optional[int] = None,
                 force_python: bool = False):
        self.config = config
        config.ensure_dirs()
        self._meta: Dict[int, Tuple[Tuple[int, int], Tuple[int, int], np.dtype]] = {}
        self._ids: Dict[str, int] = {}
        self._next_sid = 1
        # per-set (block_rows, block_starts) cache — derived from page
        # sizes once and reused, so read_block/stream starts stay O(1)
        # per call instead of O(pages); invalidated on put/append/drop
        self._layout: Dict[int, Tuple[list, list]] = {}
        # live prefetch reader threads: must be joined before the
        # backend is destroyed (a reader mid-read_page on a freed C++
        # arena is a use-after-free); mutations happen under _readers_lock
        # so concurrent streams can't interleave the prune/append and
        # drop a tracked reader
        self._readers: list = []
        self._readers_lock = TrackedLock("PagedTensorStore._readers_lock")
        self._closed = False
        if force_python:
            self.backend = _PyPageBackend()
            self.native = False
        else:
            from netsdb_tpu.native.pagestore import NativePageStore

            self.backend = NativePageStore(
                pool_bytes or config.shared_mem_bytes,
                os.path.join(config.data_dir, "pages"),
            )
            self.native = True

    def _set_id(self, name: str) -> int:
        # MONOTONIC allocation: len()+1 would recycle the id of a live
        # set after any drop() popped an entry, intermixing two sets'
        # pages (r5 review finding — reproduced as cross-set
        # corruption via the PagedObjects drop/re-ingest lifecycle)
        if name not in self._ids:
            self._ids[name] = self._next_sid
            self._next_sid += 1
        return self._ids[name]

    def put(self, name: str, dense: np.ndarray,
            row_block: Optional[int] = None,
            append: bool = False) -> None:
        """Page a matrix in as contiguous row-blocks. ``append=True``
        writes the batch as ADDITIONAL pages after the existing ones
        (the reference's addData appending pages to a set): blocks may
        then be ragged mid-stream (each batch's tail is short), which
        every reader handles by deriving per-page row counts from the
        actual page sizes (``_block_rows``)."""
        dense = np.ascontiguousarray(dense)
        if dense.ndim != 2:
            raise ValueError(f"paged store holds matrices; got rank-{dense.ndim} "
                             f"array of shape {dense.shape}")
        rows, cols = dense.shape
        if append and name in self._ids:
            sid = self._ids[name]
            (orows, ocols), (rb, _), dtype = self._meta[sid]
            if ocols != cols or dtype != dense.dtype:
                raise ValueError(
                    f"append to {name!r}: schema mismatch "
                    f"({ocols} cols/{dtype} vs {cols} cols/{dense.dtype})")
            for r0 in range(0, rows, rb):
                self.backend.write_page(sid, dense[r0:r0 + rb])
            self._meta[sid] = ((orows + rows, cols), (rb, cols), dtype)
            self._layout.pop(sid, None)
            return
        row_block = row_block or max(
            1, self.config.page_size_bytes // max(dense.dtype.itemsize * cols, 1))
        replacing = name in self._ids
        sid = self._set_id(name)
        self.backend.create_set(sid)
        if replacing:  # drop the old pages, else reads mix stale data
            for pid in self.backend.set_pages(sid):
                self.backend.free_page(pid)
        for r0 in range(0, rows, row_block):
            self.backend.write_page(sid, dense[r0:r0 + row_block])
        self._meta[sid] = ((rows, cols), (row_block, cols), dense.dtype)
        self._layout.pop(sid, None)

    def truncate_to(self, name: str, n_pages: int, rows: int) -> None:
        """Roll a set back to its first ``n_pages`` pages / ``rows``
        rows — the append-failure undo (frees the partially written
        pages so a failed batch cannot desynchronize co-paged
        matrices)."""
        sid = self._ids.get(name)
        if sid is None:
            return
        for pid in self.backend.set_pages(sid)[n_pages:]:
            self.backend.free_page(pid)
        (_, cols), (rb, _), dtype = self._meta[sid]
        self._meta[sid] = ((rows, cols), (rb, cols), dtype)
        self._layout.pop(sid, None)

    def _block_layout(self, sid: int) -> Tuple[list, list]:
        """(per-page row counts, per-page start rows), derived from
        ACTUAL page sizes (metadata-only backend calls) — correct for
        ragged appended streams, where start = index * row_block would
        lie. Cached per set (O(pages) once, O(1) per access)."""
        cached = self._layout.get(sid)
        if cached is not None:
            return cached
        import itertools

        (rows, cols), _, dtype = self._meta[sid]
        width = max(dtype.itemsize * cols, 1)
        ns = [self.backend.page_size(pid) // width
              for pid in self.backend.set_pages(sid)]
        starts = list(itertools.accumulate([0] + ns[:-1]))
        self._layout[sid] = (ns, starts)
        return ns, starts

    def meta(self, name: str) -> Tuple[Tuple[int, int], Tuple[int, int],
                                       np.dtype]:
        """((rows, cols), (row_block, cols), dtype) of a stored matrix
        — the public face of the per-set metadata (PagedTensor and the
        serve layer read shape/dtype through this, never the private
        maps)."""
        return self._meta[self._ids[name]]

    def read_block(self, name: str, index: int) -> Tuple[int, np.ndarray]:
        """Random access to one row-block: (start_row, block). The
        pin-one-partition access pattern of a partitioned hash table
        (ref ``src/queryExecution/headers/HashSetManager.h`` /
        PartitionedHashSet) — a build side stored with
        ``row_block=partition_rows`` makes partition *p* exactly block
        *p*, resident only while probed, spillable in between."""
        sid = self._ids[name]
        (rows, cols), _, dtype = self._meta[sid]
        pids = self.backend.set_pages(sid)
        if not 0 <= index < len(pids):
            raise IndexError(f"block {index} out of range "
                             f"({len(pids)} blocks in {name!r})")
        ns, starts = self._block_layout(sid)
        raw = self.backend.read_page(pids[index])
        return starts[index], np.frombuffer(raw, dtype=dtype).reshape(
            ns[index], cols)

    def rewrite_block(self, name: str, index: int,
                      block: np.ndarray) -> None:
        """Overwrite one row-block IN PLACE (same shape — the
        update-in-place write path: a column update rewrites each page
        it lives in without moving any other page). The block layout
        is unchanged by construction, so derived metadata stays
        valid."""
        sid = self._ids[name]
        (_rows, cols), _, dtype = self._meta[sid]
        pids = self.backend.set_pages(sid)
        if not 0 <= index < len(pids):
            raise IndexError(f"block {index} out of range "
                             f"({len(pids)} blocks in {name!r})")
        ns, _starts = self._block_layout(sid)
        block = np.ascontiguousarray(block, dtype=dtype)
        if block.shape != (ns[index], cols):
            raise ValueError(
                f"rewrite_block: block {index} of {name!r} is "
                f"{(ns[index], cols)}, got {block.shape} — in-place "
                f"rewrites must preserve the block's shape")
        self.backend.overwrite_page(pids[index], block.tobytes())

    def num_blocks(self, name: str) -> int:
        return len(self.backend.set_pages(self._ids[name]))

    def block_ranges(self, name: str) -> list:
        """[(start_row, end_row)] per block, METADATA ONLY (derived
        from page sizes — zero page-data reads). The partial-run
        device cache plans its range stitching against this: each
        streamed chunk's identity is its row range."""
        sid = self._ids[name]
        ns, starts = self._block_layout(sid)
        return [(s, s + n) for s, n in zip(starts, ns)]

    def stream_blocks(self, name: str,
                      prefetch: Optional[int] = None,
                      blocks: Optional[list] = None
                      ) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield (start_row, block) in order — the PageScanner loop.

        ``prefetch`` pages are read ahead on a background thread (the
        reference's PageCircularBuffer between its scan thread and the
        pipeline threads — ``src/storage/headers/PageCircularBuffer.h``)
        so disk/arena reads overlap the consumer's compute; 0 disables,
        None takes the ``config.stream_prefetch_pages`` knob.

        ``blocks`` (sorted block indices) restricts the stream to just
        those pages — the GAP feed of a range-stitched cached stream
        (``plan/staging``): pages whose chunks are already device-
        resident are never read from the arena at all.
        """
        if prefetch is None:
            prefetch = getattr(self.config, "stream_prefetch_pages", 2)
        sid = self._ids[name]
        (rows, cols), _, dtype = self._meta[sid]
        pids = self.backend.set_pages(sid)
        _, starts = self._block_layout(sid)
        if blocks is not None:
            pids = [pids[i] for i in blocks]
            starts = [starts[i] for i in blocks]

        def view(raw, start):
            n = len(raw) // max(dtype.itemsize * cols, 1)
            return np.frombuffer(raw, dtype=dtype).reshape(n, cols)

        if prefetch <= 0 or len(pids) <= 1:
            for pid, start in zip(pids, starts):
                yield start, view(self.backend.read_page(pid), start)
            return

        import queue

        q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        SENTINEL = object()
        stop = threading.Event()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def reader():
            try:
                for pid, start in zip(pids, starts):
                    if not put((start, self.backend.read_page(pid))):
                        return  # consumer abandoned the stream
            except BaseException as e:  # ANY death must unblock the consumer
                put((SENTINEL, e))
                return
            put((SENTINEL, None))

        t = threading.Thread(target=reader, daemon=True)
        with self._readers_lock:
            if self._closed:  # backend may already be freed
                raise RuntimeError("PagedTensorStore is closed")
            self._readers[:] = [(rt, rs) for rt, rs in self._readers
                                if rt.is_alive()]
            self._readers.append((t, stop))
        t.start()
        try:
            while True:
                try:
                    start, raw = q.get(timeout=0.5)
                except queue.Empty:
                    if not t.is_alive():  # died without a sentinel
                        raise RuntimeError("page reader thread died")
                    continue
                if start is SENTINEL:
                    if raw is not None:
                        raise raw
                    break
                yield start, view(raw, start)
        finally:
            stop.set()
            t.join(timeout=5)

    def to_device_blocked(self, name: str, block_shape=None):
        """Stream into HBM chunk-by-chunk and assemble a BlockedTensor —
        the dense array never exists on host; uploads run a staging
        depth ahead of the assembly (``plan/staging``)."""
        import contextlib

        import jax.numpy as jnp

        from netsdb_tpu.core.blocked import BlockMeta, BlockedTensor
        from netsdb_tpu.plan.staging import stage_stream
        from netsdb_tpu.storage.devcache import to_device

        sid = self._ids[name]
        (rows, cols), _, dtype = self._meta[sid]
        block_shape = block_shape or self.config.default_block_shape
        meta = BlockMeta((rows, cols), tuple(block_shape))
        chunks = []
        with contextlib.closing(stage_stream(
                self.stream_blocks(name),
                lambda item: to_device(item[1]),
                depth=getattr(self.config, "stage_depth", 2),
                name=f"blocked:{name}")) as staged:
            for chunk in staged:
                chunks.append(chunk)
        data = jnp.concatenate(chunks, axis=0)
        pad = [(0, p - s) for s, p in zip((rows, cols), meta.padded_shape)]
        if any(p for _, p in pad):
            data = jnp.pad(data, pad)
        return BlockedTensor(data, meta)

    def matmul_streamed(self, name: str, rhs: np.ndarray,
                        stage_depth: Optional[int] = None,
                        devcache=None,
                        cache_scope: Optional[str] = None,
                        stats_out: Optional[Dict[str, Any]] = None
                        ) -> np.ndarray:
        """out = M @ rhs with M streamed page-by-page through the device
        — the larger-than-HBM compute pattern (reference: pipelines over
        pinned pages). Only one page + rhs (plus the staged NEXT page)
        live on device at a time: the upload of block *i+1* runs on the
        staging thread while block *i*'s matmul computes
        (``plan/staging.stage_stream``), and ragged blocks pad up to
        the row-block's shape bucket (zero rows, output rows sliced
        back off — exact) so the whole stream runs ONE compiled
        program. ``stage_depth`` pins the staging depth (None = the
        ``config.stage_depth`` knob; 0 = synchronous).

        With ``config.distributed_matmul`` on and >1 device visible,
        the stream routes through the SUMMA engine instead
        (``parallel/summa.py``): each mesh participant stages only its
        own panel of M and rhs, per-step panel broadcasts move B over
        the mesh axis, and per-host staged bytes drop to ~1/N.
        ``devcache``/``cache_scope`` (store-owned sets pass them) opt
        the SUMMA panels into the block-granular device cache under
        the mesh-labelled key."""
        import contextlib

        import jax
        import jax.numpy as jnp

        from netsdb_tpu.plan.staging import pad_rows_target, stage_stream
        from netsdb_tpu.storage.devcache import to_device

        if getattr(self.config, "distributed_matmul", False):
            from netsdb_tpu.parallel import summa

            devices = jax.devices()
            cap = getattr(self.config, "summa_participants", None)
            if cap:
                devices = devices[:int(cap)]
            grid = summa.grid_shape(self.config, len(devices))
            if grid is not None:
                return summa.summa_grid_matmul_streamed(
                    self, name, rhs, devices=devices, grid=grid,
                    stage_depth=stage_depth, cache=devcache,
                    cache_scope=cache_scope, stats_out=stats_out)
            if len(devices) >= 2:
                return summa.summa_matmul_streamed(
                    self, name, rhs, devices=devices,
                    stage_depth=stage_depth, cache=devcache,
                    cache_scope=cache_scope, stats_out=stats_out)

        depth = getattr(self.config, "stage_depth", 2) \
            if stage_depth is None else stage_depth
        bucketing = getattr(self.config, "shape_bucketing", True)
        density = getattr(self.config, "bucket_density", 2)
        rb = self._meta[self._ids[name]][1][0]
        rhs_dev = to_device(rhs)

        @jax.jit
        def block_mm(a, b):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)

        def place(item):
            _start, block = item
            n = block.shape[0]
            target = pad_rows_target(max(n, rb), bucketing,
                                     density=density)
            if target > n:
                block = np.pad(block, ((0, target - n), (0, 0)))
            return n, to_device(block)

        outs = []
        with contextlib.closing(stage_stream(
                self.stream_blocks(name), place, depth,
                name=f"mm:{name}")) as staged:
            for n, block in staged:
                out = np.asarray(block_mm(block, rhs_dev))
                outs.append(out[:n] if out.shape[0] != n else out)
        return np.concatenate(outs, axis=0)

    def drop(self, name: str) -> None:
        """Free a matrix's pages from the arena (and its spill files) —
        the page-reclaim hook ``SetStore.remove_set`` uses so dropping
        a paged set returns its space to the shared capped pool."""
        sid = self._ids.pop(name, None)
        if sid is None:
            return
        for pid in self.backend.set_pages(sid):
            self.backend.free_page(pid)
        self._meta.pop(sid, None)
        self._layout.pop(sid, None)

    def stats(self) -> dict:
        return self.backend.stats()

    def close(self):
        # stop + join any live prefetch readers BEFORE freeing the
        # native arena they may be reading from
        with self._readers_lock:
            self._closed = True  # no new readers may register after this
            readers = list(self._readers)
            self._readers.clear()
        for t, stop in readers:
            stop.set()
        for t, stop in readers:
            t.join(timeout=30)
        still_alive = [t for t, _ in readers if t.is_alive()]
        if still_alive or getattr(self, "_leaked", False):
            # a reader wedged inside read_page (hung IO): destroying the
            # arena under it is a use-after-free — leak the backend
            # instead (process exit reclaims it). The flag makes later
            # close() calls keep leaking rather than free it after all.
            self._leaked = True
            import warnings

            warnings.warn(
                f"PagedTensorStore.close: {len(still_alive)} prefetch "
                f"reader(s) did not stop; leaking the page store to "
                f"avoid freeing memory they may still touch")
            return
        self.backend.close()
