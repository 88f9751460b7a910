"""Overlapped device staging — the PageCircularBuffer for HBM uploads.

The reference overlaps page IO with pipeline compute by putting a
bounded ring buffer between the scan thread and the worker threads
(``src/storage/headers/PageCircularBuffer.h``): the scan thread pins
the NEXT page while the workers chew on the current one.  Our port had
that for the HOST read stage (``PagedTensorStore.stream_blocks``
prefetch readers) but not for the DEVICE stage: every out-of-core
consumer ran ``jax.device_put`` synchronously per chunk, so the
accelerator idled through every host→device copy.  On TPU-class
hardware hiding transfer latency dominates out-of-core throughput
(arxiv 2112.09017 §IV; arxiv 2301.13062) — this module is that hiding
layer.

:func:`stage_stream` wraps any host-side chunk iterator with a bounded
double buffer: a background thread runs the caller's ``place`` function
(pad + ``jax.device_put`` with the target sharding) ``depth`` items
ahead of the consumer, so the next block lands in HBM while the current
fold step computes.  The pipeline is therefore three stages deep
end-to-end::

    arena/disk --(prefetch readers)--> host chunk --(staging thread,
    place: pad+device_put)--> HBM block --(consumer)--> fold step

Discipline matches ``stream_blocks`` (the template this generalizes):

- the staging thread OWNS the source iterator: it is advanced and
  closed there, so read locks held by source generators are acquired
  and released on one thread and an abandoned consumer can never leak
  a lock until GC;
- any death of the staging thread (source raised, ``place`` raised)
  re-raises AT THE CONSUMER, never swallowed;
- ``close()`` (idempotent, also via ``contextlib.closing`` /
  ``__del__``) stops the thread, drains the queue and joins — the
  ``active_count``/``active_stagers`` registry exists so tests can
  assert no thread outlives its stream.

Shape-bucketed compilation rides the same module: :func:`bucket_rows`
rounds ragged row counts up to a small fixed set of bucket sizes
(powers of two and 1.5× powers of two — <50% pad waste worst case,
~20% typical), so a stream
with a ragged tail — or repeated serve ``EXECUTE``s over different row
counts — compiles once per bucket instead of once per distinct shape.
Padded rows ride the validity mask exactly like the pad-and-mask idiom
in ``parallel/placement.py`` (masks, not garbage rows).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

from netsdb_tpu import obs

# ---------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------

#: no bucket below this many rows — tiny chunks all share one shape
BUCKET_FLOOR = 8


def bucket_rows(n: int, density: int = 2) -> int:
    """Smallest bucket ≥ ``n`` from the fixed ladder. ``density`` is
    the ``config.bucket_density`` knob — buckets per octave:

    * ``2`` (default): {2^k, 3·2^(k-1)} (…, 8, 12, 16, 24, 32, 48, 64,
      96, 128, …) — padding <50% worst case (~20% typical). Buckets
      ≥ 16 are multiples of 8, so mesh-sharded chunks usually divide
      their shard count without a second padding round.
    * ``4``: 2^(k-1)·{1.25, 1.5, 1.75} plus 2^k — padding <25% worst
      case at twice the compile count (one XLA program per bucket).

    Every distinct row count inside a bucket's span compiles to the
    SAME XLA program either way."""
    if density not in (2, 4):
        # a typo'd knob silently behaving as the default would fragment
        # device-cache keys for no behavioral difference
        raise ValueError(f"bucket_density must be 2 or 4, got {density!r}")
    if n <= BUCKET_FLOOR:
        return BUCKET_FLOOR
    p = 1 << (n - 1).bit_length()  # next power of two ≥ n
    if density >= 4:
        for mul in (10, 12, 14):   # (p/2)·{1.25, 1.5, 1.75} = p·mul/16
            c = (p * mul) // 16
            if c >= n:
                return c
        return p
    half = (3 * p) // 4            # the 1.5× step below it
    return half if half >= n else p


def pad_rows_target(n: int, bucketing: bool, multiple: int = 1,
                    density: int = 2) -> int:
    """Row count a chunk of ``n`` valid rows pads to: its bucket when
    ``bucketing`` (``density`` = the config's buckets-per-octave knob),
    else ``n`` itself; then rounded up to ``multiple`` (a placement's
    shard granularity) so placed chunks shard without a second padding
    round."""
    target = bucket_rows(n, density) if bucketing else n
    if multiple > 1:
        target += (-target) % multiple
    return target


# ---------------------------------------------------------------------
# fold-buffer donation
# ---------------------------------------------------------------------

def fold_donate_argnums(config=None) -> tuple:
    """``(0,)`` when fold-step accumulators should be donated to XLA
    (``donate_argnums``), else ``()``.  Donating argument 0 — the
    carried state of ``step(state, chunk, *resident)`` — lets XLA
    update the per-stream accumulator in place instead of allocating a
    fresh HBM buffer every block (the state is dead after each step by
    construction: the loop immediately rebinds it).

    ``config.donate_fold_buffers``: True/False pins it; None (default)
    auto-enables only on backends that implement donation (TPU/GPU) —
    CPU ignores donation with a per-compile warning, so tier-1 CPU runs
    stay quiet.  Folds whose ``init`` returns a RESIDENT input array as
    part of the state must pin this off (donation would invalidate the
    resident for later steps)."""
    flag = getattr(config, "donate_fold_buffers", None)
    if flag is None:
        import jax

        flag = jax.default_backend() in ("tpu", "gpu")
    return (0,) if flag else ()


# ---------------------------------------------------------------------
# the staged stream
# ---------------------------------------------------------------------

_SENT_END = "end"
_SENT_ERR = "err"
_SENT_ITEM = "item"

# live staging threads — the leak registry tests assert on (the staging
# analogue of PagedTensorStore._readers). Guarded by _stagers_lock.
_stagers: list = []
_stagers_lock = threading.Lock()


def active_count() -> int:
    """Number of staging threads still alive (dead ones are pruned) —
    must be 0 once every stream is consumed or closed."""
    with _stagers_lock:
        _stagers[:] = [t for t in _stagers if t.is_alive()]
        return len(_stagers)


# the leak registry, absorbed into the central metrics snapshot (the
# accessor above keeps its callers; COLLECT_STATS "metrics" reports
# the same number under "staging")
obs.REGISTRY.register_collector(
    "staging", lambda: {"active_stagers": active_count()})


# --- event trace (tests only; production pays one bool check) ---------
# A flat ordered log of staging milestones: ("place", name, seq) when a
# stream's Nth item finishes placing (i.e. its upload completed),
# ("end", name) when a stream's source exhausts, ("close", name) when
# the consumer closes it, ("cache_hit", name) when a run is served from
# the device cache. The grace-hash overlap test asserts on the ORDER:
# pair i+1's build "place" must precede pair i's probe "close".
_events: list = []
_events_on = False
_events_lock = threading.Lock()


def trace_events(on: bool) -> None:
    """Enable/disable the staging event log (clearing it either way)."""
    global _events_on
    with _events_lock:
        _events.clear()
        _events_on = bool(on)


def events() -> list:
    """Snapshot of the event log in emission order."""
    with _events_lock:
        return list(_events)


def _emit(kind: str, name: str, seq: Optional[int] = None) -> None:
    if not _events_on:
        return
    with _events_lock:
        if _events_on:
            _events.append((kind, name, seq))


def _stage_put(q: "queue.Queue", stop: threading.Event, item) -> bool:
    """Bounded put that gives up when the consumer closed the stream
    (same pattern as ``stream_blocks``'s reader)."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _stage_worker(source, place, q: "queue.Queue",
                  stop: threading.Event, name: str,
                  on_complete=None, want_nbytes: bool = False) -> None:
    """The staging thread body. DELIBERATELY a free function over
    explicit state, never a bound method: the Thread must not hold a
    reference to the StagedStream, or an abandoned stream could never
    be garbage-collected (its own worker would keep it alive) and the
    worker would spin in ``put`` until process exit.

    ``on_complete`` fires only on NATURAL source exhaustion (never on
    error or abandonment) — the device-cache install hook: only a FULL
    run may be installed, a truncated one never.

    ``want_nbytes``: byte-size each placed chunk HERE (shipped to the
    consumer alongside it) — device-array metadata reads cost ~4µs per
    XLA property, so a multi-column chunk is tens of µs to measure;
    on this thread the cost overlaps the consumer's compute instead of
    stalling it (the accounting the trace/attribution paths need)."""
    from netsdb_tpu.storage.devcache import _value_nbytes

    seq = 0
    try:
        try:
            for item in source:
                if stop.is_set():
                    return
                placed = place(item)
                _emit("place", name, seq)
                seq += 1
                nb = _value_nbytes(placed) if want_nbytes else None
                if not _stage_put(q, stop, (_SENT_ITEM, (placed, nb))):
                    return  # consumer abandoned the stream
        finally:
            # the worker owns the source: close it HERE so read locks
            # held by source generators release on the thread that
            # acquired them, promptly, even when the consumer
            # abandoned us mid-stream
            close = getattr(source, "close", None)
            if close is not None:
                close()
    except BaseException as e:  # ANY death must surface at consumer
        _stage_put(q, stop, (_SENT_ERR, e))
        return
    _emit("end", name)
    if on_complete is not None:
        try:
            on_complete()
        except Exception:  # a failed cache install must not kill the
            pass           # stream — the run simply stays uncached
    _stage_put(q, stop, (_SENT_END, None))


class StagedStream:
    """Iterator over ``place(item)`` for each item of ``source``, with
    ``place`` running up to ``depth`` items ahead on a background
    thread.  ``depth <= 0`` degenerates to the synchronous inline path
    (no thread, no overlap, same results)."""

    def __init__(self, source: Iterable, place: Callable[[Any], Any],
                 depth: int = 2, name: str = "stage",
                 on_complete: Optional[Callable[[], None]] = None,
                 scope: Optional[str] = None):
        self._source = iter(source)
        self._place = place
        self._depth = int(depth)
        self._name = name
        self._closed = False
        self._on_complete = on_complete
        self._sync_seq = 0
        # query-scoped accounting: the trace AND the client identity
        # are captured HERE, on the consumer's thread (context vars
        # don't cross into the staging worker); the stream reports
        # COUNTERS only — cross-thread spans would misrepresent the
        # overlap this class exists for. ``scope`` is the set identity
        # ("db:set") the per-client resource ledger attributes staged
        # bytes to (None = unattributed temporaries).
        self._trace = obs.current_trace()
        self._scope = scope
        self._client = obs.attrib.current_client()
        # the per-operator explain record, likewise captured on the
        # consumer's thread (the plan node whose dispatch built this
        # stream): chunk/byte/wait ticks attribute to that node
        self._op = obs.operators.current_op()
        # byte-sizing placed chunks costs tens of µs of device-array
        # metadata reads — decide ONCE whether any accounting consumer
        # (ledger scope / active trace / explain op record) needs it,
        # and do it on the worker thread where it overlaps compute
        want_nbytes = (scope is not None or self._trace is not None
                       or self._op is not None)
        self._want_nbytes = want_nbytes
        self._thread: Optional[threading.Thread] = None
        if self._depth > 0:
            self._q: "queue.Queue" = queue.Queue(maxsize=self._depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=_stage_worker,
                args=(self._source, self._place, self._q, self._stop,
                      name, on_complete, want_nbytes),
                daemon=True, name=f"netsdb-stage-{name}")
            with _stagers_lock:
                _stagers[:] = [t for t in _stagers if t.is_alive()]
                _stagers.append(self._thread)
            self._thread.start()

    # --- consumer side ------------------------------------------------
    def __iter__(self) -> Iterator[Any]:
        return self

    def _account(self, nbytes: Optional[int], wait_s: float) -> None:
        """Per-chunk bookkeeping: one registry tick always (plus the
        wait histogram the staging-wait-fraction SLO reads and, for
        store-owned streams, the per-(client, set) resource ledger);
        bytes/wait additionally land on an active query trace (the
        profile's "bytes staged" and upload-wait counters). ``nbytes``
        was measured on the WORKER thread (overlapped, not here —
        device-array metadata reads are µs-expensive)."""
        obs.REGISTRY.counter("staging.chunks").inc()
        if nbytes:
            # cumulative staged bytes: the MB/s-staged rate feed the
            # telemetry history derives (obs/history.py)
            obs.REGISTRY.counter("staging.bytes").inc(int(nbytes))
        if wait_s > 0:
            # total-seconds feed for obs/slo.py "staging_wait_fraction"
            obs.REGISTRY.histogram("staging.wait_s").observe(wait_s)
        if self._op is not None:
            self._op.add("stage.chunks")
            if nbytes:
                self._op.add("stage.bytes", nbytes)
            if wait_s > 0:
                self._op.add("stage.wait_s", wait_s)
        if self._scope is not None:
            obs.attrib.account("staged_chunks", 1, scope=self._scope,
                               client=self._client)
            obs.attrib.account("staged_bytes", nbytes or 0,
                               scope=self._scope, client=self._client)
        tr = self._trace
        if tr is None:
            return
        tr.add("stage.chunks")
        tr.add("stage.bytes", nbytes or 0)
        if wait_s > 0:
            tr.add("stage.wait_s", wait_s)

    def __next__(self):
        if self._thread is None:  # synchronous inline mode
            if self._closed:
                raise StopIteration
            try:
                item = next(self._source)
            except StopIteration:
                _emit("end", self._name)
                if self._on_complete is not None:
                    try:
                        self._on_complete()
                    except Exception:  # install failure ≠ stream failure
                        pass
                self.close()
                raise
            placed = self._place(item)
            _emit("place", self._name, self._sync_seq)
            self._sync_seq += 1
            if self._want_nbytes:
                from netsdb_tpu.storage.devcache import _value_nbytes

                self._account(_value_nbytes(placed), 0.0)
            else:
                self._account(None, 0.0)
            return placed
        if self._closed:
            raise StopIteration
        t_wait = time.perf_counter()
        while True:
            try:
                kind, val = self._q.get(timeout=0.5)
            except queue.Empty:
                if not self._thread.is_alive():  # died without a sentinel
                    self._closed = True
                    raise RuntimeError(
                        f"staging thread {self._name!r} died")
                continue
            if kind is _SENT_ERR:
                self._closed = True
                raise val
            if kind is _SENT_END:
                self._closed = True
                # the CONSUMER observed exhaustion — the "stream
                # finished" moment the overlap tests anchor on
                _emit("close", self._name)
                raise StopIteration
            placed, nbytes = val
            self._account(nbytes, time.perf_counter() - t_wait)
            return placed

    def close(self) -> None:
        """Stop + drain + join the staging thread (idempotent). After
        this the source iterator has been closed on the worker thread
        and no staging thread of this stream is alive."""
        if self._thread is None:
            if not self._closed:
                self._closed = True
                _emit("close", self._name)
                close = getattr(self._source, "close", None)
                if close is not None:
                    close()
            return
        if not self._closed:
            _emit("close", self._name)
        self._closed = True
        self._stop.set()
        # drain so a worker blocked in put() observes the stop quickly
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=30)
        with _stagers_lock:
            _stagers[:] = [t for t in _stagers if t.is_alive()]

    def __enter__(self) -> "StagedStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        # best-effort: an abandoned stream must not leak its thread (or
        # the read locks its source generator holds) until interpreter
        # exit — mirrors generator finalization semantics
        with contextlib.suppress(Exception):
            self.close()


class _CachedRun:
    """Iterator over a device-cached run — what :func:`stage_stream`
    returns on a cache hit: the blocks are ALREADY device-resident, so
    there is no source, no staging thread, no transfer. Supports the
    same ``close()`` discipline as :class:`StagedStream` so consumers
    under ``contextlib.closing`` need not care which they got."""

    def __init__(self, blocks, name: str):
        self._it = iter(blocks)
        self._name = name

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self) -> None:
        self._it = iter(())

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _CacheRecorder:
    """Wraps a ``place`` function so a completed run installs into the
    device cache: every placed block is recorded, and ``complete`` —
    fired by the stream only on NATURAL source exhaustion — installs
    the full ordered run under ``key``. An abandoned or failed stream
    never installs (a truncated run must not masquerade as the set).

    Recording is BYTE-BOUNDED as it goes: the moment the accumulated
    run exceeds the cache budget, every held block is dropped and
    recording stops — a set bigger than the cache must stream with
    only ``depth`` blocks live (the out-of-core discipline), never
    hold its whole self device-resident waiting for an install that
    would be rejected anyway."""

    def __init__(self, cache, key, place, validator=None):
        from netsdb_tpu.storage.devcache import _value_nbytes

        self._cache = cache
        self._key = key
        self._place = place
        self._validator = validator
        self._nbytes_of = _value_nbytes
        self._blocks: list = []
        self._bytes = 0
        self._cap = cache.budget_bytes
        self._overflow = False
        # attribution identity, captured on the CONSUMER thread at
        # construction: ``complete`` fires on the staging worker, which
        # does not inherit the dispatch context var
        self._client = obs.attrib.current_client()

    def __call__(self, item):
        placed = self._place(item)
        if not self._overflow:
            self._bytes += self._nbytes_of(placed)
            if self._bytes > self._cap:
                self._overflow = True
                self._blocks = []  # release NOW, not at stream end
            else:
                self._blocks.append(placed)
                # evict AS the run grows: resident entries + this run
                # must together stay ~one budget, not spike to two at
                # install time
                self._cache.make_room(self._bytes)
        return placed

    def complete(self) -> None:
        if self._overflow:
            self._cache.reject_oversized()
            return
        # the validator runs INSIDE install's critical section: a
        # write racing this install either invalidates after it (normal
        # eviction) or bumps the version before it (validator rejects)
        # — either way no dead entry can squat on the budget
        self._cache.install(self._key, self._blocks,
                            validator=self._validator,
                            client=self._client)


class PartialPlan:
    """Everything :func:`stage_stream` needs to range-stitch one
    stream against the partial-run device cache (built by the caller,
    which knows the set's block layout):

    * ``cache`` — the :class:`~netsdb_tpu.storage.devcache.
      DeviceBlockCache` (must have ``partial`` on);
    * ``base_key`` — the composite ``(scope, kind, bucket, sharding)``
      block entries key under (scope FIRST — the invalidation index
      relies on it); NO write version — freshness is dirty-range
      invalidation's job;
    * ``ranges`` — the full ordered ``[(start_row, end_row)]`` block
      layout of the set (metadata only, zero arena reads);
    * ``source_for(gap_indices)`` — builds a host iterator yielding
      ONLY those block positions (the arena never reads pages whose
      chunks are already device-resident).
    """

    __slots__ = ("cache", "base_key", "ranges", "source_for")

    def __init__(self, cache, base_key, ranges, source_for):
        self.cache = cache
        self.base_key = tuple(base_key)
        self.ranges = [(int(s), int(e)) for s, e in ranges]
        self.source_for = source_for


class _BlockInstaller:
    """Wraps ``place`` so every placed GAP block installs into the
    partial cache as it streams — partial consumption caches the
    consumed prefix (an early-exit consumer keeps what it paid for,
    unlike the whole-run recorder which discarded everything). Runs on
    the staging thread; the attributed client identity is captured on
    the consumer thread at construction. Installs are epoch-gated, so
    a write racing the stream refuses the in-flight blocks instead of
    stranding stale entries."""

    def __init__(self, cache, base_key, gap_ranges, epoch, place):
        self._cache = cache
        self._base_key = base_key
        self._gaps = list(gap_ranges)  # consumed positionally, in order
        self._epoch = epoch
        self._place = place
        self._i = 0
        self._all_installed = True
        self._client = obs.attrib.current_client()

    def __call__(self, item):
        placed = self._place(item)
        if self._i < len(self._gaps):
            ok = self._cache.install_block(
                self._base_key, self._gaps[self._i], placed,
                epoch=self._epoch, client=self._client)
            self._all_installed = self._all_installed and ok
            self._i += 1
        return placed

    def complete(self) -> None:
        # natural exhaustion with every gap block landed = the
        # partial-mode analogue of one whole-run install (run-level
        # counter semantics preserved for dashboards/SLOs/tests)
        if self._all_installed and self._i == len(self._gaps):
            self._cache.record_run_install(str(self._base_key[0]),
                                           client=self._client)


class _StitchedStream:
    """Row-order interleave of device-cached blocks and a staged gap
    stream — what :func:`stage_stream` returns on a PARTIAL cache hit:
    cached ranges serve from HBM (zero arena reads, zero transfers,
    ticked as ``devcache.partial_hits``) while gap ranges arrive
    through the normal host-prefetch→upload pipeline, so the consumer
    sees one seamless stream in block order. Same ``close()``
    discipline as :class:`StagedStream`."""

    def __init__(self, segments, staged, cache, scope: str, name: str):
        # segments: [("hit", block) | ("gap", None)] in block order
        self._segments = segments
        self._staged = staged  # StagedStream over the gaps (or None)
        self._cache = cache
        self._scope = scope
        self._name = name
        self._i = 0
        self._closed = False
        # count the stitch joints once, up front: a contiguous run of
        # cached blocks is ONE stitched range
        stitched = sum(1 for j, (kind, _b) in enumerate(segments)
                       if kind == "hit"
                       and (j == 0 or segments[j - 1][0] != "hit"))
        self._pending_ranges = stitched

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if self._i >= len(self._segments):
            self.close()
            raise StopIteration
        kind, block = self._segments[self._i]
        self._i += 1
        if kind == "hit":
            # per-block residency tick (the counters the partial-
            # invalidation proof reads) + the one-time stitch count
            self._cache.tick_partial(self._scope, 1,
                                     self._pending_ranges)
            self._pending_ranges = 0
            return block
        return next(self._staged)

    def close(self) -> None:
        self._closed = True
        if self._staged is not None:
            self._staged.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        with contextlib.suppress(Exception):
            self.close()


def _stage_partial(plan: PartialPlan, place, depth: int, name: str,
                   scope: Optional[str]):
    """The partial-mode leg of :func:`stage_stream`: consult, stitch,
    install-as-you-go."""
    scope = scope if scope is not None else str(plan.base_key[0])
    epoch, covered = plan.cache.plan_ranges(plan.base_key, plan.ranges)
    gaps = [i for i, r in enumerate(plan.ranges) if r not in covered]
    if not gaps:
        _emit("cache_hit", name)
        # a fully resident stream: the query profile's zero-transfer
        # marker keeps its whole-run meaning
        obs.add("stage.cached_runs")
        obs.operators.op_add("stage.cached_runs")
        segments = [("hit", covered[r]) for r in plan.ranges]
        return _StitchedStream(segments, None, plan.cache, scope, name)
    rec = _BlockInstaller(plan.cache, plan.base_key,
                          [plan.ranges[i] for i in gaps], epoch, place)
    staged = StagedStream(plan.source_for(gaps), rec, depth=depth,
                          name=name, on_complete=rec.complete,
                          scope=scope)
    if not covered:
        return staged  # fully cold: plain staged stream, installing
    segments = [("hit", covered[r]) if r in covered else ("gap", None)
                for r in plan.ranges]
    return _StitchedStream(segments, staged, plan.cache, scope, name)


def stage_stream(source: Iterable, place: Callable[[Any], Any],
                 depth: int = 2, name: str = "stage",
                 cache=None, cache_key=None, cache_validator=None,
                 scope: Optional[str] = None, partial=None):
    """Wrap ``source`` so ``place`` (pad + upload via
    ``storage/devcache.to_device``) runs up to ``depth`` items ahead on
    a background thread.  The ONE constructor every out-of-core
    consumer goes through — the static check in
    ``tests/test_static_checks.py`` bans loose ``device_put`` call
    sites in ``storage/``, ``plan/`` and ``relational/outofcore.py``
    so neither the overlap nor the cache can silently regress.

    ``cache``/``cache_key`` (a :class:`~netsdb_tpu.storage.devcache.
    DeviceBlockCache` and its versioned key) make the stream
    cache-aware: a hit replays the device-resident run with ZERO
    host→device transfers (no thread, no arena reads); a miss streams
    normally and installs the completed run on the way through — the
    staged-uploads-install-into-the-cache leg of the tentpole.
    ``cache_validator`` (no-arg callable → bool) re-checks at install
    time that ``cache_key`` is still current — a write racing the
    stream must not leave a dead entry squatting on the budget.

    ``scope`` names the set ("db:set") the per-(client, set) resource
    ledger attributes this stream's staged bytes to; defaults to the
    cache key's scope component for cache-aware streams (store-bound
    handles), None for uncached temporaries (grace-hash spills).

    ``partial`` (a :class:`PartialPlan`) takes the BLOCK-GRANULAR
    cache path instead: cached ranges stitch into the stream from HBM
    (zero arena reads), gap ranges stream + install per block, and
    ``source`` is ignored (the plan's ``source_for`` builds the
    gap-only feed). Mutually exclusive with ``cache``/``cache_key``."""
    if partial is not None and partial.cache.enabled \
            and getattr(partial.cache, "partial", False) \
            and partial.ranges:
        return _stage_partial(partial, place, depth, name, scope)
    if partial is not None and source is None:
        # partial plan declined (cache off / empty layout): fall back
        # to a plain uncached stream over the plan's full block feed
        source = partial.source_for(None)
    if scope is None and cache_key is not None:
        scope = str(cache_key[0])
    if cache is not None and cache_key is not None and cache.enabled:
        hit = cache.get(cache_key)
        if hit is not None:
            _emit("cache_hit", name)
            # a whole run served device-resident: the query profile's
            # zero-transfer marker (per-block hit ticks come from the
            # cache itself), attributed to the consuming plan node too
            obs.add("stage.cached_runs")
            obs.operators.op_add("stage.cached_runs")
            return _CachedRun(hit, name)
        rec = _CacheRecorder(cache, cache_key, place, cache_validator)
        return StagedStream(source, rec, depth=depth, name=name,
                            on_complete=rec.complete, scope=scope)
    return StagedStream(source, place, depth=depth, name=name,
                        scope=scope)
