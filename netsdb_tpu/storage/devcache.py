"""Cross-query device-resident block cache — the buffer pool for HBM.

netsDB's workers owe their repeat-query speed to the shared-memory
buffer pool: pages of a hot set stay PINNED across jobs, so the second
query over ``lineitem`` never touches storage again
(``src/storage/headers/PageCache.h:106-118`` — pin/unpin + eviction
under one memory budget). Our reproduction had no analogue: every
serve ``EXECUTE`` re-read the arena, re-padded and re-``device_put``
every chunk of a set that was device-resident milliseconds ago. The
TPU literature says the same discipline is what makes pipelines fast —
keep operands device-resident across calls and ship only deltas
(arxiv 2112.09017 §IV); at this scale the avoided TRANSFERS dominate,
not kernel tweaks.

:class:`DeviceBlockCache` is that buffer pool for placed blocks:

* **Keying** — entries key on
  ``(scope, version, mutations, kind, bucket, sharding)`` where
  ``scope`` is the set identity (``"db:set"``), ``version`` the
  store's monotonic per-set write version (bumped by EVERY path that
  can change a set: ingest, BULK COMMIT, mirrored frames, resync,
  checkpoint restore — ``SetStore._touch``), ``mutations`` the
  relation handle's own append/drop counter (covers direct
  ``PagedColumns.append`` callers that bypass the store), ``bucket``
  the chunk pad target and ``sharding`` the placement label. A write
  moves the version, so a stale entry can never MATCH again — version
  keying is the correctness mechanism; eviction is only about memory.
* **Budget** — entries LRU-evict under ``config.device_cache_bytes``
  (``PageCache::evict`` under one pool size). An entry bigger than the
  whole budget is simply not installed.
* **Introspection** — hit/miss/install/evict/invalidate counters plus
  live bytes/entries, surfaced ``compile_stats()``-style via
  :meth:`stats` and through the serve ``COLLECT_STATS`` frame.
* **Ownership** — cached blocks are owned by the CACHE, not by any one
  execution: they are never donation targets. Fold steps donate only
  their carried accumulator (argument 0 — ``staging.
  fold_donate_argnums``); a jit must never be handed a cached block
  with ``donate_argnums`` covering it, or XLA would free a buffer the
  next query expects to reuse.

With ``config.device_cache_partial`` (the default) the cache is
additionally **block-granular** — netsDB pins per PAGE, never per set
(``PageCache.h`` pin/unpin is a page-level contract), and the
whole-run design above could not keep a huge set's hot prefix
resident across appends: one small write unkeyed the entire run.
Partial mode installs each placed chunk as its own entry under
``(scope, kind, bucket, sharding, block_range)``, stitches contiguous
cached ranges into cold streams (``plan/staging.stage_stream`` serves
cached ranges from HBM with zero arena reads while gaps stream
normally), replaces version keying with per-page **dirty-range
invalidation** (``SetStore._touch`` passes the appended tail range;
only intersecting blocks drop), and optionally PINS a set's head
blocks under ``config.device_cache_pin_bytes`` so the hot prefix
survives LRU pressure. ``device_cache_partial=False`` restores the
whole-run behavior byte-for-byte.

The one blessed upload helper, :func:`to_device`, lives here so the
static check (``tests/test_static_checks.py``) can ban direct
``device_put`` of store-owned set blocks everywhere else in
``storage/``, ``plan/`` and the out-of-core engine — future call sites
cannot silently bypass the cache/staging layer.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from netsdb_tpu import obs
from netsdb_tpu.utils.locks import TrackedLock


def to_device(x, sharding=None):
    """The ONE sanctioned host→device upload for store-owned blocks
    (everything else goes through ``plan/staging.stage_stream``, whose
    ``place`` functions call this). Centralized so the static check can
    ban loose ``device_put`` call sites."""
    import jax

    if sharding is not None:
        return jax.device_put(x, sharding)
    return jax.device_put(x)


#: scope prefix for session-state entries — namespaced so a session
#: scope can never collide with a set scope ("db:set") in the
#: by-scope index or the affinity gate's warm probe.
SESSION_SCOPE_PREFIX = "__session__:"


def session_scope(sid: str) -> str:
    """The by-scope index key for one session's state entries."""
    return SESSION_SCOPE_PREFIX + str(sid)


def _array_nbytes(arr) -> int:
    """Bytes of one column/array WITHOUT touching its data: jax and
    numpy arrays expose ``nbytes`` as shape×itemsize metadata — calling
    ``np.asarray`` here would be a blocking device→host copy of the
    whole buffer just for accounting."""
    nbytes = getattr(arr, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    import numpy as np

    return int(np.asarray(arr).nbytes)


def _value_nbytes(value) -> int:
    """Recursive byte accounting for a cached run: ColumnTables, jax
    arrays, numpy arrays, (n, block) tuples — anything a ``place``
    function yields. Metadata-only (never reads array data)."""
    if isinstance(value, (bytes, bytearray)):
        return len(value)
    cols = getattr(value, "cols", None)
    if cols is not None:  # ColumnTable-shaped
        total = sum(_array_nbytes(v) for v in cols.values())
        valid = getattr(value, "valid", None)
        if valid is not None:
            total += _array_nbytes(valid)
        return total
    if getattr(value, "nbytes", None) is not None:
        return int(value.nbytes)
    if isinstance(value, dict):  # raw column maps (PagedColumns.stream)
        return sum(_value_nbytes(v) for v in value.values())
    if isinstance(value, (tuple, list)):
        return sum(_value_nbytes(v) for v in value)
    return 64  # scalars / ints riding along with blocks


class SessionSlab:
    """One decode model's session state on the device: a dict of arrays
    that each carry a SLOT axis (``models/decode.py`` declares the
    layout), every slot one session's whole state — recurrent state,
    convolution window, key/value cache, position — side by side.

    A session leases a slot from open to close or eviction (the lease is
    a session entry of the :class:`DeviceBlockCache`, charged the
    slot's bytes). A decode step takes ``arrays`` under :attr:`mu`,
    dispatches a program that DONATES them, and stores what it returns:
    the state of every slot advances in place and never crosses the
    host. Spill, revive, move and handoff copy one slot's slices.

    :attr:`mu` guards ``arrays`` and the free list. It nests under the
    cache's lock (the spill callback reads a slot while the cache drops
    its lease) and under nothing else; a step holds it for a dispatch,
    never across a transfer it waits on."""

    def __init__(self, model: str, arrays: Dict[str, Any], slots: int,
                 slot_nbytes: int):
        self.model = str(model)
        self.mu = TrackedLock("SessionSlab.mu")
        self.arrays = arrays
        self.slots = int(slots)
        self.slot_nbytes = int(slot_nbytes)
        self._free = list(range(self.slots))

    @property
    def nbytes(self) -> int:
        return self.slots * self.slot_nbytes

    def take(self) -> Optional[int]:
        """The lowest free slot, or None (caller holds :attr:`mu`)."""
        if not self._free:
            return None
        self._free.sort()
        return self._free.pop(0)

    def give(self, slot: int) -> None:
        """Return ``slot`` (caller holds :attr:`mu`)."""
        if slot not in self._free:
            self._free.append(int(slot))

    def live(self) -> int:
        return self.slots - len(self._free)

    def release_all(self) -> None:
        with self.mu:
            self._free = list(range(self.slots))


class DeviceBlockCache:
    """LRU cache of placed set blocks under one byte budget.

    Two entry granularities share the budget, the LRU order and the
    invalidation index:

    * **whole-run entries** (the PR 4 design, and the only kind when
      ``partial=False``) — one entry per complete stream of a set,
      keyed ``(scope, version, mutations, kind, bucket, sharding)``;
      version keying is the correctness mechanism, eviction is only
      about memory.
    * **block entries** (``partial=True`` — the netsDB pin-per-page
      discipline) — one entry per placed chunk, keyed
      ``base_key + ((start, end),)`` where ``base_key`` is
      ``(scope, kind, bucket, sharding, …)`` WITHOUT the write
      version: freshness comes from **dirty-range invalidation**
      (:meth:`invalidate_range` drops only intersecting blocks), so a
      tail append leaves every pre-append block resident and a warm
      re-query re-stages only the gap. A per-scope **epoch** (bumped
      by every invalidation touching the scope) gates installs: a
      block placed before a racing write carries the old epoch and is
      refused, so a dead entry can never squat on the budget.

    Partial-mode run-level counters keep their PR 4 meaning: a
    ``plan_ranges`` consult with FULL coverage counts one ``hit``, any
    gap counts one ``miss``, and an installer that lands every gap
    block of its stream counts one ``install`` — while per-block
    serving ticks ``partial_hits`` and stitched contiguous cached
    ranges tick ``stitched_ranges``.

    Thread-safe: consults happen on consumer threads, installs on
    staging threads, invalidations on serve handler threads.
    """

    def __init__(self, budget_bytes: int = 0, partial: bool = False,
                 pin_bytes: int = 0):
        self._mu = TrackedLock("DeviceBlockCache._mu")
        self._budget = int(budget_bytes or 0)
        self.partial = bool(partial)
        self._pin_budget = int(pin_bytes or 0)
        # key -> (blocks, nbytes); insertion order IS recency order.
        # Block entries hold a single-element blocks list.
        self._entries: "OrderedDict[Tuple, Tuple[List[Any], int]]" = \
            OrderedDict()
        # scope -> keys, for prompt invalidation (version keying alone
        # already guarantees freshness; this returns the bytes NOW)
        self._by_scope: Dict[str, set] = {}
        self._bytes = 0
        self._stats = {"hits": 0, "misses": 0, "installs": 0,
                       "evictions": 0, "invalidations": 0,
                       "rejected": 0}
        if self.partial:
            self._stats.update({"partial_hits": 0, "stitched_ranges": 0,
                                "dirty_invalidations": 0,
                                "pinned_bytes": 0})
        # --- partial-mode state (all guarded by _mu) ---
        # scope -> monotonic dirty epoch (bumped by every invalidation
        # touching the scope; installs are epoch-gated)
        self._epochs: Dict[str, int] = {}
        # pinned block keys (skipped by LRU eviction) + the global
        # pinned-byte total under _pin_budget
        self._pinned: set = set()
        self._pinned_bytes = 0
        # base_key -> end row of the contiguous pinned head prefix
        # (pinning only ever extends the prefix, in install order)
        self._pin_hw: Dict[Tuple, int] = {}
        # base_key -> total rows of the set as of the last plan (the
        # coverage probe's denominator)
        self._totals: Dict[Tuple, int] = {}
        # True when the pin budget is being driven by the feedback
        # loop (config.device_cache_pin_auto) rather than the static
        # knob — annotated in stats() so operators can tell which
        self._pin_auto = False
        # --- session-state entries (serve/sessions.py) ---
        # the third entry family: TTL'd MUTABLE per-session decode
        # state (recurrent h/c vectors, KV pages), keyed
        # ``(session_scope(sid), model, layer)``. Never version-keyed —
        # the blessed sessions.py update path swaps the value in place
        # on every decode step, so freshness is the writer's contract,
        # not the cache's. Entries share the LRU order and byte budget
        # with both block families; eviction and TTL expiry SPILL the
        # state through ``_session_spill_cb`` (the host-arena escape
        # hatch) instead of losing it. key -> meta dict
        # {"deadline": monotonic expiry, "ttl": seconds,
        #  "expired": bool (set by the sweep for counter attribution)}.
        self._session_meta: Dict[Tuple, Dict[str, Any]] = {}
        # model -> SessionSlab: the device arrays the leases index into.
        # The slab is allocated whole when its model registers; the
        # budget is charged by LEASE (a session holding a slot between
        # steps), so that pressure and TTL free slots, not memory
        self._slabs: Dict[str, "SessionSlab"] = {}
        self._session_spill_cb: Optional[
            Callable[[str, str, str, Any], None]] = None
        self._stats.update({"session_evictions": 0,
                            "session_expirations": 0})
        # session_* stats keys stay hidden until the session lane is
        # actually wired (set_session_spill / session_put) — a plain
        # client cache keeps the original stats surface, same deal as
        # the partial-mode keys
        self._session_on = False

    # --- sizing -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._budget > 0

    @property
    def budget_bytes(self) -> int:
        return self._budget

    def resize(self, budget_bytes: int) -> None:
        """Re-point the budget. Shrinking evicts immediately."""
        with self._mu:
            self._budget = int(budget_bytes or 0)
            if self._budget < self._pinned_bytes:
                # a shrink below the pinned total lifts every pin —
                # the operator explicitly chose the smaller pool
                self._pinned.clear()
                self._pinned_bytes = 0
                self._pin_hw.clear()
                if "pinned_bytes" in self._stats:
                    self._stats["pinned_bytes"] = 0
            self._evict_to_fit_locked(0)

    def set_pin_budget(self, pin_bytes: int, auto: bool = False) -> None:
        """Re-point the hot-prefix pin budget (partial mode only) —
        the ``device_cache_pin_auto`` feedback hook and the serve knob
        path. Shrinking below the currently pinned total lifts every
        pin (head blocks re-pin as streams reinstall them — the
        conservative reset; LRU then treats them like any entry).
        ``auto`` annotates :meth:`stats` with who is driving the
        budget."""
        with self._mu:
            if not self.partial:
                return
            self._pin_budget = max(int(pin_bytes or 0), 0)
            self._pin_auto = bool(auto)
            if self._pinned_bytes > self._pin_budget:
                self._pinned.clear()
                self._pinned_bytes = 0
                self._pin_hw.clear()
            if "pinned_bytes" in self._stats:
                self._stats["pinned_bytes"] = self._pinned_bytes
        obs.REGISTRY.gauge("devcache.pinned_bytes").set(
            self._pinned_bytes)

    # --- the data path ------------------------------------------------
    def get(self, key: Tuple) -> Optional[List[Any]]:
        """The run cached under ``key``, or None (counted as a miss).
        Hits refresh LRU recency. Per-store counters stay on this
        instance (``stats()`` keeps its shape); the process-wide
        registry, the active query trace and the per-(client, set)
        resource ledger get the same tick — the profile's devcache
        hit/miss decomposition and the attribution the scheduler
        admits against. ``devcache.lookups`` (hits + misses in one
        monotonic counter) feeds the hit-rate SLO (obs/slo.py)."""
        with self._mu:
            if not self.enabled:
                return None
            entry = self._entries.get(key)
            if entry is None:
                self._stats["misses"] += 1
                entry = None
            else:
                self._entries.move_to_end(key)
                self._stats["hits"] += 1
        obs.REGISTRY.counter("devcache.lookups").inc()
        scope = str(key[0])
        if entry is None:
            obs.REGISTRY.counter("devcache.misses").inc()
            obs.add("devcache.misses")
            obs.operators.op_add("devcache.misses")
            obs.attrib.account("devcache.misses", scope=scope)
            return None
        obs.REGISTRY.counter("devcache.hits").inc()
        obs.add("devcache.hits")
        obs.operators.op_add("devcache.hits")
        obs.attrib.account("devcache.hits", scope=scope)
        return entry[0]

    def has_scope(self, scope: str) -> bool:
        """True when ANY run of ``scope`` ("db:set") is resident — the
        cache-aware admission probe (serve/sched/policy.AffinityGate):
        "is this set warm?", without touching the hit/miss counters
        (an admission decision must not move the SLO feeds it reads)."""
        with self._mu:
            return bool(self._by_scope.get(str(scope)))

    def make_room(self, nbytes: int) -> None:
        """Evict LRU entries until ``nbytes`` of headroom exists under
        the budget. Called INCREMENTALLY by the recorder while a cold
        stream installs-in-progress (``staging._CacheRecorder``), so
        peak device residency stays ~one budget — resident entries plus
        the in-flight run together — instead of transiently doubling at
        install time. Best-effort across concurrent recorders (two
        simultaneous cold streams can still briefly sum above budget)."""
        with self._mu:
            if self.enabled:
                self._evict_to_fit_locked(min(int(nbytes), self._budget))

    def reject_oversized(self) -> None:
        """Count a run the recorder refused to hold (it outgrew the
        whole budget mid-stream — ``staging._CacheRecorder``)."""
        with self._mu:
            if self.enabled:
                self._stats["rejected"] += 1

    def install(self, key: Tuple, blocks: List[Any],
                validator=None, client: Optional[str] = None) -> bool:
        """Insert one complete run. Returns False when the run exceeds
        the whole budget (never installed — a set bigger than the cache
        streams every time, it does not thrash everyone else out).

        ``client``: the attributed identity for the per-(client, set)
        ledger — installs run on STAGING threads, which don't inherit
        the dispatch context var, so the recorder captures the identity
        on the consumer thread and passes it here explicitly.

        ``validator`` (no-arg → bool) is evaluated INSIDE the cache
        lock: the write path bumps the set version BEFORE invalidating
        (``SetStore._touch``), so a validator that re-derives the key
        from the current version and runs after an invalidate always
        sees the bump and rejects — check-then-install cannot race a
        write into stranding a dead entry on the budget."""
        nbytes = _value_nbytes(blocks)
        with self._mu:
            if not self.enabled or nbytes > self._budget:
                if self.enabled:
                    self._stats["rejected"] += 1
                return False
            if validator is not None and not validator():
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._evict_to_fit_locked(nbytes)
            self._entries[key] = (blocks, nbytes)
            self._bytes += nbytes
            self._by_scope.setdefault(str(key[0]), set()).add(key)
            self._stats["installs"] += 1
        obs.REGISTRY.counter("devcache.installs").inc()
        obs.add("devcache.installs")
        obs.attrib.account("devcache.installs", scope=str(key[0]),
                           client=client)
        return True

    def _evict_to_fit_locked(self, incoming: int) -> None:
        # ONE pass in LRU order collecting victims, skipping PINNED
        # block entries (a set's hot head prefix under the pin budget
        # — only invalidation drops them; when everything left is
        # pinned, eviction stops and the caller's install simply fails
        # to fit). A restart-per-victim scan would re-walk the pinned
        # head for every eviction — O(pinned × evicted) inside _mu.
        if self._bytes + incoming <= self._budget:
            return
        victims = []
        freed = 0
        for key, (_, nbytes) in self._entries.items():
            if key in self._pinned:
                continue
            victims.append(key)
            freed += nbytes
            if self._bytes - freed + incoming <= self._budget:
                break
        for key in victims:
            self._drop_entry_locked(key)
            self._stats["evictions"] += 1
        if victims:
            obs.REGISTRY.counter("devcache.evictions").inc(len(victims))

    def _drop_entry_locked(self, key: Tuple) -> bool:
        """Remove one entry (any granularity) from every index. A
        SESSION entry additionally spills its live state through the
        registered callback (host arena) before vanishing — LRU
        pressure and TTL expiry demote session state, they never lose
        it — and ticks the eviction/expiry counters the chaos tests
        and ``cli obs --sessions`` read."""
        entry = self._entries.pop(key, None)
        if entry is None:
            return False
        self._bytes -= entry[1]
        scoped = self._by_scope.get(str(key[0]))
        if scoped is not None:
            scoped.discard(key)
            if not scoped:
                self._by_scope.pop(str(key[0]), None)
        if key in self._pinned:
            self._pinned.discard(key)
            self._pinned_bytes -= entry[1]
        meta = self._session_meta.pop(key, None)
        if meta is not None:
            which = ("session_expirations" if meta.get("expired")
                     else "session_evictions")
            self._stats[which] += 1
            obs.REGISTRY.counter("session.evicted").inc()
            if self._session_spill_cb is not None:
                sid = str(key[0])[len(SESSION_SCOPE_PREFIX):]
                try:
                    self._session_spill_cb(sid, str(key[1]),
                                           str(key[2]), entry[0][0])
                except Exception:
                    pass  # spill is best-effort; the table still
                    # knows the step count and refuses silent reuse
        return True

    # --- partial mode: per-block entries + range stitching ------------
    @staticmethod
    def _block_key(base_key: Tuple, rng: Tuple[int, int]) -> Tuple:
        return tuple(base_key) + ((int(rng[0]), int(rng[1])),)

    def scope_epoch(self, scope: str) -> int:
        """The scope's current dirty epoch — captured by a stream at
        plan time and checked again at each block install, so a write
        racing the stream can never strand a stale block entry."""
        with self._mu:
            return self._epochs.get(str(scope), 0)

    def plan_ranges(self, base_key: Tuple,
                    ranges: List[Tuple[int, int]]
                    ) -> Tuple[int, Dict[Tuple[int, int], Any]]:
        """(epoch, {range: block}) for the block entries of
        ``base_key`` matching the expected ``ranges`` of one stream —
        the stitching consult. Run-level counters keep their whole-run
        meaning: full coverage is one hit, any gap one miss; the
        per-block ``partial_hits`` tick happens when blocks are
        actually SERVED (staging._StitchedStream), not here."""
        scope = str(base_key[0])
        with self._mu:
            if not (self.enabled and self.partial):
                return 0, {}
            epoch = self._epochs.get(scope, 0)
            if ranges:
                self._totals[tuple(base_key)] = int(ranges[-1][1])
            covered: Dict[Tuple[int, int], Any] = {}
            for rng in ranges:
                key = self._block_key(base_key, rng)
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.move_to_end(key)
                    covered[(int(rng[0]), int(rng[1]))] = entry[0][0]
            full = bool(ranges) and len(covered) == len(ranges)
            self._stats["hits" if full else "misses"] += 1
        obs.REGISTRY.counter("devcache.lookups").inc()
        name = "devcache.hits" if full else "devcache.misses"
        obs.REGISTRY.counter(name).inc()
        obs.add(name)
        obs.operators.op_add(name)
        obs.attrib.account(name, scope=scope)
        return epoch, covered

    def install_block(self, base_key: Tuple, rng: Tuple[int, int],
                      block: Any, epoch: int,
                      client: Optional[str] = None) -> bool:
        """Insert ONE placed block under ``base_key + (range,)``.
        Refused when the scope's dirty epoch moved past ``epoch`` (a
        write raced the stream — the block may predate it), when the
        block alone exceeds the budget, or when eviction cannot make
        room without touching pinned entries. Head blocks (the
        contiguous prefix from row 0, in install order) are PINNED
        while the global pin budget lasts."""
        nbytes = _value_nbytes(block)
        scope = str(base_key[0])
        with self._mu:
            if not (self.enabled and self.partial):
                return False
            if self._epochs.get(scope, 0) != int(epoch):
                return False  # a write landed since the stream planned
            if nbytes > self._budget:
                self._stats["rejected"] += 1
                return False
            key = self._block_key(base_key, rng)
            if key in self._entries:  # concurrent stream won the race
                self._entries.move_to_end(key)
                return True
            self._evict_to_fit_locked(nbytes)
            if self._bytes + nbytes > self._budget:
                # everything evictable is gone and pinned entries hold
                # the rest — a cache full of pinned heads must not
                # thrash, the block simply streams uncached
                self._stats["rejected"] += 1
                return False
            self._entries[key] = ([block], nbytes)
            self._bytes += nbytes
            self._by_scope.setdefault(scope, set()).add(key)
            base = tuple(base_key)
            hw = self._pin_hw.get(base, 0)
            if (self._pin_budget > 0 and int(rng[0]) == hw
                    and self._pinned_bytes + nbytes <= self._pin_budget):
                self._pinned.add(key)
                self._pinned_bytes += nbytes
                self._pin_hw[base] = int(rng[1])
            self._stats["pinned_bytes"] = self._pinned_bytes
        obs.REGISTRY.gauge("devcache.pinned_bytes").set(
            self._pinned_bytes)
        return True

    def record_run_install(self, scope: str,
                           client: Optional[str] = None) -> None:
        """Tick the run-level ``installs`` counter once a stitched
        stream's installer landed every gap block of its run — the
        partial-mode analogue of one whole-run :meth:`install`."""
        with self._mu:
            if not (self.enabled and self.partial):
                return
            self._stats["installs"] += 1
        obs.REGISTRY.counter("devcache.installs").inc()
        obs.add("devcache.installs")
        obs.attrib.account("devcache.installs", scope=str(scope),
                           client=client)

    def tick_partial(self, scope: str, blocks_served: int,
                     stitched_ranges: int) -> None:
        """Account blocks served device-resident by a stitched stream
        (called from the consumer side as cached blocks are yielded)."""
        if blocks_served <= 0 and stitched_ranges <= 0:
            return
        with self._mu:
            if "partial_hits" in self._stats:
                self._stats["partial_hits"] += int(blocks_served)
                self._stats["stitched_ranges"] += int(stitched_ranges)
        if blocks_served > 0:
            obs.REGISTRY.counter("devcache.partial_hits").inc(
                int(blocks_served))
            obs.add("devcache.partial_hits", int(blocks_served))
            obs.operators.op_add("devcache.partial_hits",
                                 int(blocks_served))
            # attributed under the per-block name: the ledger's
            # "devcache.hits" stays run-level (plan_ranges ticks it),
            # so per-client hit-rate math against lookups never
            # exceeds 100%
            obs.attrib.account("devcache.partial_hits",
                               int(blocks_served), scope=str(scope))
        if stitched_ranges > 0:
            obs.REGISTRY.counter("devcache.stitched_ranges").inc(
                int(stitched_ranges))

    def coverage(self, scope: str) -> Tuple[int, Optional[int]]:
        """(covered_prefix_rows, total_rows) — the best contiguous
        cached prefix from row 0 over any base key of ``scope``, and
        that key's last-planned total (None when never planned). The
        scheduler's remainder-range probe (serve/sched/policy.py);
        counter-free like :meth:`has_scope`."""
        best = (0, None)
        with self._mu:
            keys = self._by_scope.get(str(scope), ())
            by_base: Dict[Tuple, List[Tuple[int, int]]] = {}
            for key in keys:
                rng = key[-1]
                if (isinstance(rng, tuple) and len(rng) == 2
                        and isinstance(rng[0], int)):
                    by_base.setdefault(key[:-1], []).append(rng)
            for base, rngs in by_base.items():
                covered = 0
                for s0, e0 in sorted(rngs):
                    if s0 > covered:
                        break
                    covered = max(covered, e0)
                total = self._totals.get(base)
                if covered > best[0] or (covered == best[0]
                                         and best[1] is None):
                    best = (covered, total)
        return best

    def invalidate_range(self, scope: str, start: int,
                         end: Optional[int] = None,
                         columns=None) -> int:
        """Drop only the entries a dirty row range intersects: block
        entries overlapping ``[start, end)`` (end=None → to infinity)
        plus every whole-run entry of the scope (version-keyed, so
        already unmatchable — dropping returns their bytes now). Bumps
        the scope's epoch either way, refusing in-flight installs
        planned before the write. Returns entries dropped.

        ``columns`` names the touched columns of an update-in-place
        write (the per-COLUMN dirty range): a block entry whose base
        key carries a column-projection marker (a ``frozenset`` —
        ``PagedColumns.partial_base_key(columns=...)``) DISJOINT from
        the touched set survives — its stream never contained the
        updated column, so its blocks are still byte-fresh. Unmarked
        entries contain every column and always drop."""
        scope = str(scope)
        columns = frozenset(columns) if columns is not None else None
        dropped = dirty = 0
        with self._mu:
            self._epochs[scope] = self._epochs.get(scope, 0) + 1
            # the write may have GROWN the set: last-planned totals are
            # stale until the next plan_ranges, and a stale total would
            # make coverage() report "fully resident" right after a
            # tail append — exactly when the affinity gate must
            # serialize the cold-tail installer, not admit everyone
            for base in [b for b in self._totals if str(b[0]) == scope]:
                self._totals.pop(base, None)
            keys = list(self._by_scope.get(scope, ()))
            for key in keys:
                rng = key[-1]
                is_block = (isinstance(rng, tuple) and len(rng) == 2
                            and isinstance(rng[0], int))
                if is_block:
                    s0, e0 = rng
                    if e0 <= start or (end is not None and s0 >= end):
                        continue  # disjoint: the block stays resident
                    if (columns is not None
                            and isinstance(key[-2], frozenset)
                            and key[-2].isdisjoint(columns)):
                        continue  # projected stream never held the
                        # updated column — still byte-fresh
                    dirty += 1
                if self._drop_entry_locked(key):
                    dropped += 1
            # the pinned head prefix may have been truncated: recompute
            # each base's high water from the SURVIVING pinned blocks
            # so re-installs re-pin from the break, not from scratch
            for base in [b for b in self._pin_hw if str(b[0]) == scope]:
                rngs = sorted(k[-1] for k in self._pinned
                              if k[:-1] == base)
                hw = 0
                for s0, e0 in rngs:
                    if s0 > hw:
                        break
                    hw = max(hw, e0)
                self._pin_hw[base] = hw
            if "dirty_invalidations" in self._stats:
                self._stats["dirty_invalidations"] += dirty
                self._stats["pinned_bytes"] = self._pinned_bytes
            self._stats["invalidations"] += dropped
        if dropped:
            obs.REGISTRY.counter("devcache.invalidations").inc(dropped)
        if dirty and self.partial:
            obs.REGISTRY.counter("devcache.dirty_invalidations").inc(
                dirty)
        obs.REGISTRY.gauge("devcache.pinned_bytes").set(
            self._pinned_bytes)
        return dropped

    # --- invalidation -------------------------------------------------
    def invalidate(self, scope: str) -> int:
        """Drop every entry of one set NOW (the write-path hook —
        version keying already prevents stale reads for whole-run
        entries; block entries NEED this, it is their correctness
        mechanism for whole-set writes). Bumps the scope's dirty epoch
        in partial mode. Returns entries dropped."""
        scope = str(scope)
        with self._mu:
            if self.partial:
                self._epochs[scope] = self._epochs.get(scope, 0) + 1
                for base in [b for b in self._pin_hw
                             if str(b[0]) == scope]:
                    self._pin_hw.pop(base, None)
                for base in [b for b in self._totals
                             if str(b[0]) == scope]:
                    self._totals.pop(base, None)
            keys = self._by_scope.pop(scope, None)
            if not keys:
                return 0
            dropped = 0
            for key in keys:
                entry = self._entries.pop(key, None)
                if entry is not None:
                    self._bytes -= entry[1]
                    dropped += 1
                if key in self._pinned:
                    self._pinned.discard(key)
                    self._pinned_bytes -= entry[1] if entry else 0
                self._session_meta.pop(key, None)  # administrative
                # drop: no spill — an operator invalidating a session
                # scope chose to discard it
            self._stats["invalidations"] += dropped
            if "pinned_bytes" in self._stats:
                self._stats["pinned_bytes"] = self._pinned_bytes
        obs.REGISTRY.counter("devcache.invalidations").inc(dropped)
        if self.partial:
            obs.REGISTRY.gauge("devcache.pinned_bytes").set(
                self._pinned_bytes)
        return dropped

    def clear(self) -> int:
        """Drop everything (the resync-restore hook: the whole store
        was just replaced wholesale)."""
        with self._mu:
            dropped = len(self._entries)
            if self.partial:
                for scope in {str(k[0]) for k in self._entries}:
                    self._epochs[scope] = self._epochs.get(scope, 0) + 1
            self._entries.clear()
            self._by_scope.clear()
            self._session_meta.clear()
            for sl in self._slabs.values():
                sl.release_all()
            self._pinned.clear()
            self._pinned_bytes = 0
            self._pin_hw.clear()
            self._totals.clear()
            self._bytes = 0
            self._stats["invalidations"] += dropped
            if "pinned_bytes" in self._stats:
                self._stats["pinned_bytes"] = 0
            return dropped

    # --- session-state entries (TTL'd MUTABLE; serve/sessions.py) -----
    # The write methods below are the BLESSED mutation path for
    # session state: the ``session-state-mutation`` lint rule bans
    # them everywhere outside ``serve/sessions.py``, the same
    # discipline that keeps ``device_put`` behind :func:`to_device`.

    def set_session_spill(
            self, cb: Optional[Callable[[str, str, str, Any], None]]
    ) -> None:
        """Register the eviction/expiry escape hatch:
        ``cb(sid, model, layer, value)`` runs for every session entry
        LRU pressure or TTL expiry drops. The callback MUST be a leaf
        (record to the host arena and return) — it runs under the
        cache lock so a racing decode can never read the entry
        half-spilled."""
        with self._mu:
            self._session_spill_cb = cb
            if cb is not None:
                self._session_on = True

    def session_put(self, sid: str, model: str, layer: str, value: Any,
                    ttl_s: float, client: Optional[str] = None,
                    nbytes: Optional[int] = None) -> bool:
        """Install (or replace) one session state entry. Unlike set
        blocks, session entries install even on a budget-less cache —
        an operator who disabled the block cache still gets sessions,
        just with no eviction pressure. Returns False only when the
        entry cannot fit under an enabled budget. ``nbytes`` states
        the entry's size where the value does not carry it: a slot
        LEASE (``{"slot", "step"}``) is charged the bytes of the slot
        it holds in the model's :class:`SessionSlab`."""
        key = (session_scope(sid), str(model), str(layer))
        nbytes = _value_nbytes(value) if nbytes is None else int(nbytes)
        with self._mu:
            self._session_on = True
            if self.enabled and nbytes > self._budget:
                self._stats["rejected"] += 1
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            if self.enabled:
                self._evict_to_fit_locked(nbytes)
            self._entries[key] = ([value], nbytes)
            self._bytes += nbytes
            self._by_scope.setdefault(key[0], set()).add(key)
            self._session_meta[key] = {
                "deadline": time.monotonic() + float(ttl_s),
                "ttl": float(ttl_s)}
            self._stats["installs"] += 1
        obs.REGISTRY.counter("devcache.installs").inc()
        obs.attrib.account("devcache.installs", scope=key[0],
                           client=client)
        obs.REGISTRY.gauge("session.resident_bytes").set(
            self.session_resident_bytes())
        return True

    def session_get(self, sid: str, model: str, layer: str,
                    touch: bool = True) -> Optional[Any]:
        """The session's resident state for one layer, or None (not
        resident — evicted/expired/never installed; the caller revives
        from the arena spill). A hit refreshes BOTH recencies: the LRU
        position and the TTL deadline — an actively decoding session
        never expires under it. Expiry is checked lazily here as well
        as by the sweep, so a shrunk-TTL test observes it without
        waiting for a cadence."""
        key = (session_scope(sid), str(model), str(layer))
        with self._mu:
            entry = self._entries.get(key)
            meta = self._session_meta.get(key)
            if entry is None or meta is None:
                return None
            if time.monotonic() >= meta["deadline"]:
                meta["expired"] = True
                self._drop_entry_locked(key)
                return None
            if touch:
                self._entries.move_to_end(key)
                meta["deadline"] = time.monotonic() + meta["ttl"]
            return entry[0][0]

    def session_update(self, sid: str, model: str, layer: str,
                       value: Any, nbytes: Optional[int] = None) -> bool:
        """Swap one resident entry's value IN PLACE (the decode step's
        state advance): same key, new value, bytes re-accounted, LRU
        and TTL refreshed. Returns False when the entry is not
        resident — the caller re-installs via :meth:`session_put`
        (the revive-from-arena path) instead of mutating a ghost.
        ``nbytes`` as for :meth:`session_put`; a value that carries no
        size of its own keeps the entry's."""
        key = (session_scope(sid), str(model), str(layer))
        if nbytes is None:
            nbytes = _value_nbytes(value)
        with self._mu:
            entry = self._entries.get(key)
            meta = self._session_meta.get(key)
            if entry is None or meta is None:
                return False
            nbytes = int(nbytes) or entry[1]
            self._bytes += nbytes - entry[1]
            self._entries[key] = ([value], nbytes)
            self._entries.move_to_end(key)
            meta["deadline"] = time.monotonic() + meta["ttl"]
            if self.enabled:
                self._evict_to_fit_locked(0)
        obs.REGISTRY.gauge("session.resident_bytes").set(
            self.session_resident_bytes())
        return True

    def session_evict_one(self, model: str, skip=()) -> Optional[str]:
        """Evict (spilling) the least recently used session entry of
        ``model`` whose session is not in ``skip`` — how a model whose
        slab is full makes room for one more session. Returns the
        evicted session's id, or None when every entry is skipped."""
        with self._mu:
            for key in self._entries:
                if key not in self._session_meta or key[1] != str(model):
                    continue
                sid = str(key[0])[len(SESSION_SCOPE_PREFIX):]
                if sid in skip:
                    continue
                self._drop_entry_locked(key)
                break
            else:
                return None
        obs.REGISTRY.gauge("session.resident_bytes").set(
            self.session_resident_bytes())
        return sid

    # --- session slabs: one model's state of every slot, on the device --
    def slab_install(self, model: str, slab: "SessionSlab"
                     ) -> "SessionSlab":
        """Keep ``slab`` as ``model``'s (first install wins)."""
        with self._mu:
            self._session_on = True
            return self._slabs.setdefault(str(model), slab)

    def slab(self, model: str) -> Optional["SessionSlab"]:
        with self._mu:
            return self._slabs.get(str(model))

    def slab_drop(self, model: str) -> bool:
        with self._mu:
            return self._slabs.pop(str(model), None) is not None

    def session_drop(self, sid: str) -> int:
        """Drop EVERY entry of one session with NO spill (the
        SESSION_CLOSE path — closed state must not linger in the
        arena). Returns entries dropped."""
        scope = session_scope(sid)
        with self._mu:
            keys = list(self._by_scope.get(scope, ()))
            for key in keys:
                self._session_meta.pop(key, None)  # popped FIRST: no
                # spill, no eviction tick — this is a close, not
                # memory pressure
                self._drop_entry_locked(key)
        obs.REGISTRY.gauge("session.resident_bytes").set(
            self.session_resident_bytes())
        return len(keys)

    def session_sweep(self, now: Optional[float] = None) -> int:
        """Drop (spilling) every session entry past its TTL deadline —
        the cadence-driven half of expiry (the lazy half lives in
        :meth:`session_get`). Returns entries expired."""
        now = time.monotonic() if now is None else now
        with self._mu:
            expired = [k for k, m in self._session_meta.items()
                       if now >= m["deadline"]]
            for key in expired:
                self._session_meta[key]["expired"] = True
                self._drop_entry_locked(key)
        if expired:
            obs.REGISTRY.gauge("session.resident_bytes").set(
                self.session_resident_bytes())
        return len(expired)

    def session_resident_bytes(self) -> int:
        """Live bytes across every resident session entry — the
        ``session.resident_bytes`` gauge's source of truth."""
        with self._mu:
            return sum(self._entries[k][1] for k in self._session_meta
                       if k in self._entries)

    def session_entries(self) -> int:
        with self._mu:
            return len(self._session_meta)

    # --- introspection ------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Counter snapshot (the ``compile_stats()`` analogue for the
        transfer path) — also shipped in the serve COLLECT_STATS
        reply."""
        with self._mu:
            out = {k: v for k, v in self._stats.items()
                   if self._session_on or not k.startswith("session_")}
            out["bytes"] = self._bytes
            out["entries"] = len(self._entries)
            out["budget_bytes"] = self._budget
            if self._session_on:
                out["session_slab_bytes"] = sum(
                    sl.nbytes for sl in self._slabs.values())
                out["session_entries"] = len(self._session_meta)
                out["session_bytes"] = sum(
                    self._entries[k][1] for k in self._session_meta
                    if k in self._entries)
            if self.partial:
                # who drives the hot-prefix pin budget: the static
                # knob or the feedback loop (device_cache_pin_auto)
                out["pin_budget_bytes"] = self._pin_budget
                out["pin_auto"] = self._pin_auto
            return out
