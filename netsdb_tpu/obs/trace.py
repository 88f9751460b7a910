"""Query-scoped tracing — the ``-DPROFILING`` spans, structured.

The reference answers "where did this query spend its time" with
wall-clock spans around planning and every pipeline phase
(``QuerySchedulerServer.cc:1336-1341``, ``PipelineStage.cc:1084-1101``)
printed per stage. Here the same spans are STRUCTURED and query-scoped:
a :class:`QueryTrace` — keyed by a query id minted client-side and
carried in frame metadata (``serve/protocol.QUERY_ID_KEY``) — collects
nested spans across client send → daemon dispatch → planner → executor
chunk loops → staging upload waits → device-cache hits, each with a
monotonic start offset, duration, category and counters (bytes staged,
chunks, traces triggered, cache hits).

Propagation is a ``contextvars.ContextVar``: the serve handler (or the
client's request path) installs the trace, and every instrumented layer
below reads it back with :func:`current_trace` — zero plumbing through
call signatures. Worker threads don't inherit the context. Staging
workers capture the trace at stream construction on the consumer's
thread and add COUNTERS only; a thread that does a piece of the
request itself (the routed ingest's slot threads) is handed the trace
with :func:`capture` / :func:`adopt`, and its spans name the span that
was open where the trace was captured as their ``parent``.

One logical request is ONE trace: a caller that sends several frames
(``ModelServing.score``: SEND_MATRIX, EXECUTE_COMPUTATIONS, then the
GET_TENSOR that reads the scores back) opens the trace around all of
them and the wire client stamps its query id on every frame, so the
daemon's profile of each frame shares it.

Every span carries an ``id`` and the ``parent`` that caused it, and
every profile a wall-clock anchor ``t0_unix_ns`` (``time.time_ns()``
read once when the trace opens). Offsets and durations stay on
``perf_counter``; the anchor only ALIGNS profiles of different
processes of one host with each other and with a device trace — a span
begins at ``t0_unix_ns + start_s * 1e9`` — and is never compared with
a deadline.

Cost discipline: tracing is ALWAYS ON (``config.obs_enabled`` is the
kill switch). The no-trace fast path of :func:`span` is one context-var
read and one ``is None`` check; with a trace active, a span is two
``perf_counter`` reads and one list append under a lock. What it costs
end to end is measured on the chip, spans on against spans off
(``PERF.md`` §3, "Cost of the instrument").

Completed traces land in a bounded :class:`TraceRing` — the daemon
keeps the last N query profiles for the ``GET_TRACE`` frame; client
processes keep their own ring (:data:`DEFAULT_RING`) for local
introspection. Everything timed is ``time.perf_counter`` — monotonic,
never wall (the serve clock discipline, enforced by the static checks).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Tuple

from netsdb_tpu.obs import metrics as _metrics
from netsdb_tpu.utils.locks import TrackedLock

#: process-wide kill switch (config.obs_enabled mirrors into this via
#: set_enabled at daemon/CLI startup); when off, no trace is ever
#: installed so every span call takes the one-check fast path
_enabled = True


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def new_query_id() -> str:
    """Client-side query-id mint — one per logical query, carried in
    frame metadata so the daemon's spans join the client's.

    HOT-PATH callers must not call this directly: qid minting decides
    whether a whole query gets traced end-to-end (client spans shipped
    via PUT_TRACE, a server profile in the ring, optional device
    profiling), and at high QPS that cost must be SAMPLED, not paid per
    request. Mint through :func:`sample_qid` (``config.
    obs_trace_sample``) — the static check in
    ``tests/test_static_checks.py`` bans ``new_query_id`` outside
    ``obs/``."""
    return uuid.uuid4().hex[:16]


class QidSampler:
    """Deterministic 1-in-N qid mint with its OWN round-robin phase.

    One per caller (each ``RemoteClient`` owns one): a PROCESS-wide
    counter phase-locks under interleaved callers — two clients
    alternating at sample=4 would give one of them ``n % 4 == 0``
    never (starved of tracing forever) and the other 1-in-2. Per-caller
    phase keeps ``RemoteClient(trace_sample=N)`` meaning exactly
    1-in-N of THAT client's requests."""

    def __init__(self):
        self._mu = threading.Lock()
        self._n = 0

    def sample(self, sample: int = 1) -> Optional[str]:
        """A fresh query id for 1 in every ``sample`` calls
        (deterministic round-robin, not random — tests and capacity
        planning both want an exact rate), None otherwise.
        ``sample <= 1`` traces everything (the PR 5 behavior); the
        serve client threads ``config.obs_trace_sample`` through here
        so high-QPS traffic traces at 1/N cost. Tracing disabled ⇒
        always None."""
        if not _enabled:
            return None
        if sample <= 1:
            return new_query_id()
        with self._mu:
            self._n += 1
            hit = self._n % int(sample) == 0
        if not hit:
            _metrics.REGISTRY.counter("obs.qid_sampled_out").inc()
            return None
        return new_query_id()


# process-default sampler for callers without their own (module-level
# sample_qid); clients mint through their own QidSampler
_default_sampler = QidSampler()


def sample_qid(sample: int = 1) -> Optional[str]:
    """Module-level convenience over the process-default
    :class:`QidSampler` — see its docstring; per-client callers hold
    their own sampler so interleaving can't skew their rate."""
    return _default_sampler.sample(sample)


class Span:
    """One timed region inside a trace. ``start_s`` is the offset from
    the trace's own start (monotonic deltas; the profile's
    ``t0_unix_ns`` places it on the wall clock). ``id`` numbers the
    span within its trace from 1; ``parent`` is the id of the span
    that was open on the recording thread when this one began (for a
    thread that :func:`adopt`-ed the trace: the span open where it was
    captured), 0 for a span directly under the trace."""

    __slots__ = ("id", "parent", "name", "category", "start_s",
                 "duration_s", "depth", "counters")

    def __init__(self, name: str, category: str, start_s: float,
                 depth: int, id: int = 0, parent: int = 0):
        self.id = id
        self.parent = parent
        self.name = name
        self.category = category
        self.start_s = start_s
        self.duration_s = 0.0
        self.depth = depth
        self.counters: Dict[str, float] = {}

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"id": self.id, "parent": self.parent,
                             "name": self.name, "category": self.category,
                             "start_s": self.start_s,
                             "duration_s": self.duration_s,
                             "depth": self.depth}
        if self.counters:
            d["counters"] = dict(self.counters)
        return d


class QueryTrace:
    """All spans + counters of one logical query on one side of the
    wire. ``origin`` says which side ("client"/"server"/"local").
    Thread-safe for counter adds and span records (staging threads
    report into the consumer's trace); the open span (``parent``) and
    its DEPTH are tracked per thread so concurrent reporters can't
    corrupt each other's stacks."""

    def __init__(self, qid: str, origin: str = "local",
                 ring: Optional["TraceRing"] = None):
        self.qid = qid
        self.origin = origin
        self._ring = ring
        self._t0 = time.perf_counter()
        # alignment only, never a deadline (module docstring)
        self._t0_unix_ns = time.time_ns()
        self._ids = itertools.count(1)
        self._mu = threading.Lock()
        self._spans: List[Span] = []
        self._counters: Dict[str, float] = {}
        self._meta: Dict[str, Any] = {}
        self._sections: Dict[str, Any] = {}
        # per thread: (id of the open span, depth of a span begun now)
        self._open = threading.local()
        self.total_s: Optional[float] = None  # set by finish()

    # --- spans --------------------------------------------------------
    def here(self) -> Tuple[int, int]:
        """(id of the span open on this thread, the depth a span begun
        now gets); (0, 0) under no span."""
        return getattr(self._open, "v", (0, 0))

    @contextlib.contextmanager
    def span(self, name: str, category: str = "") -> Iterator[Span]:
        outer = self.here()
        sp = Span(name, category, time.perf_counter() - self._t0,
                  outer[1], next(self._ids), outer[0])
        self._open.v = (sp.id, outer[1] + 1)
        try:
            yield sp
        finally:
            sp.duration_s = (time.perf_counter() - self._t0) - sp.start_s
            self._open.v = outer
            with self._mu:
                self._spans.append(sp)

    def record(self, name: str, duration_s: float, category: str = "",
               start_s: Optional[float] = None, **counters) -> None:
        """Record an already-measured region (e.g. the frame decode
        that finished before the trace could open)."""
        if start_s is None:
            start_s = (time.perf_counter() - self._t0) - duration_s
        parent, depth = self.here()
        sp = Span(name, category, start_s, depth, next(self._ids), parent)
        sp.duration_s = duration_s
        if counters:
            sp.counters.update(counters)
        with self._mu:
            self._spans.append(sp)

    def backdate(self, seconds: float) -> None:
        """Shift the trace start ``seconds`` earlier — for work that
        finished before the trace could open (the serve frame decode):
        a span then :meth:`record`-ed at offset 0 occupies real
        timeline ahead of the first live span instead of overlapping
        it, and ``total_s`` covers it. The wall-clock anchor moves with
        the start."""
        self._t0 -= float(seconds)
        self._t0_unix_ns -= int(float(seconds) * 1e9)

    # --- counters -----------------------------------------------------
    def add(self, counter: str, n: float = 1) -> None:
        with self._mu:
            self._counters[counter] = self._counters.get(counter, 0) + n

    def annotate(self, key: str, value: Any) -> None:
        """Attach a non-numeric fact to the profile (``meta`` section):
        the device-profile directory, the client identity, a sampling
        note — things counters cannot carry."""
        with self._mu:
            self._meta[str(key)] = value

    def attach_section(self, name: str, payload: Any) -> None:
        """Attach a structured top-level profile section BEFORE the
        trace finishes — the in-process form of
        :meth:`TraceRing.merge_section` (which handles sections that
        arrive after the push, e.g. PUT_TRACE). The executor's
        per-operator tree rides here as ``operators``."""
        with self._mu:
            self._sections[str(name)] = payload

    # --- lifecycle ----------------------------------------------------
    def finish(self) -> Dict[str, Any]:
        """Close the trace (idempotent on total_s) and push its profile
        to the ring. Returns the profile."""
        if self.total_s is None:
            self.total_s = time.perf_counter() - self._t0
        prof = self.profile()
        if self._ring is not None:
            self._ring.push(prof)
        return prof

    def profile(self) -> Dict[str, Any]:
        """Msgpack-safe profile dict — what GET_TRACE ships."""
        with self._mu:
            spans = [s.as_dict() for s in
                     sorted(self._spans, key=lambda s: s.start_s)]
            counters = dict(self._counters)
            meta = dict(self._meta)
            sections = dict(self._sections)
        out: Dict[str, Any] = {"qid": self.qid, "origin": self.origin,
                               "t0_unix_ns": self._t0_unix_ns,
                               "total_s": self.total_s, "spans": spans,
                               "counters": counters}
        out.update(sections)
        if meta:
            out["meta"] = meta
        return out


class TraceRing:
    """Bounded ring of completed query profiles — the GET_TRACE
    source. Push-side cheap; ``last(n)`` returns newest-last."""

    def __init__(self, capacity: int = 64, pending_capacity: int = 32):
        self._mu = TrackedLock("TraceRing._mu")
        self._cap = max(int(capacity), 1)
        self._items: List[Dict[str, Any]] = []
        # the newest sections, for profiles that ring AFTER their
        # section arrived (the reply-before-ring race, merge_section
        # docstring); qid → {section: payload}, oldest evicted first
        self._pending_cap = max(int(pending_capacity), 1)
        self._pending: Dict[str, Dict[str, Any]] = {}

    def push(self, profile: Dict[str, Any]) -> None:
        with self._mu:
            qid = profile.get("qid")
            # get, not pop: one query id may ring SEVERAL profiles (a
            # request of several frames), and each joins the section
            pend = self._pending.get(qid) if qid else None
            if pend:
                profile = {**profile, **pend}
            self._items.append(profile)
            if len(self._items) > self._cap:
                del self._items[:len(self._items) - self._cap]

    def last(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._mu:
            items = list(self._items)
        return items if n is None else items[-int(n):]

    def find(self, qid: str) -> List[Dict[str, Any]]:
        with self._mu:
            return [p for p in self._items if p.get("qid") == qid]

    def merge_section(self, qid: str, section: str, payload: Any) -> bool:
        """Attach ``payload`` under ``section`` on every ringed profile
        of ``qid`` — the PUT_TRACE merge: a client's shipped span
        profile joins the daemon profile minted under the same query
        id, so GET_TRACE returns ONE end-to-end decomposition. Returns
        True when at least one ringed profile matched.

        NO causal ordering protects this: the reply goes out INSIDE
        the trace context (``_dispatch_traced``), the ring push happens
        at trace finish AFTER it — so a fast client shipping on its
        own connection can beat the push. The section is therefore
        also BUFFERED (bounded, oldest-evicted) and :meth:`push`
        folds it into every profile of the qid that lands later — the
        last frame's profile of a several-frame request rings after
        the client has shipped; only a qid that never rings (rotated
        out, never sampled) stays unmatched.

        COPY-ON-MERGE: ``last``/``find`` hand out the ringed dicts
        themselves (a GET_TRACE reply may be mid-serialization on
        another connection) — mutating one in place would change a
        dict under iteration. The merge REPLACES the ring slot with an
        extended shallow copy instead; readers holding the old dict
        keep a consistent (pre-merge) profile."""
        with self._mu:
            hit = False
            for i, p in enumerate(self._items):
                if p.get("qid") == qid:
                    merged = dict(p)
                    merged[section] = payload
                    self._items[i] = merged
                    hit = True
            self._pending.setdefault(qid, {})[section] = payload
            while len(self._pending) > self._pending_cap:
                self._pending.pop(next(iter(self._pending)))
            return hit

    def clear(self) -> None:
        with self._mu:
            self._items.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._items)


#: ring for traces opened without an explicit ring (client-side
#: requests, in-process queries) — daemons own a per-controller ring
DEFAULT_RING = TraceRing()

_current: "contextvars.ContextVar[Optional[QueryTrace]]" = \
    contextvars.ContextVar("netsdb_obs_trace", default=None)


def current_trace() -> Optional[QueryTrace]:
    return _current.get()


def capture() -> Optional[Tuple[QueryTrace, Tuple[int, int]]]:
    """The current trace and the span open on this thread, for a
    worker thread to :func:`adopt` (None without a trace)."""
    tr = _current.get()
    return None if tr is None else (tr, tr.here())


@contextlib.contextmanager
def adopt(captured) -> Iterator[None]:
    """Install a :func:`capture`-d trace on THIS thread for the
    duration: spans recorded here join it as children of the span that
    was open where it was captured. A no-op for None."""
    if captured is None:
        yield
        return
    tr, here = captured
    token = _current.set(tr)
    tr._open.v = here
    try:
        yield
    finally:
        tr._open.v = (0, 0)
        _current.reset(token)


def record_into(captured, name: str, duration_s: float,
                category: str = "", ended_ago_s: float = 0.0,
                **counters) -> None:
    """Record a region that ended ``ended_ago_s`` ago (just now by
    default), ``duration_s`` long, into a :func:`capture`-d trace from
    a thread that serves many requests' traces at once and so adopts
    none (a decode batch leader): the span becomes a child of the span
    that was open where the trace was captured. A no-op for None; a
    trace that has finished meanwhile keeps the span off its pushed
    profile."""
    if captured is None:
        return
    tr, (parent, depth) = captured
    sp = Span(name, category,
              (time.perf_counter() - tr._t0) - float(ended_ago_s)
              - float(duration_s), depth, next(tr._ids), parent)
    sp.duration_s = float(duration_s)
    if counters:
        sp.counters.update(counters)
    with tr._mu:
        tr._spans.append(sp)


@contextlib.contextmanager
def trace(qid: Optional[str] = None, origin: str = "local",
          ring: Optional[TraceRing] = None) -> Iterator[Optional[QueryTrace]]:
    """Install a :class:`QueryTrace` as the current context's trace for
    the duration; finish (and ring-push) it on exit. Yields None — and
    installs nothing — when tracing is disabled or a trace is already
    active (a nested logical query joins the outer trace's spans
    instead of shadowing it)."""
    if not _enabled or _current.get() is not None:
        yield None
        return
    tr = QueryTrace(qid or new_query_id(), origin,
                    ring if ring is not None else DEFAULT_RING)
    token = _current.set(tr)
    try:
        yield tr
    finally:
        _current.reset(token)
        tr.finish()
        _metrics.REGISTRY.counter(f"obs.traces.{origin}").inc()


@contextlib.contextmanager
def span(name: str, category: str = "") -> Iterator[Optional[Span]]:
    """Span on the current trace, or a no-op when none is active — the
    form every instrumented layer uses (executor loops, staging waits,
    serve dispatch). The inactive path is one context-var read."""
    tr = _current.get()
    if tr is None:
        yield None
        return
    with tr.span(name, category) as sp:
        yield sp


def add(counter: str, n: float = 1) -> None:
    """Bump a counter on the current trace (no-op without one)."""
    tr = _current.get()
    if tr is not None:
        tr.add(counter, n)
