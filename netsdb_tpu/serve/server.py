"""Resident controller daemon — PDBServer + master functionalities.

One process plays the reference's master *and* worker roles: it owns the
TPU (single-controller JAX), the SetStore with device-resident weight
tensors, the catalog, and the compiled-plan cache — all of which stay
live across client sessions, the way netsDB's master runs forever with
model weight sets loaded while many clients run queries
(``src/mainServer/source/MasterMain.cc:64-96``,
``src/queries/headers/QueryClient.h:160-224``).

Structure mirrors ``PDBServer``: a listener thread accepts connections
and hands each to a worker thread; a handler map keyed by frame type
dispatches messages (``src/pdbServer/headers/PDBServer.h:39-152``, where
handlers are registered per object TYPEID). Query jobs additionally pass
through a bounded admission semaphore — the job-queue role of
``QuerySchedulerServer`` — so N clients can run concurrently without
overcommitting the controller.
"""

from __future__ import annotations

import contextlib
import contextvars
import importlib
import importlib.util
import inspect
import itertools
import os
import socket
import threading
import time
import traceback
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration, DEFAULT_CONFIG
from netsdb_tpu.serve import sched as _sched
from netsdb_tpu.serve import placement as _placement
from netsdb_tpu.serve import rebalance as _rebalance
from netsdb_tpu.serve import shard as _shard
from netsdb_tpu.serve import ha as _ha
from netsdb_tpu.serve import sessions as _sessions
from netsdb_tpu.serve.sched.sessions import DECODE_LANE
from netsdb_tpu.serve.errors import (
    BACKPRESSURE_FIELDS,
    AdmissionFull,
    CorruptFrame,
    FollowerDegraded,
    LaneSaturated,
    NotLeader,
    NotLeaderError,
    PlacementStale,
    RequestInFlight,
    ShardUnavailable,
)
from netsdb_tpu.serve.protocol import (
    CLIENT_ID_KEY,
    CODEC_MSGPACK,
    CODEC_PICKLE,
    HA_TERM_KEY,
    IDEMPOTENCY_KEY,
    LANE_KEY,
    MAX_FRAME_BYTES,
    OBS_FRAMES,
    PLACEMENT_EPOCH_KEY,
    PROTO_VERSION,
    QUERY_ID_KEY,
    SESSION_KEY,
    SHARD_SLOT_KEY,
    MsgType,
    ProtocolError,
    decode_body,
    recv_frame,
    recv_frame_raw,
    send_frame,
    tensor_from_wire,
)
from netsdb_tpu.storage.mutlog import MutationLog
from netsdb_tpu.storage.store import SetIdentifier
from netsdb_tpu.utils.locks import TrackedLock
from netsdb_tpu.utils.timing import deadline_after, seconds_left, wall_now

#: the in-flight frame's idempotency token, installed for the
#: handler's dynamic extent. The handoff path needs it: a batch
#: buffered for a degraded shard must drain under the CLIENT's token,
#: so a retry re-routed through the leader after the shard already
#: applied the original (reply lost, then eviction) deduplicates at
#: the shard instead of double-appending.
_idem_token_var: "contextvars.ContextVar[Optional[str]]" = \
    contextvars.ContextVar("netsdb_idem_token", default=None)


def resolve_entry_point(entry: str, source: Optional[str] = None) -> Any:
    """'pkg.mod:attr' → live object — the analogue of the reference
    loading a registered UDF .so and fixing up its vtable
    (``src/objectModel/headers/VTableMap.h:36-80``).

    ``source``: shipped module text from the catalog. If the module is
    not importable here, it is exec'd into a fresh module under the
    shipped name (the daemon-side ``dlopen`` of a replicated .so,
    ``PDBCatalog.h:45-50``). TRUST BOUNDARY: executing shipped source
    is code execution by design, exactly like the pickle codec
    (serve/protocol.py security note) and the reference's .so shipping
    — the serve layer is a trusted-cluster control plane behind the
    HELLO token."""
    mod_name, _, attr = entry.partition(":")
    try:
        obj = importlib.import_module(mod_name)
    except ModuleNotFoundError:
        if source is None:
            raise
        import sys

        spec = importlib.util.spec_from_loader(mod_name, loader=None)
        mod = importlib.util.module_from_spec(spec)
        exec(compile(source, f"<registered:{mod_name}>", "exec"),
             mod.__dict__)
        sys.modules[mod_name] = mod  # later imports see the shipped code
        obj = mod
    for part in attr.split(".") if attr else []:
        obj = getattr(obj, part)
    return obj


class _RWOrder:
    """Tiny readers-writer lock for mirrored-frame ordering: SET-scoped
    frames hold it shared (plus their per-set lock), global frames
    (jobs, flush, DDL without a set target) hold it exclusively — so
    frames on DIFFERENT sets run concurrently while anything that can
    observe multiple sets serializes against all of them."""

    def __init__(self):
        self._mu = threading.Lock()
        self._readers = 0
        self._no_readers = threading.Condition(self._mu)
        self._writer = threading.Lock()

    def acquire_read(self):
        self._writer.acquire()  # barrier: writers exclude new readers
        with self._mu:
            self._readers += 1
        self._writer.release()

    def release_read(self):
        with self._mu:
            self._readers -= 1
            if self._readers == 0:
                self._no_readers.notify_all()

    def acquire_write(self):
        self._writer.acquire()
        with self._mu:
            while self._readers:
                self._no_readers.wait()

    def release_write(self):
        self._writer.release()


class _FollowerLink:
    """One follower daemon's ordered frame pipe: a FIFO queue drained by
    a dedicated sender thread, so the follower receives mirrored frames
    in exactly the enqueue order while the enqueuer (and the master's
    handler) runs on. ``submit`` returns a record whose ``done`` event
    fires when the follower acked (or errored)."""

    def __init__(self, addr: str, client):
        import queue

        self.addr = addr
        self.client = client
        self.q: "queue.Queue" = queue.Queue()
        # submit/close are atomic under this lock, so every real item
        # precedes the close sentinel in the queue — nothing can be
        # enqueued behind it and wait forever on its "done" event
        self._lk = TrackedLock("_FollowerLink._lk")
        self._closed = False
        #: mutation-log END offset of the last frame this follower
        #: ACKED — the log-replay resync's resume position. Written
        #: only by the drain thread (FIFO: monotone by construction),
        #: read by the evictor after close(); None until the first
        #: logged frame acks (or when the mutation log is off).
        self.acked_offset: Optional[int] = None
        self.thread = threading.Thread(target=self._drain, daemon=True)
        self.thread.start()

    def submit(self, typ, payload, codec,
               offset: Optional[int] = None) -> Dict[str, Any]:
        """Enqueue one frame; ``offset`` is its mutation-log END
        offset (None when the frame was not logged — stats fan-outs,
        HA_STATE announcements, or the log is off)."""
        rec: Dict[str, Any] = {"done": threading.Event(),
                               "mutlog_off": offset}
        with self._lk:
            if self._closed:
                rec["error"] = (f"{self.addr}: follower link closed "
                                f"(evicted or daemon shutdown)")
                rec["done"].set()
                return rec
            self.q.put((typ, payload, codec, rec))
        return rec

    def close(self, abort: bool = False) -> None:
        """Stop the drain thread. ``abort=True`` additionally tears the
        client socket down from this thread, so a drain blocked in a
        recv on a hung follower fails immediately instead of holding
        mirror records (and their waiters) forever — the eviction
        path."""
        with self._lk:
            if not self._closed:
                self._closed = True
                self.q.put(None)
        if abort:
            self.client._force_close()

    def _drain(self) -> None:
        while True:
            item = self.q.get()
            if item is None:
                return
            typ, payload, codec, rec = item
            if self._closed:
                # evicted mid-queue: items behind the failed one must
                # fail fast, NOT re-dial the dead follower (the client
                # would reconnect with no timeout and could hang this
                # thread forever, un-abortable — the link is done).
                # Each such frame never reached the follower — counted
                # so operators see the divergence depth before the
                # resync closes it (COLLECT_STATS mirror section).
                obs.REGISTRY.counter("serve.mirror_dropped").inc()
                rec["error"] = (f"{self.addr}: follower link closed "
                                f"(evicted) — frame not forwarded")
                rec["done"].set()
                continue
            try:
                rec["reply"] = self.client._request(typ, payload, codec)
                if rec.get("mutlog_off") is not None:
                    self.acked_offset = rec["mutlog_off"]
            except Exception as e:  # noqa: BLE001 — surfaced by caller
                rec["error"] = (f"{self.addr}: {type(e).__name__}: {e}")
                rec["exc"] = e  # typed inspection (NotLeader fencing)
            finally:
                rec["done"].set()


class _IdempotencyCache:
    """Completed-reply cache keyed by client idempotency token — the
    server half of the at-most-once contract for mutating frames. A
    retry whose original is still executing parks on its event instead
    of re-running the handler (double-apply is the failure mode this
    whole class exists to prevent); a retry of a completed request gets
    the cached reply frame verbatim.

    ``persist_path`` (a sqlite file next to the catalog sqlite) makes
    completed tokens survive a daemon RESTART: without it the cache is
    in-memory only, so a client retrying a mutation across a restart
    would re-execute it (the double-apply the ROADMAP open item names).
    Replies persist pickled (the trusted-control-plane boundary, same
    as the checkpoint snapshots); unpicklable replies simply stay
    memory-only — the restart window then degrades to re-execution for
    that one request, never a crash. Rows are pruned to ``capacity``
    on the snapshot-prune path (:meth:`prune`)."""

    def __init__(self, capacity: int = 4096,
                 persist_path: Optional[str] = None):
        self._mu = TrackedLock("_IdempotencyCache._mu")
        self._done: "OrderedDict[str, Tuple]" = OrderedDict()
        self._inflight: Dict[str, threading.Event] = {}
        self._capacity = capacity
        self._db = None
        #: tokens answered from the persisted table (observability for
        #: the restart tests; memory hits don't count)
        self.persist_hits = 0
        self._since_prune = 0
        if persist_path:
            import sqlite3

            parent = os.path.dirname(persist_path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            # one connection, shared across handler threads under _mu.
            # WAL + synchronous=NORMAL: the per-mutation commit must
            # not fsync on the request path (durable across clean
            # restarts, which is the contract — a power loss losing the
            # last tokens degrades to re-execution, same as no cache)
            self._db = sqlite3.connect(persist_path,
                                       check_same_thread=False)
            try:
                self._db.execute("PRAGMA journal_mode=WAL")
                self._db.execute("PRAGMA synchronous=NORMAL")
            except sqlite3.Error:
                pass  # fall back to default journaling
            self._db.execute("CREATE TABLE IF NOT EXISTS idem "
                             "(token TEXT PRIMARY KEY, reply BLOB)")
            self._db.commit()

    def _load_persisted(self, token: str) -> Optional[Tuple]:
        """Caller holds ``_mu``. None on any persistence trouble — the
        worst case is re-execution, never a wedged request."""
        import pickle
        import sqlite3

        if self._db is None:
            return None
        try:
            row = self._db.execute(
                "SELECT reply FROM idem WHERE token = ?",
                (token,)).fetchone()
            if row is None:
                return None
            result = pickle.loads(row[0])
        except (sqlite3.Error, pickle.UnpicklingError, ValueError,
                EOFError, AttributeError, ImportError):
            return None
        self.persist_hits += 1
        self._done[token] = result
        return result

    def _persist(self, token: str, result: Tuple) -> None:
        """Caller holds ``_mu``. Best-effort: replies that cannot
        pickle (live buffers) or a busy sqlite stay memory-only."""
        import pickle
        import sqlite3

        if self._db is None:
            return
        try:
            blob = pickle.dumps(result, protocol=pickle.HIGHEST_PROTOCOL)
            self._db.execute(
                "INSERT OR REPLACE INTO idem (token, reply) VALUES (?, ?)",
                (token, blob))
            self._db.commit()
        except (sqlite3.Error, pickle.PicklingError, TypeError,
                ValueError):
            return

    def claim(self, token: str, wait_s: float) -> Optional[Tuple]:
        """Returns the cached (reply_type, reply, codec) when ``token``
        already completed; None when the caller now OWNS execution (it
        must call :meth:`finish` or :meth:`abort`). Raises
        :class:`RequestInFlight` when the original execution is still
        running after ``wait_s`` — the client backs off and retries."""
        deadline = deadline_after(wait_s)
        while True:
            with self._mu:
                if token in self._done:
                    self._done.move_to_end(token)
                    obs.REGISTRY.counter("serve.idem.memory_hits").inc()
                    return self._done[token]
                cached = self._load_persisted(token)
                if cached is not None:
                    obs.REGISTRY.counter("serve.idem.persist_hits").inc()
                    return cached
                ev = self._inflight.get(token)
                if ev is None:
                    self._inflight[token] = threading.Event()
                    return None
            left = seconds_left(deadline)
            if left <= 0 or not ev.wait(left):
                raise RequestInFlight(
                    f"duplicate request {token[:8]}… still executing "
                    f"after {wait_s}s")
            # original finished (or aborted) — loop to re-check

    def finish(self, token: str, result: Tuple) -> None:
        with self._mu:
            self._done[token] = result
            self._persist(token, result)
            self._since_prune += 1
            # a daemon with no followers never hits the snapshot-prune
            # path, so the table must self-bound too (cheap: one DELETE
            # per _capacity/4 inserts)
            prune_now = self._since_prune >= max(self._capacity // 4, 64)
            if prune_now:
                self._since_prune = 0
            while len(self._done) > self._capacity:
                self._done.popitem(last=False)
            ev = self._inflight.pop(token, None)
        if ev is not None:
            ev.set()
        if prune_now:
            self.prune()

    def abort(self, token: str) -> None:
        """The execution failed without a durable effect worth caching
        (transient fault) — release waiters so a retry re-executes."""
        with self._mu:
            ev = self._inflight.pop(token, None)
        if ev is not None:
            ev.set()

    def alias(self, token: str, target: str) -> bool:
        """Finish ``token`` with ``target``'s cached reply — the
        follower half of the TOKEN_ALIAS frame: a coalesce WAITER's
        token maps onto its leader's mirrored execution, so the
        waiter's post-failover retry dedupes here instead of
        re-executing. False when ``target`` is unknown (the alias
        outran or outlived the mirrored execution's cached reply —
        the retry then degrades to re-execution, never divergence)."""
        with self._mu:
            result = self._done.get(target)
            if result is not None:
                self._done.move_to_end(target)
            else:
                result = self._load_persisted(target)
            if result is None:
                return False
            self._done[token] = result
            self._persist(token, result)
            while len(self._done) > self._capacity:
                self._done.popitem(last=False)
            ev = self._inflight.pop(token, None)
        if ev is not None:
            ev.set()
        return True

    def prune(self) -> None:
        """Drop the oldest persisted tokens beyond ``capacity`` — runs
        on the existing snapshot-prune path (a flapping follower must
        not fill the leader's disk with either snapshots or tokens)."""
        import sqlite3

        with self._mu:
            if self._db is None:
                return
            try:
                self._db.execute(
                    "DELETE FROM idem WHERE rowid NOT IN (SELECT rowid "
                    "FROM idem ORDER BY rowid DESC LIMIT ?)",
                    (self._capacity,))
                self._db.commit()
            except sqlite3.Error:
                return

    def close(self) -> None:
        import sqlite3

        with self._mu:
            db, self._db = self._db, None
            if db is not None:
                try:
                    db.close()
                except sqlite3.Error:
                    pass


def _blob_view(b) -> memoryview:
    """Chunk blob → memoryview. Out-of-band blobs arrive as writable
    uint8 arrays, small inline ones as bytes; both are buffers."""
    return memoryview(b)


class _BulkAssembler:
    """Server half of one streamed-ingest conversation: ``add`` decodes
    a chunk as it lands (OUTSIDE any set lock — the windowed pipeline
    overlaps this work with the client's next sends), ``finish`` builds
    the payload the target op's handler applies under its normal
    ordering locks at COMMIT."""

    def __init__(self, meta: dict):
        self.meta = meta
        self.chunks = 0

    def add(self, payload: dict) -> None:
        raise NotImplementedError

    def finish(self) -> Tuple[dict, int]:
        raise NotImplementedError


class _ItemsAssembler(_BulkAssembler):
    """Pickled item batches (object rows / as_table row dicts)."""

    def __init__(self, meta: dict, allow_pickle: bool):
        super().__init__(meta)
        if not allow_pickle:
            raise ProtocolError(
                "bulk item ingest refused: chunks carry pickle and this "
                "daemon has allow_pickle off")
        self.items: list = []

    def add(self, payload: dict) -> None:
        import pickle

        self.items.extend(pickle.loads(_blob_view(payload["blob"])))
        self.chunks += 1

    def finish(self) -> Tuple[dict, int]:
        out = {"db": self.meta["db"], "set": self.meta["set"],
               "items": self.items}
        if self.meta.get("as_table"):
            out.update(as_table=True,
                       date_cols=list(self.meta.get("date_cols") or ()),
                       append=bool(self.meta.get("append")))
        return out, CODEC_PICKLE


class _TableAssembler(_BulkAssembler):
    """Row-range column slices of one ColumnTable: the full columns are
    preallocated from the BEGIN meta (``nrows``) on the first chunk and
    each chunk lands at its row offset INSIDE ``add`` — the assembly
    copy overlaps the client's in-flight sends instead of serializing
    at COMMIT. ``finish`` only rebuilds the table around the filled
    arrays (with the dictionaries that traveled once in BEGIN) after
    checking row coverage."""

    def __init__(self, meta: dict):
        super().__init__(meta)
        self.nrows = int(meta.get("nrows") or 0)
        self.cols: Optional[Dict[str, np.ndarray]] = None
        self.filled = 0

    def add(self, payload: dict) -> None:
        start, stop = (int(v) for v in payload["rows"])
        if self.cols is None:
            self.cols = {
                name: np.empty((self.nrows,) + np.asarray(arr).shape[1:],
                               np.asarray(arr).dtype)
                for name, arr in payload["cols"].items()}
        for name, arr in payload["cols"].items():
            self.cols[name][start:stop] = np.asarray(arr)
        self.filled += stop - start
        self.chunks += 1

    def finish(self) -> Tuple[dict, int]:
        from netsdb_tpu.relational.table import ColumnTable

        if self.filled != self.nrows or self.cols is None:
            raise CorruptFrame(
                f"bulk table stream covered {self.filled} of "
                f"{self.nrows} rows")
        table = ColumnTable(
            self.cols,
            {k: list(v) for k, v in (self.meta.get("dicts") or {}).items()},
            None)
        return {"db": self.meta["db"], "set": self.meta["set"],
                "items": table, "as_table": True,
                "date_cols": list(self.meta.get("date_cols") or ()),
                "append": bool(self.meta.get("append"))}, CODEC_PICKLE


class _BlobAssembler(_BulkAssembler):
    """Opaque byte stream (the wire-streamed RESYNC_FOLLOWER snapshot):
    chunks land in a preallocated buffer at their running offset."""

    def __init__(self, meta: dict):
        super().__init__(meta)
        self.buf = bytearray(int(meta.get("nbytes") or 0))
        self.off = 0

    def add(self, payload: dict) -> None:
        mv = _blob_view(payload["blob"])
        end = self.off + mv.nbytes
        if end > len(self.buf):
            # more bytes than BEGIN declared: a torn/duplicated stream
            # (or a lying peer) — refuse instead of growing unbounded
            raise CorruptFrame(
                f"bulk blob stream overflowed its declared "
                f"{len(self.buf)} bytes at offset {self.off}")
        self.buf[self.off:end] = mv
        self.off = end
        self.chunks += 1

    def finish(self) -> Tuple[dict, int]:
        out = dict(self.meta)
        out.pop("nbytes", None)
        out["snapshot_blob"] = memoryview(self.buf)[:self.off]  # no copy
        return out, CODEC_PICKLE


class ServeController:
    """The daemon. ``start()`` runs the listener on a background thread
    (tests); ``serve_forever()`` blocks (the CLI ``serve`` command)."""

    #: frame types every worker must replay for SPMD consistency — the
    #: reference's DDL fan-out + job broadcast (DistributedStorageManager
    #: / HermesExecutionServer.cc:1225-1274). Reads stay master-local.
    MIRRORED = frozenset({
        MsgType.CREATE_DATABASE, MsgType.CREATE_SET, MsgType.REMOVE_SET,
        MsgType.CLEAR_SET, MsgType.REGISTER_TYPE, MsgType.SEND_DATA,
        MsgType.SEND_MATRIX, MsgType.ADD_SHARED_MAPPING,
        MsgType.FLUSH_DATA, MsgType.LOAD_SET,
        MsgType.EXECUTE_COMPUTATIONS, MsgType.EXECUTE_PLAN,
        MsgType.DEDUP_RESIDENT,
        # the session lane: replaying opens/steps/closes at every
        # follower is what replicates the session table AND (decode
        # being deterministic) the per-session state itself — the
        # leader-kill chaos test's resume-with-no-token-reuse story
        MsgType.SESSION_OPEN, MsgType.GENERATE, MsgType.SESSION_CLOSE,
    })

    def __init__(self, config: Configuration = DEFAULT_CONFIG,
                 host: str = "127.0.0.1", port: int = 8108,
                 token: Optional[str] = None,
                 max_jobs: Optional[int] = None,
                 allow_pickle: bool = True,
                 followers: Optional[list] = None,
                 admission_timeout_s: float = 120.0,
                 frame_timeout_s: float = 30.0,
                 handshake_timeout_s: float = 10.0,
                 heartbeat_interval_s: float = 2.0,
                 heartbeat_timeout_s: float = 5.0,
                 heartbeat_misses: int = 3,
                 mirror_ack_timeout_s: Optional[float] = 300.0,
                 resync_grace_s: float = 30.0,
                 resync_timeout_s: float = 120.0,
                 workers: Optional[list] = None,
                 ha_peers: Optional[list] = None,
                 chaos=None, follower_chaos=None):
        """``followers``: addresses of worker daemons (one per other
        jax.distributed process). Every state-mutating/job frame this
        master handles is forwarded to them CONCURRENTLY with local
        execution — all processes then run the same SPMD program in the
        same order, which is what XLA's multi-controller collectives
        require (compilation is a rendezvous; sequential forwarding
        would deadlock it). The reference's master→worker job flow.

        ``workers``: addresses of SHARD daemons forming this leader's
        partitioned worker pool (the horizontal scale-out topology —
        distinct from ``followers``, which mirror for redundancy; the
        two pools are orthogonal and a sharded set's pages are never
        mirrored beyond the leader's own slot). Sets created with
        ``placement="hash"``/``"range"`` partition their pages across
        ``[this daemon] + workers``; ingest routes to owning shards,
        queries scatter-gather (``serve/shard.py``), and the leader
        owns the versioned placement map shipped in the handshake.
        Plain ``placement=None`` sets are untouched — the
        single-daemon and mirror paths stay byte-for-byte identical.

        Fault-tolerance knobs (defaults are production-shaped; the
        chaos tests shrink them):

        * ``admission_timeout_s`` — how long a job waits for an
          admission slot before the typed retryable ``AdmissionFull``.
        * ``frame_timeout_s`` — mid-frame recv bound per worker thread
          (a peer silent mid-frame can never wedge a handler), and the
          bound a duplicate idempotent request waits for its original.
        * ``heartbeat_*`` — leader→follower liveness probing over a
          dedicated connection; ``heartbeat_misses`` consecutive
          failures evict the follower into the degraded state.
        * ``mirror_ack_timeout_s`` — bound on waiting for a follower's
          mirror ack before evicting it (None = wait forever).
        * ``resync_grace_s`` — how long a mutating frame waits for an
          in-progress follower resync before the typed retryable
          ``FollowerDegraded``.
        * ``chaos``/``follower_chaos`` — explicit
          :class:`~netsdb_tpu.serve.chaos.ChaosInjector` objects for
          the client-facing and the leader→follower frame paths
          (tests only; production pays one ``is None`` check).

        ``ha_peers``: the ordered succession list arming automatic
        failover (``serve/ha.py``) — index 0 is the initial leader,
        every daemon in the pool passes the SAME list. Armed at the
        end of :meth:`start` (equivalently: call :meth:`arm_ha` after
        start). Orthogonal to ``followers``/``workers``: HA decides
        WHO leads; the mirror stream is still what carries the data."""
        self.config = config
        self.host = host
        self.port = port
        self.token = token
        self.allow_pickle = allow_pickle
        self.admission_timeout_s = admission_timeout_s
        self.frame_timeout_s = frame_timeout_s
        self.handshake_timeout_s = handshake_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.heartbeat_misses = heartbeat_misses
        self.mirror_ack_timeout_s = mirror_ack_timeout_s
        self.resync_grace_s = resync_grace_s
        self.resync_timeout_s = resync_timeout_s
        self._chaos = chaos
        self._follower_chaos = follower_chaos
        # followers dial LAZILY (with retry) on the first mirrored
        # frame: a master may legitimately start before its workers
        # bind, and eager dialing would kill it with a raw
        # ConnectionRefusedError at startup
        self._follower_addrs: list = list(followers or [])
        # addr → _FollowerLink (active, mirrored-to) and addr → reason
        # (degraded: evicted, awaiting reattach+resync). Both guarded
        # by _followers_mu; a follower address is always in exactly one
        # of {undialed, active, degraded}.
        self._links: Dict[str, _FollowerLink] = {}
        self._degraded: Dict[str, str] = {}
        self._followers_mu = TrackedLock("ServeController._followers_mu")
        # --- sharded worker pool (horizontal scale-out) ---------------
        # the leader's authoritative set→shard map (empty on plain
        # daemons — every placement probe then answers None and the
        # un-sharded paths run unchanged)
        self._worker_addrs: list = list(workers or [])
        self.placement = _placement.PlacementMap()
        # worker-side registrations: (db, set) → {"epoch", "slot"} for
        # sets this daemon holds ONE slot of (written by CREATE_SET's
        # __shard__ marker and SHARD_RESYNC, read on every routed frame)
        self._shard_sets: Dict[Tuple[str, str], Dict[str, int]] = {}
        self._shard_mu = TrackedLock("ServeController._shard_mu")
        # live-rebalance move state (serve/rebalance.py), guarded by
        # _shard_mu with the registrations they fence: write-seals
        # ((db, set) → monotonic expiry — sealed slots answer routed
        # writes typed retryable while their copy drains) and move
        # tombstones (scopes whose local copy a committed move
        # dropped — stale-epoch frames must reject, never apply into
        # the cleared set)
        self._reshard_seals: Dict[Tuple[str, str], float] = {}
        self._reshard_moved: set = set()
        # --- HA runtime (serve/ha.py) ---------------------------------
        # armed by arm_ha() / the ha_peers ctor list; None keeps every
        # single-daemon and plain-mirror path byte-identical
        self._ha: Optional[_ha.HAState] = None
        self._ha_monitor: Optional[_ha.HAMonitor] = None
        self._ha_peers: list = list(ha_peers or [])
        # per-follower mutation-log resume offsets: the END offset of
        # the last frame each (possibly former) follower is known to
        # have applied — written at eviction (link.acked_offset) and
        # after every resync; guarded by _followers_mu
        self._follower_offsets: Dict[str, int] = {}
        # durable mutation log (config.ha_mutlog): the mirror path
        # appends every forwarded frame, so a readmitted follower
        # resyncs by log REPLAY from its last applied offset instead
        # of a whole-store snapshot; `spill` is the handoff buffer's
        # disk shadow — buffered routed ingest survives leader restart
        self.mutlog: Optional[MutationLog] = None
        spill: Optional[MutationLog] = None
        if getattr(config, "ha_mutlog", False):
            self.mutlog = MutationLog(os.path.join(
                config.root_dir, "mutlog", "mirror.log"))
            spill = MutationLog(os.path.join(
                config.root_dir, "mutlog", "handoff.log"))
        # pool connections + handoff buffers + the scatter coordinator
        self.shards = _shard.ShardPool(
            self, handoff_max_bytes=getattr(config,
                                            "shard_handoff_bytes",
                                            256 << 20),
            spill=spill)
        # inbound distributed-shuffle buckets (shard side)
        self._shuffle = _shard.ShuffleInbox()
        # the self-rebalancing loop's leader-side driver: skew
        # detector on the sched-feedback cadence + the byte-bounded
        # move planner/executor (no-op until config.rebalance)
        self.rebalancer = _rebalance.Rebalancer(self)
        #: this daemon's pool identity — rewritten by start() once the
        #: real port is bound (port=0 tests)
        self.advertise_addr = f"{host}:{port}"
        # the runtime lock-order witness (utils/locks.py): config-
        # gated so a production daemon can run lockdep-style checks
        if getattr(config, "lock_witness", False):
            from netsdb_tpu.utils.locks import enable_witness

            enable_witness()
        # set while a follower resync holds the mutation path; mutating
        # frames wait for it (bounded by resync_grace_s) then fail typed
        self._resync_idle = threading.Event()
        self._resync_idle.set()
        self._resync_seq = itertools.count(1)
        #: how the last RESYNC_FOLLOWER restored ("wire" | "path") —
        #: observability for the no-shared-fs acceptance test
        self.last_resync_mode: Optional[str] = None
        # completed-token cache persists NEXT TO the catalog sqlite so
        # a daemon restart cannot double-apply a mutation retried
        # across it (ROADMAP: idempotency across daemon restarts)
        self._idem = _IdempotencyCache(persist_path=os.path.join(
            os.path.dirname(config.catalog_path), "idempotency.sqlite"))
        # query-scoped observability: this daemon's completed trace
        # profiles (GET_TRACE source) — per-controller, NOT the
        # process-default ring, so leader/follower pairs in one test
        # process keep distinct profiles
        self._obs_enabled = bool(getattr(config, "obs_enabled", True))
        self.trace_ring = obs.TraceRing(
            getattr(config, "obs_trace_ring", 64) or 64)
        # the ACTIVE observability layer (this PR): SLO/health engine
        # over the registry (HEALTH frame), the bounded on-disk
        # slow-query ring, and the opt-in per-qid device profiler
        from netsdb_tpu.obs.slo import SLOEngine
        from netsdb_tpu.obs.slowlog import SlowQueryLog

        self.slo = SLOEngine()
        # continuous telemetry: the bounded registry-snapshot ring the
        # GET_METRICS deltas and `cli obs --top` refresh from; the
        # thread starts with the listener and is JOINED at shutdown
        from netsdb_tpu.obs.history import TelemetryHistory

        self.history = TelemetryHistory(
            capacity=getattr(config, "obs_history_len", 120) or 0,
            interval_s=getattr(config, "obs_history_interval_s", 5.0)
            or 0.0)
        self.slowlog = SlowQueryLog(
            config.root_dir,
            capacity=getattr(config, "obs_slowlog_entries", 64) or 64,
            threshold_s=getattr(config, "obs_slow_query_s", None))
        self._device_profile_dir = getattr(
            config, "obs_device_profile_dir", None)
        # one jax.profiler session at a time: concurrent traced queries
        # SKIP (non-blocking acquire), never queue behind the profiler
        self._profiler_mu = TrackedLock("ServeController._profiler_mu")
        self.library = Client(config)  # the resident state
        # the stateful-serving subsystem (serve/sessions.py): session
        # table + host arena + per-model decode batcher, TTL'd mutable
        # state in the devcache above. Constructed unconditionally —
        # a daemon with no sessions pays one idle object
        self.sessions = _sessions.SessionManager(self)
        # ORDERING MODEL for mirrored frames (the SPMD argument):
        # - _mirror_lock is held only long enough to ENQUEUE a frame
        #   onto every follower's FIFO sender queue; the enqueue always
        #   happens while the frame's ORDERING lock (below) is held, so
        #   for any two frames that conflict, the master's local
        #   execution order equals every follower's receipt order —
        #   stores cannot silently diverge.
        # - jax.process_count() > 1 (true SPMD over the followers):
        #   EVERY mirrored frame serializes under _collective_lock
        #   across enqueue + local handler. Multi-controller XLA
        #   requires all processes to launch collective programs in one
        #   order, and any mutation can change what a later jitted job
        #   observes, so the only sound order is a total one — the same
        #   per-worker-connection serialization the reference's job
        #   flow has (PDBServer.h:39-152: concurrent handlers, but one
        #   socket per worker orders that worker's stream).
        # - process_count() == 1 (replicated-daemon topology, no
        #   cross-process collectives): SET-scoped frames serialize
        #   per (db,set) and hold _order shared; multi-set frames
        #   (jobs, flush) hold _order exclusively. Frames on different
        #   sets — the common ingest pattern — run concurrently, which
        #   is the round-4 concurrency win; reads never block on any
        #   of this.
        self._mirror_lock = TrackedLock("ServeController._mirror_lock")
        self._collective_lock = TrackedLock(
            "ServeController._collective_lock")
        self._order = _RWOrder()
        # per-set locks share ONE witness rank: lock LEVELS order, not
        # instances (two different sets' locks never nest)
        self._set_locks: Dict[Tuple[str, str], TrackedLock] = {}
        self._set_locks_mu = TrackedLock("ServeController._set_locks_mu")
        # the query scheduler (serve/sched/): policy-driven admission
        # replacing the old bare bounded semaphore — per-client lanes
        # with quotas/aging, identical-EXECUTE coalescing, and
        # cache-aware hot-set affinity driven by the devcache probe
        self.sched = _sched.QueryScheduler(
            slots=max_jobs or config.num_threads,
            lanes=getattr(config, "sched_lanes", None),
            quota=getattr(config, "sched_lane_quota", 0),
            aging_every=getattr(config, "sched_aging_every", 8),
            coalesce=getattr(config, "sched_coalesce", True),
            affinity=getattr(config, "sched_affinity", True),
            affinity_wait_s=getattr(config, "sched_affinity_wait_s",
                                    30.0),
            # a coalesced waiter waits out the same bound a mirror ack
            # gets: EXECUTEs may legitimately run for minutes, but a
            # hung leader must never wedge waiter handler threads
            coalesce_wait_s=mirror_ack_timeout_s or 300.0,
            coalesce_done_ttl_s=getattr(
                config, "sched_coalesce_done_ttl_s", 0.0),
            coalesce_done_max=getattr(
                config, "sched_coalesce_done_max", 32),
            cache_probe=self._devcache_warm,
            feedback=getattr(config, "sched_feedback", False),
            feedback_every=getattr(config, "sched_feedback_every", 64),
            # SLO burn-rate load shedding (opt-in): the scheduler
            # halves the heaviest non-reserved lane's quota while any
            # objective breaches on all windows (obs/slo.py's
            # multi-window agreement), restoring on recovery
            slo_source=(self.slo.breached_objectives
                        if getattr(config, "sched_slo_shed", False)
                        else None),
            # pin-budget auto-sizing: when the static knob is unset,
            # the feedback cadence re-derives the devcache hot-prefix
            # pin budget from the attribution ledger's hot-set table
            # (serve/sched/feedback.pin_budget — pinned formula)
            pin_auto=(self._refresh_pin_auto
                      if (getattr(config, "device_cache_pin_auto",
                                  False)
                          and not getattr(config,
                                          "device_cache_pin_bytes", 0))
                      else None),
            # live shard rebalancing: one skew-detector pass per
            # feedback window (serve/rebalance.py) — the loop that
            # turns sustained per-slot imbalance into bounded,
            # epoch-bumped slot moves
            rebalance_cb=(self.rebalancer.check
                          if getattr(config, "rebalance", False)
                          else None))
        self._job_seq = itertools.count(1)
        self._jobs: Dict[int, Dict[str, Any]] = {}
        self._jobs_lock = TrackedLock("ServeController._jobs_lock")
        self._started = time.monotonic()  # uptime only — never wall
        # what jax computes on; None until start() has taken the device
        self.device: Optional[Dict[str, Any]] = None
        self._stop = threading.Event()
        self._listener: Optional[socket.socket] = None
        # live accepted sockets — shutdown() half-closes them so a
        # "killed" daemon stops serving established connections too
        # (idle handler threads block in recv and never see _stop;
        # without this a dead worker could still ACK decode steps into
        # state nobody will ever push home)
        self._conns: set = set()
        self._conns_mu = TrackedLock("ServeController._conns_mu")
        self._threads: list = []
        # health/pool loop handles — promotion must be able to start
        # them on a daemon that booted with neither role
        self._health_thread: Optional[threading.Thread] = None
        self._pool_thread: Optional[threading.Thread] = None
        # handler map keyed by frame type — PDBServer::registerHandler
        self.handlers: Dict[MsgType, Callable[[Any], Tuple[MsgType, Any]]] = {
            MsgType.PING: self._on_ping,
            MsgType.CREATE_DATABASE: self._on_create_database,
            MsgType.CREATE_SET: self._on_create_set,
            MsgType.REMOVE_SET: self._on_remove_set,
            MsgType.CLEAR_SET: self._on_clear_set,
            MsgType.SET_EXISTS: self._on_set_exists,
            MsgType.LIST_SETS: self._on_list_sets,
            MsgType.REGISTER_TYPE: self._on_register_type,
            MsgType.SEND_DATA: self._on_send_data,
            MsgType.SEND_MATRIX: self._on_send_matrix,
            MsgType.GET_TENSOR: self._on_get_tensor,
            MsgType.SCAN_SET: self._on_scan_set,
            MsgType.SCAN_SET_STREAM: self._on_scan_set_stream,
            MsgType.GET_TENSOR_CHUNKED: self._on_get_tensor_chunked,
            MsgType.ADD_SHARED_MAPPING: self._on_add_shared_mapping,
            MsgType.DEDUP_RESIDENT: self._on_dedup_resident,
            MsgType.FLUSH_DATA: self._on_flush_data,
            MsgType.LOAD_SET: self._on_load_set,
            MsgType.EXECUTE_COMPUTATIONS: self._on_execute_computations,
            MsgType.EXECUTE_PLAN: self._on_execute_plan,
            MsgType.LIST_JOBS: self._on_list_jobs,
            MsgType.COLLECT_STATS: self._on_collect_stats,
            MsgType.GET_TRACE: self._on_get_trace,
            MsgType.PUT_TRACE: self._on_put_trace,
            MsgType.HEALTH: self._on_health,
            MsgType.GET_METRICS: self._on_get_metrics,
            MsgType.ANALYZE_SET: self._on_analyze_set,
            MsgType.LOCAL_SHARDS: self._on_local_shards,
            MsgType.PAGED_MATMUL: self._on_paged_matmul,
            MsgType.RESYNC_FOLLOWER: self._on_resync_follower,
            MsgType.PLACEMENT: self._on_placement,
            MsgType.SUBPLAN: self._on_subplan,
            MsgType.SHUFFLE_PUT: self._on_shuffle_put,
            MsgType.SHARD_RESYNC: self._on_shard_resync,
            MsgType.HA_STATE: self._on_ha_state,
            MsgType.TOKEN_ALIAS: self._on_token_alias,
            MsgType.RESHARD: self._on_reshard,
            MsgType.SESSION_OPEN: self._on_session_open,
            MsgType.GENERATE: self._on_generate,
            MsgType.SESSION_CLOSE: self._on_session_close,
        }

    # --- lifecycle ----------------------------------------------------
    def start(self) -> int:
        """Bind + start the listener thread; returns the bound port
        (``port=0`` picks an ephemeral one)."""
        # the daemon is not up until it holds its device: initialise
        # the backend BEFORE listening, so a daemon that cannot reach
        # its chip dies here (with jax's message in its log) instead of
        # accepting frames, and PING can always say where it runs
        self.device = _device_info()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((self.host, self.port))
        self._listener.listen(128)
        self.port = self._listener.getsockname()[1]
        self.advertise_addr = f"{self.host}:{self.port}"
        if self.mutlog is not None:
            # durable HA restart: reload the persisted placement map +
            # spilled handoff buffer BEFORE serving any frame, so a
            # restarted leader routes (and drains) exactly what it
            # owned when it died
            self._restore_ha_runtime()
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="netsdb-serve-accept")
        t.start()
        self._threads.append(t)
        if (getattr(self.config, "obs_history_len", 120) or 0) >= 2:
            self.history.start()
        self._start_pool_threads()
        if self._ha_peers:
            self.arm_ha(self._ha_peers)
        return self.port

    def _start_pool_threads(self) -> None:
        """(Re)start the follower-health and shard-pool-health loops
        for whichever roles this daemon currently has. Idempotent —
        called at start() and again by :meth:`_promote_self`, which
        GRANTS roles to a daemon that booted with neither."""
        if self._follower_addrs and (self._health_thread is None
                                     or not self._health_thread.is_alive()):
            h = threading.Thread(target=self._health_loop, daemon=True,
                                 name="netsdb-serve-health")
            h.start()
            self._health_thread = h
            self._threads.append(h)
        if self._worker_addrs and (self._pool_thread is None
                                   or not self._pool_thread.is_alive()):
            s = threading.Thread(target=self._pool_health_loop,
                                 daemon=True,
                                 name="netsdb-serve-pool-health")
            s.start()
            self._pool_thread = s
            self._threads.append(s)

    # --- HA: arming, promotion, durable restart -----------------------
    def arm_ha(self, peers: list,
               election_timeout_s: Optional[float] = None,
               probe_interval_s: Optional[float] = None):
        """Arm automatic failover over the ordered succession list
        ``peers`` (index 0 = initial leader; this daemon's
        ``advertise_addr`` must appear in it). Call after
        :meth:`start` so the advertised address carries the real
        bound port. Returns the live :class:`~netsdb_tpu.serve.ha.HAState`."""
        if election_timeout_s is None:
            election_timeout_s = getattr(
                self.config, "ha_election_timeout_s", 5.0)
        self._ha = _ha.HAState(
            self.advertise_addr, list(peers),
            state_dir=os.path.join(self.config.root_dir, "ha"))
        self._ha_monitor = _ha.HAMonitor(
            self, self._ha, election_timeout_s,
            probe_interval_s=probe_interval_s)
        self._ha_monitor.start()
        return self._ha

    def _promote_self(self) -> None:
        """Follower → leader, called by the HA monitor once every
        earlier succession peer stayed dead through the election
        window. Mints the new term (fencing every straggler from the
        deposed leader), adopts the replicated placement map with the
        dead leader's slots rebound to THIS daemon, adopts the LATER
        succession peers as mirror followers, and replicates the new
        epoch so routed clients re-point after exactly one typed
        ``PlacementStale``."""
        ha = self._ha
        if ha is None or ha.role == _ha.LEADER:
            return
        old_leader = ha.leader_addr
        term = ha.promote()
        wire = ha.placement_wire()
        if wire and (wire.get("sets") or {}):
            self.placement.restore(wire)
        if old_leader and old_leader != self.advertise_addr:
            self.placement.rebind_addr(old_leader, self.advertise_addr)
        later = list(ha.later_peers())
        with self._followers_mu:
            self._follower_addrs = list(later)
        # shard daemons named by the map (minus self and the corpse)
        # become this leader's pool; their health loop starts below
        pool = set()
        for ident in self.placement.sets():
            entry = self.placement.entry(*ident)
            for slot in (entry or {}).get("slots", ()):
                pool.add(slot["addr"])
        pool.discard(self.advertise_addr)
        if old_leader:
            pool.discard(old_leader)
        for addr in sorted(pool):
            if addr not in self._worker_addrs:
                self._worker_addrs.append(addr)
        self._start_pool_threads()
        if self._worker_addrs:
            # prune: the adopted map is authoritative — a slot move
            # the deposed leader committed but never dropped finishes
            # here (stale source registrations retire tombstoned)
            self._push_epochs(prune=True)
        try:
            # eagerly dial the adopted followers (bounded — a dead
            # later peer degrades and reattaches on the normal path)
            self._ensure_followers(
                timeout_s=min(self.heartbeat_timeout_s, 5.0))
        except FollowerDegraded as e:
            del e  # degraded peers reattach via the health loop
        self._replicate_placement()
        from netsdb_tpu.utils.profiling import get_logger

        get_logger("netsdb_tpu.serve").warning(
            "promoted %s to leader (term %d, deposed %s)",
            self.advertise_addr, term, old_leader)

    def _restore_ha_runtime(self) -> None:
        """Durable-restart half of ``ha_mutlog``: reload the persisted
        placement map (rebinding this daemon's possibly-changed
        advertise address) and the spilled handoff buffer, then mark
        the still-absent shard owners degraded so the pool health loop
        readmits them and DRAINS the restored buffer."""
        stored = self._load_placement()
        if stored:
            wire = stored.get("wire") or {}
            if wire.get("sets"):
                self.placement.restore(wire)
                old_addr = stored.get("advertise_addr")
                if old_addr and old_addr != self.advertise_addr:
                    self.placement.rebind_addr(old_addr,
                                               self.advertise_addr)
                # the reconcile push: workers re-register under the
                # persisted (post-move) epochs, and registrations the
                # map no longer grants are pruned — a restart
                # mid-rebalance resumes from the committed map, with
                # any undropped source copy retired here
                self._push_epochs(prune=True)
        pending = self.shards.load_spill()
        if pending:
            owners = set()
            for ident in self.placement.sets():
                entry = self.placement.entry(*ident)
                for slot in (entry or {}).get("slots", ()):
                    if slot.get("state") == _placement.HANDOFF \
                            and slot["addr"] != self.advertise_addr:
                        owners.add(slot["addr"])
            for addr in sorted(owners):
                self.shards.note_degraded(
                    addr, "handoff pending across leader restart")

    def _placement_path(self) -> str:
        return os.path.join(self.config.root_dir, "ha",
                            "placement.json")

    def _save_placement(self) -> None:
        """Best-effort durable copy of the placement map (only when
        the mutation log is on — the durability opt-in). Atomic
        tmp+replace; a failed save degrades to snapshot-era behavior,
        never a crash on the ingest path."""
        if self.mutlog is None:
            return
        import json

        path = self._placement_path()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"advertise_addr": self.advertise_addr,
                           "wire": self.placement.to_wire()}, f)
            os.replace(tmp, path)
        except OSError as e:
            del e  # best-effort: an unsaved map degrades the NEXT
            pass   # restart to snapshot-era recovery, never this frame

    def _load_placement(self) -> Optional[Dict[str, Any]]:
        import json

        try:
            with open(self._placement_path(), "r", encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _ha_state_payload(self) -> Dict[str, Any]:
        snap = self._ha.snapshot()
        return {"term": snap["term"], "leader": snap["leader"],
                "placement": self.placement.to_wire()}

    def _replicate_placement(self) -> None:
        """Ship the current (term, leader, placement) to every active
        follower — called on every epoch bump so a promoted leader
        serves routed ingest from the instant it wins, without a
        discovery scan. Fire-and-forget through the FIFO links: the
        map rides the same ordered stream as the data it describes."""
        self._save_placement()
        if self._ha is None or self._ha.role != _ha.LEADER:
            return
        payload = self._ha_state_payload()
        with self._followers_mu:
            links = list(self._links.values())
        for link in links:
            link.submit(MsgType.HA_STATE, dict(payload), CODEC_MSGPACK)

    def _send_token_alias(self, alias: str, target: str) -> None:
        """Ship one waiter-token → leader-token alias to every active
        follower (satellite of the coalesce/failover contract). Sent
        AFTER the leader's mirrored execution acked, through the same
        FIFO links — so the target token's reply is already cached on
        the follower when the alias lands. Bounded wait; a miss
        degrades that follower to re-execution on retry, never
        divergence."""
        payload: Dict[str, Any] = {"alias": alias, "target": target}
        if self._ha is not None:
            payload[HA_TERM_KEY] = self._ha.term
        if self.mutlog is not None:
            self.mutlog.append({"op": "alias", "alias": alias,
                                "target": target})
        with self._followers_mu:
            pending = [link.submit(MsgType.TOKEN_ALIAS, dict(payload),
                                   CODEC_MSGPACK)
                       for link in self._links.values()]
        deadline = deadline_after(self.heartbeat_timeout_s)
        for rec in pending:
            rec["done"].wait(max(seconds_left(deadline), 0.0))

    def serve_forever(self) -> None:
        if self._listener is None:
            self.start()
        try:
            while not self._stop.wait(0.5):
                pass
        except KeyboardInterrupt:
            pass
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        self._stop.set()
        # the session housekeeping thread is JOINED (same discipline
        # as the history thread below)
        self.sessions.stop()
        # the telemetry snapshot thread is JOINED, not abandoned — no
        # history thread may outlive its daemon (the leak-registry
        # discipline every obs thread follows)
        self.history.stop()
        # drop this scheduler's registry collector (only if it is
        # still the registered one — a newer controller in the same
        # process may have replaced it)
        obs.REGISTRY.unregister_collector("sched", self.sched.snapshot)
        with self._followers_mu:
            links = list(self._links.values())
        for link in links:
            link.close()
        self.shards.close()
        self._idem.close()
        if self.mutlog is not None:
            self.mutlog.close()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        with self._conns_mu:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    # --- connection handling ------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._listener.accept()
            except OSError:
                return  # listener closed
            t = threading.Thread(target=self._serve_connection,
                                 args=(conn, addr), daemon=True)
            t.start()

    def _serve_connection(self, conn: socket.socket, addr) -> None:
        with self._conns_mu:
            self._conns.add(conn)
        try:
            self._serve_connection_inner(conn, addr)
        finally:
            with self._conns_mu:
                self._conns.discard(conn)

    def _serve_connection_inner(self, conn: socket.socket,
                                addr) -> None:
        with conn:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                # the handshake must complete promptly; after it, the
                # connection may idle between frames, but once a frame
                # STARTS the peer must finish it within frame_timeout_s
                # (recv_frame_raw's mid-frame bound) — a hung peer can
                # never wedge this worker thread
                conn.settimeout(self.handshake_timeout_s)
                typ, hello = recv_frame(conn, allow_pickle=False)
                if typ != MsgType.HELLO:
                    raise ProtocolError("expected HELLO")
                if hello.get("proto") != PROTO_VERSION:
                    # mixed wire formats are refused OUTRIGHT: a v2 peer
                    # would misparse a v3 segment table as body bytes
                    send_frame(conn, MsgType.ERR, {
                        "error": "ProtocolVersionError",
                        "message": f"this daemon speaks wire format "
                                   f"v{PROTO_VERSION}; peer sent "
                                   f"proto={hello.get('proto')!r}",
                        "retryable": False})
                    return
                if self.token and hello.get("token") != self.token:
                    send_frame(conn, MsgType.ERR,
                               {"error": "AuthError", "message": "bad token"})
                    return
                ok_reply = {"server": "netsdb_tpu",
                            "version": PROTO_VERSION}
                if len(self.placement):
                    # v3 handshake placement shipping: ONLY when sharded
                    # sets exist, so the plain handshake (and every
                    # existing test's frame trace) stays byte-identical
                    ok_reply["placement"] = self.placement.to_wire()
                send_frame(conn, MsgType.OK, ok_reply)
                conn.settimeout(None)
            except (ProtocolError, ConnectionError, OSError):
                return
            while not self._stop.is_set():
                try:
                    typ, codec_in, raw, segs, nbytes, recv_s = \
                        recv_frame_raw(
                            conn, chaos=self._chaos,
                            mid_frame_timeout=self.frame_timeout_s)
                except (ProtocolError, ConnectionError, OSError):
                    return
                workload = typ not in OBS_FRAMES
                if workload:
                    obs.REGISTRY.counter("serve.wire.bytes_in").inc(nbytes)
                t_dec = time.perf_counter()
                try:
                    payload = decode_body(raw, codec_in, self.allow_pickle,
                                          segments=segs)
                except ProtocolError as e:
                    # refused codec — deterministic, fatal to retry
                    if not self._send_err(conn, e, retryable=False,
                                          count=workload):
                        return
                    continue
                except Exception as e:
                    # body failed to decode: bit flips / torn frame.
                    # The request never executed, so a resend is safe —
                    # typed retryable (the chaos corrupt path).
                    fault = CorruptFrame(f"{type(e).__name__}: {e}")
                    if not self._send_err(conn, fault, retryable=True,
                                          count=workload):
                        return
                    continue
                decode_s = time.perf_counter() - t_dec
                if typ == MsgType.SHUTDOWN:
                    send_frame(conn, MsgType.OK, {})
                    self.shutdown()
                    return
                if typ == MsgType.BULK_BEGIN:
                    # windowed streamed ingest: a multi-frame
                    # conversation owned by this worker thread
                    if not self._handle_bulk(conn, payload):
                        return
                    continue
                if not self._dispatch_frame(conn, typ, codec_in, payload,
                                            decode_s=decode_s,
                                            recv_s=recv_s):
                    return

    def _send_reply(self, conn, typ, payload, codec=CODEC_MSGPACK,
                    count: bool = True) -> None:
        """Reply send with the same deadline discipline as mid-frame
        recv: the peer must DRAIN within frame_timeout_s or the send
        fails (socket.timeout → the caller drops the connection) — a
        client that stops reading can never wedge a handler thread in
        sendall. The idle-recv timeout (None) is restored after.
        ``count`` is False where the frame answers an introspection
        request (``OBS_FRAMES``): its bytes stay out of
        ``serve.wire.bytes_out``."""
        conn.settimeout(self.frame_timeout_s)
        try:
            nbytes = send_frame(conn, typ, payload, codec,
                                chaos=self._chaos)
        finally:
            conn.settimeout(None)
        if count:
            obs.REGISTRY.counter("serve.wire.bytes_out").inc(nbytes)

    def _send_err(self, conn, exc, retryable: Optional[bool] = None,
                  with_traceback: bool = False,
                  count: bool = True) -> bool:
        """ERR frame for ``exc``; False when the connection is dead.
        ``retryable`` rides the payload so clients classify without
        string-matching (errors.classify_remote)."""
        if retryable is None:
            retryable = bool(getattr(exc, "retryable", False))
        body = {"error": type(exc).__name__, "message": str(exc),
                "retryable": retryable}
        # scheduler backpressure details ride the frame so the client's
        # backoff can honor the server's own hint (the same field list
        # classify_remote rebuilds client-side)
        for field in BACKPRESSURE_FIELDS:
            value = getattr(exc, field, None)
            if value is not None:
                body[field] = value
        if with_traceback:
            body["traceback"] = traceback.format_exc(limit=20)
        try:
            self._send_reply(conn, MsgType.ERR, body, count=count)
            return True
        except OSError:
            return False

    def _dispatch_frame(self, conn, typ, codec_in, payload,
                        decode_s: float = 0.0,
                        recv_s: float = 0.0) -> bool:
        """Execute one decoded request frame and send its reply. A
        frame carrying a client-minted query id opens a query-scoped
        trace first (``obs.trace``): the handler, the executor below
        it, staging and the device cache all report spans/counters
        into it, and the completed profile lands in this daemon's
        GET_TRACE ring — the ``-DPROFILING`` decomposition, per query,
        always on (``config.obs_enabled`` is the kill switch).

        Around the trace, the ACTIVE layer: every workload frame
        (``OBS_FRAMES`` excluded) ticks the request counters at
        OUTCOME time + the latency histogram the SLO engine evaluates;
        a frame carrying a client identity attributes its handler's
        resource use per (client, set); a traced query may capture an
        opt-in ``jax.profiler`` session; and a trace whose total
        exceeds ``obs_slow_query_s`` persists to the on-disk slowlog
        ring after it closes."""
        qid = payload.pop(QUERY_ID_KEY, None) \
            if isinstance(payload, dict) else None
        client = payload.pop(CLIENT_ID_KEY, None) \
            if isinstance(payload, dict) else None
        lane = payload.pop(LANE_KEY, None) \
            if isinstance(payload, dict) else None
        if isinstance(payload, dict) and SESSION_KEY in payload:
            # session-scoped frames admit through the reserved decode
            # lane unless the client pinned one explicitly — decode
            # loops and one-shot analytics get weighted fairness
            payload.pop(SESSION_KEY, None)
            if lane is None:
                lane = DECODE_LANE
        # introspection frames are EXCLUDED from the request counters
        # and latency histogram (t0=None): the SLOs those instruments
        # feed must measure the workload, not the monitoring of it —
        # a 10s HEALTH poll plus a per-query PUT_TRACE shipper would
        # otherwise flood the p99 sample ring with microsecond
        # dispatches and mask real slow queries
        t0 = None if typ in OBS_FRAMES else time.perf_counter()
        if qid is None or not self._obs_enabled:
            return self._dispatch_traced(conn, typ, codec_in,
                                         payload, None, client, t0,
                                         lane=lane)
        with obs.trace(str(qid), origin="server",
                       ring=self.trace_ring) as tr:
            if tr is not None:
                # the socket receive and the body decode finished
                # before the trace could open: back-date the trace so
                # their spans occupy real timeline [0, recv_s] and
                # [recv_s, recv_s + decode_s] AHEAD of the dispatch
                # span (and total_s covers them) instead of
                # overlapping it
                tr.backdate(recv_s + decode_s)
                tr.record("server.recv", recv_s, "serve", start_s=0.0)
                tr.record("server.decode", decode_s, "serve",
                          start_s=recv_s)
                if client is not None:
                    tr.annotate("client", str(client))
            with self._maybe_device_profile(tr):
                ok = self._dispatch_traced(conn, typ, codec_in,
                                           payload, str(qid), client,
                                           t0, lane=lane)
        if tr is not None:
            # the trace closed on context exit — total_s is final
            self._maybe_slowlog(tr)
        return ok

    @contextlib.contextmanager
    def _maybe_device_profile(self, tr):
        """Opt-in per-qid ``jax.profiler`` session
        (``config.obs_device_profile_dir``): the REAL device half of a
        traced query, captured into ``<dir>/<qid>`` for
        TensorBoard/XProf. One session at a time — a concurrent traced
        query skips (non-blocking acquire) rather than queueing the
        serve path behind the profiler; profiler failures annotate the
        trace and never fail the query."""
        if (tr is None or not self._device_profile_dir
                or not self._profiler_mu.acquire(blocking=False)):
            yield
            return
        sess = None
        try:
            try:
                from netsdb_tpu.utils.profiling import qid_profile_session

                sess = qid_profile_session(tr.qid,
                                           self._device_profile_dir)
                tr.annotate("device_profile", sess.__enter__())
            except Exception as e:  # noqa: BLE001 — annotated, not fatal
                tr.annotate("device_profile_error",
                            f"{type(e).__name__}: {e}")
                sess = None
            try:
                yield
            finally:
                if sess is not None:
                    try:
                        sess.__exit__(None, None, None)
                    except Exception as e:  # noqa: BLE001 — annotated
                        tr.annotate("device_profile_error",
                                    f"{type(e).__name__}: {e}")
        finally:
            self._profiler_mu.release()

    def _maybe_slowlog(self, tr) -> None:
        """Persist a just-closed slow trace to the on-disk ring (the
        structured slow-query log). Prefers the RINGED profile over
        ``tr.profile()``: a client section shipped before the ring
        push (TraceRing's pending buffer) is already folded into the
        ringed copy but absent from a fresh profile(). Never fails
        the request path."""
        try:
            # threshold gate FIRST: almost every traced request is
            # fast, and the ring find is an O(capacity) scan under
            # the ring mutex — don't pay it just to reject
            thr = self.slowlog.threshold_s
            if not thr or tr.total_s is None or tr.total_s < thr:
                return
            ringed = self.trace_ring.find(tr.qid)
            self.slowlog.maybe_record(ringed[-1] if ringed
                                      else tr.profile())
        except Exception as e:  # noqa: BLE001 — counted, never fatal
            obs.REGISTRY.counter("obs.slowlog_errors").inc()
            del e

    def _dispatch_traced(self, conn, typ, codec_in, payload, qid,
                         client=None, t0=None, lane=None) -> bool:
        """The dispatch body (trace context, if any, already
        installed). Returns False when the connection is dead. Mutating
        frames carrying an idempotency token are deduplicated here: a
        retry of a COMPLETED request replays the cached reply without
        re-running the handler — the at-most-once half of the client's
        retry contract.

        ``t0`` anchors the ``serve.request_s`` histogram (the p99
        SLO's feed): unary frames observe through the reply send,
        streaming frames observe TIME TO FIRST FRAME — a multi-GB scan
        drain rides the client's consumption rate, and folding tens of
        seconds of TCP backpressure into "request latency" would make
        the p99 objective breach on perfectly healthy bulk reads.
        ``t0`` is None for introspection frames (``OBS_FRAMES``) —
        they observe nothing and count nowhere."""
        observed = [False]
        workload = t0 is not None

        def mark():
            if not observed[0] and t0 is not None:
                observed[0] = True
                obs.REGISTRY.histogram("serve.request_s").observe(
                    time.perf_counter() - t0)

        def done(ok):
            # availability counts BOTH sides at outcome time: ticking
            # serve.requests at dispatch start read every in-flight
            # request as a failure — one long EXECUTE in a low-QPS
            # window pushed good/total under the 0.999 target and
            # flapped breach events with zero real errors
            if t0 is None:
                return
            obs.REGISTRY.counter("serve.requests").inc()
            if ok:
                obs.REGISTRY.counter("serve.requests_ok").inc()

        token = payload.pop(IDEMPOTENCY_KEY, None) \
            if isinstance(payload, dict) else None
        # the sender's HA term (mirrored frames, handoff drains, log
        # replays): popped here so handlers never see it, fenced in
        # _execute_frame against the receiver's own term
        term = payload.pop(HA_TERM_KEY, None) \
            if isinstance(payload, dict) else None
        try:
            if token is not None:
                cached = self._idem.claim(token, wait_s=self.frame_timeout_s)
                if cached is not None:
                    reply_type, reply, codec = cached
                    self._send_reply(conn, reply_type, reply, codec,
                                     count=workload)
                    mark()
                    done(True)
                    return True
            with obs.span(f"server.dispatch:{getattr(typ, 'name', typ)}",
                          "serve"):
                out = self._execute_frame(typ, payload, codec_in, token,
                                          qid=qid, client=client,
                                          lane=lane, term=term)
            if inspect.isgenerator(out):
                # streaming handler: each yielded (type, payload
                # [, codec]) goes out as its own frame; TCP
                # backpressure bounds server buffering to ONE
                # frame (the reference's page-by-page result
                # streaming, FrontendQueryTestServer.cc:785-890).
                # The contract: ends with STREAM_END, or ERR on
                # a mid-stream failure — either way the
                # connection stays frame-synchronized. Streams are
                # not idempotency-cached (mutating frames never
                # stream).
                for frame in out:
                    if len(frame) == 3:
                        f_type, f_payload, f_codec = frame
                    else:
                        (f_type, f_payload), f_codec = frame, CODEC_MSGPACK
                    self._send_reply(conn, f_type, f_payload, f_codec,
                                     count=workload)
                    mark()  # first frame = the latency that matters
                mark()  # empty stream: observe at STREAM_END
                done(True)
                return True
            with obs.span("server.reply", "serve"):
                self._send_reply(conn, *out, count=workload)
            mark()
            done(True)
            return True
        except BrokenPipeError:
            mark()
            done(False)  # died mid-reply: dispatched, not answered OK
            return False
        except Exception as e:  # handler errors go back as typed ERR
            mark()
            done(False)
            return self._send_err(conn, e, with_traceback=True,
                                  count=workload)

    #: frame types eligible for identical-query coalescing: idempotent
    #: job launches whose reply reuse the idempotency-token cache
    #: already proves safe (serve/sched/coalesce.py)
    COALESCED_FRAMES = frozenset({MsgType.EXECUTE_COMPUTATIONS,
                                  MsgType.EXECUTE_PLAN})

    def _devcache_warm(self, scope: str):
        """The scheduler's cache probe: is ``scope`` ("db:set") warm in
        the device cache? Answers warm (= no gating) for a disabled
        cache AND for non-paged sets: resident sets never enter the
        devcache, so an affinity gate keyed on them could only
        serialize concurrent queries with no warm cache to wake into.
        Only a COLD PAGED set — the one whose first stream installs
        the run every later sibling replays — is worth queueing
        behind.

        With block-granular partial caching the answer is RANGE-aware
        (the AffinityGate's per-page-range keying): ``True`` when the
        set's block coverage is complete (a query over an
        already-warm prefix admits immediately — mere ``has_scope``
        would also read one resident block as "warm" and let every
        sibling race the gap installs), an ``int`` (the contiguous
        covered prefix's end row) when partially covered so only the
        cold-remainder installer serializes, ``False`` when cold."""
        cache = self.library.store.device_cache()
        if not cache.enabled:
            return True
        partial = getattr(cache, "partial", False)
        if partial:
            covered, total = cache.coverage(scope)
            if total is not None and 0 < total <= covered:
                return True  # fully resident: no gating
        elif cache.has_scope(scope):
            return True
        else:
            covered = 0
        db, _, set_name = scope.partition(":")
        try:
            storage = self.library.store.storage_of(
                SetIdentifier(db, set_name))
        except Exception as e:  # noqa: BLE001 — unknown set → ungated
            del e
            return True
        if storage != "paged":
            return True
        return int(covered) if covered > 0 else False

    def _refresh_pin_auto(self) -> None:
        """One pin-budget auto-sizing pass (config.device_cache_pin_
        auto, run on the scheduler-feedback cadence): the attribution
        ledger's hot-set table → ``feedback.pin_budget`` (pinned
        formula) → the devcache pin budget, annotated ``pin_auto`` in
        its stats section."""
        from netsdb_tpu.serve.sched import feedback as _feedback

        cache = self.library.store.device_cache()
        if not (cache.enabled and getattr(cache, "partial", False)):
            return
        cache.set_pin_budget(
            _feedback.pin_budget(obs.attrib.LEDGER.snapshot(),
                                 cache.budget_bytes),
            auto=True)

    def _execute_frame(self, typ, payload, codec_in, token, qid=None,
                       client=None, lane=None, term=None):
        """Run one request's handler with the idempotency-token
        lifecycle (the caller has already claimed ``token``). Returns a
        generator (streaming handlers) or the normalized ``(type,
        payload, codec)`` reply; on every exit path the token has been
        finished or aborted exactly once. Shared by the per-frame
        dispatch and the bulk-ingest COMMIT. ``qid`` (the client's
        query id, already popped) rides mirrored forwards so follower
        traces share the leader's id; ``client`` (the frame's client
        identity, already popped) likewise — and is installed for the
        handler's dynamic extent so every instrumented layer below
        attributes its resource use per (client, db:set). ``lane``
        (the frame's scheduler hint, already popped) installs the same
        way and steers the job's admission lane.

        EXECUTE frames additionally pass the scheduler's COALESCE
        point here — BEFORE mirroring and admission: a byte-identical
        in-flight execution absorbs this frame entirely (no slot, no
        mirror forward, no handler run) and its reply fans out under
        this frame's own token/trace; a waiter whose leader dies gets
        the typed retryable CoalesceAborted and this token is aborted,
        so the retry re-executes."""
        handler = self.handlers.get(typ)
        if client is not None or isinstance(payload, dict):
            scope = None
            if isinstance(payload, dict) and payload.get("db") \
                    and payload.get("set"):
                scope = f"{payload['db']}:{payload['set']}"
            obs.attrib.account("requests", 1, scope=scope, client=client)
        try:
            if handler is None:
                raise ProtocolError(f"no handler for {typ!r}")
            if self._ha is not None:
                if term is not None:
                    # peer-originated frame (mirror/drain/replay): a
                    # STALE term means a deposed leader's straggler —
                    # reject typed, never double-apply
                    self._ha.observe_term(term)
                elif typ in self.MIRRORED:
                    # client-originated mutation: only the leader
                    # accepts; the typed NotLeader carries the
                    # current leader's address for rediscovery
                    self._ha.check_client_write()

            def invoke():
                if self._follower_addrs and typ in self.MIRRORED:
                    return self._run_mirrored(typ, payload, codec_in,
                                              handler, token=token,
                                              qid=qid, client=client)
                return handler(payload)

            tok_reset = _idem_token_var.set(token)
            try:
                with obs.attrib.client_context(client), \
                        _sched.lane_context(lane):
                    if typ in self.COALESCED_FRAMES:
                        winfo: Dict[str, Any] = {}
                        out = self.sched.coalesced(typ, payload,
                                                   invoke, token=token,
                                                   waiter_info=winfo)
                    else:
                        winfo = None
                        out = invoke()
            finally:
                _idem_token_var.reset(tok_reset)
        except FollowerDegraded as e:
            # the LOCAL mutation applied; only the mirror failed.
            # Cache the local reply under the token so the client's
            # retry returns success instead of double-applying,
            # then surface the typed retryable error for THIS
            # attempt (the ambiguous-outcome contract).
            if token is not None:
                if e.local_result is not None:
                    self._idem.finish(
                        token, self._normalize_reply(e.local_result))
                else:
                    self._idem.abort(token)
            raise
        except BaseException:
            if token is not None:
                # transient or handler failure: nothing durable to
                # replay — release waiters so a retry re-executes
                self._idem.abort(token)
            raise
        if inspect.isgenerator(out):
            # streams are not idempotency-cached (mutating frames
            # never stream)
            if token is not None:
                self._idem.abort(token)
            return out
        result = self._normalize_reply(out)
        if token is not None:
            self._idem.finish(token, result)
            # coalesce WAITER absorbed by another flight: its token
            # finished HERE but followers only saw the leader's —
            # ship the alias so the waiter's post-failover retry
            # still dedupes (the PR 9 at-most-once gap)
            ltok = winfo.get("leader_token") if winfo else None
            if ltok and ltok != token and self._follower_addrs:
                self._send_token_alias(token, ltok)
        return result

    @staticmethod
    def _normalize_reply(out) -> Tuple[MsgType, Any, int]:
        if len(out) == 3:  # handler picked the reply codec
            return out[0], out[1], out[2]
        return out[0], out[1], CODEC_MSGPACK

    # --- windowed bulk ingest (BULK_BEGIN/CHUNK/COMMIT) ---------------

    #: ops that accept the streamed-ingest conversation; anything else
    #: in a BULK_BEGIN is a deterministic protocol violation
    BULK_OPS = frozenset({MsgType.SEND_DATA, MsgType.RESYNC_FOLLOWER})

    def _bulk_assembler(self, op: MsgType, meta: dict) -> "_BulkAssembler":
        if op == MsgType.RESYNC_FOLLOWER:
            return _BlobAssembler(meta)
        if meta.get("mode") == "table":
            return _TableAssembler(meta)
        return _ItemsAssembler(meta, self.allow_pickle)

    def _handle_bulk(self, conn, p) -> bool:
        """One streamed-ingest conversation: BEGIN (already decoded in
        ``p``) → N CHUNK frames, each acked AFTER it decodes so the
        client pipelines ``window`` chunks deep → COMMIT, which
        assembles the payload and dispatches it through the normal
        handler path (mirroring + ordering locks + idempotency all
        apply at commit — chunks decode OUTSIDE the per-set lock, the
        apply runs under it). Returns False when the connection must
        close (transport desync or a mid-stream fault: the chunk
        stream cannot be resynchronized, so the typed ERR is sent and
        the socket dropped — the client retries the whole conversation
        under its idempotency token)."""
        try:
            op = MsgType(int(p.get("op", -1)))
            if op not in self.BULK_OPS:
                raise ProtocolError(
                    f"op {p.get('op')!r} is not bulk-streamable")
            meta = dict(p.get("meta") or {})
        except (ProtocolError, ValueError) as e:
            return self._send_err(conn, e, retryable=False)
        token = p.get(IDEMPOTENCY_KEY)
        client = p.get(CLIENT_ID_KEY)  # one identity for the whole
        # conversation — the COMMIT's apply attributes under it
        if token is not None:
            try:
                cached = self._idem.claim(token, wait_s=self.frame_timeout_s)
            except Exception as e:  # RequestInFlight → typed retryable
                return self._send_err(conn, e)
            if cached is not None:
                # completed execution replay: the final reply goes out
                # INSTEAD of "go" — the client skips streaming entirely
                try:
                    self._send_reply(conn, *cached)
                    return True
                except OSError:
                    return False
        owned = token is not None
        try:
            try:
                asm = self._bulk_assembler(op, meta)
            except ProtocolError as e:
                # deterministic refusal (e.g. pickle chunks with
                # allow_pickle off): typed fatal ERR instead of "go";
                # the connection stays frame-synchronized
                return self._send_err(conn, e, retryable=False)
            if meta.get("pepoch") is not None or self.is_sharded(
                    meta.get("db"), meta.get("set")):
                # placement-epoch gate at BEGIN — a stale map must
                # reject before the client streams the payload, not
                # after (the COMMIT-time check below still guards the
                # race where the epoch moves mid-conversation)
                self._shard_route(meta.get("db"), meta.get("set"),
                                  meta.get("pepoch"), meta.get("slot"))
            if self._ha is not None and op in self.MIRRORED \
                    and HA_TERM_KEY not in (p or {}):
                # leadership gate at BEGIN, same rationale as the
                # epoch gate: a demoted daemon must bounce the client
                # BEFORE it streams gigabytes, not at COMMIT
                self._ha.check_client_write()
            self._send_reply(conn, MsgType.OK, {"go": True})
            total_in = 0
            while True:
                typ, codec_in, raw, segs, nbytes, _ = recv_frame_raw(
                    conn, chaos=self._chaos,
                    mid_frame_timeout=self.frame_timeout_s)
                obs.REGISTRY.counter("serve.wire.bytes_in").inc(nbytes)
                total_in += len(raw) + sum(b.nbytes for b, _ in segs)
                if total_in > MAX_FRAME_BYTES:
                    # the streamed path keeps the single-frame sanity
                    # cap — one conversation must not balloon daemon
                    # RSS without bound before COMMIT validation
                    self._send_err(conn, ProtocolError(
                        f"bulk conversation exceeded the "
                        f"{MAX_FRAME_BYTES}-byte cap"), retryable=False)
                    return False
                try:
                    payload = decode_body(raw, codec_in, self.allow_pickle,
                                          segments=segs)
                except ProtocolError:
                    raise
                except Exception as e:
                    raise CorruptFrame(f"{type(e).__name__}: {e}") from e
                if typ == MsgType.BULK_CHUNK:
                    asm.add(payload)  # decode work, outside any set lock
                    self._send_reply(conn, MsgType.OK,
                                     {"ack": payload.get("seq")})
                elif typ == MsgType.BULK_COMMIT:
                    if asm.chunks != int(payload.get("chunks", -1)):
                        raise CorruptFrame(
                            f"ingest stream torn: committed "
                            f"{payload.get('chunks')} chunks, received "
                            f"{asm.chunks}")
                    final_payload, fwd_codec = asm.finish()
                    if meta.get("pepoch") is not None \
                            and isinstance(final_payload, dict):
                        # the routed conversation's epoch/slot ride to
                        # the apply (validated again there — COMMIT
                        # must reject a mid-stream membership change)
                        final_payload[PLACEMENT_EPOCH_KEY] = \
                            meta["pepoch"]
                        if meta.get("slot") is not None:
                            final_payload[SHARD_SLOT_KEY] = meta["slot"]
                    owned = False  # _execute_frame consumes the token
                    result = self._execute_frame(op, final_payload,
                                                 fwd_codec, token,
                                                 client=client)
                    self._send_reply(conn, *result)
                    return True
                else:
                    raise ProtocolError(
                        f"unexpected frame {typ!r} inside a bulk-ingest "
                        f"conversation")
        except BrokenPipeError:
            return False
        except (ProtocolError, ConnectionError, OSError):
            return False  # transport desync — client retries fresh
        except Exception as e:
            self._send_err(conn, e, with_traceback=True)
            return False  # chunk stream unsynchronizable past a fault
        finally:
            if owned:
                self._idem.abort(token)

    # --- multi-host mirroring (master → workers) ----------------------
    def _dial_follower(self, addr: str, timeout: Optional[float] = None):
        """One follower connection with mirror-path semantics: NO
        client-side retries (a mirror failure must surface immediately
        so the leader can evict + resync, not be papered over by a
        silent reconnect that breaks frame ordering). The dial +
        handshake is always bounded — a peer that accepts TCP and goes
        silent must fail the dial, not wedge the dialing thread;
        ``timeout`` additionally bounds steady-state replies (used by
        the resync RPC; mirror links leave it None because a mirrored
        EXECUTE may legitimately run for minutes, and the ack-timeout
        eviction already unsticks those)."""
        from netsdb_tpu.serve.client import RemoteClient, RetryPolicy

        return RemoteClient(addr, token=self.token,
                            retry=RetryPolicy(max_attempts=1),
                            chaos=self._follower_chaos,
                            timeout=timeout,
                            connect_timeout=self.handshake_timeout_s)

    def _ensure_followers(self, timeout_s: float = 30.0) -> None:
        """Dial any not-yet-connected follower, retrying while it comes
        up (bring-up order between master and workers is free — the
        deadline is MONOTONIC, so wall-clock jumps cannot break the
        retry window). Each follower gets a :class:`_FollowerLink` — a
        FIFO sender thread whose queue order IS the follower's frame
        order. A follower that never answers within the window is
        evicted into the degraded state (the reattach loop keeps
        trying) and the frame that needed it fails typed-retryable."""
        with self._followers_mu:
            undialed = [a for a in self._follower_addrs
                        if a not in self._links and a not in self._degraded]
        if not undialed:
            return
        for addr in undialed:
            deadline = deadline_after(timeout_s)
            while True:
                try:
                    fc = self._dial_follower(addr)
                    with self._followers_mu:
                        self._links[addr] = _FollowerLink(addr, fc)
                    break
                except OSError as e:
                    if seconds_left(deadline) <= 0:
                        self._evict_follower(
                            addr, f"unreachable after {timeout_s:.0f}s: {e}")
                        raise FollowerDegraded(
                            f"follower daemon {addr} unreachable after "
                            f"{timeout_s:.0f}s; evicted for background "
                            f"reattach: {e}") from e
                    time.sleep(0.3)

    def _evict_follower(self, addr: str, reason: str) -> None:
        """Move a follower out of the mirror set into the degraded
        state. The leader keeps serving reads/queries from its own
        store; a background reattach loop resyncs the follower from a
        leader checkpoint before readmitting it. Idempotent."""
        with self._followers_mu:
            link = self._links.pop(addr, None)
            if link is not None and link.acked_offset is not None:
                # the log-replay resume position: everything at or
                # before this END offset is applied on that follower
                self._follower_offsets[addr] = link.acked_offset
            self._degraded[addr] = reason
        if link is not None:
            link.close(abort=True)

    def follower_status(self) -> Dict[str, Any]:
        with self._followers_mu:
            out = {"active": sorted(self._links),
                   "degraded": dict(self._degraded)}
        out["mirror_dropped"] = int(
            obs.REGISTRY.counter("serve.mirror_dropped").value)
        return out

    # --- sharded worker pool (horizontal scale-out) -------------------
    def is_sharded(self, db: str, set_name: str) -> bool:
        """Placement probe: does this daemon coordinate a partitioned
        placement for (db, set)? Empty map → always False — the
        un-sharded paths never branch."""
        return self.placement.entry(db, set_name) is not None

    def shard_registration(self, db: str,
                           set_name: str) -> Optional[Dict[str, int]]:
        """Worker-side shard registration for (db, set), or None."""
        with self._shard_mu:
            reg = self._shard_sets.get((db, set_name))
            return dict(reg) if reg is not None else None

    def _register_shard(self, db: str, set_name: str, slot: int,
                        epoch: int) -> None:
        with self._shard_mu:
            self._shard_sets[(db, set_name)] = {"epoch": int(epoch),
                                                "slot": int(slot)}

    def _shard_route(self, db: Optional[str], set_name: Optional[str],
                     epoch, slot) -> str:
        """Classify one (possibly routed) mutating frame against this
        daemon's placement knowledge: ``"local"`` (apply here),
        ``"handoff"`` (buffer for a degraded slot), or a typed
        retryable :class:`PlacementStale` — the placement-epoch
        rejection. Validation happens BEFORE any execution, so a
        revised membership can never partially apply."""
        if not db or not set_name:
            return "local"
        entry = self.placement.entry(db, set_name)
        if entry is not None:  # this daemon coordinates the set
            current = entry["epoch"]
            if epoch is None:
                self._reject_stale(
                    f"set {db}:{set_name} is partitioned across a "
                    f"worker pool; fetch the placement map and route "
                    f"to the owning shards", current)
            if int(epoch) != current:
                self._reject_stale(
                    f"placement epoch rejected for {db}:{set_name}: "
                    f"frame rode epoch {epoch}, current is {current}",
                    current)
            if slot is None or not (0 <= int(slot)
                                    < len(entry["slots"])):
                self._reject_stale(
                    f"routed frame for {db}:{set_name} carries no "
                    f"valid shard slot", current)
            sl = entry["slots"][int(slot)]
            if sl["state"] == _placement.HANDOFF:
                return "handoff"
            if sl["addr"] == self.advertise_addr:
                if _rebalance.sealed(self, db, set_name):
                    raise ShardUnavailable(
                        f"slot {slot} of {db}:{set_name} is "
                        f"write-sealed for rebalancing; retry after "
                        f"the move commits", slot=int(slot),
                        epoch=current)
                return "local"
            self._reject_stale(
                f"slot {slot} of {db}:{set_name} is owned by "
                f"{sl['addr']}, not this daemon", current)
        reg = self.shard_registration(db, set_name)
        if reg is not None:  # this daemon holds one slot
            # the write-seal outranks the epoch check: a mid-move
            # source must answer retryable even to correctly-routed
            # frames — the tail drain after the seal is what makes
            # the copy's row count exact
            if _rebalance.sealed(self, db, set_name):
                raise ShardUnavailable(
                    f"shard slot of {db}:{set_name} is write-sealed "
                    f"for rebalancing; retry after the move commits",
                    slot=reg["slot"], epoch=reg["epoch"])
            if epoch is None or int(epoch) != reg["epoch"]:
                self._reject_stale(
                    f"placement epoch rejected for {db}:{set_name}: "
                    f"frame rode epoch {epoch}, shard registered "
                    f"{reg['epoch']}", reg["epoch"])
        elif epoch is not None \
                and _rebalance.tombstoned(self, db, set_name):
            # a committed move dropped this daemon's copy: a frame
            # still riding the old map must reject typed — applying
            # it into the cleared set would silently lose the row
            self._reject_stale(
                f"shard slot of {db}:{set_name} moved away from this "
                f"daemon; re-fetch the placement map", None)
        return "local"

    @staticmethod
    def _reject_stale(message: str, epoch) -> None:
        obs.REGISTRY.counter("shard.epoch_rejects").inc()
        raise PlacementStale(message, epoch=epoch)

    def _pool_health_loop(self) -> None:
        """Leader-side shard liveness: heartbeat every pool worker
        over a dedicated short-timeout connection, evict into the
        degraded (handoff) state after ``heartbeat_misses`` failures,
        and readmit — shard-scoped resync + handoff drain, never a
        whole-store snapshot — once the worker answers again."""
        from netsdb_tpu.serve.client import RemoteClient, RetryPolicy

        probes: Dict[str, Any] = {}
        misses: Dict[str, int] = {}
        while not self._stop.wait(self.heartbeat_interval_s):
            for addr in list(self._worker_addrs):
                try:
                    probe = probes.get(addr)
                    if probe is None:
                        probe = RemoteClient(
                            addr, token=self.token,
                            timeout=self.heartbeat_timeout_s,
                            retry=RetryPolicy(max_attempts=1))
                        probes[addr] = probe
                    probe.ping()
                    misses[addr] = 0
                    if self.shards.is_degraded(addr):
                        self._try_readmit_shard(addr)
                except Exception as e:  # noqa: BLE001 — counted below
                    probe = probes.pop(addr, None)
                    if probe is not None:
                        probe.close()
                    misses[addr] = misses.get(addr, 0) + 1
                    if misses[addr] >= self.heartbeat_misses \
                            and not self.shards.is_degraded(addr):
                        misses[addr] = 0
                        self._evict_shard(
                            addr, f"{self.heartbeat_misses} missed "
                                  f"heartbeats: {type(e).__name__}: {e}")
            if getattr(self.config, "rebalance", False):
                # liveness for the rebalance loop on pools with no
                # query traffic (the sched-feedback cadence only
                # fires on admissions): a cheap no-op unless the
                # detector's verdict or a pool change is pending
                try:
                    self.rebalancer.check()
                except Exception as e:  # noqa: BLE001 — a broken
                    del e              # planner must never kill the
                    pass               # heartbeat loop; skip the pass
        for probe in probes.values():
            probe.close()

    def _evict_shard(self, addr: str, reason: str) -> None:
        """Degrade one pool worker: its slots flip to handoff (epoch
        bump — in-flight stale routes reject typed), its ingest
        buffers at this leader until readmit, and every OTHER live
        worker learns the new epochs (``ShardPool.degrade`` pushes,
        best-effort). Idempotent. A membership change is also a
        rebalance trigger: the remaining LIVE members re-plan on the
        next skew check without waiting out the sustained windows."""
        self.shards.degrade(addr, reason)
        self.rebalancer.pool_changed()

    def _push_epochs(self, exclude: Tuple[str, ...] = (),
                     prune: bool = False) -> None:
        """Re-register CURRENT placement epochs on every live worker —
        an epoch bump is leader-local until this push, and a live
        worker still registered under the old epoch would reject every
        correctly-routed new-epoch frame. Best-effort per worker: a
        push failure leaves that worker answering typed-retryable
        (clients back off) until a later push lands.

        ``prune=True`` (the restart/promotion reconcile) additionally
        sends the push to EVERY pool worker — slotless ones get an
        empty list — with the prune marker: each worker drops (and
        tombstones + clears) registrations absent from its list. This
        finishes any slot move a dead leader committed but never got
        to drop: the persisted map is authoritative, the stale source
        copy must not keep applying old-epoch frames."""
        sets_by_addr: Dict[str, list] = {}
        keep_by_addr: Dict[str, list] = {}
        for db, s in self.placement.sets():
            entry = self.placement.entry(db, s)
            for i, sl in enumerate(entry["slots"]):
                addr = sl["addr"]
                if addr == self.advertise_addr or addr in exclude:
                    continue
                if sl["state"] != _placement.LIVE:
                    # A handoff slot still BELONGS to its degraded
                    # owner — the prune keep-list must cover it, or
                    # the reconcile would strip a worker that is
                    # merely awaiting readmit. Epochs are not
                    # re-registered for it here; that is readmit's
                    # job.
                    keep_by_addr.setdefault(addr, []).append(
                        {"db": db, "set": s})
                    continue
                sets_by_addr.setdefault(addr, []).append(
                    {"db": db, "set": s, "slot": i,
                     "epoch": entry["epoch"]})
        if prune:
            for addr in self._worker_addrs:
                if addr not in exclude:
                    sets_by_addr.setdefault(addr, [])
        for addr, sets in sets_by_addr.items():
            try:
                payload: Dict[str, Any] = {"sets": sets}
                if prune:
                    payload["prune"] = True
                    if keep_by_addr.get(addr):
                        payload["keep"] = keep_by_addr[addr]
                self.shards.peer_request(addr, MsgType.SHARD_RESYNC,
                                         payload)
            except Exception as e:  # noqa: BLE001 — best-effort push
                del e
                self.shards.drop_client(addr)

    def _try_readmit_shard(self, addr: str) -> bool:
        """Readmit one degraded shard: re-register its placement
        epochs (SHARD_RESYNC — required, a failure re-degrades), push
        the bumped epochs to the REST of the pool, then drain ONLY the
        shard's own buffered pages. The drain's per-batch idempotency
        tokens make a retried drain safe."""
        try:
            self.placement.readmit_addr(addr)
            sets = []
            for db, s in self.placement.sets_for_addr(addr):
                entry = self.placement.entry(db, s)
                for i, sl in enumerate(entry["slots"]):
                    if sl["addr"] == addr:
                        sets.append({"db": db, "set": s, "slot": i,
                                     "epoch": entry["epoch"]})
            if sets:
                self.shards.peer_request(addr, MsgType.SHARD_RESYNC,
                                         {"sets": sets})
                self._push_epochs(exclude=(addr,))
                self.shards.drain_handoff(addr)
            self.shards.clear_degraded(addr)
            obs.REGISTRY.counter("shard.readmits").inc()
            self._replicate_placement()
            return True
        except Exception as e:  # noqa: BLE001 — re-degraded, retried
            self.shards.degrade(addr, f"readmit failed: "
                                      f"{type(e).__name__}: {e}")
            return False

    # --- shard-pool handlers ------------------------------------------
    def _on_placement(self, p):
        """The placement map (the PLACEMENT frame a client's stale-map
        retry re-fetches)."""
        return MsgType.OK, self.placement.to_wire()

    def _on_subplan(self, p):
        """Shard side of scatter-gather: run one pushed subplan over
        this daemon's local pages. Admission happened at the
        coordinator (one client EXECUTE = one admission slot pool-
        wide); the shard's own devcache/staging/fusion state still
        applies — that is the per-shard payoff."""
        return MsgType.OK, _shard.execute_subplan(self, p), CODEC_PICKLE

    def _on_shuffle_put(self, p):
        """One inbound distributed-shuffle bucket (shard → shard)."""
        cols = p.get("cols")
        nbytes = sum(np.asarray(v).nbytes for v in (cols or {}).values())
        obs.REGISTRY.counter("shard.shuffle_parts").inc()
        if nbytes:
            obs.REGISTRY.counter("shard.shuffle_bytes").inc(nbytes)
        self._shuffle.put(p["sid"], p["side"], int(p["slot"]), cols,
                          p.get("dicts"))
        return MsgType.OK, {}

    def _on_shard_resync(self, p):
        """Leader → readmitted shard: re-register placement epochs for
        this daemon's slots (the metadata half of the shard-scoped
        resync; the data half is the handoff drain of ordinary routed
        SEND_DATA frames that follows). ``prune: true`` (the leader's
        restart/promotion reconcile) makes the list AUTHORITATIVE:
        registrations absent from it are dropped, tombstoned, and
        their local copies cleared — the worker-side completion of
        any slot move the map committed but a dead leader never got
        to drop."""
        count = 0
        for s in p.get("sets", ()):
            self._register_shard(s["db"], s["set"], s["slot"],
                                 s["epoch"])
            count += 1
        if p.get("prune"):
            keep = {(s["db"], s["set"]) for s in p.get("sets", ())}
            keep |= {(s["db"], s["set"]) for s in p.get("keep", ())}
            with self._shard_mu:
                stale = [k for k in self._shard_sets
                         if k not in keep]
                for k in stale:
                    del self._shard_sets[k]
                    self._reshard_seals.pop(k, None)
                    self._reshard_moved.add(k)
            for db, set_name in stale:
                try:
                    self.library.clear_set(db, set_name)
                except Exception as e:  # noqa: BLE001 — tombstoned
                    del e              # above; a clear failure only
                    pass               # leaves unreachable garbage
        return MsgType.OK, {"sets": count}

    def _on_reshard(self, p):
        """The RESHARD frame (serve/rebalance.py): worker ops run one
        leg of a slot move against this daemon's local state; admin
        ops (status / check / add_worker) drive the leader's
        campaign. Everything answers CODEC_PICKLE — partitions ride
        the reply."""
        op = p.get("op")
        if op == "status":
            return MsgType.OK, self.rebalancer.status(), CODEC_PICKLE
        if op == "view":
            return (MsgType.OK, self.rebalancer.placement_view(),
                    CODEC_PICKLE)
        if op == "check":
            moves = self.rebalancer.check(force=bool(p.get("force")))
            return MsgType.OK, {"moves": moves}, CODEC_PICKLE
        if op == "add_worker":
            return (MsgType.OK,
                    self.add_worker(p["addr"],
                                    campaign=bool(
                                        p.get("campaign", True))),
                    CODEC_PICKLE)
        return (MsgType.OK, _rebalance.handle_reshard(self, p),
                CODEC_PICKLE)

    def add_worker(self, addr: str,
                   campaign: bool = True) -> Dict[str, Any]:
        """Register one NEW pool worker on a live leader (the 5th
        daemon joining a running 4-daemon pool). The health loop
        starts heartbeating it immediately; the rebalancer treats the
        growth as a forced trigger — when ``config.rebalance`` is on,
        a move round runs synchronously and the reply carries its
        results, so callers observe the pool absorb the member.
        ``campaign=False`` registers only, leaving the move decision
        to a later pass (the advisor's measured commit-or-revert)."""
        addr = str(addr)
        if addr != self.advertise_addr \
                and addr not in self._worker_addrs:
            self._worker_addrs.append(addr)
        self._start_pool_threads()
        self.rebalancer.pool_changed()
        moves = None
        if campaign and getattr(self.config, "rebalance", False):
            moves = self.rebalancer.check()
        return {"workers": list(self._worker_addrs), "moves": moves}

    # --- follower health + graceful degradation -----------------------
    def _health_loop(self) -> None:
        """Leader-side liveness: heartbeat every active follower over a
        DEDICATED connection (never the ordered mirror link — a probe
        must not queue behind a big forward), evict after
        ``heartbeat_misses`` consecutive failures, and keep trying to
        reattach + resync degraded followers."""
        from netsdb_tpu.serve.client import RemoteClient, RetryPolicy

        misses: Dict[str, int] = {}
        probes: Dict[str, Any] = {}
        while not self._stop.wait(self.heartbeat_interval_s):
            with self._followers_mu:
                active = list(self._links)
                degraded = list(self._degraded)
            for addr in active:
                try:
                    probe = probes.get(addr)
                    if probe is None:
                        probe = RemoteClient(
                            addr, token=self.token,
                            timeout=self.heartbeat_timeout_s,
                            retry=RetryPolicy(max_attempts=1))
                        probes[addr] = probe
                    probe.ping()
                    misses[addr] = 0
                except Exception as e:  # noqa: BLE001 — counted, typed below
                    probe = probes.pop(addr, None)
                    if probe is not None:
                        probe.close()
                    misses[addr] = misses.get(addr, 0) + 1
                    if misses[addr] >= self.heartbeat_misses:
                        misses[addr] = 0
                        self._evict_follower(
                            addr, f"{self.heartbeat_misses} missed "
                                  f"heartbeats: {type(e).__name__}: {e}")
            for addr in degraded:
                if self._stop.is_set():
                    return
                self._try_reattach(addr)
        for probe in probes.values():
            probe.close()

    def _try_reattach(self, addr: str) -> bool:
        """Attempt to bring one degraded follower back: dial it, resync
        its store from a leader checkpoint, readmit it to the mirror
        set. Quietly returns False while the follower stays down. The
        resync connection is FULLY bounded (dial, handshake, reply) —
        the resync holds the leader's write path, so a follower that
        answers the dial and then hangs must fail the resync within
        ``resync_timeout_s``, never wedge the health thread (and with
        it every mutation) forever."""
        try:
            fc = self._dial_follower(addr, timeout=self.resync_timeout_s)
        except OSError:
            return False
        try:
            with self._followers_mu:
                offset = self._follower_offsets.get(addr)
            if self.mutlog is not None and offset is not None \
                    and offset <= self.mutlog.last_offset():
                # log replay: re-send only the frames this follower
                # missed since its last ack — minutes of divergence
                # costs kilobytes, not a whole-store snapshot
                self._resync_follower_log(addr, fc, offset)
            else:
                self._resync_follower(addr, fc)
            return True
        except Exception as e:  # noqa: BLE001 — recorded, retried later
            fc.close()
            with self._followers_mu:
                if addr in self._degraded:
                    self._degraded[addr] = (f"resync failed: "
                                            f"{type(e).__name__}: {e}")
            return False

    def _resync_follower(self, addr: str, fc) -> None:
        """Rebuild ``addr``'s store from a leader snapshot, then
        readmit it. The snapshot is taken under the exclusive frame
        order (and the collective lock), so no mutation can interleave
        between 'what the checkpoint holds' and 'first mirrored frame
        the readmitted follower sees' — the store-equality guarantee.
        Reads never take these locks: the leader keeps serving them
        throughout (degraded mode is only a write-path pause). Old
        snapshot steps are pruned after success — a flapping follower
        must not fill the leader's disk.

        The snapshot pickles ONCE, lands in the leader's local
        checkpoint dir (durability), and STREAMS to the follower in
        bounded frames over the wire (``RemoteClient.resync_follower``)
        — no shared-filesystem assumption: leader and follower may run
        with completely disjoint root dirs or on different hosts."""
        from netsdb_tpu.storage import checkpoint

        self._resync_idle.clear()
        self._order.acquire_write()
        try:
            with self._collective_lock:
                step = next(self._resync_seq)
                root = os.path.join(self.config.root_dir, "resync")
                blob = checkpoint.dumps_store(self._snapshot_state())
                checkpoint.save_store_bytes(root, blob, step)
                fc.resync_follower(blob, step)
                # the resync client carries resync_timeout_s on every
                # recv; the LINK must not (mirrored EXECUTEs may run
                # for minutes) — so the readmitted link gets a fresh
                # unbounded-reply connection
                fc.close()
                if self.mutlog is not None:
                    # the snapshot captures everything up to HERE in
                    # the log (we hold the exclusive order — no frame
                    # can append concurrently); a later eviction of
                    # this follower resumes replay from this offset
                    off = self.mutlog.last_offset()
                    with self._followers_mu:
                        self._follower_offsets[addr] = off
                    checkpoint.save_meta(root, step,
                                         {"mutlog_offset": off})
                link_client = self._dial_follower(addr)
                with self._followers_mu:
                    self._degraded.pop(addr, None)
                    link = self._links[addr] = _FollowerLink(
                        addr, link_client)
                if self._ha is not None \
                        and self._ha.role == _ha.LEADER:
                    # the readmitted follower may have missed epochs
                    # (or a whole term) — re-announce on its fresh link
                    link.submit(MsgType.HA_STATE,
                                self._ha_state_payload(), CODEC_MSGPACK)
                checkpoint.prune_steps(root, keep=1)
                self._idem.prune()  # same disk-bounding moment: old
                # persisted idempotency tokens go with old snapshots
        finally:
            self._order.release_write()
            self._resync_idle.set()

    def _resync_follower_log(self, addr: str, fc, offset: int) -> None:
        """Log-replay readmission (``ha_mutlog``): re-send every
        mutation-log frame past ``offset`` to the reattached follower,
        then readmit it — the snapshot's store-equality argument holds
        because the replay runs under the same exclusive frame order
        (nothing can append between 'replay bound captured' and 'link
        installed'). Each replayed frame carries a deterministic
        fallback idempotency token (``mutlog-<end>``) so a frame the
        follower DID apply before dying dedupes instead of
        double-applying, and the CURRENT term so a deposed leader's
        replay is rejected typed."""
        self._resync_idle.clear()
        self._order.acquire_write()
        try:
            with self._collective_lock:
                bound = self.mutlog.last_offset()
                for end, rec in self.mutlog.replay(offset):
                    if rec.get("op") == "alias":
                        fc._request(MsgType.TOKEN_ALIAS,
                                    {"alias": rec["alias"],
                                     "target": rec["target"]},
                                    CODEC_MSGPACK)
                        continue
                    if rec.get("op") != "frame":
                        continue
                    payload = dict(rec["payload"])
                    payload.setdefault(IDEMPOTENCY_KEY, f"mutlog-{end}")
                    if self._ha is not None:
                        payload[HA_TERM_KEY] = self._ha.term
                    fc._request(MsgType(rec["typ"]), payload,
                                rec.get("codec", CODEC_PICKLE))
                fc.close()
                link_client = self._dial_follower(addr)
                with self._followers_mu:
                    self._degraded.pop(addr, None)
                    self._follower_offsets[addr] = bound
                    link = self._links[addr] = _FollowerLink(
                        addr, link_client)
                if self._ha is not None \
                        and self._ha.role == _ha.LEADER:
                    link.submit(MsgType.HA_STATE,
                                self._ha_state_payload(), CODEC_MSGPACK)
        finally:
            self._order.release_write()
            self._resync_idle.set()

    def _snapshot_state(self) -> Dict[str, Any]:
        """The leader's replayable state: databases, registered types,
        and every set as host values. Paged relations snapshot as their
        host-assembled form (chunk tables / records) and re-page on the
        follower; a paged MATRIX — which by design never materializes
        densely (PAGED_MATMUL streams it) — snapshots as its ordered
        arena PAGE BLOCKS and replays page by page on the follower
        (``SetStore.restore_paged_matrix``), closing the PR 2 leftover
        where it resynced as an empty set."""
        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.storage.paged import PagedObjects
        from netsdb_tpu.storage.store import _PagedMatrix

        cat = self.library.catalog
        types = []
        for t in cat.list_types():
            types.append({"type": t["type"],
                          "entry_point": t["entry_point"],
                          "source": cat.get_type_source(t["type"])})
        sets = []
        for ident in self.library.store.list_sets():
            meta = cat.get_set(ident.db, ident.set) or {}
            storage = self.library.store.storage_of(ident)
            entry: Dict[str, Any] = {
                "db": ident.db, "set": ident.set,
                "type_name": meta.get("type", "tensor"),
                "persistence": meta.get("persistence", "transient"),
                "storage": storage,
            }
            items = self.library.store.get_items(ident)
            if storage == "paged":
                if len(items) == 1 and isinstance(items[0], PagedColumns):
                    entry["kind"] = "paged-table"
                    entry["table"] = items[0].to_host_table()
                elif len(items) == 1 and isinstance(items[0], PagedObjects):
                    entry["kind"] = "paged-objects"
                    entry["items"] = list(items[0])
                elif len(items) == 1 and isinstance(items[0],
                                                    _PagedMatrix):
                    # paged MATRIX: snapshot its arena pages in order
                    # so the follower re-pages them block by block.
                    # Peak: ALL pages host-resident in the snapshot at
                    # once — the SAME whole-relation bound the
                    # paged-table branch above pays (to_host_table) and
                    # the one-blob resync wire format imposes anyway;
                    # a bounded page-streamed resync is the ROADMAP
                    # follow-on. The read lock pins the pages against a
                    # concurrent replace; the snapshot itself already
                    # holds the exclusive frame order.
                    pm = items[0]
                    ps = self.library.store.page_store()
                    with pm.rw.read():
                        blocks = [np.asarray(b) for _, b in
                                  ps.stream_blocks(f"{pm.ident}.mat",
                                                   prefetch=0)]
                        rb = int(ps.meta(f"{pm.ident}.mat")[1][0])
                    entry["kind"] = "paged-matrix"
                    entry["blocks"] = blocks
                    entry["row_block"] = rb
                else:
                    # unknown/empty paged content: recreate the (empty)
                    # set so the follower keeps accepting frames for it
                    entry["kind"] = "paged-empty"
            elif len(items) == 1 and isinstance(items[0], BlockedTensor):
                t = items[0]
                entry["kind"] = "tensor"
                entry["dense"] = np.asarray(t.to_dense())
                entry["block_shape"] = list(t.meta.block_shape)
            else:
                entry["kind"] = "objects"
                entry["items"] = list(items)
            sets.append(entry)
        return {"databases": cat.list_databases(), "types": types,
                "sets": sets}

    def _on_resync_follower(self, p):
        """Follower side: replace this daemon's store with the leader's
        snapshot. The primary form is ``snapshot_blob`` — the pickled
        snapshot assembled from the wire-streamed bulk conversation
        (no shared filesystem: the blob never touches this daemon's
        disk); ``path`` remains as the legacy shared-fs form. Either
        way the restore executes pickle — the codec-1 trust boundary,
        so it requires allow_pickle (trusted-cluster control planes
        only)."""
        if not self.allow_pickle:
            raise ProtocolError(
                "RESYNC_FOLLOWER refused: snapshot restore executes "
                "pickle and this daemon has allow_pickle off")
        from netsdb_tpu.storage import checkpoint

        if "snapshot_blob" in p:
            snap = checkpoint.loads_store(p["snapshot_blob"])
            self.last_resync_mode = "wire"
        else:
            snap = checkpoint.load_store(p["path"], p.get("step"))
            self.last_resync_mode = "path"
        for ident in list(self.library.store.list_sets()):
            self.library.remove_set(ident.db, ident.set)
        for db in snap["databases"]:
            self.library.create_database(db)
        for t in snap.get("types", []):
            self.library.register_type(t["type"], t["entry_point"],
                                       source=t.get("source"))
        restored = 0
        for entry in snap["sets"]:
            self.library.create_set(entry["db"], entry["set"],
                                    type_name=entry["type_name"],
                                    persistence=entry["persistence"],
                                    storage=entry.get("storage", "memory"))
            kind = entry["kind"]
            if kind == "tensor":
                self.library.send_matrix(entry["db"], entry["set"],
                                         entry["dense"],
                                         tuple(entry["block_shape"]))
            elif kind == "paged-table":
                # host chunk table re-pages through the ingest path
                self.library.send_table(entry["db"], entry["set"],
                                        entry["table"])
            elif kind == "paged-matrix":
                # leader arena pages replay page by page — the matrix
                # never materializes densely on this side either
                self.library.store.restore_paged_matrix(
                    SetIdentifier(entry["db"], entry["set"]),
                    entry["blocks"], int(entry.get("row_block") or 1))
            elif kind == "paged-empty":
                pass  # set exists; content streams in on next ingest
            elif entry["items"]:
                # verbatim replay (items are already post-ingest form;
                # send_data would re-columnarize "objects" sets)
                self.library.store.add_data(
                    SetIdentifier(entry["db"], entry["set"]),
                    list(entry["items"]))
            restored += 1
        # the whole store was just replaced wholesale: every remove/
        # re-ingest above already bumped its set's version, but the
        # explicit clear returns the dead device blocks to the budget
        # NOW (the resync invalidation hook the cache contract names)
        self.library.store.device_cache().clear()
        return MsgType.OK, {"restored_sets": restored}

    #: mirrored frames scoped to ONE (db, set) target — these serialize
    #: per set (and hold the RW order shared) in replicated-daemon
    #: topologies; everything else mirrored is multi-set and holds the
    #: RW order exclusively (ordering model in ``__init__``)
    SET_SCOPED_FRAMES = frozenset({
        MsgType.CREATE_SET, MsgType.REMOVE_SET, MsgType.CLEAR_SET,
        MsgType.SEND_DATA, MsgType.SEND_MATRIX, MsgType.LOAD_SET,
        # GENERATE rides the set-scoped lane keyed (model db, sid):
        # concurrent SESSIONS mirror-execute in parallel (and so can
        # coalesce into one padded batch), while one session's steps
        # stay serialized — per-session FIFO to every follower
        MsgType.GENERATE,
    })

    def _set_lock(self, db: str, set_name: str) -> TrackedLock:
        with self._set_locks_mu:
            return self._set_locks.setdefault(
                (db, set_name),
                TrackedLock("ServeController._set_locks[]"))

    def _run_mirrored(self, typ, payload, codec, handler, token=None,
                      qid=None, client=None):
        """Execute one mutating/job frame on EVERY process, holding the
        frame's ORDERING lock across both the follower enqueue and the
        local handler (see the ordering model in ``__init__`` — the
        lock choice is what keeps master execution order equal to
        follower receipt order for conflicting frames). Forwarding
        itself still overlaps local execution (the processes rendezvous
        inside XLA). A follower failure after local success EVICTS the
        follower into the degraded state (background resync reattaches
        it from a leader checkpoint) and surfaces as the typed
        retryable ``FollowerDegraded`` — the idempotent retry then
        returns the locally-applied result instead of double-applying
        (this replaces the old raise-and-diverge split-brain error)."""
        import jax

        if not self._resync_idle.wait(self.resync_grace_s):
            # a resync holds the write path; shed typed-retryable
            # instead of queueing unboundedly behind it
            raise FollowerDegraded(
                f"follower resync in progress (> {self.resync_grace_s}s); "
                f"retry shortly")
        if jax.process_count() > 1:
            # true SPMD: one total order for everything mirrored
            with self._collective_lock:
                return self._mirror_once(typ, payload, codec, handler,
                                         token, qid, client)
        if typ in self.SET_SCOPED_FRAMES and "db" in payload \
                and "set" in payload:
            self._order.acquire_read()
            try:
                with self._set_lock(payload["db"], payload["set"]):
                    return self._mirror_once(typ, payload, codec, handler,
                                             token, qid, client)
            finally:
                self._order.release_read()
        self._order.acquire_write()
        try:
            return self._mirror_once(typ, payload, codec, handler, token,
                                     qid, client)
        finally:
            self._order.release_write()

    def _mirror_once(self, typ, payload, codec, handler, token=None,
                     qid=None, client=None):
        # forward the CLIENT's idempotency token (popped before
        # dispatch) so followers dedupe too: if the local handler fails
        # retryably AFTER the forward (e.g. AdmissionFull), the
        # client's retry re-forwards the frame — without the shared
        # token each follower would apply it twice and diverge.
        # The query id rides along for the same reason traces exist:
        # one logical query's spans must join up across every daemon
        # that executed it (GET_TRACE merges them by qid) — and the
        # client identity likewise, so follower-side attribution books
        # the same tenant the leader does.
        fwd = payload
        lane = _sched.current_lane()  # the frame's hint, if any —
        # followers admit their mirrored copy through the same lane
        if token is not None or qid is not None or client is not None \
                or lane is not None or self._ha is not None:
            fwd = dict(payload)
            if token is not None:
                fwd[IDEMPOTENCY_KEY] = token
            if qid is not None:
                fwd[QUERY_ID_KEY] = qid
            if client is not None:
                fwd[CLIENT_ID_KEY] = client
            if lane is not None:
                fwd[LANE_KEY] = lane
            if self._ha is not None:
                # every mirrored frame is fenced by the sender's term:
                # a follower that adopted a newer leader rejects this
                # straggler typed instead of double-applying it
                fwd[HA_TERM_KEY] = self._ha.term
        with self._mirror_lock:  # short: dial + ordered enqueue only
            self._ensure_followers()
            offset = None
            if self.mutlog is not None:
                # append INSIDE the enqueue lock: log order == every
                # FIFO link's frame order, so "replay from offset N"
                # reconstructs exactly the stream a follower missed
                offset = self.mutlog.append(
                    {"op": "frame", "typ": int(typ), "codec": codec,
                     "payload": fwd})
            with self._followers_mu:
                pending = [(addr, link.submit(typ, fwd, codec,
                                              offset=offset))
                           for addr, link in self._links.items()]
        try:
            out = handler(payload)
        finally:
            failures, deposed = self._collect_mirror_failures(pending)
        if deposed is not None:
            # a follower answered NotLeader: it adopted a NEWER term —
            # this daemon was deposed while the frame was in flight.
            # Step down (keeping the follower: its link is healthy and
            # the new leader owns resyncing it) and bounce the client
            # to the real leader. The locally-applied copy is private
            # divergence — wiped when this daemon rejoins as a
            # follower and resyncs; the client's retry executes on
            # the real leader, exactly once in authoritative history.
            addr, exc = deposed
            self._ha.step_down(getattr(exc, "term", None),
                               getattr(exc, "leader_addr", None))
            raise NotLeader(
                f"this daemon was deposed mid-mirror ({addr} rejected "
                f"the frame: {exc}); retry against the current leader",
                leader_addr=getattr(exc, "leader_addr", None),
                term=self._ha.term)
        if failures:
            exc = FollowerDegraded(
                "mirror failed; follower(s) evicted for resync: "
                + "; ".join(f"{a}: {m}" for a, m in failures))
            exc.local_result = out  # applied here — retry must not redo
            raise exc
        return out

    def _collect_mirror_failures(self, pending) -> Tuple[list, Any]:
        """Wait (bounded) for every follower ack; evict the ones that
        errored or hung. ONE shared deadline covers the whole frame —
        three hung followers cost one timeout, not three stacked. The
        ack-timeout eviction aborts the link's socket, so its drain
        thread unblocks — a hung follower can never wedge the leader's
        handler thread.

        Returns ``(failures, deposed)``: ``deposed`` is ``(addr,
        NotLeaderError)`` when a follower rejected the frame because
        it follows a NEWER term — that is a fencing verdict on THIS
        daemon, not a follower fault, so the follower is NOT
        evicted."""
        deadline = (deadline_after(self.mirror_ack_timeout_s)
                    if self.mirror_ack_timeout_s is not None else None)
        failures = []
        deposed = None
        for addr, rec in pending:
            left = (max(0.0, seconds_left(deadline))
                    if deadline is not None else None)
            if not rec["done"].wait(left):
                failures.append(
                    (addr, f"no mirror ack within the frame's "
                           f"{self.mirror_ack_timeout_s}s budget"))
                self._evict_follower(
                    addr, f"mirror ack timeout "
                          f"({self.mirror_ack_timeout_s}s)")
            elif rec.get("error"):
                exc = rec.get("exc")
                if self._ha is not None \
                        and isinstance(exc, NotLeaderError):
                    if deposed is None:
                        deposed = (addr, exc)
                    continue
                failures.append((addr, rec["error"]))
                self._evict_follower(addr, rec["error"])
        return failures, deposed

    # --- job bookkeeping ----------------------------------------------
    def _run_job(self, job_name: str, fn: Callable[[], Any],
                 scopes=()) -> Any:
        """Admit + run one job under the query scheduler. Admission is
        lane-keyed (the frame's LANE_KEY hint, else its client
        identity, else the default lane) and bounded: a saturated lane
        refuses typed-retryable (LaneSaturated on quota, AdmissionFull
        with the lane's retry_after_s hint on timeout) instead of
        parking the handler thread forever. ``scopes`` ("db:set" scan
        leaves) then pass the cache-aware affinity gate: siblings of a
        cold-set installer wait (bounded) and wake into the warm
        device cache instead of racing cold streams."""
        job_id = next(self._job_seq)
        # "submitted" is a display timestamp (list_jobs), never compared
        # against a deadline — the one sanctioned wall-clock read
        rec = {"id": job_id, "name": job_name, "status": "queued",
               "submitted": wall_now(), "elapsed": None, "lane": None}
        with self._jobs_lock:
            self._jobs[job_id] = rec
            # bounded history so a long-lived daemon cannot grow this
            while len(self._jobs) > 1024:
                self._jobs.pop(next(iter(self._jobs)))
        lane = _sched.current_lane() or obs.attrib.current_client()
        try:
            with obs.span("server.sched.admit", "serve"):
                ticket = self.sched.acquire(
                    lane, timeout_s=self.admission_timeout_s)
        except (AdmissionFull, LaneSaturated):
            rec["status"] = "rejected"
            raise
        rec["status"] = "running"
        rec["lane"] = ticket.lane
        tr = obs.current_trace()
        if tr is not None:
            tr.annotate("sched.lane", ticket.lane)
        t0 = time.perf_counter()
        try:
            with self.sched.affinity(scopes):
                with obs.span(f"server.job:{job_name}", "job"):
                    out = fn()
            rec["status"] = "done"
            return out
        except Exception:
            rec["status"] = "failed"
            raise
        finally:
            rec["elapsed"] = time.perf_counter() - t0
            self.sched.release(ticket)

    # --- handlers -----------------------------------------------------
    def _on_ping(self, p) -> Tuple[MsgType, Any]:
        with self._jobs_lock:
            done = sum(1 for j in self._jobs.values() if j["status"] == "done")
        out = {"uptime": time.monotonic() - self._started,
               "jobs_done": done,
               "sets": len(self.library.store.list_sets()),
               "device": self.device}
        if self._follower_addrs:
            out["followers"] = self.follower_status()
        if self._ha is not None:
            # the probe doubles as leader discovery: a follower's ping
            # reply names who IT believes leads, and the HA monitor's
            # liveness check reads the role straight off this
            out["ha"] = self._ha.snapshot()
        return MsgType.OK, out

    def _on_ha_state(self, p):
        """Leader → follower state announcement (term, leader address,
        placement map) — shipped through the ordered mirror links on
        every epoch bump and on arming, so a promoted follower already
        HOLDS the routing map the instant it wins an election."""
        if self._ha is None:
            return MsgType.OK, {"armed": False}
        self._ha.adopt_leader(p.get("leader"), int(p.get("term") or 0))
        placement = p.get("placement")
        if placement:
            self._ha.store_placement(placement)
        return MsgType.OK, self._ha.snapshot()

    def _on_token_alias(self, p):
        """Leader → follower: finish a coalesce WAITER's idempotency
        token with its leader-token's cached reply (the frame rides
        the same FIFO link as the mirrored execution, so the target is
        already cached when this lands)."""
        ok = self._idem.alias(str(p["alias"]), str(p["target"]))
        return MsgType.OK, {"aliased": bool(ok)}

    def _on_create_database(self, p):
        self.library.create_database(p["db"])
        return MsgType.OK, {}

    @staticmethod
    def _shard_mode(placement_arg) -> Tuple[Optional[str], Optional[str]]:
        """(mode, key) when ``placement`` asks for pool sharding —
        the string forms ``"hash"``/``"range"`` or ``{"shard": mode,
        "key": col}`` — else (None, None): mesh Placement metas and
        plain sets flow through untouched."""
        if isinstance(placement_arg, str) \
                and placement_arg in ("hash", "range"):
            return placement_arg, None
        if isinstance(placement_arg, dict) and placement_arg.get("shard"):
            return str(placement_arg["shard"]), placement_arg.get("key")
        return None, None

    def _create_local_set(self, p) -> None:
        self.library.create_set(
            p["db"], p["set"], type_name=p.get("type_name", "tensor"),
            persistence=p.get("persistence", "transient"),
            eviction=p.get("eviction", "lru"),
            partition_lambda=p.get("partition_lambda"),
            placement=None,
            storage=p.get("storage", "memory"))

    def _on_create_set(self, p):
        shard_info = p.get("__shard__")
        if shard_info is not None:
            # worker side of a sharded create: one local slot set plus
            # the epoch registration routed frames validate against
            # (create_database is idempotent — workers need the db
            # even though only the leader saw CREATE_DATABASE)
            self.library.create_database(p["db"])
            self._create_local_set(p)
            self._register_shard(p["db"], p["set"],
                                 shard_info["slot"],
                                 shard_info["epoch"])
            return MsgType.OK, {}
        if p.get("placement") == "mirror":
            # the explicit spelling of the default replication mode:
            # full copy on every follower, nothing sharded
            p = {**p, "placement": None}
        mode, key = self._shard_mode(p.get("placement"))
        if mode is not None:
            # leader side: this daemon is slot 0; every pool worker
            # gets one slot. A degraded pool refuses typed BEFORE any
            # mutation — registering a dead worker's slot as live
            # would turn every later routed frame into a raw
            # connection error instead of the typed story.
            degraded = self.shards.degraded()
            if degraded:
                raise ShardUnavailable(
                    f"cannot create partitioned set "
                    f"{p['db']}:{p['set']}: pool worker(s) "
                    f"{sorted(degraded)} are degraded; retry after "
                    f"readmit")
            self._create_local_set(p)
            addrs = [self.advertise_addr] + list(self._worker_addrs)
            entry = self.placement.create(p["db"], p["set"], addrs,
                                          mode=mode, key=key)
            fwd = {k: v for k, v in p.items() if k != "placement"}
            try:
                for i, addr in enumerate(addrs[1:], start=1):
                    self.shards.peer_request(
                        addr, MsgType.CREATE_SET,
                        {**fwd, "__shard__": {"slot": i,
                                              "epoch": entry["epoch"]}})
            except Exception as e:
                # a worker died mid-create: unregister the half-born
                # entry (the local set stays — harmless, and a retry
                # recreates over it) and surface typed retryable
                self.placement.remove(p["db"], p["set"])
                raise ShardUnavailable(
                    f"partitioned create of {p['db']}:{p['set']} "
                    f"failed mid-fanout ({type(e).__name__}: {e}); "
                    f"placement rolled back — retry") from e
            self._replicate_placement()
            return MsgType.OK, {"placement": entry}
        self.library.create_set(
            p["db"], p["set"], type_name=p.get("type_name", "tensor"),
            persistence=p.get("persistence", "transient"),
            eviction=p.get("eviction", "lru"),
            partition_lambda=p.get("partition_lambda"),
            placement=p.get("placement"),  # Placement.to_meta dict
            storage=p.get("storage", "memory"))
        return MsgType.OK, {}

    def _fanout_sharded_ddl(self, typ, p) -> bool:
        """Forward one DDL frame to every worker slot of a sharded
        set. DDL is all-or-nothing like the partial merges: a
        degraded slot REFUSES typed-retryable (a clear/remove that
        skipped an unreachable shard would leave it holding pages
        every other slot deleted — divergence at readmit), and a
        forward failure raises. True when the set was sharded."""
        entry = self.placement.entry(p["db"], p["set"])
        if entry is None:
            return False
        for i, sl in enumerate(entry["slots"]):
            if sl["state"] != _placement.LIVE:
                raise ShardUnavailable(
                    f"slot {i} of {p['db']}:{p['set']} ({sl['addr']}) "
                    f"is degraded; pool-wide DDL refused rather than "
                    f"diverge the absent shard — retry after readmit",
                    slot=i, epoch=entry["epoch"])
        for sl in entry["slots"]:
            if sl["addr"] != self.advertise_addr:
                self.shards.peer_request(sl["addr"], typ,
                                         {"db": p["db"],
                                          "set": p["set"]})
        return True

    def _on_remove_set(self, p):
        if self._fanout_sharded_ddl(MsgType.REMOVE_SET, p):
            self.placement.remove(p["db"], p["set"])
            self._replicate_placement()
        # bytes-accounting hygiene: any buffered handoff for the set
        # dies with it (unreachable once the placement entry is gone)
        self.shards.purge_handoff(p["db"], p["set"])
        with self._shard_mu:
            self._shard_sets.pop((p["db"], p["set"]), None)
        self.library.remove_set(p["db"], p["set"])
        return MsgType.OK, {}

    def _on_clear_set(self, p):
        if self._fanout_sharded_ddl(MsgType.CLEAR_SET, p):
            self.shards.purge_handoff(p["db"], p["set"])
        self.library.clear_set(p["db"], p["set"])
        return MsgType.OK, {}

    def _on_set_exists(self, p):
        return MsgType.OK, {"exists": self.library.set_exists(p["db"], p["set"])}

    def _on_list_sets(self, p):
        return MsgType.OK, {"sets": [list(i) for i in self.library.store.list_sets()]}

    def _on_register_type(self, p):
        self.library.register_type(p["type_name"], p["entry_point"],
                                   source=p.get("source"))
        return MsgType.OK, {}

    def _resolve_registered(self, name_or_entry: str) -> Any:
        """Resolve a registry value: a registered type name goes through
        the catalog (picking up shipped source for modules the daemon
        doesn't have installed); anything else is a raw entry point."""
        entry = self.library.catalog.get_type(name_or_entry)
        if entry is not None:
            return resolve_entry_point(
                entry, self.library.catalog.get_type_source(name_or_entry))
        return resolve_entry_point(name_or_entry)

    def _on_send_data(self, p):
        epoch = p.pop(PLACEMENT_EPOCH_KEY, None)
        slot = p.pop(SHARD_SLOT_KEY, None)
        route = self._shard_route(p.get("db"), p.get("set"), epoch, slot)
        if route == "handoff":
            # the slot's shard is away: buffer EXACTLY this slot's
            # batch at the leader; the readmit drain ships it (and
            # only it) back — the shard-scoped resync. The drain rides
            # the CLIENT's idempotency token: if the shard already
            # applied this batch before the eviction (reply lost), its
            # cache dedupes the drained copy instead of doubling it.
            items = p.get("items")
            count = int(getattr(items, "num_rows", None)
                        or (len(items) if hasattr(items, "__len__")
                            else 0))
            self.shards.handoff_put(p["db"], p["set"], int(slot),
                                    _idem_token_var.get(), p)
            return MsgType.OK, {"count": count, "handoff": True}
        # objects arrive via the pickle codec (whole payload is a dict)
        if p.get("as_table"):
            # rows → one dictionary-encoded ColumnTable, sharded by the
            # set's placement (dispatcher page-building + partitioning);
            # append=True adds the batch instead of replacing
            t = self.library.send_table(p["db"], p["set"], p["items"],
                                        date_cols=p.get("date_cols", ()),
                                        append=bool(p.get("append")))
            return MsgType.OK, {"count": t.num_rows,
                                "columns": sorted(t.cols)}
        self.library.send_data(p["db"], p["set"], p["items"])
        return MsgType.OK, {"count": len(p["items"])}

    def _on_send_matrix(self, p):
        # a batch-partitioned TENSOR set (the model-serving input
        # shape) takes routed frames exactly like SEND_DATA: the
        # client splits rows by the placement's range slices and each
        # slot daemon ingests its contiguous slice as the local
        # partition. An unrouted frame against a sharded set gets
        # _shard_route's typed placement rejection.
        epoch = p.pop(PLACEMENT_EPOCH_KEY, None)
        slot = p.pop(SHARD_SLOT_KEY, None)
        route = self._shard_route(p.get("db"), p.get("set"), epoch, slot)
        if route == "handoff":
            # matrix slices are not handoff-buffered (a scoring batch
            # is transient, unlike durable table rows): refuse typed
            # retryable — the client re-routes after readmit
            raise ShardUnavailable(
                f"slot {slot} of {p['db']}:{p['set']} is degraded; "
                f"matrix ingest refused — retry after readmit",
                slot=slot, epoch=epoch)
        dense, block_shape = tensor_from_wire(p["tensor"])
        # the set write: blocking, pad, host→HBM dispatch, put_tensor.
        # The handler does not wait for the placed array (the reply
        # goes out while the copy may still be in flight), so neither
        # does the span
        with obs.span("store.ingest", "storage") as sp:
            t = self.library.send_matrix(p["db"], p["set"], dense,
                                         block_shape)
            if sp is not None:
                sp.counters["bytes"] = int(np.asarray(dense).nbytes)
        if t is None:
            # storage="paged" set: the matrix went into the arena, not
            # HBM — reply from the ingested array (there is no blocked
            # tensor to describe)
            return MsgType.OK, {"shape": list(dense.shape),
                                "dtype": str(np.asarray(dense).dtype),
                                "block_shape": None}
        return MsgType.OK, {"shape": list(t.shape), "dtype": str(t.dtype),
                            "block_shape": list(t.meta.block_shape)}

    def _on_paged_matmul(self, p):
        """stored @ rhs with the stored matrix streamed from the arena
        page by page — the daemon-side consumption path for paged
        TENSOR sets (whose GET_TENSOR deliberately raises)."""
        out = self.library.paged_matmul(p["db"], p["set"],
                                        np.asarray(p["rhs"]))
        return MsgType.OK, {"data": np.asarray(out)}

    def _on_get_tensor(self, p):
        t = self.library.get_tensor(p["db"], p["set"])
        # mesh-spanning placed tensors assemble via follower shards
        t = self._fetch_global(p["db"], p["set"], t)
        dense = np.asarray(t.to_dense())
        return MsgType.OK, {"data": dense,
                            "block_shape": list(t.meta.block_shape)}

    # --- multi-host reads of placed sets -----------------------------
    # A mesh-spanning jax.Array cannot be np.asarray'd on one process.
    # Reads therefore assemble the GLOBAL value host-side: the master
    # fills from its own addressable shards and asks each follower
    # daemon for its local shards over the serve protocol (LOCAL_SHARDS
    # frames) — the reference streaming each node's local pages to the
    # frontend (FrontendQueryTestServer.cc:785-890). Reads never enter
    # the SPMD program: no collectives, no frame-ordering constraints.

    @staticmethod
    def _item_leaves(item) -> Optional[Dict[str, Any]]:
        """Named jax.Array leaves of a stored item (None = host object)."""
        import jax

        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.relational.table import ColumnTable

        if isinstance(item, ColumnTable):
            leaves = dict(item.cols)
            if item.valid is not None:
                leaves["__valid__"] = item.valid
            return leaves
        if isinstance(item, BlockedTensor):
            return {"data": item.data}
        if isinstance(item, jax.Array):
            return {"value": item}
        return None

    @staticmethod
    def _rebuild_item(item, arrays: Dict[str, np.ndarray]):
        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.relational.table import ColumnTable

        if isinstance(item, ColumnTable):
            valid = arrays.pop("__valid__", None)
            return ColumnTable(arrays, dict(item.dicts), valid)
        if isinstance(item, BlockedTensor):
            return BlockedTensor(arrays["data"], item.meta)
        return arrays["value"]

    @staticmethod
    def _shard_ranges(shard, shape):
        return [[s.start or 0, s.stop if s.stop is not None else dim]
                for s, dim in zip(shard.index, shape)]

    def _on_local_shards(self, p):
        """Follower side: this process's addressable shards of one
        stored item's arrays, as (index ranges, raw buffer) pairs."""
        item = self._single_item(p["db"], p["set"])
        leaves = self._item_leaves(item)
        if leaves is None:
            return MsgType.OK, {"leaves": None}
        out = {}
        for name, arr in leaves.items():
            out[name] = [
                {"idx": self._shard_ranges(s, arr.shape),
                 "data": np.asarray(s.data)}
                for s in arr.addressable_shards]
        return MsgType.OK, {"leaves": out,
                            "shapes": {n: list(a.shape)
                                       for n, a in leaves.items()}}

    def _single_item(self, db: str, set_name: str):
        items = self.library.store.get_items(SetIdentifier(db, set_name))
        if len(items) != 1:
            raise ValueError(f"set {db}:{set_name} holds {len(items)} "
                             f"items; shard assembly expects 1")
        return items[0]

    def _fetch_global(self, db: str, set_name: str, item):
        """Item with every mesh-spanning array replaced by its full
        host value (local shards + follower LOCAL_SHARDS)."""
        import jax

        leaves = self._item_leaves(item)
        if leaves is None or all(
                (not isinstance(a, jax.Array)) or a.is_fully_addressable
                for a in leaves.values()):
            return item
        if self._single_item(db, set_name) is not item:
            raise ValueError(
                f"set {db}:{set_name}: shard assembly of mesh-spanning "
                f"arrays supports single-item sets only")
        from netsdb_tpu.serve.protocol import CODEC_MSGPACK

        # the WHOLE assembly — master-local shard copy AND follower
        # fetches — runs under the collective lock, which every
        # spanning mutation (EXECUTE_*/SEND_* in multi-process mode)
        # also holds: without it, a concurrent replacement could tear
        # the result into pre-mutation master halves + post-mutation
        # follower halves
        with self._collective_lock:
            # re-read under the lock: the set may have been replaced
            # while we waited
            item = self._single_item(db, set_name)
            leaves = self._item_leaves(item)
            out: Dict[str, np.ndarray] = {}
            covered: Dict[str, np.ndarray] = {}
            for name, arr in leaves.items():
                buf = np.empty(arr.shape, arr.dtype)
                cov = np.zeros(arr.shape, np.bool_)
                for s in arr.addressable_shards:
                    idx = tuple(slice(a, b) for a, b
                                in self._shard_ranges(s, arr.shape))
                    buf[idx] = np.asarray(s.data)
                    cov[idx] = True
                out[name] = buf
                covered[name] = cov
            with self._mirror_lock:
                self._ensure_followers()
                with self._followers_mu:
                    recs = [(addr, link.submit(MsgType.LOCAL_SHARDS,
                                               {"db": db, "set": set_name},
                                               CODEC_MSGPACK))
                            for addr, link in self._links.items()]
            # same deadline discipline as the mutation mirror: a
            # follower that hangs serving LOCAL_SHARDS (heartbeats may
            # still pass — the daemon is alive, one handler is stuck)
            # is evicted at the shared ack deadline and the read fails
            # TYPED-retryable, never wedging this handler thread
            failures = self._collect_mirror_failures(recs)
            if failures:
                raise FollowerDegraded(
                    "follower shard fetch failed; evicted for resync: "
                    + "; ".join(f"{a}: {m}" for a, m in failures))
            for _addr, rec in recs:
                for name, shards in (rec["reply"]["leaves"] or {}).items():
                    for sh in shards:
                        idx = tuple(slice(a, b) for a, b in sh["idx"])
                        out[name][idx] = sh["data"]
                        covered[name][idx] = True
            missing = [n for n, c in covered.items() if not c.all()]
            if missing:
                # e.g. a client reading through a WORKER daemon (no
                # follower links): returning np.empty garbage would be
                # silent corruption — reads of spanning sets must go to
                # the daemon that knows every holder
                raise RuntimeError(
                    f"set {db}:{set_name}: cannot assemble mesh-spanning "
                    f"columns {missing} — this daemon's local + follower "
                    f"shards do not cover the arrays (read through the "
                    f"master daemon)")
        return self._rebuild_item(item, out)

    def _scan_items(self, db: str, set_name: str):
        """Set scan for the wire: a paged set's PagedColumns handle is
        process-local (it wraps the native arena), so it ships as its
        HOST-assembled table (numpy columns — the device never sees a
        set that was paged because it does not fit; the STREAMED scan
        ships it page by page instead), and mesh-spanning placed items
        assemble their global value first (``_fetch_global``) — clients
        wanting summaries only should use ANALYZE_SET instead."""
        entry = self.placement.entry(db, set_name)
        if entry is not None:
            # sharded set: chain every slot's scan in slot order — the
            # leader's own partition streams locally, worker partitions
            # stream over their pool connections (bounded frames)
            for i, sl in enumerate(entry["slots"]):
                if sl["state"] != _placement.LIVE:
                    raise ShardUnavailable(
                        f"slot {i} of {db}:{set_name} ({sl['addr']}) "
                        f"is degraded; scan refused rather than return "
                        f"a partial set", slot=i, epoch=entry["epoch"])
            for sl in entry["slots"]:
                if sl["addr"] == self.advertise_addr:
                    yield from self._scan_items_local(db, set_name)
                else:
                    client = self.shards.client(sl["addr"])
                    with contextlib.closing(
                            client.scan_stream(db, set_name)) as items:
                        yield from items
            return
        yield from self._scan_items_local(db, set_name)

    def _scan_items_local(self, db: str, set_name: str):
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.storage.paged import PagedObjects
        from netsdb_tpu.storage.store import _PagedMatrix

        for item in self.library.get_set_iterator(db, set_name):
            if isinstance(item, PagedColumns):
                yield item.to_host_table()
            elif isinstance(item, PagedObjects):
                # record pages stream as records (the handle is
                # process-local; in the STREAMED scan these pack into
                # adaptive bounded frames like any object items).
                # closing(): the record generator holds the relation's
                # read lock — a client abandoning the scan mid-stream
                # (this generator is then closed, not exhausted) must
                # release it NOW, not when GC finds the frame
                with contextlib.closing(iter(item)) as records:
                    yield from records
            elif isinstance(item, _PagedMatrix):
                # the handle is process-local (it wraps the native
                # arena + a lock); the matrix itself deliberately never
                # materializes — consume it with PAGED_MATMUL
                raise ValueError(
                    f"set {db}:{set_name} holds a PAGED matrix — it "
                    f"streams (PAGED_MATMUL) and cannot be scanned "
                    f"over the wire")
            else:
                yield self._fetch_global(db, set_name, item)

    def _on_scan_set(self, p):
        from netsdb_tpu.serve.protocol import CODEC_PICKLE

        items = list(self._scan_items(p["db"], p["set"]))
        # host objects are arbitrary Python → pickle codec on the reply
        return MsgType.OK, {"items": items}, CODEC_PICKLE

    @staticmethod
    def _stream_paged(pc):
        """One host-side compact chunk table per frame, straight off
        the arena stream — the paged relation never materializes on
        the device or as one wire blob."""
        import contextlib
        import pickle

        def gen():
            seq = 0
            with contextlib.closing(
                    pc.stream_host_tables(prefetch=2)) as chunks:
                for tbl in chunks:
                    blob = pickle.dumps([tbl],
                                        protocol=pickle.HIGHEST_PROTOCOL)
                    yield MsgType.STREAM_ITEM, {"seq": seq,
                                                "batch": blob,
                                                "paged_chunk": True}
                    seq += 1
            yield MsgType.STREAM_END, {"frames": seq, "items": seq}

        return gen()

    def _on_scan_set_stream(self, p):
        """Streamed scan: items go out in frames of ~``max_frame_bytes``
        of pickled payload each — the server never materializes the
        whole set's wire form, and TCP backpressure holds buffering to
        one frame (ref FrontendQueryTestServer.cc:785-890 paging results
        to the client page by page).

        Each frame is ONE pickled list of items (per-item pickling
        measured 11× slower at 50k small rows). The items-per-frame
        count adapts to the observed bytes-per-item of the previous
        frame (growth capped at 4×/frame), so a frame overshoots the
        budget only while item sizes are growing and re-converges on
        the next frame — bounded memory, amortized serialization.

        A PAGED set streams its pages directly: one host-side compact
        chunk table per frame straight off the arena stream — the
        relation never materializes on the device OR as one wire blob
        (the reference streaming each node's local pages to the client
        page by page, ``FrontendQueryTestServer.cc:785-890``)."""
        import pickle

        from netsdb_tpu.relational.outofcore import PagedColumns

        budget = int(p.get("max_frame_bytes") or (4 << 20))
        # cheap storage peek — listing a big (possibly spilled)
        # non-paged set's items here would double-iterate it
        pc = None
        store = getattr(self.library, "store", None)
        if store is not None \
                and not self.is_sharded(p["db"], p["set"]):
            # a SHARDED set must take the generic path — _scan_items
            # chains every slot; the paged fast-path below would
            # stream only this daemon's local partition
            from netsdb_tpu.storage.store import SetIdentifier

            ident = SetIdentifier(p["db"], p["set"])
            if store.storage_of(ident) == "paged":
                items = store.get_items(ident)
                if len(items) == 1 and isinstance(items[0],
                                                  PagedColumns):
                    pc = items[0]
        if pc is not None:
            return self._stream_paged(pc)

        def stream():
            seq = 0
            total = 0
            # target starts at 1: the FIRST frame must not pack an
            # unmeasured batch (32 × 20 MB items would be a ~640 MB
            # frame — the exact both-ends spike streaming exists to
            # remove); the 4×/frame growth reaches steady state in a
            # handful of frames
            target = 1
            batch: list = []
            for item in self._scan_items(p["db"], p["set"]):
                batch.append(item)
                if len(batch) < target:
                    continue
                blob = pickle.dumps(batch,
                                    protocol=pickle.HIGHEST_PROTOCOL)
                yield MsgType.STREAM_ITEM, {"seq": seq, "batch": blob}
                seq += 1
                total += len(batch)
                per_item = max(len(blob) // len(batch), 1)
                target = max(1, min(budget // per_item, 4 * target))
                batch = []
            if batch:
                yield MsgType.STREAM_ITEM, {
                    "seq": seq,
                    "batch": pickle.dumps(batch,
                                          protocol=pickle.HIGHEST_PROTOCOL)}
                seq += 1
                total += len(batch)
            yield MsgType.STREAM_END, {"frames": seq, "items": total}

        return stream()

    def _on_get_tensor_chunked(self, p):
        """Chunked tensor pull: one meta frame, then the dense buffer in
        ``chunk_bytes`` slices, then STREAM_END. Bounds the *transfer*
        buffering to one chunk on each side (vs. a single frame holding
        the full payload twice); the dense host materialization itself
        is one copy, as in `_on_get_tensor`."""
        t = self.library.get_tensor(p["db"], p["set"])
        t = self._fetch_global(p["db"], p["set"], t)
        dense = np.ascontiguousarray(np.asarray(t.to_dense()))
        chunk = int(p.get("chunk_bytes") or (8 << 20))
        view = memoryview(dense).cast("B")
        nbytes = view.nbytes

        def stream():
            yield MsgType.STREAM_ITEM, {
                "seq": 0, "meta": {
                    "shape": list(dense.shape), "dtype": dense.dtype.str,
                    "block_shape": list(t.meta.block_shape),
                    "nbytes": nbytes,
                    "nchunks": max(1, -(-nbytes // chunk))}}
            seq = 1
            for off in range(0, max(nbytes, 1), chunk):
                # uint8 view over the dense buffer: the chunk rides as
                # an out-of-band segment — no per-chunk byte copy
                yield MsgType.STREAM_ITEM, {
                    "seq": seq,
                    "b": np.frombuffer(view[off:off + chunk], np.uint8)}
                seq += 1
            yield MsgType.STREAM_END, {"frames": seq}

        return stream()

    def _on_dedup_resident(self, p):
        """Pool shared blocks across resident model weight sets so
        fine-tuned variants share HBM (``Client.dedup_resident``) — the
        serve-time dedup flow (``SharedTensorBlockSet.h:25``)."""
        report = self.library.dedup_resident(
            [tuple(s) for s in p["sets"]], bands=int(p.get("bands", 16)),
            seed=int(p.get("seed", 0)))
        return MsgType.OK, report

    def _on_add_shared_mapping(self, p):
        self.library.add_shared_mapping(
            p["private_db"], p["private_set"], p["shared_db"], p["shared_set"],
            p.get("mapping"))
        return MsgType.OK, {}

    def _on_flush_data(self, p):
        self.library.flush_data()
        return MsgType.OK, {}

    def _on_load_set(self, p):
        self.library.store.load_set(SetIdentifier(p["db"], p["set"]))
        return MsgType.OK, {}

    @staticmethod
    def _sync_results(results: Dict[SetIdentifier, Any]) -> None:
        """Barrier on tensor/table results: the OK reply must mean the
        value exists, not that XLA enqueued it. (Object-set results are
        host values already; flattening them would cost O(items).)"""
        import jax

        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.relational.table import ColumnTable

        jax.block_until_ready([v for v in results.values()
                               if isinstance(v, (BlockedTensor,
                                                 ColumnTable))])

    def _result_summaries(self, results: Dict[SetIdentifier, Any]) -> dict:
        from netsdb_tpu.core.blocked import BlockedTensor
        from netsdb_tpu.relational.table import ColumnTable

        out = {}
        for ident, val in results.items():
            if isinstance(val, BlockedTensor):
                out[str(ident)] = {"kind": "tensor", "shape": list(val.shape),
                                   "dtype": str(val.dtype)}
            elif isinstance(val, ColumnTable):
                out[str(ident)] = {"kind": "table", "rows": val.num_rows,
                                   "columns": sorted(val.cols)}
            elif isinstance(val, dict):
                out[str(ident)] = {"kind": "map", "count": len(val)}
            else:
                out[str(ident)] = {"kind": "objects",
                                   "count": len(list(val))}
        return out

    def _on_execute_computations(self, p):
        """Body (pickle codec): {sinks: [WriteSet...], job_name}. The
        DAG's callables were cloudpickled by the client — the analogue of
        ``executeComputations`` shipping serialized Computation objects
        whose code the worker loads from registered .so files.

        ``explain: true`` runs the job with per-operator recording
        FORCED (``obs.operators.explain_capture``) and round-trips the
        annotated plan tree in the reply — EXPLAIN ANALYZE over the
        wire; the same tree also rides the query's GET_TRACE profile
        when the frame carried a qid."""
        sinks = p["sinks"]
        job_name = p.get("job_name", "remote-job")
        if self._scatter_touched(sinks):
            return self._execute_scatter(p, job_name, sinks)

        def run():
            results = self.library.execute_computations(
                *sinks, job_name=job_name,
                materialize=p.get("materialize", True))
            if p.get("sync", True):
                self._sync_results(results)
            return results

        return self._execute_with_explain(
            p, job_name, run,
            scopes=_sched.sets_touched(MsgType.EXECUTE_COMPUTATIONS, p))

    def _scatter_touched(self, sinks) -> bool:
        """Does this DAG scan any set this daemon coordinates a
        partitioned placement for? Empty map (every non-pool daemon)
        short-circuits — the local path never pays a walk."""
        if not len(self.placement):
            return False
        from netsdb_tpu.plan import scatter

        return bool(scatter.sharded_scan_sets(sinks, self.is_sharded))

    def _execute_scatter(self, p, job_name, sinks):
        """Coordinator path for queries over partitioned sets: admit
        ONE job (admission/lanes/affinity at the coordinator — one
        client EXECUTE is one pool-wide execution), scatter subplans
        to every shard slot, merge partials all-or-nothing, reply with
        the same summary shape the local path produces. ``explain``
        replies carry the coordinator slot's tree as ``operators``
        (rendered exactly like a local EXPLAIN) plus the full
        per-shard forest as ``shard_operators`` — every node annotated
        with the daemon that executed its region."""
        explain = bool(p.get("explain"))
        tr = obs.current_trace()
        # mirror the local path's default: a traced query records its
        # operator tree when obs_explain is on, explicit explain or
        # not — so GET_TRACE shows the distributed region forest for
        # every traced scatter query, not only EXPLAIN requests
        collect = explain or (tr is not None and getattr(
            self.config, "obs_explain", True))
        qid = tr.qid if tr is not None else None
        client = obs.attrib.current_client()
        holder: Dict[str, Any] = {}

        def run():
            results, shard_ops = self.shards.scatter_execute(
                sinks, job_name,
                materialize=p.get("materialize", True),
                explain=collect, qid=qid, client_id=client)
            if p.get("sync", True):
                self._sync_results(results)
            holder["ops"] = shard_ops
            return results

        scopes = _sched.sets_touched(MsgType.EXECUTE_COMPUTATIONS,
                                     {"sinks": sinks})
        results = self._run_job(job_name, run, scopes=scopes)
        out: Dict[str, Any] = {"results": self._result_summaries(results)}
        ops = holder.get("ops") or {}
        if explain:
            local = ops.get(self.advertise_addr)
            if local is not None:
                out["operators"] = local
            out["shard_operators"] = ops
        if collect and tr is not None and ops:
            # the distributed region forest rides the query's own
            # trace — GET_TRACE shows coordinator regions AND every
            # shard's region forest under ONE qid
            tr.attach_section("shard_operators", ops)
        return MsgType.OK, out

    def _execute_with_explain(self, p, job_name, run, scopes=()):
        """Shared EXECUTE tail: run the job (under an explain capture
        when asked) and shape the reply. ``scopes`` are the plan's
        scan-leaf sets — the affinity gate's key."""
        if p.get("explain"):
            with obs.operators.explain_capture() as cap:
                results = self._run_job(job_name, run, scopes=scopes)
            out = {"results": self._result_summaries(results)}
            if cap.get("operators") is not None:
                out["operators"] = cap["operators"]
            return MsgType.OK, out
        results = self._run_job(job_name, run, scopes=scopes)
        return MsgType.OK, {"results": self._result_summaries(results)}

    def _on_execute_plan(self, p):
        """Body (msgpack): {plan: text, registry: {label: entry_point or
        {kwargs..., fn: entry_point}}, job_name}. Pickle-free remote
        execution: labels rebind to *registered* entry points, the
        TCAP-text path (``ComputePlan.cc:20-56`` reparsing TCAP at the
        worker and binding against registered types)."""
        from netsdb_tpu.plan.parser import parse_plan

        registry: Dict[str, Any] = {}
        for label, spec in (p.get("registry") or {}).items():
            if isinstance(spec, str):
                registry[label] = self._resolve_registered(spec)
            elif isinstance(spec, dict):
                kw = dict(spec)
                for k, v in list(kw.items()):
                    if isinstance(v, str) and ":" in v:
                        kw[k] = self._resolve_registered(v)
                registry[label] = kw
            else:
                raise ProtocolError(
                    f"registry entry for {label!r} must be an entry-point "
                    f"string or kwargs dict")
        sinks = parse_plan(p["plan"]).to_computations(registry)
        job_name = p.get("job_name", "remote-plan")
        if self._scatter_touched(sinks):
            return self._execute_scatter(p, job_name, sinks)

        def run():
            results = self.library.execute_computations(
                *sinks, job_name=job_name,
                materialize=p.get("materialize", True))
            if p.get("sync", True):
                self._sync_results(results)
            return results

        return self._execute_with_explain(
            p, job_name, run,
            scopes=_sched.sets_touched(MsgType.EXECUTE_PLAN, p))

    def _on_list_jobs(self, p):
        with self._jobs_lock:
            return MsgType.OK, {"jobs": [dict(j) for j in self._jobs.values()]}

    def _fanout_read(self, typ, payload) -> Dict[str, Any]:
        """Best-effort read fan-out to every ACTIVE follower over its
        ordered link (stats/trace collection — the leader-merges-
        follower-sections leg of COLLECT_STATS and GET_TRACE). One
        shared deadline covers all followers; a follower that can't
        answer in time reports ``{"error": ...}`` instead of being
        evicted — liveness stays the health loop's job, a slow stats
        read must never degrade the mirror set."""
        with self._followers_mu:
            links = dict(self._links)
        if not links:
            return {}
        recs = [(addr, link.submit(typ, payload, CODEC_MSGPACK))
                for addr, link in links.items()]
        deadline = deadline_after(self.frame_timeout_s)
        out: Dict[str, Any] = {}
        for addr, rec in recs:
            if not rec["done"].wait(max(0.0, seconds_left(deadline))):
                out[addr] = {"error": f"no reply within "
                                      f"{self.frame_timeout_s}s"}
            elif rec.get("error"):
                out[addr] = {"error": rec["error"]}
            else:
                out[addr] = rec["reply"]
        return out

    def _on_collect_stats(self, p):
        # device_cache: the cross-query device-resident block cache's
        # hit/miss/evict/bytes counters (storage/devcache.py) — the
        # serve STATUS view of the warm-EXECUTE path.
        # metrics: the central registry snapshot (obs/metrics.py) —
        # compile stats, staging, devcache aggregates, serve counters
        # and span-time histograms in ONE section.
        out = {"sets": self.library.collect_stats(),
               "cache": self.library.store.stats.as_dict(),
               "device_cache": self.library.store.device_cache().stats(),
               "metrics": obs.REGISTRY.snapshot(),
               # the stateful-serving section: open sessions, batcher
               # occupancy, arena revive counters, decode program/
               # trace counts, multi-model residency attribution
               "sessions": self.sessions.stats()}
        page_store = self.library.store.page_store_stats()
        if page_store is not None:
            # the paged arena (storage="paged" sets): spills/loads are
            # the proof a table larger than the pool really streamed
            out["page_store"] = page_store
        if self._follower_addrs:
            # the mirror section: active/degraded links plus the
            # silently-dropped-frame count (satellite of the HA work —
            # an abort-closed link's queued frames now surface here)
            out["mirror"] = self.follower_status()
        if self._ha is not None:
            out["ha"] = self._ha.snapshot()
        if not p.get("local_only"):
            followers = self._fanout_read(MsgType.COLLECT_STATS,
                                          {"local_only": True})
            if followers:
                out["followers"] = followers
            shards = self.shards.fanout(MsgType.COLLECT_STATS,
                                        {"local_only": True})
            if shards:
                # per-shard sections, same best-effort merge contract
                # as the follower fan-out (a slow shard reports an
                # error entry, never gets evicted by a stats read)
                out["shards"] = shards
        return MsgType.OK, out

    # --- stateful serving (serve/sessions.py) -------------------------
    def _on_session_open(self, p):
        """SESSION_OPEN: ``op`` sub-dispatch — ``open`` (client),
        ``adopt``/``spill``/``handoff`` (daemon→daemon), ``lookup``/
        ``move`` (routing/rebalance). Mirrored: followers re-derive
        the session table from the replayed stream."""
        return self.sessions.handle_open(p)

    def _on_generate(self, p):
        """GENERATE: one decode step, sticky to the session's owner
        (typed retryable ``SessionMoved`` elsewhere), coalesced into
        a padded batch with every concurrent session of the model."""
        return self.sessions.handle_generate(p)

    def _on_session_close(self, p):
        """SESSION_CLOSE: drop state everywhere (devcache + arena +
        table), forwarding to a worker owner. Idempotent."""
        return self.sessions.handle_close(p)

    def _on_put_trace(self, p):
        """Client half of a traced query arriving after its reply: the
        RemoteClient ships its send/wait/hedge span profile once the
        logical request completes, and it merges into the qid's ringed
        profile as the ``client`` section — GET_TRACE then returns one
        end-to-end client→leader→follower decomposition. Best-effort
        by design (an unmatched qid — ring already rotated — is
        counted, not an error)."""
        prof = p.get("profile")
        if not isinstance(prof, dict):
            raise ProtocolError("PUT_TRACE needs a profile dict")
        qid = str(p.get("qid") or prof.get("qid") or "")
        merged = slow = False
        if qid and self._obs_enabled:
            merged = self.trace_ring.merge_section(qid, "client", prof)
            try:
                # a slow query persisted its profile when the trace
                # closed — before this section could exist; rewrite it
                slow = self.slowlog.merge_section(qid, "client", prof)
            except Exception as e:  # noqa: BLE001 — counted, never fatal
                obs.REGISTRY.counter("obs.slowlog_errors").inc()
                del e
        obs.REGISTRY.counter(
            "obs.put_trace.merged" if merged
            else "obs.put_trace.unmatched").inc()
        return MsgType.OK, {"merged": merged, "slowlog_merged": slow}

    def _on_health(self, p):
        """The SLO/health readout: every objective evaluated with
        multi-window burn rates (obs/slo.py), recent breach/recovery
        events, and the slowlog summary. On a leader, follower
        sections merge exactly like COLLECT_STATS — best-effort over
        the ordered links, a slow follower reports an error entry and
        is NEVER evicted by a health read."""
        out = {"objectives": self.slo.evaluate(),
               "events": self.slo.events(),
               "slowlog": self.slowlog.summary(),
               "followers_status": self.follower_status()
               if self._follower_addrs else None}
        if not p.get("local_only"):
            followers = self._fanout_read(MsgType.HEALTH,
                                          {"local_only": True})
            if followers:
                out["followers"] = followers
            shards = self.shards.fanout(MsgType.HEALTH,
                                        {"local_only": True})
            if shards:
                out["shards"] = shards
        if self._worker_addrs:
            out["pool"] = {"workers": list(self._worker_addrs),
                           "degraded": self.shards.degraded(),
                           "placement_epoch":
                               self.placement.to_wire()["epoch"]}
        return MsgType.OK, out

    def _on_get_trace(self, p):
        """The last N completed query profiles from this daemon's ring.
        On a leader, each profile additionally carries the follower
        sections that share its query id (``followers``: addr →
        profiles) — mirrored EXECUTEs forward the qid, so one logical
        query decomposes across every daemon that ran it.
        ``slow: true`` reads the persisted slow-query ring
        (``<root>/slowlog/``) instead of the in-memory one."""
        n = p.get("last")
        qid = p.get("qid")
        if p.get("slow"):
            # qid filter BEFORE the last-N truncation (the in-memory
            # path's semantics): a persisted slow query must stay
            # findable by id even after N newer outliers landed
            profiles = self.slowlog.entries()
            if qid:
                profiles = [pr for pr in profiles
                            if pr.get("qid") == str(qid)]
            if n:
                profiles = profiles[-int(n):]
            return MsgType.OK, {"profiles": profiles,
                                "enabled": self._obs_enabled,
                                "slowlog": self.slowlog.summary()}
        if qid:
            profiles = self.trace_ring.find(str(qid))
        else:
            profiles = self.trace_ring.last(int(n) if n else None)
        out: Dict[str, Any] = {"profiles": profiles,
                               "enabled": self._obs_enabled}

        def _merge_sections(profs, replies, section):
            merged = []
            for prof in profs:
                sections = {
                    addr: [fp for fp in reply.get("profiles", ())
                           if fp.get("qid") == prof.get("qid")]
                    for addr, reply in replies.items()
                    if "error" not in reply}
                sections = {a: s for a, s in sections.items() if s}
                if sections:
                    prof = {**prof, section: sections}
                merged.append(prof)
            return merged

        if not p.get("local_only"):
            freplies = self._fanout_read(
                MsgType.GET_TRACE, {"local_only": True, "qid": qid,
                                    "last": n})
            if freplies:
                out["profiles"] = _merge_sections(out["profiles"],
                                                  freplies, "followers")
                out["followers"] = freplies
            sreplies = self.shards.fanout(
                MsgType.GET_TRACE, {"local_only": True, "qid": qid,
                                    "last": n})
            if sreplies:
                # per-shard trace sections: a scatter-gather query's
                # subplans ran on the shards UNDER THE SAME qid, so
                # one logical query decomposes across the whole pool
                out["profiles"] = _merge_sections(out["profiles"],
                                                  sreplies, "shards")
                out["shards"] = sreplies
        return MsgType.OK, out

    def _on_get_metrics(self, p):
        """Continuous telemetry export. Two forms:

        * ``format="openmetrics"`` — the Prometheus text exposition
          (``obs/export.py``): stable catalogued family names,
          ``client``/``set`` labels from the attribution ledger, and —
          on a leader — every follower's samples merged under a
          ``follower`` label. The scrape endpoint's payload.
        * default (structured) — the registry snapshot plus the
          telemetry history's summary and derived rates (QPS, staged
          MB/s, hit-rate trend over ``window_s``), the feed ``cli obs
          --top`` refreshes from.

        Either way a reading is taken first, so a poller gets deltas
        exactly as fresh as its own cadence even when the snapshot
        thread is disabled."""
        from netsdb_tpu.obs import export as _export

        self.history.observe()
        snapshot = obs.REGISTRY.snapshot()
        followers: Dict[str, Any] = {}
        if not p.get("local_only"):
            followers = self._fanout_read(MsgType.GET_METRICS,
                                          {"local_only": True})
        if p.get("format") == "openmetrics":
            text = _export.to_openmetrics(
                snapshot,
                followers={a: (r.get("metrics") if isinstance(r, dict)
                               else {"error": "bad reply"})
                           for a, r in followers.items()})
            return MsgType.OK, {"format": "openmetrics", "text": text}
        window = p.get("window_s")
        out: Dict[str, Any] = {
            "metrics": snapshot,
            "history": self.history.summary(),
            "deltas": self.history.deltas(
                float(window) if window else None)}
        if followers:
            out["followers"] = followers
        return MsgType.OK, out

    def _on_analyze_set(self, p):
        """Planner statistics computed where the data lives — the
        summaries ship, the table stays (ref StorageCollectStats,
        ``PangeaStorageServer.h:48``). ColumnStats flatten to 4-int
        rows; dictionaries are lists of strings (msgpack-safe). A
        mesh-spanning placed table assembles its global columns first
        (stats need every host's rows)."""
        from netsdb_tpu.client import table_info
        from netsdb_tpu.relational.table import ColumnTable

        if self.is_sharded(p.get("db"), p.get("set")) \
                and not p.get("local_only"):
            return MsgType.OK, self._analyze_sharded(p["db"], p["set"])
        items = self.library.store.get_items(
            SetIdentifier(p["db"], p["set"]))
        if len(items) == 1 and isinstance(items[0], ColumnTable):
            info = table_info(
                self._fetch_global(p["db"], p["set"], items[0]))
        else:
            info = self.library.analyze_set(p["db"], p["set"])
        return MsgType.OK, {
            "num_rows": int(info["num_rows"]),
            "dicts": {k: list(v) for k, v in info["dicts"].items()},
            "stats": {k: [s.n_rows, s.min_val, s.max_val, s.n_distinct]
                      for k, s in info["stats"].items()}}

    def _analyze_sharded(self, db: str, set_name: str) -> Dict[str, Any]:
        """ANALYZE_SET fan-out over a partitioned set: every LIVE slot
        analyzes its local pages, the coordinator merges the summaries
        — rows sum, per-column [n_rows, min, max, n_distinct] merge by
        sum/min/max, dictionaries union in slot order. ``n_distinct``
        merges as the max over shards: a shard-local distinct count
        never exceeds the global one, so the merged figure is the
        tightest lower bound the summaries can give (exact when the
        partition key correlates with the column — range ingest keeps
        runs together). Degraded slots refuse, like scatter-gather:
        stats covering a subset of shards would silently mis-cost every
        plan built on them."""
        entry = self.placement.entry(db, set_name)
        parts: List[Tuple[int, Dict[str, Any]]] = []
        payload = {"db": db, "set": set_name, "local_only": True}
        for i, sl in enumerate(entry["slots"]):
            if sl["state"] != _placement.LIVE:
                raise ShardUnavailable(
                    f"slot {i} of {db}:{set_name} ({sl['addr']}) is "
                    f"degraded; partial statistics would mis-cost "
                    f"every plan — retry after readmit",
                    slot=i, epoch=entry["epoch"])
            if sl["addr"] == self.advertise_addr:
                _typ, rep = self._on_analyze_set(dict(payload))
            else:
                rep = self.shards.peer_request(
                    sl["addr"], MsgType.ANALYZE_SET, payload)
            parts.append((i, rep))
        merged_rows = 0
        dicts: Dict[str, List[Any]] = {}
        stats: Dict[str, List[Any]] = {}
        for _i, rep in parts:
            merged_rows += int(rep.get("num_rows") or 0)
            for k, vals in (rep.get("dicts") or {}).items():
                seen = dicts.setdefault(k, [])
                known = set(seen)
                for v in vals:
                    if v not in known:
                        seen.append(v)
                        known.add(v)
            for k, row in (rep.get("stats") or {}).items():
                n, lo, hi, nd = row
                cur = stats.get(k)
                if cur is None:
                    stats[k] = [int(n), lo, hi, int(nd)]
                else:
                    cur[0] += int(n)
                    if lo is not None:
                        cur[1] = lo if cur[1] is None else min(cur[1], lo)
                    if hi is not None:
                        cur[2] = hi if cur[2] is None else max(cur[2], hi)
                    cur[3] = max(cur[3], int(nd))
        obs.REGISTRY.counter("shard.analyze_fanouts").inc()
        return {"num_rows": merged_rows, "dicts": dicts, "stats": stats,
                "sharded": len(parts)}


def _device_info() -> Dict[str, Any]:
    """What jax says this process computes on (initialises the
    backend): the ``device`` section of every PING reply."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


def run_daemon(config: Configuration, host: str = "127.0.0.1",
               port: int = 8108, token: Optional[str] = None,
               max_jobs: Optional[int] = None,
               followers: Optional[list] = None,
               workers: Optional[list] = None,
               ha_peers: Optional[list] = None) -> int:
    """Start a daemon and block until shutdown — shared by the CLI
    ``serve`` subcommand and :func:`main`. ``followers``: worker-daemon
    addresses for multi-host fan-out (one per other jax.distributed
    process; call ``parallel.distributed.initialize_cluster`` first).
    ``workers``: shard-daemon addresses forming this leader's
    partitioned pool (horizontal scale-out — plain daemons, no
    jax.distributed requirement). ``ha_peers``: the ordered succession
    list arming automatic failover (index 0 = initial leader; pass the
    SAME list to every daemon in the pool)."""
    from netsdb_tpu.utils.profiling import get_logger

    ctl = ServeController(config, host=host, port=port, token=token,
                          max_jobs=max_jobs, followers=followers,
                          workers=workers, ha_peers=ha_peers)
    bound = ctl.start()
    get_logger("netsdb_tpu.serve", level="INFO").info(
        "netsdb_tpu serving on %s:%s — device %s x%d (%s)", host, bound,
        ctl.device["device_kind"], ctl.device["count"],
        ctl.device["platform"])
    ctl.serve_forever()
    return 0


def main(argv=None) -> int:
    """``python -m netsdb_tpu.serve.server`` — standalone daemon entry
    (the CLI's ``serve`` subcommand wraps this)."""
    import argparse

    ap = argparse.ArgumentParser(prog="netsdb-tpu-serve")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8108)
    ap.add_argument("--root", default=None, help="database root dir")
    ap.add_argument("--token", default=None, help="shared auth token")
    ap.add_argument("--max-jobs", type=int, default=None)
    ap.add_argument("--followers", default=None,
                    help="comma-separated worker daemon addresses for "
                         "multi-host fan-out (jax.distributed must be "
                         "initialized in every process)")
    ap.add_argument("--workers", default=None,
                    help="comma-separated shard daemon addresses "
                         "forming this leader's partitioned worker "
                         "pool (horizontal scale-out)")
    ap.add_argument("--ha-peers", default=None,
                    help="comma-separated ORDERED succession list for "
                         "automatic failover (index 0 = initial "
                         "leader; pass the same list to every daemon)")
    args = ap.parse_args(argv)
    config = Configuration(root_dir=args.root) if args.root else DEFAULT_CONFIG
    followers = ([a.strip() for a in args.followers.split(",") if a.strip()]
                 if args.followers else None)
    workers = ([a.strip() for a in args.workers.split(",") if a.strip()]
               if args.workers else None)
    ha_peers = ([a.strip() for a in args.ha_peers.split(",") if a.strip()]
                if args.ha_peers else None)
    return run_daemon(config, host=args.host, port=args.port,
                      token=args.token, max_jobs=args.max_jobs,
                      followers=followers, workers=workers,
                      ha_peers=ha_peers)


if __name__ == "__main__":
    raise SystemExit(main())
