"""Thin RPC client — the PDBClient facade over the wire.

Mirrors :class:`netsdb_tpu.client.Client` method-for-method but sends
typed frames to a resident :class:`~netsdb_tpu.serve.server.ServeController`
instead of owning a store, the way ``PDBClient`` aggregates catalog/
dispatcher/storage/query clients all speaking ``simpleRequest`` RPCs to
the master (``src/mainClient/headers/PDBClient.h:28-295``).

Deliberately JAX-free: a client process never initializes a device
backend (the daemon owns the TPU). Tensors come back as numpy-backed
:class:`RemoteTensor` values whose ``to_dense()`` matches
``BlockedTensor.to_dense()``, so model drivers (``FFModel`` etc.) run
unchanged against either client.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue as _queue
import random
import socket
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.serve.errors import (  # noqa: F401 — re-exported API
    AdmissionFullError,
    AuthError,
    CoalesceAbortedError,
    ConnectionLostError,
    CorruptFrameError,
    DeadlineExceededError,
    FollowerDegradedError,
    LaneSaturatedError,
    NotLeaderError,
    PlacementStaleError,
    ProtocolVersionError,
    RemoteError,
    RemoteTimeoutError,
    RetryableRemoteError,
    SessionMovedError,
    SessionUnknownError,
    ShardUnavailableError,
    classify_remote,
)
from netsdb_tpu.serve.protocol import (
    CLIENT_ID_KEY,
    CODEC_MSGPACK,
    CODEC_PICKLE,
    IDEMPOTENCY_KEY,
    LANE_KEY,
    MUTATING_TYPES,
    OBS_FRAMES,
    PLACEMENT_EPOCH_KEY,
    PROTO_VERSION,
    QUERY_ID_KEY,
    SESSION_KEY,
    session_output_set,
    SHARD_SLOT_KEY,
    MsgType,
    ProtocolError,
    recv_frame,
    send_frame,
    tensor_to_wire,
)
from netsdb_tpu.utils.locks import TrackedLock
from netsdb_tpu.utils.timing import deadline_after, seconds_left

#: frame types that open a client-side query trace (and mint the query
#: id the daemon's trace joins on) — the query-shaped requests whose
#: time decomposition GET_TRACE answers; decode steps trace too, so a
#: slow GENERATE decomposes into coalesce-wait / state-load / device.
#: Under a trace that is ALREADY current (RemoteClient.request_trace:
#: one logical request of several frames) every workload frame carries
#: that trace's id instead, whatever its type
TRACED_TYPES = frozenset({MsgType.EXECUTE_COMPUTATIONS,
                          MsgType.EXECUTE_PLAN,
                          MsgType.GENERATE})


@dataclasses.dataclass
class RetryPolicy:
    """Exponential backoff with jitter for retryable failures.

    ``deadline_s`` bounds one LOGICAL request across all its attempts
    (a per-request deadline, measured on the monotonic clock); when the
    next backoff would cross it, :class:`DeadlineExceededError` is
    raised instead of sleeping. ``max_attempts=1`` disables retries
    (the follower mirror links use this: a mirror failure must surface
    immediately so the leader can evict + resync, not be papered over)."""

    max_attempts: int = 4
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.5
    deadline_s: Optional[float] = None

    def backoff_s(self, attempt: int, rng: random.Random) -> float:
        d = min(self.base_delay_s * self.multiplier ** (attempt - 1),
                self.max_delay_s)
        return d * (1.0 - self.jitter * rng.random())


class RemoteTableInfo:
    """Summary of a daemon-side table ingest (``send_table`` reply)."""

    def __init__(self, num_rows: int, columns: list):
        self.num_rows = num_rows
        self.columns = columns

    def __repr__(self):
        return f"RemoteTableInfo(rows={self.num_rows}, cols={self.columns})"


class RemoteTensor:
    """Dense result fetched from the daemon — quacks like BlockedTensor
    for the read side (``to_dense``/``shape``/``dtype``)."""

    def __init__(self, dense: np.ndarray, block_shape=None):
        self._dense = dense
        self.block_shape = tuple(block_shape) if block_shape else None

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._dense.shape)

    @property
    def dtype(self):
        return self._dense.dtype

    def to_dense(self) -> np.ndarray:
        return self._dense

    def __repr__(self) -> str:
        return f"RemoteTensor(shape={self.shape}, dtype={self.dtype})"


class RemoteIdent(Tuple[str, str]):
    """(db, set) result key, printable like SetIdentifier."""

    def __new__(cls, db: str, set_: str):
        return super().__new__(cls, (db, set_))

    @property
    def db(self) -> str:
        return self[0]

    @property
    def set(self) -> str:
        return self[1]

    def __str__(self) -> str:
        return f"{self[0]}:{self[1]}"


class RemoteClient:
    """``Client(address="host:port")`` returns one of these."""

    def __init__(self, address: str, token: Optional[str] = None,
                 timeout: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None,
                 chaos=None, seed: Optional[int] = None,
                 connect_timeout: Optional[float] = None,
                 replicas: Optional[Sequence[str]] = None,
                 hedge_delay_s: Optional[float] = None,
                 ingest_window: int = 4,
                 ingest_chunk_bytes: int = 8 << 20,
                 client_id: Optional[str] = None,
                 lane: Optional[str] = None,
                 trace_sample: Optional[int] = None,
                 ship_traces: bool = True,
                 failover: Optional[Sequence[str]] = None):
        """``timeout``: socket-level timeout applied to every blocking
        recv after the handshake (None = block; a hung server then
        surfaces as :class:`RemoteTimeoutError` instead of a wedged
        caller). ``connect_timeout`` bounds the dial + handshake
        separately — a caller that must tolerate slow REPLIES (long
        jobs) can still refuse to hang on a peer that accepts the TCP
        connection and then goes silent (defaults to ``timeout``).
        ``retry``: :class:`RetryPolicy` for retryable failures; the
        default retries 4 attempts with jittered exponential backoff.
        ``chaos``: a :class:`~netsdb_tpu.serve.chaos.ChaosInjector`
        faulting this client's request/reply frames (tests only).
        ``seed`` seeds the backoff jitter for reproducible schedules.

        ``replicas``: addresses of other daemons holding the same data
        (mirrored followers). When set, idempotent READS hedge: if the
        primary's reply hasn't landed after the observed-p99 latency
        (or ``hedge_delay_s`` when given), the same request is issued
        to a replica over a one-shot connection and the first success
        wins — tail latency becomes the replicas' min, not the
        primary's max. Mutations never hedge (ordering runs through the
        leader).

        ``ingest_window``/``ingest_chunk_bytes``: the bulk-ingest
        pipeline knobs — ``send_data``/``send_table`` stream large
        payloads as ~``ingest_chunk_bytes`` chunks with up to
        ``ingest_window`` chunks in flight before waiting on acks
        (depth-W pipelining, not stop-and-wait).

        ``client_id``: the identity (tenant/service string) attached to
        every frame (``protocol.CLIENT_ID_KEY``); the daemon aggregates
        staged bytes, device-cache traffic and executor chunk counts
        per (client, db:set) — visible in COLLECT_STATS'
        ``attribution`` section. None = unattributed ("anon" daemon
        bucket).

        ``lane``: optional scheduler lane hint
        (``protocol.LANE_KEY``) attached to every frame — the daemon
        admits this client's jobs through that priority lane of its
        query scheduler (``serve/sched/``). Absent, jobs ride the
        client-identity lane. Lane *weights* are server configuration
        (``config.sched_lanes``) — naming a lane grants no priority
        the operator didn't configure.

        ``trace_sample``: mint a query id (and therefore pay
        end-to-end tracing) for 1 in N query-shaped requests —
        ``obs.sample_qid``. None takes ``DEFAULT_CONFIG.
        obs_trace_sample``; 1 traces everything. ``ship_traces``: after
        a traced request completes, ship the client's span profile to
        the daemon (PUT_TRACE, on a background shipper thread over its
        own connection — never the request critical path) so GET_TRACE
        returns one merged client→leader→follower decomposition;
        best-effort — a lost ship costs the client section, never the
        request. :meth:`flush_traces` drains the queue.

        ``failover``: candidate leader addresses (the HA succession
        list). Two rediscovery paths use it: a typed ``NotLeader``
        refusal that NAMES the current leader re-points there
        immediately; a connection loss (or a NotLeader with no known
        leader — mid-election) rotates through the candidates across
        the normal retry/backoff schedule, which doubles as the
        bounded election-window wait. Empty = PR 9 behavior (retries
        stay pinned to one address)."""
        host, _, port = address.rpartition(":")
        self.host = host or "127.0.0.1"
        self.port = int(port)
        self.token = token
        # one in-flight request per conn; tracked rank (the witness
        # coverage the PR 8 carry-over asked for)
        self._lock = TrackedLock("RemoteClient._lock")
        self._sock: Optional[socket.socket] = None
        self._timeout = timeout
        self._connect_timeout = (connect_timeout if connect_timeout
                                 is not None else timeout)
        self._retry = retry or RetryPolicy()
        self._chaos = chaos
        self._rng = random.Random(seed)
        #: attempts consumed by the most recent logical request (1 = no
        #: retry) and total retries over this client's lifetime —
        #: observability for tests and callers tuning policies
        self.last_attempts = 0
        self.total_retries = 0
        # hedged-read state: replica ring + observed read latencies.
        # The adaptive p99 hedge trigger and the metrics registry read
        # the SAME numbers: latencies land in this client's bounded
        # histogram (obs.Histogram — what hedge_delay_s quantiles over)
        # and every observation is mirrored into the shared registry
        # histogram "serve.client.read_latency_s" that COLLECT_STATS
        # ships, so introspection and stats can never disagree.
        self._replicas = list(replicas or [])
        self._hedge_delay_s = hedge_delay_s
        self._read_hist = obs.Histogram(max_samples=256)
        self._hedge_rr = 0
        self.hedges_issued = 0
        self.hedges_won = 0
        self.ingest_window = max(1, int(ingest_window))
        self.ingest_chunk_bytes = max(64 << 10, int(ingest_chunk_bytes))
        self.client_id = client_id
        self.lane = lane
        if trace_sample is None:
            from netsdb_tpu.config import DEFAULT_CONFIG

            trace_sample = getattr(DEFAULT_CONFIG, "obs_trace_sample", 1)
        self._trace_sample = max(1, int(trace_sample))
        # own sampler phase: the process-default sampler would
        # phase-lock under interleaved clients (obs.QidSampler
        # docstring) — per-client state keeps trace_sample=N meaning
        # exactly 1-in-N of THIS client's requests
        self._qid_sampler = obs.QidSampler()
        self.ship_traces = bool(ship_traces)
        # background PUT_TRACE shipper (lazy): completed client traces
        # queue here and ship over a dedicated connection OFF the
        # request critical path
        self._ship_mu = TrackedLock("RemoteClient._ship_mu")
        self._ship_q: Optional["_queue.Queue"] = None
        self._ship_thread: Optional[threading.Thread] = None
        # thread id that currently drives a streaming reply (scan_stream
        # / chunked pulls) — a nested request from that thread must NOT
        # wait on the lock (self-deadlock) nor write to the streaming
        # socket (frame corruption); it gets a one-shot side connection
        self._stream_owner: Optional[int] = None
        # placement-aware routing state: the daemon's sharded-set map
        # (shipped in the handshake ONLY when sharded sets exist —
        # un-sharded clients never pay a frame), per-shard connection
        # cache, and the stale-map refresh guard. A PlacementStale
        # rejection refreshes the cache between retry attempts.
        self._placement_mu = TrackedLock("RemoteClient._placement_mu")
        self._placement_wire: Optional[Dict[str, Any]] = None
        self._shard_clients: Dict[str, "RemoteClient"] = {}
        # serializes the PLACEMENT fetch: concurrent refreshers wait
        # for the in-flight result; owner thread id breaks re-entry
        self._placement_fetch_mu = TrackedLock(
            "RemoteClient._placement_fetch_mu")
        self._refreshing_placement: Optional[int] = None
        # HA failover: candidate leaders + rotation cursor (guarded by
        # _lock with the rest of the connection state)
        self._failover = [a for a in (failover or [])]
        self._failover_idx = 0
        #: times this client re-pointed at a different daemon
        #: (observability for the failover tests)
        self.failovers = 0
        self._connect()

    # --- transport ----------------------------------------------------
    def _dial(self, budget_s: Optional[float] = None,
              address: Optional[str] = None) -> socket.socket:
        """Open + handshake one connection (the single copy of the
        dial sequence — main connection, one-shot side requests,
        nested streams and replica hedges all come through here).
        ``budget_s`` caps the connect + handshake below the configured
        connect timeout — the per-request deadline must bound a hung
        DIAL too (a blackholed host, or a peer that accepts TCP and
        never answers HELLO), not just a hung reply. The HELLO carries
        :data:`~netsdb_tpu.serve.protocol.PROTO_VERSION`; a
        wire-format mismatch in either direction is the typed fatal
        :class:`ProtocolVersionError` — mixed-version peers never get
        past the handshake."""
        host, port = self.host, self.port
        if address is not None:
            h, _, p = address.rpartition(":")
            host, port = (h or "127.0.0.1"), int(p)
        ct = self._connect_timeout
        if budget_s is not None:
            ct = budget_s if ct is None else min(ct, budget_s)
        s = socket.create_connection((host, port), timeout=ct)
        try:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            send_frame(s, MsgType.HELLO, {"token": self.token,
                                          "proto": PROTO_VERSION})
            typ, reply = recv_frame(s, allow_pickle=False)
            if typ == MsgType.ERR:
                # handshake refusals are fatal by construction
                # (auth / wire-format mismatch)
                raise classify_remote(reply)
            if reply.get("version") != PROTO_VERSION:
                raise ProtocolVersionError(
                    "ProtocolVersionError",
                    f"daemon at {host}:{port} speaks wire format "
                    f"v{reply.get('version')}; this client is "
                    f"v{PROTO_VERSION} — mixed versions are refused")
            if isinstance(reply.get("placement"), dict):
                # v3 handshake placement shipping: cache the sharded-
                # set map so ingest routes to owning shards without an
                # extra fetch
                with self._placement_mu:
                    self._placement_wire = reply["placement"]
            s.settimeout(self._timeout)  # steady-state I/O bound
        except BaseException:
            s.close()
            raise
        return s

    def _connect(self, budget_s: Optional[float] = None) -> None:
        self._sock = self._dial(budget_s)

    def _oneshot_request(self, msg_type: MsgType, payload: Any,
                         codec: int,
                         io_timeout: Optional[float] = None,
                         address: Optional[str] = None) -> Any:
        """Issue one request over a throwaway connection — used when the
        caller's thread is mid-stream on the main connection (e.g.
        ``for item in c.scan_stream(...): c.send_data(...)``), which
        must neither block on the held lock nor interleave frames, and
        by hedged reads dialing a replica (``address``)."""
        s = self._dial(io_timeout, address=address)
        try:
            if io_timeout is not None:
                s.settimeout(io_timeout)
            send_frame(s, msg_type, payload, codec, chaos=self._chaos)
            typ, reply = self._recv_reply(s)
        finally:
            s.close()
        if typ == MsgType.ERR:
            raise classify_remote(reply)
        return reply

    @staticmethod
    def _recv_reply(sock) -> Tuple[Any, Any]:
        """Reply recv with decode failures typed: a body that fails to
        decode (bit flips on the wire) is the retryable CorruptFrame
        family, not an anonymous pickle/msgpack exception. Replies may
        carry host objects (SCAN_SET) → pickle allowed on this side:
        the client already trusts the server it chose to connect to."""
        try:
            return recv_frame(sock, allow_pickle=True)
        except (ConnectionError, OSError):
            raise
        except Exception as e:
            raise CorruptFrameError(
                type(e).__name__, f"reply body failed to decode: {e}") from e

    def _request_once(self, msg_type: MsgType, payload: Any, codec: int,
                      io_timeout: Optional[float] = None) -> Any:
        """One attempt on the persistent connection. Any mid-request
        failure leaves the frame stream desynced — a later request
        would read THIS request's late reply as its own — so the socket
        is closed and the next attempt re-dials lazily. ``io_timeout``
        tightens this attempt's socket timeout (the per-request
        deadline must bound a HUNG attempt, not just the gaps between
        attempts); the steady-state timeout is restored on success."""
        with self._lock:
            if self._sock is None:
                self._connect(io_timeout)
            try:
                if io_timeout is not None:
                    self._sock.settimeout(io_timeout)
                with obs.span("client.send", "client"):
                    send_frame(self._sock, msg_type, payload, codec,
                               chaos=self._chaos,
                               encode_span="client.encode")
                with obs.span("client.wait", "client"):
                    # lint: disable=lock-blocking-call -- the conn lock exists to serialize one in-flight request per connection; holding it across the reply IS the protocol, and the wait is bounded by the socket timeout set at dial
                    typ, reply = self._recv_reply(self._sock)
                if io_timeout is not None:
                    self._sock.settimeout(self._timeout)
            except Exception:
                self._drop_connection()
                raise
        if typ == MsgType.ERR:
            raise classify_remote(reply)
        return reply

    def _retry_driver(self, attempt_fn,
                      deadline_s: Optional[float] = None) -> Any:
        """The ONE retry engine (plain requests, hedged reads and bulk
        conversations all run through here): call ``attempt_fn(
        io_timeout)`` under the client's :class:`RetryPolicy` and the
        per-request deadline, retrying typed-retryable failures with
        jittered exponential backoff. ``io_timeout`` caps the attempt's
        socket timeout at the remaining budget — the deadline bounds a
        HUNG attempt too, not just the backoff gaps. Every raised error
        is typed (:class:`RemoteError` family) — callers never see a
        bare socket exception."""
        policy = self._retry
        budget_s = deadline_s if deadline_s is not None else policy.deadline_s
        deadline = deadline_after(budget_s) if budget_s is not None else None
        attempt = 1
        while True:
            self.last_attempts = attempt
            io_timeout = None  # None = keep the steady-state timeout
            if deadline is not None:
                left = seconds_left(deadline)
                if left <= 0:
                    raise DeadlineExceededError(
                        "DeadlineExceeded",
                        f"request deadline of {budget_s}s already spent "
                        f"before attempt {attempt}")
                io_timeout = left if self._timeout is None \
                    else min(self._timeout, left)
            try:
                return attempt_fn(io_timeout)
            except RemoteError as e:
                if not e.retryable:
                    raise
                failure: RemoteError = e
            except (socket.timeout, TimeoutError) as e:
                failure = RemoteTimeoutError(type(e).__name__,
                                             str(e) or "socket timeout")
            except (ConnectionError, OSError) as e:
                # includes ProtocolError (desync/truncation) and refused
                # re-dials — the connection is already dropped, the next
                # attempt re-dials fresh
                failure = ConnectionLostError(type(e).__name__, str(e))
            if attempt >= policy.max_attempts:
                raise failure
            if isinstance(failure, NotLeaderError):
                addr = getattr(failure, "leader_addr", None)
                if addr:
                    # the refusal NAMES the leader: re-point and retry
                    # immediately — deterministic redirect, not
                    # congestion, so backoff would only add latency
                    self._switch_address(addr)
                    attempt += 1
                    self.total_retries += 1
                    obs.REGISTRY.counter("serve.client.retries").inc()
                    continue
                # mid-election (no leader known yet): fall through to
                # the normal backoff — it doubles as the bounded
                # election-window wait — rotating candidates meanwhile
                self._rotate_failover()
            elif isinstance(failure, (ConnectionLostError,
                                      RemoteTimeoutError)) \
                    and self._failover:
                # the daemon died outright (no typed refusal to carry
                # a leader address): walk the succession list — one of
                # the candidates is (or is about to become) the leader
                self._rotate_failover()
            if isinstance(failure, PlacementStaleError):
                # the frame rode an out-of-date placement map: refresh
                # the cache and retry IMMEDIATELY — the rejection is
                # deterministic (not congestion), so exponential
                # backoff would only delay the re-route
                self._refresh_placement()
                attempt += 1
                self.total_retries += 1
                obs.REGISTRY.counter("serve.client.retries").inc()
                continue
            delay = policy.backoff_s(attempt, self._rng)
            hint = getattr(failure, "retry_after_s", None)
            if hint is not None and hint > 0:
                # the server computed this from its lane's observed
                # queue-wait histogram (serve/sched/) — honor it when
                # it says to wait LONGER than the exponential policy
                # would. The policy stays the floor: a near-zero
                # historical median during a fresh saturation spike
                # must not collapse backoff into a retry storm. Small
                # multiplicative jitter keeps a rejected herd from
                # re-synchronizing on the exact same instant.
                delay = max(delay, float(hint)
                            * (1.0 + 0.25 * self._rng.random()))
            if deadline is not None and delay > seconds_left(deadline):
                raise DeadlineExceededError(
                    "DeadlineExceeded",
                    f"request deadline of {budget_s}s exhausted after "
                    f"{attempt} attempt(s); last failure: {failure}",
                ) from failure
            time.sleep(delay)
            attempt += 1
            self.total_retries += 1
            obs.REGISTRY.counter("serve.client.retries").inc()

    def _request(self, msg_type: MsgType, payload: Any,
                 codec: int = CODEC_MSGPACK,
                 deadline_s: Optional[float] = None) -> Any:
        """One logical request: attach an idempotency token to mutating
        frames and this client's identity to every frame, stamp the
        CURRENT trace's query id on a workload frame — or, with no
        trace current, mint a SAMPLED one for query-shaped frames (the
        trace the daemon's spans join on — 1 in ``trace_sample``) —
        then retry under :meth:`_retry_driver`. A request that opened
        its own trace ships its client span profile to the daemon
        afterwards (PUT_TRACE, best-effort); under a caller's trace
        the caller does (:meth:`request_trace`)."""
        if isinstance(payload, dict):
            extra = {}
            if msg_type in MUTATING_TYPES \
                    and IDEMPOTENCY_KEY not in payload:
                # one token per LOGICAL request: every retry resends the
                # same token, so the server can dedupe a mutation whose
                # first reply was lost mid-wire
                extra[IDEMPOTENCY_KEY] = uuid.uuid4().hex
            if self.client_id is not None \
                    and CLIENT_ID_KEY not in payload:
                extra[CLIENT_ID_KEY] = str(self.client_id)
            if self.lane is not None and LANE_KEY not in payload:
                extra[LANE_KEY] = str(self.lane)
            if extra:
                payload = dict(payload)
                payload.update(extra)
        qid = None
        if isinstance(payload, dict) and QUERY_ID_KEY not in payload \
                and msg_type not in OBS_FRAMES:
            # a payload already carrying a qid is a forwarded frame
            # (the leader's mirror path) — its originating client owns
            # the trace
            cur = obs.current_trace()
            if cur is not None:
                # a frame of a larger traced request: the daemon's
                # profile of it joins the caller's trace
                payload = dict(payload)
                payload[QUERY_ID_KEY] = cur.qid
            elif msg_type in TRACED_TYPES:
                # one id per LOGICAL query (retries reuse it), minted
                # 1-in-N (config.obs_trace_sample via the constructor)
                # so high-QPS traffic traces at bounded cost
                qid = self._qid_sampler.sample(self._trace_sample)
                if qid is not None:
                    payload = dict(payload)
                    payload[QUERY_ID_KEY] = qid
        oneshot = self._stream_owner == threading.get_ident()

        def attempt(io_timeout):
            if oneshot:
                return self._oneshot_request(msg_type, payload, codec,
                                             io_timeout=io_timeout)
            if self._replicas and msg_type not in MUTATING_TYPES \
                    and msg_type != MsgType.SHUTDOWN:
                return self._request_hedged(msg_type, payload, codec,
                                            io_timeout=io_timeout)
            return self._request_once(msg_type, payload, codec,
                                      io_timeout=io_timeout)

        if qid is None:
            return self._retry_driver(attempt, deadline_s)
        with obs.trace(qid, origin="client") as tr:
            out = self._retry_driver(attempt, deadline_s)
        if tr is not None and self.ship_traces:
            # the trace closed on context exit (total_s final): ship
            # the client half so the daemon's GET_TRACE returns one
            # merged end-to-end profile
            self._ship_trace(qid, tr)
        return out

    @contextlib.contextmanager
    def request_trace(self, name: str):
        """One client trace around a logical request of SEVERAL frames
        (``ModelServing.score``: ship the batch, execute, read back), with a
        span ``name`` over the whole of it: sampled through this
        client's own :class:`~netsdb_tpu.obs.QidSampler` like a
        single traced frame, every workload frame sent inside carries
        its query id (:meth:`_request`), and the profile ships to the
        daemon when it closes. Yields None — and traces nothing —
        where the request is sampled out, tracing is off, or a trace
        is already current (the frames then join that one)."""
        qid = None if obs.current_trace() is not None \
            else self._qid_sampler.sample(self._trace_sample)
        if qid is None:
            yield None
            return
        with obs.trace(qid, origin="client") as tr:
            with obs.span(name, "client"):
                yield tr
        if tr is not None and self.ship_traces:
            self._ship_trace(qid, tr)

    def _ship_trace(self, qid: str, tr) -> None:
        """Queue a completed client trace for the background shipper —
        NEVER on the caller's critical path: at ``trace_sample=1`` a
        synchronous PUT_TRACE would add a full extra RPC to every
        request (doubling client-observed latency for small warm
        queries). Best-effort end to end: a full queue drops the
        profile (``trace_ship_dropped``), ship failures are counted,
        neither ever surfaces to the request that produced the trace.
        :meth:`flush_traces` waits for the queue to drain (tests,
        orderly shutdown)."""
        with self._ship_mu:
            if self._ship_q is None:
                self._ship_q = _queue.Queue(maxsize=64)
                self._ship_thread = threading.Thread(
                    target=self._ship_loop, args=(self._ship_q,),
                    daemon=True, name="netsdb-trace-ship")
                self._ship_thread.start()
            q = self._ship_q
        try:
            q.put_nowait({"qid": qid, "profile": tr.profile()})
        except _queue.Full:
            obs.REGISTRY.counter("serve.client.trace_ship_dropped").inc()

    def _ship_loop(self, q: "_queue.Queue") -> None:
        """Shipper thread body: drain queued profiles over its own
        dedicated connection (the main connection and its lock stay
        untouched — a ship can never interleave with a stream or block
        a request). The socket persists across ships and re-dials
        after any failure. ``q`` is bound at spawn — ``close()`` nulls
        the instance attribute, and this loop must keep draining to
        its sentinel regardless."""
        sock = None
        try:
            while True:
                item = q.get()
                try:
                    if item is None:
                        return  # close() sentinel
                    try:
                        if sock is None:
                            sock = self._dial()
                        send_frame(sock, MsgType.PUT_TRACE, item,
                                   CODEC_MSGPACK, chaos=self._chaos)
                        typ, reply = self._recv_reply(sock)
                        if typ == MsgType.ERR:
                            raise classify_remote(reply)
                        obs.REGISTRY.counter(
                            "serve.client.traces_shipped").inc()
                    except Exception as e:  # noqa: BLE001 — counted
                        obs.REGISTRY.counter(
                            "serve.client.trace_ship_failures").inc()
                        del e
                        if sock is not None:
                            try:
                                sock.close()
                            except OSError:
                                pass
                            sock = None
                finally:
                    q.task_done()
        finally:
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass

    def flush_traces(self, timeout_s: float = 5.0) -> bool:
        """Wait until every queued client trace has shipped (or
        failed), up to ``timeout_s``; True when the queue drained. The
        request path never waits — this is for tests and orderly
        shutdown."""
        q = self._ship_q
        if q is None:
            return True
        deadline = time.monotonic() + timeout_s
        with q.all_tasks_done:
            while q.unfinished_tasks:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                q.all_tasks_done.wait(left)
        return True

    # --- windowed bulk ingest (BULK_BEGIN/CHUNK/COMMIT) ---------------
    def _bulk_once(self, sock: socket.socket, begin: dict,
                   chunk_fn) -> Any:
        """One attempt of a streamed-ingest conversation on ``sock``:
        BEGIN, then chunks pipelined ``ingest_window`` deep (each chunk
        is acked by the server after it DECODES — outside any set lock
        — so acks overlap the client's next sends instead of
        stop-and-wait), then COMMIT, whose reply is the target op's
        reply. A BEGIN answered without ``go`` is the server replaying
        a completed execution from the idempotency cache — the retry
        path after a lost final ack — and ends the conversation
        immediately."""
        send_frame(sock, MsgType.BULK_BEGIN, begin, chaos=self._chaos)
        typ, reply = self._recv_reply(sock)
        if typ == MsgType.ERR:
            raise classify_remote(reply)
        if not (isinstance(reply, dict) and reply.get("go")):
            return reply  # deduplicated replay of the completed reply
        seq = 0
        unacked = 0
        for chunk in chunk_fn():
            chunk["seq"] = seq
            send_frame(sock, MsgType.BULK_CHUNK, chunk, chaos=self._chaos)
            seq += 1
            unacked += 1
            while unacked >= self.ingest_window:
                typ, ack = self._recv_reply(sock)
                if typ == MsgType.ERR:
                    raise classify_remote(ack)
                unacked -= 1
        while unacked:
            typ, ack = self._recv_reply(sock)
            if typ == MsgType.ERR:
                raise classify_remote(ack)
            unacked -= 1
        send_frame(sock, MsgType.BULK_COMMIT, {"chunks": seq},
                   chaos=self._chaos)
        typ, reply = self._recv_reply(sock)
        if typ == MsgType.ERR:
            raise classify_remote(reply)
        return reply

    def _bulk_request(self, op: MsgType, meta: dict, chunk_fn,
                      deadline_s: Optional[float] = None,
                      token: Optional[str] = None) -> Any:
        """One LOGICAL bulk ingest: stream ``chunk_fn()``'s chunks under
        the windowed-ack protocol, retrying the whole conversation on
        retryable failures under the client's :class:`RetryPolicy`.
        ``chunk_fn`` must return a fresh chunk iterator per call (each
        retry re-streams). The single idempotency token spans every
        attempt: nothing applies server-side until COMMIT, and a retry
        after a lost COMMIT reply replays the cached result instead of
        double-applying. From a thread that is mid-stream on the main
        connection the whole conversation rides a one-shot side
        connection (same rule as nested plain requests). ``token``
        overrides the minted idempotency token — routed shard ingest
        passes its slot-stable token so retries across placement
        refreshes stay at-most-once."""
        token = token or uuid.uuid4().hex
        begin = {"op": int(op), "meta": meta, IDEMPOTENCY_KEY: token}
        if self.client_id is not None:
            begin[CLIENT_ID_KEY] = str(self.client_id)

        def attempt(io_timeout):
            if self._stream_owner == threading.get_ident():
                s = self._dial(io_timeout)
                try:
                    if io_timeout is not None:
                        s.settimeout(io_timeout)
                    return self._bulk_once(s, begin, chunk_fn)
                finally:
                    s.close()
            with self._lock:
                if self._sock is None:
                    self._connect(io_timeout)
                try:
                    if io_timeout is not None:
                        self._sock.settimeout(io_timeout)
                    out = self._bulk_once(self._sock, begin, chunk_fn)
                    if io_timeout is not None:
                        self._sock.settimeout(self._timeout)
                    return out
                except Exception:
                    # ANY mid-conversation failure desyncs the
                    # chunk stream — drop and re-dial on retry
                    self._drop_connection()
                    raise

        return self._retry_driver(attempt, deadline_s)

    # --- hedged reads -------------------------------------------------
    def _observe_read_latency(self, dt: float) -> None:
        """One read's latency, recorded ONCE into both views: this
        client's bounded histogram (what :meth:`hedge_delay_s`
        quantiles over) and the process-shared registry histogram
        (what COLLECT_STATS ships) — same observations, same numbers."""
        self._read_hist.observe(dt)
        obs.REGISTRY.histogram("serve.client.read_latency_s").observe(dt)

    def hedge_delay_s(self) -> float:
        """Current hedge trigger: the explicit knob when set, else the
        observed p99 of this client's recent read latencies (adaptive —
        a hedge should fire only when THIS request is already in the
        tail; quantiled over the shared latency histogram), else a
        50 ms cold-start default."""
        if self._hedge_delay_s is not None:
            return self._hedge_delay_s
        if self._read_hist.sample_count >= 8:
            p99 = self._read_hist.quantile(0.99)
            if p99 is not None:
                return p99
        return 0.05

    def read_latency_stats(self) -> Dict[str, Any]:
        """Summary of this client's observed read latencies — the same
        histogram the hedge trigger quantiles over."""
        return self._read_hist.summary()

    def _request_hedged(self, msg_type: MsgType, payload: Any, codec: int,
                        io_timeout: Optional[float] = None) -> Any:
        """One attempt of an idempotent read with tail-latency hedging:
        the primary runs on the persistent connection; if its reply
        hasn't landed within :meth:`hedge_delay_s`, the SAME request is
        issued to the next replica over a one-shot connection and the
        first success wins. When the hedge wins, the primary's socket
        is force-closed so its worker thread (and the connection lock)
        are released promptly instead of waiting out a slow reply.
        Reads are idempotent by classification, so duplicated execution is
        harmless; failures surface exactly like an unhedged attempt
        (the retry loop above classifies them).

        Cost note: the primary runs on a short-lived thread so the
        caller can time it — ~tens of µs per read, small against a
        loopback RPC and irrelevant against the tail latencies hedging
        exists to cut. Clients that never want that overhead simply
        don't pass ``replicas``."""
        t0 = time.perf_counter()
        results: "_queue.Queue" = _queue.Queue()

        def attempt(tag, fn):
            try:
                results.put((tag, None, fn()))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                results.put((tag, e, None))

        threading.Thread(
            target=attempt, daemon=True,
            args=("primary", lambda: self._request_once(
                msg_type, payload, codec, io_timeout=io_timeout)),
        ).start()
        try:
            tag, err, val = results.get(timeout=self.hedge_delay_s())
        except _queue.Empty:
            self.hedges_issued += 1
            obs.REGISTRY.counter("serve.client.hedges_issued").inc()
            addr = self._replicas[self._hedge_rr % len(self._replicas)]
            self._hedge_rr += 1
            threading.Thread(
                target=attempt, daemon=True,
                args=("hedge", lambda: self._oneshot_request(
                    msg_type, payload, codec, io_timeout=io_timeout,
                    address=addr)),
            ).start()
            tag, err, val = results.get()
            if err is not None:
                # first responder failed — wait for the straggler
                tag2, err2, val2 = results.get()
                if err2 is None:
                    tag, err, val = tag2, None, val2
                elif tag == "hedge":
                    tag, err = "primary", err2  # prefer the primary's error
        if err is not None:
            raise err
        if tag == "hedge":
            self.hedges_won += 1
            obs.REGISTRY.counter("serve.client.hedges_won").inc()
            # release the primary (it holds _lock until its recv ends)
            self._force_close()
            # if the primary ALREADY finished and released the lock,
            # nobody else will reap the now-closed socket — a later
            # request would find it non-None, fail, and burn a retry
            # attempt. Non-blocking: when the primary still holds the
            # lock, its own failure path drops the connection.
            if self._lock.acquire(blocking=False):
                try:
                    self._drop_connection()
                finally:
                    self._lock.release()
        self._observe_read_latency(time.perf_counter() - t0)
        return val

    def _drop_connection(self) -> None:
        """Tear down the persistent socket (idempotent, never raises);
        the next request re-dials lazily. Callers must hold ``_lock``
        or be the only thread touching the client."""
        s, self._sock = self._sock, None
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def _switch_address(self, address: str) -> None:
        """Re-point this client at a different daemon (HA failover:
        a NotLeader refusal named the real leader, or the candidate
        rotation picked the next succession peer). The persistent
        connection drops; the next attempt re-dials the new address.
        The placement cache is KEPT — epochs validate it, and the
        promotion's rebind bumped exactly the epochs that moved, so a
        genuinely stale map costs one typed PlacementStale, not a
        mandatory refetch on every failover."""
        host, _, port = address.rpartition(":")
        with self._lock:
            if (host or "127.0.0.1") == self.host \
                    and int(port) == self.port:
                return
            self.host = host or "127.0.0.1"
            self.port = int(port)
            self._drop_connection()
        self.failovers += 1

    def _rotate_failover(self) -> None:
        """Advance to the next failover candidate (skipping the
        current address). No-op without a candidate list."""
        if not self._failover:
            return
        n = len(self._failover)
        for _ in range(n):
            cand = self._failover[self._failover_idx % n]
            self._failover_idx += 1
            h, _, p = cand.rpartition(":")
            if (h or "127.0.0.1") != self.host or int(p) != self.port:
                self._switch_address(cand)
                return

    def _force_close(self) -> None:
        """Unstick an in-flight request from ANOTHER thread: shut the
        socket down without taking ``_lock`` (the stuck thread holds
        it), making its blocking recv fail immediately. Used by the
        leader's follower eviction so a hung mirror can never wedge the
        sender thread."""
        s = self._sock
        if s is not None:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def close(self) -> None:
        with self._ship_mu:
            q, t = self._ship_q, self._ship_thread
            self._ship_q = None
            self._ship_thread = None
        if q is not None:
            # give in-flight ships a bounded grace, then stop the
            # shipper (daemon thread — an unreachable server can't
            # wedge close)
            try:
                q.put_nowait(None)
            except _queue.Full:
                pass
            if t is not None:
                t.join(timeout=2.0)
        with self._placement_mu:
            shard_clients = list(self._shard_clients.values())
            self._shard_clients.clear()
        for sc in shard_clients:
            sc.close()
        with self._lock:
            self._drop_connection()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- session ------------------------------------------------------
    def ping(self) -> Dict[str, Any]:
        return self._request(MsgType.PING, {})

    def shutdown_server(self) -> None:
        with self._lock:
            if self._sock is None:
                self._connect()
            try:
                send_frame(self._sock, MsgType.SHUTDOWN, {})
                # lint: disable=lock-blocking-call -- shutdown ack wait on the serialized connection; bounded by the socket timeout, and the daemon dying mid-wait is the success path
                recv_frame(self._sock, allow_pickle=False)
            except (ConnectionError, OSError):
                pass  # the daemon may die before acking — that's success
            finally:
                self._drop_connection()

    # --- DDL (same facade as Client) ----------------------------------
    def create_database(self, db: str) -> None:
        self._request(MsgType.CREATE_DATABASE, {"db": db})

    def create_set(self, db: str, set_name: str, type_name: str = "tensor",
                   persistence: str = "transient", eviction: str = "lru",
                   partition_lambda: Optional[str] = None,
                   placement=None, storage: str = "memory"):
        """``placement`` may be a Placement (serialized via ``to_meta``)
        or its meta dict; the daemon applies it to all ingest into the
        set (distribution declared at createSet, as in the reference's
        PartitionPolicy). ``storage="paged"`` backs the set with the
        daemon's page arena (out-of-core as a set property)."""
        if placement is not None and hasattr(placement, "to_meta"):
            placement = placement.to_meta()
        reply = self._request(MsgType.CREATE_SET, {
            "db": db, "set": set_name, "type_name": type_name,
            "persistence": persistence, "eviction": eviction,
            "partition_lambda": partition_lambda,
            "placement": placement, "storage": storage})
        entry = reply.get("placement") if isinstance(reply, dict) \
            else None
        if isinstance(entry, dict):
            # a SHARDED create returns its placement entry — cache it
            # now so the very first ingest routes instead of paying a
            # stale-map rejection round-trip
            with self._placement_mu:
                wire = self._placement_wire or {"epoch": 0, "sets": {}}
                wire.setdefault("sets", {})[f"{db}:{set_name}"] = entry
                wire["epoch"] = max(int(wire.get("epoch") or 0),
                                    int(entry.get("epoch") or 0))
                self._placement_wire = wire
        return RemoteIdent(db, set_name)

    def remove_set(self, db: str, set_name: str) -> None:
        self._request(MsgType.REMOVE_SET, {"db": db, "set": set_name})

    def clear_set(self, db: str, set_name: str) -> None:
        self._request(MsgType.CLEAR_SET, {"db": db, "set": set_name})

    def set_exists(self, db: str, set_name: str) -> bool:
        return self._request(MsgType.SET_EXISTS,
                             {"db": db, "set": set_name})["exists"]

    def list_sets(self) -> List[Tuple[str, str]]:
        return [tuple(s) for s in
                self._request(MsgType.LIST_SETS, {})["sets"]]

    def register_type(self, type_name: str, entry_point: str,
                      source: Optional[str] = None,
                      ship_module: bool = False) -> None:
        """``source``/``ship_module`` ship the UDF module's code to the
        daemon (the reference's .so replication on registerType) so
        EXECUTE_PLAN can bind types the server never installed. Shipped
        source is code the daemon executes — same trust boundary as the
        pickle codec (serve/protocol.py security note)."""
        if ship_module and source is None:
            from netsdb_tpu.catalog.catalog import read_module_source

            source = read_module_source(entry_point)
        self._request(MsgType.REGISTER_TYPE,
                      {"type_name": type_name, "entry_point": entry_point,
                       "source": source})

    # --- placement-aware routing (sharded worker pools) ---------------
    def _refresh_placement(self) -> None:
        """Re-fetch the daemon's placement map (best-effort: a refresh
        failure leaves the old cache — the next routed attempt then
        rejects typed again and retries). Concurrent callers WAIT for
        the in-flight fetch and use its result (returning immediately
        would hand them the known-stale map for another doomed
        round); same-thread re-entry (the PLACEMENT request's own
        retry path) is a no-op."""
        me = threading.get_ident()
        if self._refreshing_placement == me:
            return
        if not self._placement_fetch_mu.acquire(blocking=False):
            # another thread is fetching: park until ITS result lands
            self._placement_fetch_mu.acquire()
            self._placement_fetch_mu.release()
            return
        self._refreshing_placement = me
        try:
            wire = self._request(MsgType.PLACEMENT, {})
            with self._placement_mu:
                self._placement_wire = wire
            obs.REGISTRY.counter(
                "serve.client.placement_refreshes").inc()
        except Exception as e:  # noqa: BLE001 — best-effort by contract
            del e
        finally:
            self._refreshing_placement = None
            self._placement_fetch_mu.release()

    def placement_map(self) -> Optional[Dict[str, Any]]:
        """The cached placement map (tests/tooling probe)."""
        with self._placement_mu:
            return self._placement_wire

    def _placement_entry(self, db: str, set_name: str,
                         refresh: bool = False) -> Optional[Dict]:
        """One set's shard entry from the CACHED map — no wire traffic
        unless ``refresh`` (the default path stays frame-identical for
        clients of un-sharded daemons, whose cache is None)."""
        from netsdb_tpu.serve.placement import PlacementMap

        if refresh:
            self._refresh_placement()
        with self._placement_mu:
            wire = self._placement_wire
        if not wire:
            return None
        return PlacementMap.entry_from_wire(wire, db, set_name)

    def _shard_client(self, addr: str) -> "RemoteClient":
        """Cached direct connection to one shard daemon. Single
        attempt per request — the ROUTED retry loop owns retries (it
        must refresh the map between attempts, which a nested
        exponential retry would just delay)."""
        with self._placement_mu:
            sc = self._shard_clients.get(addr)
        if sc is not None:
            return sc
        sc = RemoteClient(addr, token=self.token, timeout=self._timeout,
                          retry=RetryPolicy(max_attempts=1),
                          connect_timeout=self._connect_timeout,
                          ingest_window=self.ingest_window,
                          ingest_chunk_bytes=self.ingest_chunk_bytes,
                          client_id=self.client_id, lane=self.lane,
                          ship_traces=False)
        with self._placement_mu:
            other = self._shard_clients.setdefault(addr, sc)
        if other is not sc:
            sc.close()
        return other

    def _drop_shard_client(self, addr: str) -> None:
        with self._placement_mu:
            sc = self._shard_clients.pop(addr, None)
        if sc is not None:
            sc.close()

    def _send_partition(self, addr: str, db: str, set_name: str,
                        part, as_table: bool, date_cols, epoch: int,
                        slot: int, token: str,
                        chunk_bytes: int) -> Any:
        """One slot's partition to its owning daemon (or the leader,
        for a handoff slot): big payloads stream under the windowed-ack
        pipeline with the placement epoch in the BEGIN meta, small ones
        ride one frame. ``token`` is the slot's STABLE idempotency
        token — every retry of this logical ingest re-sends it, so a
        partition whose first apply succeeded (reply lost) deduplicates
        instead of double-appending."""
        from netsdb_tpu.relational.table import ColumnTable

        sc = self._shard_client(addr)
        if isinstance(part, ColumnTable):
            nbytes = sum(np.asarray(v).nbytes
                         for v in part.cols.values())
            if nbytes >= chunk_bytes:
                return sc._bulk_request(
                    MsgType.SEND_DATA,
                    {"db": db, "set": set_name, "mode": "table",
                     "date_cols": list(date_cols), "append": True,
                     "dicts": {k: list(v)
                               for k, v in part.dicts.items()},
                     "nrows": part.num_rows,
                     "pepoch": int(epoch), "slot": int(slot)},
                    sc._table_chunks(part, chunk_bytes), token=token)
            payload: Dict[str, Any] = {
                "db": db, "set": set_name, "items": part,
                "as_table": True, "date_cols": list(date_cols),
                "append": True}
        elif as_table:
            if len(part) >= self.PIPELINE_MIN_ITEMS:
                return sc._bulk_request(
                    MsgType.SEND_DATA,
                    {"db": db, "set": set_name, "mode": "items",
                     "as_table": True, "date_cols": list(date_cols),
                     "append": True,
                     "pepoch": int(epoch), "slot": int(slot)},
                    sc._item_chunks(list(part), chunk_bytes),
                    token=token)
            payload = {"db": db, "set": set_name, "items": list(part),
                       "as_table": True, "date_cols": list(date_cols),
                       "append": True}
        else:
            if len(part) >= self.PIPELINE_MIN_ITEMS:
                return sc._bulk_request(
                    MsgType.SEND_DATA,
                    {"db": db, "set": set_name, "mode": "items",
                     "pepoch": int(epoch), "slot": int(slot)},
                    sc._item_chunks(list(part), chunk_bytes),
                    token=token)
            payload = {"db": db, "set": set_name, "items": list(part)}
        payload[PLACEMENT_EPOCH_KEY] = int(epoch)
        payload[SHARD_SLOT_KEY] = int(slot)
        payload[IDEMPOTENCY_KEY] = token
        return sc._request(MsgType.SEND_DATA, payload,
                           codec=CODEC_PICKLE)

    def _routed_ingest(self, db: str, set_name: str,
                       parts: Dict[int, Any], as_table: bool,
                       date_cols, chunk_bytes: int) -> Dict[int, Any]:
        """One logical ingest fanned out to the owning shards in
        parallel — aggregate bandwidth scales with pool size. Failed
        slots retry under the client's RetryPolicy with the placement
        map REFRESHED between rounds (an evicted slot's partition then
        re-routes to the leader's handoff buffer under the new epoch);
        per-slot idempotency tokens make every retry at-most-once."""
        tokens = {slot: uuid.uuid4().hex for slot in parts}
        remaining = dict(parts)
        replies: Dict[int, Any] = {}
        policy = self._retry
        attempt = 1
        obs.REGISTRY.counter("serve.client.routed_ingests").inc()
        while True:
            entry = self._placement_entry(db, set_name,
                                          refresh=attempt > 1)
            if entry is None:
                raise PlacementStaleError(
                    "PlacementStale",
                    f"{db}:{set_name} vanished from the placement map")
            errors: Dict[int, BaseException] = {}
            lock = threading.Lock()

            def send_slot(slot, part, entry=entry, errors=errors,
                          lock=lock):
                sl = entry["slots"][slot]
                addr = (f"{self.host}:{self.port}"
                        if sl["state"] != "live" else sl["addr"])
                try:
                    reply = self._send_partition(
                        addr, db, set_name, part, as_table, date_cols,
                        entry["epoch"], slot, tokens[slot],
                        chunk_bytes)
                    with lock:
                        replies[slot] = reply
                except Exception as e:  # noqa: BLE001 — EVERY failure
                    # must land in `errors`: a slot in neither dict
                    # would be dropped from `remaining` and its
                    # partition silently lost while the ingest
                    # reports success
                    self._drop_shard_client(addr)
                    with lock:
                        errors[slot] = e
            threads = []
            for slot, part in remaining.items():
                t = threading.Thread(target=send_slot,
                                     args=(slot, part), daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            remaining = {slot: part for slot, part in remaining.items()
                         if slot in errors}
            if not remaining:
                return replies
            # a deterministic (non-retryable) slot failure wins
            # immediately — retrying the whole round against it would
            # burn the backoff schedule on a hopeless slot and could
            # surface a different slot's transient error instead
            fatal = next((e for e in errors.values()
                          if isinstance(e, RemoteError)
                          and not e.retryable), None)
            if fatal is not None:
                raise fatal
            if attempt >= policy.max_attempts:
                raise next(iter(errors.values()))
            if not all(isinstance(e, PlacementStaleError)
                       for e in errors.values()):
                # transient transport faults back off; pure stale-map
                # rejections are deterministic — the refresh at the
                # top of the next round resolves them instantly
                time.sleep(policy.backoff_s(attempt, self._rng))
            attempt += 1
            self.total_retries += 1
            obs.REGISTRY.counter("serve.client.retries").inc()

    # --- data path ----------------------------------------------------

    #: below this many items, ``send_data`` keeps the single-frame path
    #: (a BEGIN/COMMIT conversation is pure overhead for tiny batches)
    PIPELINE_MIN_ITEMS = 64

    def _item_chunks(self, items: list, chunk_bytes: int):
        """Adaptive item batching — ``scan_stream``'s frame sizing
        applied to the SEND direction: the first chunk holds one item
        (never pack an unmeasured batch), then the batch size tracks
        observed bytes-per-item with growth capped at 4×/chunk. Each
        blob rides as a uint8 view so the pickled bytes go out-of-band
        (no msgpack body copy)."""
        import pickle

        def chunks():
            i = 0
            target = 1
            while i < len(items):
                batch = items[i:i + target]
                blob = pickle.dumps(batch, protocol=pickle.HIGHEST_PROTOCOL)
                yield {"n": len(batch), "blob": np.frombuffer(blob, np.uint8)}
                per_item = max(len(blob) // len(batch), 1)
                target = max(1, min(chunk_bytes // per_item, 4 * target))
                i += len(batch)

        return chunks

    def send_data(self, db: str, set_name: str, items: Sequence[Any],
                  pipeline: Optional[bool] = None,
                  chunk_bytes: Optional[int] = None) -> None:
        """Object ingest. Large batches stream as bounded chunks under
        the depth-W windowed-ack pipeline (``pipeline=None`` decides by
        item count; force ``True``/``False`` to pin a path).

        A set the cached placement map shows as PARTITIONED routes
        instead: items split across the owning shards (hash or range,
        per the set's placement) and every partition ships directly to
        its shard in parallel — aggregate ingest bandwidth scales with
        the pool. A stale map rejects typed and the retry re-routes."""
        from netsdb_tpu.serve import placement as _pl

        items = list(items)
        entry = self._placement_entry(db, set_name)
        if entry is not None:
            cb = int(chunk_bytes or self.ingest_chunk_bytes)
            parts = dict(_pl.split_items(items, entry))
            self._routed_ingest(db, set_name, parts, as_table=False,
                                date_cols=(), chunk_bytes=cb)
            return
        use = (pipeline if pipeline is not None
               else len(items) >= self.PIPELINE_MIN_ITEMS)
        if not use:
            try:
                self._request(MsgType.SEND_DATA,
                              {"db": db, "set": set_name,
                               "items": items},
                              codec=CODEC_PICKLE)
            except PlacementStaleError:
                # the set sharded after this client's map snapshot:
                # refresh and route (the one-hop upgrade path)
                if self._placement_entry(db, set_name,
                                         refresh=True) is None:
                    raise
                self.send_data(db, set_name, items, pipeline=pipeline,
                               chunk_bytes=chunk_bytes)
            return
        cb = int(chunk_bytes or self.ingest_chunk_bytes)
        try:
            self._bulk_request(
                MsgType.SEND_DATA,
                {"db": db, "set": set_name, "mode": "items"},
                self._item_chunks(items, cb))
        except PlacementStaleError:
            if self._placement_entry(db, set_name, refresh=True) is None:
                raise
            self.send_data(db, set_name, items, pipeline=pipeline,
                           chunk_bytes=chunk_bytes)

    def _table_chunks(self, table, chunk_bytes: int):
        """Row-range slices of a ColumnTable's columns: numpy views
        (zero copy) that ride as out-of-band segments — the zero-copy
        bulk-table path. The dictionaries travel once in the BEGIN
        meta; every chunk shares them."""
        cols = {k: np.ascontiguousarray(np.asarray(v))
                for k, v in table.cols.items()}
        nrows = table.num_rows
        row_bytes = max(1, sum(c.dtype.itemsize for c in cols.values()))
        per_chunk = max(1, chunk_bytes // row_bytes)

        def chunks():
            for start in range(0, max(nrows, 1), per_chunk):
                stop = min(nrows, start + per_chunk)
                yield {"rows": [start, stop],
                       "cols": {k: v[start:stop] for k, v in cols.items()}}

        return chunks

    def send_table(self, db: str, set_name: str, rows_or_table,
                   date_cols: Sequence[str] = (),
                   append: bool = False,
                   pipeline: Optional[bool] = None,
                   chunk_bytes: Optional[int] = None) -> "RemoteTableInfo":
        """Ship rows (or a pre-built ColumnTable) for daemon-side
        columnar ingest — dictionary encoding + the set's placement
        happen server-side, where the devices are. Returns a
        :class:`RemoteTableInfo` quacking like the ingested table's
        summary (``num_rows``/``columns``), mirroring the in-process
        facade without pulling the whole table back.

        Bulk payloads stream: a ColumnTable goes out as row-range
        column slices riding out-of-band segments (zero host-side
        copies of the column bytes); a rows list goes out as adaptive
        pickled batches. Both run ``ingest_window`` chunks deep under
        the windowed-ack pipeline. ``pipeline=None`` decides by size;
        pin ``True``/``False`` to force a path.

        A PARTITIONED set (cached placement map) routes instead: the
        rows split across the owning shards and every partition
        streams directly to its shard in parallel. ``append=False``
        first clears the set pool-wide (the leader fans the clear
        out), then appends each shard's partition."""
        from netsdb_tpu.relational.table import ColumnTable

        cb = int(chunk_bytes or self.ingest_chunk_bytes)
        entry = self._placement_entry(db, set_name)
        if entry is not None:
            return self._send_table_routed(db, set_name, rows_or_table,
                                           date_cols, append, cb)
        try:
            return self._send_table_plain(db, set_name, rows_or_table,
                                          date_cols, append, pipeline,
                                          cb)
        except PlacementStaleError:
            # the set sharded after this client's map snapshot
            if self._placement_entry(db, set_name, refresh=True) is None:
                raise
            return self.send_table(db, set_name, rows_or_table,
                                   date_cols=date_cols, append=append,
                                   pipeline=pipeline,
                                   chunk_bytes=chunk_bytes)

    def _send_table_routed(self, db: str, set_name: str, rows_or_table,
                           date_cols, append: bool,
                           chunk_bytes: int) -> "RemoteTableInfo":
        from netsdb_tpu.relational.table import ColumnTable
        from netsdb_tpu.serve import placement as _pl

        entry = self._placement_entry(db, set_name)
        if not append:
            # replace = pool-wide clear (leader fans out), then append
            # partitions; the slot idempotency tokens keep the append
            # half at-most-once across retries
            self.clear_set(db, set_name)
        if isinstance(rows_or_table, ColumnTable):
            table = rows_or_table
            parts = dict(_pl.split_table(table, entry))
            replies = self._routed_ingest(db, set_name, parts,
                                          as_table=True,
                                          date_cols=date_cols,
                                          chunk_bytes=chunk_bytes)
            cols = sorted(table.cols)
            total = int(table.compact().num_rows
                        if table.valid is not None else table.num_rows)
        else:
            items = list(rows_or_table)
            parts = dict(_pl.split_items(items, entry))
            replies = self._routed_ingest(db, set_name, parts,
                                          as_table=True,
                                          date_cols=date_cols,
                                          chunk_bytes=chunk_bytes)
            cols = sorted({c for r in replies.values()
                           if isinstance(r, dict)
                           for c in (r.get("columns") or ())})
            total = len(items)
        return RemoteTableInfo(total, cols)

    def _send_table_plain(self, db, set_name, rows_or_table, date_cols,
                          append, pipeline, cb) -> "RemoteTableInfo":
        from netsdb_tpu.relational.table import ColumnTable

        if isinstance(rows_or_table, ColumnTable):
            table = rows_or_table
            if table.valid is not None:
                table = table.compact()
            nbytes = sum(np.asarray(v).nbytes for v in table.cols.values())
            use = pipeline if pipeline is not None else nbytes >= cb
            if use:
                reply = self._bulk_request(
                    MsgType.SEND_DATA,
                    {"db": db, "set": set_name, "mode": "table",
                     "date_cols": list(date_cols), "append": append,
                     "dicts": {k: list(v) for k, v in table.dicts.items()},
                     "nrows": table.num_rows},
                    self._table_chunks(table, cb))
                return RemoteTableInfo(reply["count"],
                                       list(reply["columns"]))
            items = table
        else:
            items = list(rows_or_table)
            use = (pipeline if pipeline is not None
                   else len(items) >= self.PIPELINE_MIN_ITEMS)
            if use:
                reply = self._bulk_request(
                    MsgType.SEND_DATA,
                    {"db": db, "set": set_name, "mode": "items",
                     "as_table": True, "date_cols": list(date_cols),
                     "append": append},
                    self._item_chunks(items, cb))
                return RemoteTableInfo(reply["count"],
                                       list(reply["columns"]))
        reply = self._request(
            MsgType.SEND_DATA,
            {"db": db, "set": set_name, "items": items,
             "as_table": True, "date_cols": list(date_cols),
             "append": append},
            codec=CODEC_PICKLE)
        return RemoteTableInfo(reply["count"], list(reply["columns"]))

    def analyze_set(self, db: str, set_name: str) -> Dict[str, Any]:
        """Planner statistics computed DAEMON-side; only the summaries
        cross the wire (ref StorageCollectStats,
        ``PangeaStorageServer.h:48``). This is what lets
        ``relational.dag.suite_sink_for`` build all ten suite sinks
        over a daemon without pulling a single table."""
        from netsdb_tpu.relational.stats import ColumnStats

        reply = self._request(MsgType.ANALYZE_SET,
                              {"db": db, "set": set_name})
        return {"num_rows": reply["num_rows"],
                "dicts": {k: list(v) for k, v in reply["dicts"].items()},
                "stats": {k: ColumnStats(*v)
                          for k, v in reply["stats"].items()}}

    def get_table(self, db: str, set_name: str):
        """Fetch a table set as a host-side ColumnTable (pickled via its
        numpy ``__getstate__``)."""
        items = list(self.get_set_iterator(db, set_name))
        from netsdb_tpu.relational.table import ColumnTable

        tables = [i for i in items if isinstance(i, ColumnTable)]
        if len(tables) != 1:
            raise ValueError(
                f"set {db}:{set_name} holds {len(tables)} tables; expected 1")
        return tables[0]

    def send_matrix(self, db: str, set_name: str, dense, block_shape=None,
                    dtype=None) -> RemoteTensor:
        dense = np.asarray(dense, dtype=dtype)
        entry = self._placement_entry(db, set_name)
        if entry is not None:
            return self._send_matrix_routed(db, set_name, dense,
                                            block_shape, entry)
        reply = self._request(MsgType.SEND_MATRIX, {
            "db": db, "set": set_name,
            "tensor": tensor_to_wire(dense, block_shape)})
        return RemoteTensor(dense, reply.get("block_shape"))

    def _send_matrix_routed(self, db: str, set_name: str, dense,
                            block_shape, entry) -> RemoteTensor:
        """Batch-partitioned tensor ingest — the model-serving scoring
        frame: rows split by the placement's contiguous range slices,
        slice *i* to slot *i*, so slot order IS batch order and the
        tensor-chain scatter-gather concat reassembles the exact input
        order byte-for-byte. Slices go out in parallel (aggregate
        ingest bandwidth scales with the pool, like routed tables); a
        degraded slot's typed refusal surfaces to the caller — scoring
        batches are transient, so there is no handoff buffering to
        fall back on. Each slice is blocked by its own rows
        (``core/blocked.py::batch_block``)."""
        from netsdb_tpu.core.blocked import batch_block
        from netsdb_tpu.serve import placement as _pl

        if entry.get("mode") != "range":
            raise ValueError(
                f"tensor set {db}:{set_name} is partitioned "
                f"{entry.get('mode')!r}; matrices shard by contiguous "
                f"row ranges only — create with placement=\"range\"")
        slots = entry["slots"]
        slices = _pl.range_slices(int(dense.shape[0]), len(slots))
        errors: Dict[int, BaseException] = {}
        lock = threading.Lock()
        # a ContextVar does not cross into the slot threads: hand them
        # the caller's trace, so each slice's frame carries its query
        # id and its send/wait spans land under the caller's open span
        traced = obs.capture()

        def send_slot(i: int, lo: int, hi: int) -> None:
            sl = slots[i]
            addr = (f"{self.host}:{self.port}"
                    if sl["state"] != "live" else sl["addr"])
            try:
                sc = self._shard_client(addr)
                with obs.adopt(traced):
                    sc._request(MsgType.SEND_MATRIX, {
                        "db": db, "set": set_name,
                        "tensor": tensor_to_wire(
                            np.ascontiguousarray(dense[lo:hi]),
                            batch_block(hi - lo, block_shape)
                            if block_shape else block_shape),
                        PLACEMENT_EPOCH_KEY: int(entry["epoch"]),
                        SHARD_SLOT_KEY: i})
            except Exception as e:  # noqa: BLE001 — surfaced below
                self._drop_shard_client(addr)
                with lock:
                    errors[i] = e

        threads = []
        for i, (lo, hi) in enumerate(slices):
            t = threading.Thread(target=send_slot, args=(i, lo, hi),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        if errors:
            raise errors[min(errors)]
        obs.REGISTRY.counter("serve.client.routed_ingests").inc()
        return RemoteTensor(dense,
                            list(block_shape) if block_shape else None)

    def get_tensor(self, db: str, set_name: str) -> RemoteTensor:
        reply = self._request(MsgType.GET_TENSOR, {"db": db, "set": set_name})
        return RemoteTensor(reply["data"], reply.get("block_shape"))

    def paged_matmul(self, db: str, set_name: str, rhs) -> np.ndarray:
        """``stored @ rhs`` computed daemon-side with the stored matrix
        streamed from the arena (paged TENSOR sets never materialize;
        their GET_TENSOR raises by design)."""
        reply = self._request(MsgType.PAGED_MATMUL,
                              {"db": db, "set": set_name,
                               "rhs": np.asarray(rhs)})
        return np.asarray(reply["data"])

    def get_tensor_chunked(self, db: str, set_name: str,
                           chunk_bytes: int = 8 << 20) -> RemoteTensor:
        """Pull a tensor as a chunked stream: client holds the result
        array plus ONE chunk (vs. array + full frame for GET_TENSOR) —
        the page-streamed model transfer path for big weight sets."""
        meta = None
        buf = None
        off = 0
        for frame in self._stream(MsgType.GET_TENSOR_CHUNKED,
                                  {"db": db, "set": set_name,
                                   "chunk_bytes": int(chunk_bytes)}):
            if meta is None:
                meta = frame["meta"]
                buf = bytearray(meta["nbytes"])
            else:
                b = frame["b"]  # uint8 ndarray (out-of-band) or bytes
                n = b.nbytes if isinstance(b, np.ndarray) else len(b)
                buf[off:off + n] = b if not isinstance(b, np.ndarray) \
                    else memoryview(b)
                off += n
        if meta is None:
            raise ProtocolError("empty chunked-tensor stream")
        # frombuffer over the assembled bytearray: writable, no copy
        dense = np.frombuffer(buf, dtype=np.dtype(meta["dtype"])
                              ).reshape(meta["shape"])
        return RemoteTensor(dense, meta.get("block_shape"))

    def get_set_iterator(self, db: str, set_name: str) -> Iterator[Any]:
        reply = self._request(MsgType.SCAN_SET, {"db": db, "set": set_name})
        return iter(reply["items"])

    def scan_stream(self, db: str, set_name: str,
                    max_frame_bytes: int = 4 << 20) -> Iterator[Any]:
        """Stream a set's items with bounded buffering on both ends:
        the server packs ≤ ``max_frame_bytes`` of pickled items per
        frame; this generator holds one frame at a time. The streamed
        ``getSetIterator`` (ref FrontendQueryTestServer.cc:785-890).

        The connection is held for the duration of the iteration (one
        in-flight request per connection, as in the reference's
        PDBCommunicator); abandoning the iterator early closes the
        socket so the next request reconnects cleanly."""
        import pickle

        for frame in self._stream(MsgType.SCAN_SET_STREAM,
                                  {"db": db, "set": set_name,
                                   "max_frame_bytes": int(max_frame_bytes)}):
            yield from pickle.loads(frame["batch"])

    def get_table_streamed(self, db: str, set_name: str,
                           max_frame_bytes: int = 4 << 20):
        """Assemble a table set from the STREAMED scan: for paged sets
        the daemon ships one host-side chunk table per frame straight
        off its arena stream (it never materializes the relation,
        device- or wire-side); this client holds the growing columns
        plus ONE chunk. The page-streamed remote read for exactly the
        sets ``get_table``'s single-frame reply is too big for."""
        from netsdb_tpu.relational.table import ColumnTable

        parts: dict = {}
        dicts: dict = {}
        got = False
        # closing: the TypeError below abandons the stream mid-scan —
        # the generator (and its socket) must close NOW, not at GC
        with contextlib.closing(
                self.scan_stream(db, set_name, max_frame_bytes)) as items:
            for item in items:
                if not isinstance(item, ColumnTable):
                    raise TypeError(
                        f"set {db}:{set_name} holds "
                        f"{type(item).__name__} items, not tables")
                got = True
                dicts.update(item.dicts)
                cols = item.compact().cols if item.valid is not None \
                    else item.cols
                for k, v in cols.items():
                    parts.setdefault(k, []).append(np.asarray(v))
        if not got:
            raise ValueError(f"set {db}:{set_name} is empty")
        return ColumnTable({k: np.concatenate(v)
                            for k, v in parts.items()}, dicts, None)

    @staticmethod
    def _stream_frames(sock: socket.socket, msg_type: MsgType,
                       payload: Any) -> Iterator[Any]:
        """Frame loop of one streaming request over ``sock``: yield each
        STREAM_ITEM payload until STREAM_END; ERR raises (the stream
        ends, the connection stays frame-synchronized)."""
        send_frame(sock, msg_type, payload)
        while True:
            typ, reply = recv_frame(sock, allow_pickle=True)
            if typ == MsgType.STREAM_END:
                return
            if typ == MsgType.ERR:
                raise classify_remote(reply)
            yield reply

    def _stream_hedged(self, msg_type: MsgType,
                       payload: Any) -> Iterator[Any]:
        """Streaming read with FIRST-ITEM hedging — ``_request_hedged``
        extended to streams: the primary opens the stream on a
        dedicated connection; if its first frame hasn't landed within
        :meth:`hedge_delay_s`, the SAME request goes to the next
        replica, and whichever connection delivers a first frame first
        WINS — the loser's socket is closed immediately (cancelled),
        so at most one duplicated first frame ever crosses the wire,
        not a duplicated full scan. After the first item the winner's
        stream is consumed inline (a half-read stream cannot switch
        connections mid-flight), so hedging bounds time-to-first-item
        — the metric that dominates interactive scans — while the
        stream body rides ordinary TCP backpressure. Reads only, like
        every hedge (mutations never stream)."""
        first_q: "_queue.Queue" = _queue.Queue()
        socks: Dict[str, socket.socket] = {}
        cancelled: set = set()
        state_lock = threading.Lock()

        def opener(tag: str, address: Optional[str]) -> None:
            s = None
            try:
                s = self._dial(address=address)
                with state_lock:
                    if tag in cancelled:
                        s.close()
                        return
                    socks[tag] = s
                send_frame(s, msg_type, payload, chaos=self._chaos)
                typ, reply = self._recv_reply(s)
                first_q.put((tag, typ, reply, None))
            except BaseException as e:  # noqa: BLE001 — surfaced below
                # a failed leg closes its own socket (the cancel sweep
                # only covers the LOSING healthy leg)
                with state_lock:
                    socks.pop(tag, None)
                if s is not None:
                    s.close()
                first_q.put((tag, None, None, e))

        threading.Thread(target=opener, daemon=True,
                         args=("primary", None)).start()
        t0 = time.perf_counter()
        try:
            winner = first_q.get(timeout=self.hedge_delay_s())
            legs = 1 if winner[0] == "primary" else 2
        except _queue.Empty:
            self.hedges_issued += 1
            obs.REGISTRY.counter("serve.client.hedges_issued").inc()
            addr = self._replicas[self._hedge_rr % len(self._replicas)]
            self._hedge_rr += 1
            threading.Thread(target=opener, daemon=True,
                             args=("hedge", addr)).start()
            legs = 2
            winner = first_q.get()
            if winner[3] is not None:
                # first responder failed — wait for the straggler; on a
                # double failure prefer the primary's error
                other = first_q.get()
                legs = 0  # both legs reported; nothing left to cancel
                if other[3] is None:
                    winner = other
                elif winner[0] == "hedge":
                    winner = other
        tag, typ, frame, err = winner
        if legs:
            # cancel the loser: close its socket (unblocks a parked
            # recv) or poison its tag so a not-yet-dialed leg closes
            # itself on arrival
            with state_lock:
                for other_tag in ("primary", "hedge"):
                    if other_tag == tag:
                        continue
                    cancelled.add(other_tag)
                    s = socks.pop(other_tag, None)
                    if s is not None:
                        try:
                            s.shutdown(socket.SHUT_RDWR)
                        except OSError:
                            pass
                        s.close()
        if err is not None:
            raise err
        if tag == "hedge":
            self.hedges_won += 1
            obs.REGISTRY.counter("serve.client.hedges_won").inc()
        self._observe_read_latency(time.perf_counter() - t0)
        with state_lock:
            sock = socks.pop(tag)
        try:
            while True:
                if typ == MsgType.STREAM_END:
                    return
                if typ == MsgType.ERR:
                    raise classify_remote(frame)
                yield frame
                typ, frame = self._recv_reply(sock)
        finally:
            # dedicated connection: never resynchronized, always closed
            sock.close()

    def _stream(self, msg_type: MsgType, payload: Any) -> Iterator[Any]:
        """Issue a streaming request; yield each STREAM_ITEM payload
        until STREAM_END. ERR aborts with RemoteError. If the consumer
        abandons the generator mid-stream, the socket is dropped (a
        half-read stream cannot be resynchronized). A stream opened
        from a thread ALREADY mid-stream (nested iteration) runs over
        its own dedicated connection — like nested plain requests
        (`_oneshot_request`), it must neither wait on the held lock nor
        interleave frames on the streaming socket. With ``replicas``
        configured, streams hedge their FIRST item over dedicated
        connections (:meth:`_stream_hedged`) — the persistent
        connection and its lock stay untouched, so nested requests
        from the consuming thread need no special-casing.

        Streams bypass :meth:`_request`, so the client identity is
        attached HERE — the heaviest read path must attribute like any
        other frame (scan batches book under this tenant's
        ``requests``/scan work, not ``anon``)."""
        if self.client_id is not None and isinstance(payload, dict) \
                and CLIENT_ID_KEY not in payload:
            payload = dict(payload)
            payload[CLIENT_ID_KEY] = str(self.client_id)
        if self._replicas and self._stream_owner != threading.get_ident():
            yield from self._stream_hedged(msg_type, payload)
            return
        if self._stream_owner == threading.get_ident():
            s = self._dial()
            try:
                yield from self._stream_frames(s, msg_type, payload)
            finally:
                s.close()
            return
        self._lock.acquire()
        self._stream_owner = threading.get_ident()
        done = False
        try:
            if self._sock is None:
                self._connect()
            # lint: disable=lock-blocking-call -- a streaming reply owns the connection for its lifetime by design; nested requests from the stream-owner thread take a one-shot side connection instead of this lock
            yield from self._stream_frames(self._sock, msg_type, payload)
            done = True
        except RemoteError:
            done = True  # ERR terminates the stream; conn is sync'd
            raise
        except (ConnectionError, OSError):
            done = False
            raise
        finally:
            self._stream_owner = None
            if not done:
                self._drop_connection()
            self._lock.release()

    def resync_follower(self, snapshot_blob, step: int,
                        chunk_bytes: int = 8 << 20) -> Dict[str, Any]:
        """Stream a leader store snapshot (``checkpoint.dumps_store``
        bytes) to this daemon in bounded frames under the windowed-ack
        pipeline — follower resync with NO shared-filesystem
        assumption (the snapshot never touches the follower's disk).
        Chunks are memoryview slices of the blob riding out-of-band
        (zero copies leader-side)."""
        mv = memoryview(snapshot_blob)

        def chunks():
            for off in range(0, max(mv.nbytes, 1), chunk_bytes):
                yield {"blob": np.frombuffer(mv[off:off + chunk_bytes],
                                             np.uint8)}

        return self._bulk_request(
            MsgType.RESYNC_FOLLOWER,
            {"step": int(step), "nbytes": mv.nbytes}, chunks)

    def dedup_resident(self, sets: Sequence[Tuple[str, str]],
                       bands: int = 16, seed: int = 0) -> Dict[str, Any]:
        """Daemon-side block-level model dedup: shared blocks across the
        given weight sets materialize once in HBM (see
        ``Client.dedup_resident``). Returns the pooling report."""
        return self._request(MsgType.DEDUP_RESIDENT,
                             {"sets": [list(s) for s in sets],
                              "bands": bands, "seed": seed})

    def add_shared_mapping(self, private_db: str, private_set: str,
                           shared_db: str, shared_set: str,
                           mapping: Optional[Dict] = None) -> None:
        self._request(MsgType.ADD_SHARED_MAPPING, {
            "private_db": private_db, "private_set": private_set,
            "shared_db": shared_db, "shared_set": shared_set,
            "mapping": mapping})

    def flush_data(self) -> None:
        self._request(MsgType.FLUSH_DATA, {})

    def load_set(self, db: str, set_name: str) -> None:
        self._request(MsgType.LOAD_SET, {"db": db, "set": set_name})

    # --- stateful serving (serve/sessions.py) -------------------------
    @property
    def current_address(self) -> str:
        return f"{self.host}:{self.port}"

    def open_session(self, db: str, kind: str = "lstm",
                     ttl_s: Optional[float] = None,
                     heads: Optional[int] = None,
                     session_id: Optional[str] = None) -> "SessionHandle":
        """Open one interactive decode session over model ``db``.
        The session id is CLIENT-minted: the mirrored open replays at
        every follower with the same sid (handler-side minting would
        not reach them — mirror forwards copy the payload before the
        handler runs). Returns a :class:`SessionHandle` whose
        ``generate`` calls route sticky to the owning daemon."""
        sid = str(session_id or uuid.uuid4().hex)
        payload: Dict[str, Any] = {"op": "open", "sid": sid, "db": db,
                                   "kind": kind, SESSION_KEY: sid}
        if ttl_s is not None:
            payload["ttl_s"] = float(ttl_s)
        if heads is not None:
            payload["heads"] = int(heads)
        rep = self._request(MsgType.SESSION_OPEN, payload)
        return SessionHandle(self, sid, db, kind,
                             owner=rep.get("owner"),
                             spec=rep.get("spec"),
                             steps=int(rep.get("steps", 0)))

    # --- query execution ----------------------------------------------
    def execute_computations(self, *sinks, job_name: str = "remote-job",
                             materialize: bool = True,
                             fetch_results: bool = True,
                             explain: bool = False):
        """Ship the Computation DAG (cloudpickle — the analogue of
        shipping serialized Computations + registered UDF code) and run
        it on the daemon. Returns {ident: value} like the library
        client; ``fetch_results=False`` skips pulling result payloads
        (they stay resident server-side, the common serving pattern).

        ``explain=True`` is EXPLAIN ANALYZE: the daemon records every
        plan node's wall/device time, rows, chunk and cache/compile
        counters and round-trips the annotated tree — the return
        becomes ``(results, operators_tree)``. Render it with
        ``obs.operators.render_tree`` (what ``cli obs --explain``
        does)."""
        reply = self._request(
            MsgType.EXECUTE_COMPUTATIONS,
            {"sinks": list(sinks), "job_name": job_name,
             "materialize": materialize, "explain": bool(explain)},
            codec=CODEC_PICKLE)
        results = self._collect_results(reply["results"], fetch_results)
        if explain:
            tree = reply.get("operators")
            if reply.get("shard_operators") and isinstance(tree, dict):
                # scatter queries: the per-shard region forest rides
                # the coordinator tree (render with
                # obs.operators.render_shard_forest)
                tree = dict(tree,
                            shard_operators=reply["shard_operators"])
            return results, tree
        return results

    def execute_plan(self, plan_text: str, registry: Dict[str, Any],
                     job_name: str = "remote-plan", materialize: bool = True,
                     fetch_results: bool = True, explain: bool = False):
        """Pickle-free execution: ship plan text + label→entry-point
        registry; the daemon rebinds labels to registered types
        (``ParsedPlan.to_computations``). The TCAP path.
        ``explain=True`` returns ``(results, operators_tree)`` — see
        :meth:`execute_computations`."""
        reply = self._request(
            MsgType.EXECUTE_PLAN,
            {"plan": plan_text, "registry": registry, "job_name": job_name,
             "materialize": materialize, "explain": bool(explain)})
        results = self._collect_results(reply["results"], fetch_results)
        if explain:
            return results, reply.get("operators")
        return results

    def _collect_results(self, summaries: Dict[str, Any],
                         fetch: bool) -> Dict[RemoteIdent, Any]:
        out: Dict[RemoteIdent, Any] = {}
        for key, summary in summaries.items():
            db, _, set_name = key.partition(":")
            ident = RemoteIdent(db, set_name)
            if not fetch:
                out[ident] = summary
            elif summary.get("kind") == "tensor":
                out[ident] = self.get_tensor(db, set_name)
            else:
                items = list(self.get_set_iterator(db, set_name))
                out[ident] = dict(items) if summary.get("kind") == "map" \
                    else items
        return out

    def list_jobs(self) -> List[Dict[str, Any]]:
        return self._request(MsgType.LIST_JOBS, {})["jobs"]

    # --- stats --------------------------------------------------------
    def collect_stats(self) -> Dict[str, Any]:
        return self._request(MsgType.COLLECT_STATS, {})

    def get_trace(self, last: Optional[int] = None,
                  qid: Optional[str] = None,
                  slow: bool = False) -> Dict[str, Any]:
        """Completed query trace profiles from the daemon's ring
        (newest last). ``qid`` filters to one query; ``last`` bounds
        the count. On a leader, profiles carry ``followers`` sections
        merged by query id (one logical query decomposed across every
        daemon that ran it) and — for queries whose client shipped its
        spans via PUT_TRACE — a ``client`` section with the send/wait
        decomposition. ``slow=True`` reads the persisted slow-query
        ring (``<root>/slowlog/``) instead: the outliers that survived
        ring rotation and daemon restarts."""
        return self._request(MsgType.GET_TRACE,
                             {"last": last, "qid": qid,
                              "slow": bool(slow)})

    def health(self) -> Dict[str, Any]:
        """The daemon's SLO/health readout (obs/slo.py): evaluated
        objectives with multi-window burn rates, recent
        breach/recovery events and the slowlog summary; leaders merge
        follower sections (best-effort — a slow follower reports an
        error entry, never gets evicted by a health read)."""
        return self._request(MsgType.HEALTH, {})

    def get_metrics(self, format: Optional[str] = None,
                    window_s: Optional[float] = None) -> Dict[str, Any]:
        """Continuous telemetry (obs/history.py). Default: the
        registry snapshot + history summary + derived rates over
        ``window_s`` (QPS, staged MB/s, hit-rate trend — what ``cli
        obs --top`` refreshes from). ``format="openmetrics"``: the
        Prometheus text exposition instead (reply ``{"text": ...}``),
        with leader-merged follower samples."""
        payload: Dict[str, Any] = {}
        if format:
            payload["format"] = format
        if window_s is not None:
            payload["window_s"] = float(window_s)
        return self._request(MsgType.GET_METRICS, payload)

    def placement_view(self) -> Dict[str, Any]:
        """The leader's live placement table (serve/rebalance.py):
        per-slot owner/state/bytes/heat for every sharded set, the
        per-member heat/byte totals, the current skew ratio, and the
        rebalancer's status + last-move log — what ``cli obs
        --placement`` renders."""
        return self._request(MsgType.RESHARD, {"op": "view"},
                             codec=CODEC_PICKLE)

    def rebalance_status(self) -> Dict[str, Any]:
        """The rebalancer's own state (enabled/running/last skew
        ratio/streak/epoch + move log), without the per-slot join."""
        return self._request(MsgType.RESHARD, {"op": "status"},
                             codec=CODEC_PICKLE)

    def add_worker(self, addr: str,
                   campaign: bool = True) -> Dict[str, Any]:
        """Register one new pool worker on a live leader (the
        scale-out path the rebalancer treats as a forced trigger).
        ``campaign=False`` registers without moving anything."""
        return self._request(
            MsgType.RESHARD,
            {"op": "add_worker", "addr": str(addr),
             "campaign": bool(campaign)}, codec=CODEC_PICKLE)


class SessionHandle:
    """Client-side handle for one interactive decode session.

    Stickiness: ``generate`` targets the session's OWNER directly —
    the main client when the leader owns it, a cached single-attempt
    shard connection when a pool worker does. Every hop the session
    takes shows up as a typed retryable signal, and the handle owns
    the re-pointing loop (the shard clients are deliberately
    max_attempts=1, so no nested retry fights it):

    * ``SessionMoved`` — the refusal NAMES the new owner: re-point and
      retry immediately.
    * ``NotLeader`` — the leader moved: follow the named leader (or
      the main client's failover rotation) and re-LOOKUP the owner.
    * connection loss / timeout / other retryables — the owner (or
      mid-election leader) died: re-LOOKUP through the main client,
      whose own retry driver rides the succession list, then retry
      here with jittered backoff.

    Each logical step mints ONE idempotency token and resends it
    across every re-route, so an applied-but-unanswered step dedupes
    at whichever daemon applied it instead of double-advancing the
    state, and a step re-applied by a NEW owner after failover
    recomputes bit-identically from the last durable state."""

    def __init__(self, client: RemoteClient, sid: str, db: str,
                 kind: str, owner: Optional[str] = None,
                 spec: Optional[Dict[str, Any]] = None, steps: int = 0):
        self._client = client
        self.sid = sid
        self.db = db
        self.kind = kind
        self.owner = owner or client.current_address
        self.spec = spec or {}
        self.steps = int(steps)
        self.moves = 0  # typed re-points this handle performed
        self._rng = random.Random(sid)
        self._closed = False

    def _target(self) -> RemoteClient:
        if self.owner == self._client.current_address:
            return self._client
        return self._client._shard_client(self.owner)

    def _lookup(self) -> str:
        """Ask the (current) leader who owns the session — riding the
        main client's NotLeader/failover handling, and healing a
        dead-owner record leader-side."""
        rep = self._client._request(
            MsgType.SESSION_OPEN,
            {"op": "lookup", "sid": self.sid, "db": self.db})
        owner = rep.get("owner") or self._client.current_address
        if owner != self.owner:
            self.moves += 1
        self.owner = owner
        return owner

    def generate(self, x=None, deadline_s: float = 30.0, *,
                 tokens=None, new_tokens: Optional[int] = None
                 ) -> np.ndarray:
        """One frame. For a kind that steps on an input row, ``x`` is
        the row and the return is the model's output row. For a
        language model, ``tokens`` are the int32 ids to append (a
        turn's prompt) and ``new_tokens`` how many to generate: the
        return is the ids the daemon chose, and the float32 logits of
        the last of them stay in the daemon (``last_logits``). Retries
        typed-retryable failures (owner moves, failovers, deaths) under
        ``deadline_s`` with ONE idempotency token for the whole frame."""
        if self._closed:
            raise RuntimeError(f"session {self.sid!r} is closed")
        payload = {"db": self.db, "set": self.sid, "sid": self.sid,
                   SESSION_KEY: self.sid,
                   IDEMPOTENCY_KEY: uuid.uuid4().hex}
        if x is not None:
            payload["x"] = np.asarray(x, np.float32)
        else:
            payload["tokens"] = np.asarray(
                () if tokens is None else tokens, np.int32)
            payload["new_tokens"] = int(new_tokens or 0)
        deadline = deadline_after(deadline_s)
        attempt = 0
        while True:
            attempt += 1
            try:
                rep = self._target()._request(
                    MsgType.GENERATE, dict(payload),
                    codec=CODEC_PICKLE)
                new_owner = rep.get("owner")
                if new_owner and new_owner != self.owner:
                    self.moves += 1
                    self.owner = new_owner
                self.steps = int(rep.get("steps", self.steps + 1))
                return np.asarray(rep["y"] if "y" in rep else rep["ids"])
            except SessionMovedError as e:
                self.moves += 1
                self.owner = getattr(e, "owner_addr", None) or \
                    self._safe_lookup(deadline, e)
            except NotLeaderError as e:
                addr = getattr(e, "leader_addr", None)
                if addr:
                    self._client._switch_address(addr)
                else:
                    self._client._rotate_failover()
                self._safe_lookup(deadline, e)
            except SessionUnknownError:
                raise
            except (RetryableRemoteError, ConnectionLostError,
                    RemoteTimeoutError, ConnectionError, OSError,
                    DeadlineExceededError) as e:
                # owner died or is mid-election: bounded backoff, then
                # re-discover through the main client's failover path
                if seconds_left(deadline) <= 0:
                    raise
                time.sleep(min(0.5, 0.05 * attempt
                               * (1.0 + self._rng.random())))
                self._safe_lookup(deadline, e)
            if seconds_left(deadline) <= 0:
                raise DeadlineExceededError(
                    "DeadlineExceeded",
                    f"generate deadline of {deadline_s}s exhausted "
                    f"after {attempt} attempt(s)")

    def last_logits(self) -> np.ndarray:
        """The float32 logits row of the last frame's last step (a
        language model), read from the session's output set."""
        return np.asarray(self._client.get_tensor(
            self.db, session_output_set(self.sid)).to_dense()).reshape(-1)

    def _safe_lookup(self, deadline, cause) -> str:
        """Owner re-discovery that tolerates the election window: a
        failed lookup keeps the current owner and lets the outer loop
        back off and try again (bounded by the step's deadline)."""
        try:
            return self._lookup()
        except (RemoteError, ConnectionError, OSError):
            if seconds_left(deadline) <= 0:
                raise cause
            return self.owner

    def close(self, deadline_s: float = 10.0) -> bool:
        """Close the session everywhere (idempotent; the daemon's TTL
        sweep collects anything a lost close leaves behind)."""
        if self._closed:
            return False
        self._closed = True
        try:
            rep = self._client._request(
                MsgType.SESSION_CLOSE,
                {"sid": self.sid, "db": self.db, "set": self.sid},
                deadline_s=deadline_s)
            return bool(rep.get("closed"))
        except (RemoteError, ConnectionError, OSError):
            return False

    def __enter__(self) -> "SessionHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return (f"<SessionHandle {self.sid[:8]} db={self.db!r} "
                f"owner={self.owner} steps={self.steps}>")
