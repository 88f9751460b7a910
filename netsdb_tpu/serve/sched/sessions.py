"""The session/decode lane shape — continuous batching of GENERATE.

One-shot analytics coalesce by FINGERPRINT (``policy.frame_fingerprint``:
identical queries share one execution). Decode traffic inverts the
shape: concurrent ``GENERATE`` frames are all DIFFERENT (each advances
its own session) yet want to share one step program dispatch —
coalescing by MODEL, not by identity. :class:`DecodeBatcher` is that
lane. A frame may need many iterations (a turn: a prompt consumed in
chunks, then a number of decode steps), so the lane batches
CONTINUOUSLY: a model's leader thread calls ``run_batch(db, live)``
once an iteration over the requests that are live; each result is
either final (the request leaves and its handler thread is answered)
or :data:`CONTINUE` (it stays for the next iteration). Frames that
arrive meanwhile join at the next iteration's boundary, up to the
model's batch limit. The first arrival for an idle model starts the
leader and lingers one small window for peers; the leader ends when
nothing is live and nothing waits. What an iteration does (admit,
one chunk of prefill, one decode step, retire) is the callee's
business (``serve/sessions.py``).

Two structural guarantees the chaos tests lean on:

* **At most one occurrence of a session among the live requests** — a
  retried or pipelined duplicate stays queued until the first has
  left, so a session's state is never advanced by two frames at once.
* **Exceptions fan out** — a failed iteration rejects every live
  request with the original fault; nothing blocks forever on a dead
  leader.

Frames carrying ``protocol.SESSION_KEY`` admit through the reserved
:data:`DECODE_LANE` of the lane scheduler (unless the client named an
explicit lane), so decode loops and one-shot analytics get weighted
fairness instead of FIFO interleaving.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from netsdb_tpu.utils.locks import TrackedLock

#: the scheduler lane session-scoped frames admit through when the
#: client named none — reserved for interactive decode so a busy
#: analytics lane can't starve sessions (and vice versa).
DECODE_LANE = "decode"


class _Waiter:
    __slots__ = ("sid", "req", "done", "result", "error")

    def __init__(self, sid: str, req: Any):
        self.sid = sid
        self.req = req
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


#: a ``run_batch`` result that keeps its request live for the next
#: iteration (a turn that has prompt left to consume or steps to run)
CONTINUE = object()


class DecodeBatcher:
    """Per-model continuous batching of concurrent decode requests.

    ``run_batch(db, reqs) -> results`` runs ONE iteration over the
    live requests (index-aligned results; :data:`CONTINUE` keeps a
    request live). ``submit`` blocks the calling handler thread until
    its request's final result (or fault) is ready.
    """

    def __init__(self, run_batch: Callable[[str, List[Any]], List[Any]],
                 max_batch: int = 8, window_s: float = 0.003):
        self._run = run_batch
        self.max_batch = max(1, int(max_batch))
        self.window_s = float(window_s)
        self._mu = TrackedLock("DecodeBatcher._mu")
        self._cv = threading.Condition(self._mu)
        self._pending: Dict[str, List[_Waiter]] = {}
        self._leader: Dict[str, bool] = {}
        self._max_of: Dict[str, int] = {}
        self._stats = {"batches": 0, "coalesced": 0, "max_occupancy": 0}

    def set_max_batch(self, db: str, n: int) -> None:
        """``db``'s own limit of live requests (a model whose slab has
        more slots than the daemon's default batch)."""
        with self._mu:
            self._max_of[db] = max(1, int(n))

    def submit(self, db: str, sid: str, req: Any) -> Any:
        """Enqueue one session's request; returns its result. The
        first waiter of an idle model starts the model's leader
        thread; everyone parks on their own event."""
        w = _Waiter(sid, req)
        with self._mu:
            self._pending.setdefault(db, []).append(w)
            lead = not self._leader.get(db, False)
            if lead:
                self._leader[db] = True
            else:
                self._cv.notify_all()
        if lead:
            threading.Thread(target=self._drain, args=(db,), daemon=True,
                             name=f"netsdb-decode-{db}").start()
        w.done.wait()
        if w.error is not None:
            raise w.error
        return w.result

    def _drain(self, db: str) -> None:
        # Leadership ends ONLY under ``_mu`` in the same critical
        # section that observed nothing live and an empty queue — a
        # waiter therefore either enqueues before that check (this
        # leader admits it) or after the flag clears (it starts the
        # next leader). Anything else loses a wakeup: waiters park on
        # their own event, not the condition variable.
        live: List[_Waiter] = []
        try:
            while True:
                with self._mu:
                    limit = self._max_of.get(db, self.max_batch)
                    if not live:
                        # an idle model lingers one window for peers
                        deadline = time.monotonic() + self.window_s
                        while len(self._pending.get(db, ())) < limit:
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            self._cv.wait(left)
                    live += self._take_locked(db, live, limit)
                    if not live:
                        self._leader[db] = False
                        return
                    self._stats["batches"] += 1
                    if len(live) > self._stats["max_occupancy"]:
                        self._stats["max_occupancy"] = len(live)
                batch, live = live, []
                try:
                    results = self._run(db, [w.req for w in batch])
                    if len(results) != len(batch):
                        raise RuntimeError(
                            f"decode batch returned {len(results)} "
                            f"results for {len(batch)} requests")
                except BaseException as e:  # noqa: BLE001 — fan out
                    for w in batch:
                        w.error = e
                        w.done.set()
                    continue
                for w, r in zip(batch, results):
                    if r is CONTINUE:
                        live.append(w)
                        continue
                    # a per-request fault (e.g. one session moved out
                    # from under the batch) fails ONLY its own waiter;
                    # the rest keep their results
                    if isinstance(r, BaseException):
                        w.error = r
                    else:
                        w.result = r
                    w.done.set()
        except BaseException as e:  # leader thread dying: fail the
            with self._mu:          # parked waiters, don't strand them
                self._leader[db] = False
                orphans = self._pending.pop(db, [])
            for w in orphans + live:
                w.error = e
                w.done.set()
            raise

    def _take_locked(self, db: str, live: List[_Waiter],
                     limit: int) -> List[_Waiter]:
        """Waiters to admit now: up to ``limit`` live in all, AT MOST
        ONE PER SESSION — a duplicate (a pipelined retry) waits until
        the first has left, so two frames never advance one session's
        state at once."""
        q = self._pending.get(db, [])
        batch: List[_Waiter] = []
        seen = {w.sid for w in live}
        rest: List[_Waiter] = []
        for w in q:
            if len(live) + len(batch) < limit and w.sid not in seen:
                batch.append(w)
                seen.add(w.sid)
            else:
                rest.append(w)
        if rest:
            self._pending[db] = rest
        else:
            self._pending.pop(db, None)
        self._stats["coalesced"] += len(batch)
        return batch

    def snapshot(self) -> Dict[str, Any]:
        with self._mu:
            out = dict(self._stats)
            out["pending"] = sum(len(v)
                                 for v in self._pending.values())
        return out
