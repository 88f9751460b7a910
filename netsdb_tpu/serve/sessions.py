"""Stateful interactive serving: the session subsystem.

A *session* is a named, TTL'd decode loop over one registered model
(``models/decode.py``): ``SESSION_OPEN`` binds ``sid → (model, owner,
ttl)``, each ``GENERATE`` advances the session's state by one frame (a
toy kind: one step on an input row; a language model: a TURN — token
ids appended, then a number of new tokens generated), ``SESSION_CLOSE``
drops it. Three stores cooperate, fastest first:

* **The model's slab** (``storage/devcache.SessionSlab``) — the hot
  copy: per registered model one set of device arrays with a slot
  axis, every slot one session's whole state (recurrent state,
  convolution window, key/value cache, position: whatever the kind's
  layout declares). A session LEASES a slot — one MUTABLE entry of the
  device cache per ``(session, model)``, charged the slot's bytes —
  from open to close or eviction. A warm step takes the slab, runs ONE
  program over every slot (row = slot, the idle ones masked) that
  donates it, and stores what comes back: no state crosses the host
  (``session.state_host_bytes`` stays flat). The methods mutating the
  cache's session entries are called ONLY from this module (the
  ``session-state-mutation`` lint rule).
* **Host arena** (:class:`SessionArena`) — where an evicted or expired
  lease's slot lands via the devcache spill callback (one slot's
  slices copied to the host), and where a session revives from after
  pressure, TTL expiry, or owner failover (the slices written back
  into a free slot). A warm decode step never touches it
  (``arena.reads`` is the structural gate's counter).
* **The replicated session table** (:class:`SessionTable`) — sid →
  metadata. Not replicated by itself: the MIRRORED ``SESSION_OPEN`` /
  ``GENERATE`` / ``SESSION_CLOSE`` frames replay at every follower,
  which re-derives the same table (and the same slab/arena state,
  since decode is deterministic) — the HA-log-shipping discipline the
  data plane already uses, reused verbatim for sessions.

State is STEP-TAGGED: the lease carries the step its slot is at, and
every layer spilled to the arena carries the step it was read at. The
newest copy of a session's state is in exactly one of the two
(resident beats arena; the arena keeps the highest-step spill). A slot
is read and written whole under the slab's lock, so a revive is
consistent by construction — and a torn assembly (layers of mixed
steps in the arena, which would mean a bookkeeping bug, not a race)
raises instead of silently decoding from mixed steps.

Batching is continuous (``sched/sessions.DecodeBatcher``): a model's
leader thread runs :meth:`SessionManager._run_batch` once an
iteration over the frames that are live. An iteration admits the
frames that joined (seats their sessions), dispatches at most ONE
chunk of ONE joining turn's prompt and then one decode step over every
turn that is past its prompt, and retires the turns whose last step's
outputs have reached the host — one step behind the dispatch, so that
the device has the next program queued while the host reads.

A turn's own timeline is recorded into the frame's trace (the one
captured under ``session.coalesce``) by the leader, as consecutive
spans: ``server.sched.session_wait`` (submit to the iteration that
seats it), ``session.admit``, ``session.turn.prefill`` (seated to its
last chunk dispatched; counters ``tokens``, ``chunks`` and
``chunks_ahead``: other turns' chunks dispatched while it had chunks
pending), ``session.turn.first_token`` (to its first output row on the
host), ``session.turn.decode`` (to its last; counters ``steps``, its
later steps, ``chunk_steps``, those whose ``session.step`` span held
another turn's chunk, and ``chunk_step_s``, their seconds),
``session.retire`` and ``session.turn.reply`` (the answer's way back
to the frame's handler thread). A step's ``session.step`` span (one a
step, in the trace of its first turn) counts ``prefill_tokens``: the
tokens of the chunks dispatched after the step before it and up to it,
which the device runs between the two.

Ownership and stickiness: the pool leader places each session
deterministically (itself, or one live worker by sid hash), pushing
``SESSION_OPEN op=adopt`` — with the model's dense weights on the
first session per (owner, model) — to a worker owner. A frame landing
on a non-owner answers the typed retryable ``SessionMoved`` carrying
the owner's address; the client re-points and retries under the SAME
idempotency token, so a frame is never double-applied to one state
copy, and a re-applied frame after failover recomputes bit-identically
from the last durable state."""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.models import decode as _decode
from netsdb_tpu.serve.errors import ServeFault, SessionMoved, SessionUnknown
from netsdb_tpu.serve.protocol import (CODEC_PICKLE, MsgType,
                                       session_output_set)
from netsdb_tpu.serve.sched.sessions import CONTINUE, DecodeBatcher
from netsdb_tpu.storage.devcache import SessionSlab
from netsdb_tpu.utils.locks import TrackedLock

#: the devcache "layer" name of a session's slot lease
LEASE = "slot"


output_set = session_output_set


def _span(trace, name: str, start: float, end: float,
          **counters) -> None:
    """Record the region between two ``perf_counter`` readings into a
    captured trace (``obs.record_into``): placed where it was, however
    late the leader gets to record it."""
    obs.record_into(trace, name, end - start, "serve",
                    ended_ago_s=time.perf_counter() - end, **counters)


def _host(value: Any) -> np.ndarray:
    """A host-side copy of one layer value (device array or ndarray).
    The spill callback runs under the devcache lock; this is the one
    transfer it performs."""
    return np.array(np.asarray(value))


class SessionTable:
    """sid → session metadata. Every daemon re-derives its own copy
    from the mirrored frame stream (module docstring); the wire dump
    only rides follower resync snapshots."""

    def __init__(self):
        self._mu = TrackedLock("SessionTable._mu")
        self._rows: Dict[str, Dict[str, Any]] = {}

    def open(self, sid: str, db: str, kind: str, owner: str,
             ttl_s: float, home: Optional[str] = None) -> Dict[str, Any]:
        with self._mu:
            row = self._rows.get(sid)
            if row is None:
                row = {"sid": sid, "db": db, "kind": kind,
                       "owner": owner, "home": home, "ttl_s": float(ttl_s),
                       "steps": 0}
                self._rows[sid] = row
            return dict(row)

    def get(self, sid: str) -> Optional[Dict[str, Any]]:
        with self._mu:
            row = self._rows.get(sid)
            return dict(row) if row else None

    def steps(self, sid: str) -> int:
        with self._mu:
            row = self._rows.get(sid)
            return int(row["steps"]) if row else 0

    def bump(self, sid: str) -> int:
        with self._mu:
            row = self._rows[sid]
            row["steps"] += 1
            return int(row["steps"])

    def set_steps(self, sid: str, steps: int) -> None:
        with self._mu:
            row = self._rows.get(sid)
            if row is not None and int(steps) > int(row["steps"]):
                row["steps"] = int(steps)

    def set_owner(self, sid: str, owner: str,
                  home: Optional[str] = None) -> None:
        with self._mu:
            row = self._rows.get(sid)
            if row is not None:
                row["owner"] = owner
                if home is not None:
                    row["home"] = home

    def close(self, sid: str) -> bool:
        with self._mu:
            return self._rows.pop(sid, None) is not None

    def count(self) -> int:
        with self._mu:
            return len(self._rows)

    def sessions(self) -> List[Dict[str, Any]]:
        with self._mu:
            return [dict(r) for r in self._rows.values()]

    def to_wire(self) -> List[Dict[str, Any]]:
        return self.sessions()

    def load_wire(self, rows: List[Dict[str, Any]]) -> None:
        with self._mu:
            for r in rows or []:
                self._rows[str(r["sid"])] = dict(r)


class SessionArena:
    """Host-side spill store for evicted/expired session state. A
    LEAF: its lock nests under the devcache lock (the spill callback)
    and under nothing else, and it never calls out. ``reads`` counts
    revive lookups that RETURNED state — the warm-decode structural
    gate asserts it stays flat across hot steps."""

    def __init__(self):
        self._mu = TrackedLock("SessionArena._mu")
        # (sid, db) → {"layers": {layer: {"step", "v"(host)}},
        #              "steps": int, "dirty": bool}
        self._slots: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self.reads = 0
        self.writes = 0

    def merge_layer(self, sid: str, db: str, layer: str, step: int,
                    value: np.ndarray, steps_hint: int = 0) -> None:
        key = (sid, db)
        with self._mu:
            slot = self._slots.setdefault(
                key, {"layers": {}, "steps": 0, "dirty": False})
            cur = slot["layers"].get(layer)
            if cur is None or int(step) >= int(cur["step"]):
                slot["layers"][layer] = {"step": int(step), "v": value}
            slot["steps"] = max(int(slot["steps"]), int(step),
                                int(steps_hint))
            slot["dirty"] = True
            self.writes += 1

    def merge_state(self, sid: str, db: str,
                    layers: Dict[str, Dict[str, Any]], steps: int,
                    dirty: bool = False) -> None:
        """A whole-state merge (the op=spill push path) — per-layer
        highest-step-wins, same rule as :meth:`merge_layer`."""
        with self._mu:
            slot = self._slots.setdefault(
                (sid, db), {"layers": {}, "steps": 0, "dirty": False})
            for layer, rec in (layers or {}).items():
                cur = slot["layers"].get(layer)
                if cur is None or int(rec["step"]) >= int(cur["step"]):
                    slot["layers"][layer] = {"step": int(rec["step"]),
                                             "v": rec["v"]}
            slot["steps"] = max(int(slot["steps"]), int(steps))
            if dirty:
                slot["dirty"] = True
            self.writes += 1

    def get_layer(self, sid: str, db: str,
                  layer: str) -> Optional[Dict[str, Any]]:
        with self._mu:
            slot = self._slots.get((sid, db))
            rec = slot["layers"].get(layer) if slot else None
            if rec is not None:
                self.reads += 1
                return dict(rec)
            return None

    def snapshot_slot(self, sid: str, db: str) -> Optional[Dict[str, Any]]:
        with self._mu:
            slot = self._slots.get((sid, db))
            if slot is None:
                return None
            return {"layers": {k: dict(v)
                               for k, v in slot["layers"].items()},
                    "steps": int(slot["steps"])}

    def steps(self, sid: str, db: str) -> int:
        with self._mu:
            slot = self._slots.get((sid, db))
            return int(slot["steps"]) if slot else 0

    def drop(self, sid: str) -> int:
        with self._mu:
            keys = [k for k in self._slots if k[0] == sid]
            for k in keys:
                del self._slots[k]
            return len(keys)

    def take_dirty(self) -> List[Tuple[str, str]]:
        """Pop the dirty markers (the housekeeping push drain)."""
        with self._mu:
            out = [k for k, s in self._slots.items() if s["dirty"]]
            for k in out:
                self._slots[k]["dirty"] = False
            return out

    def mark_dirty(self, sid: str, db: str) -> None:
        with self._mu:
            slot = self._slots.get((sid, db))
            if slot is not None:
                slot["dirty"] = True

    def stats(self) -> Dict[str, int]:
        with self._mu:
            return {"entries": len(self._slots),
                    "reads": self.reads, "writes": self.writes,
                    "bytes": sum(rec["v"].nbytes
                                 for s in self._slots.values()
                                 for rec in s["layers"].values())}


class SessionManager:
    """One per daemon: owns the decode runtime, the table/arena pair,
    the per-model batch coalescer, and the housekeeping thread (TTL
    sweep + spill push to the session's home leader)."""

    def __init__(self, ctl):
        self._ctl = ctl
        cfg = ctl.config
        self.ttl_s = float(getattr(cfg, "session_ttl_s", 600.0))
        self.state_cap = int(getattr(cfg, "session_state_bytes",
                                     16 << 20))
        batch_max = int(getattr(cfg, "decode_batch_max", 8))
        self.runtime = _decode.DecodeRuntime(
            ctl.library,
            model_dedup=bool(getattr(cfg, "model_dedup", False)),
            slots=batch_max)
        self.table = SessionTable()
        self.arena = SessionArena()
        self.batcher = DecodeBatcher(self._run_batch, max_batch=batch_max)
        # model -> its slab (also registered with the device cache; kept
        # here too because the spill callback runs UNDER the cache's
        # lock and must not ask the cache for it)
        self._slabs: Dict[str, SessionSlab] = {}
        self._slabs_mu = TrackedLock("SessionManager._slabs_mu")
        # model -> {"turns": sid -> the live turn, "steps": dispatched
        # steps whose outputs the host has not read yet}. Touched by the
        # model's batch leader alone (one iteration at a time)
        self._lanes: Dict[str, Dict[str, Any]] = {}
        # models whose dense weights already shipped to an owner —
        # later sessions of the same (owner, model) adopt weight-less.
        # Guarded by _shipped_mu (handler threads race on it) and
        # invalidated by forget_owner() when the pool health loop
        # degrades/readmits a member: a restarted worker lost its
        # resident models, so a weight-less adopt there would fail
        # register_model and silently degrade placement to
        # leader-local ownership.
        self._shipped: set = set()
        self._shipped_mu = TrackedLock("SessionManager._shipped_mu")
        # per-session last-applied idempotency record
        # {token, steps, out (the reply's arrays)}: the daemon-local idempotency cache only
        # dedupes retries that land on the SAME daemon — this record
        # travels WITH the state (spill push, move, handoff, adopt),
        # so a retry under the same token landing at the session's
        # NEW owner replays the recorded reply instead of advancing
        # the state a second time (the handle's no-double-apply
        # contract across relocations).
        self._applied: Dict[str, Dict[str, Any]] = {}
        self._applied_mu = TrackedLock("SessionManager._applied_mu")
        self._hk_thread: Optional[threading.Thread] = None
        self._hk_stop = threading.Event()
        self._hk_mu = TrackedLock("SessionManager._hk_mu")
        # per-session exclusion between a decode step's load→step→save
        # and a handoff/move/close packing or dropping that state. The
        # server's mirrored-frame ordering locks only exist on daemons
        # WITH followers — a plain pool worker needs this or a live
        # move can tear an in-flight step. A batch takes its sids in
        # sorted order; every other holder takes exactly one, so the
        # two can never deadlock.
        self._sid_locks: Dict[str, TrackedLock] = {}
        self._sid_locks_mu = TrackedLock("SessionManager._sid_locks_mu")
        # diagnostics breadcrumbs (racy-by-design single slots: the
        # LAST best-effort fault, surfaced via stats(); the counters
        # next to each write are the authoritative tally)
        self._last_spill_fault: Optional[str] = None
        self._last_place_fault: Optional[str] = None
        ctl.library.store.device_cache().set_session_spill(self._on_spill)

    # --- roles ---------------------------------------------------------
    def _me(self) -> str:
        return self._ctl.advertise_addr

    def _authoritative(self, row: Dict[str, Any]) -> bool:
        """Is this daemon the session's authority (may adopt, place,
        and answer SessionMoved)? With HA armed, the current LEADER
        is; unarmed, the session's home daemon is (a pool worker's
        rows carry the leader as home, so the worker only ever
        applies what it owns or bounces)."""
        ha = self._ctl._ha
        if ha is not None:
            from netsdb_tpu.serve import ha as _ha

            return ha.role == _ha.LEADER
        home = row.get("home")
        return home is None or home == self._me()

    def _replica(self) -> bool:
        ha = self._ctl._ha
        if ha is None:
            return False
        from netsdb_tpu.serve import ha as _ha

        return ha.role != _ha.LEADER

    def _live_workers(self) -> List[str]:
        ctl = self._ctl
        return [a for a in ctl._worker_addrs
                if not ctl.shards.is_degraded(a)]

    def _pick_owner(self, sid: str) -> str:
        """Deterministic placement from replicated inputs only: sid
        hash over the sorted live workers, or self when the pool is
        plain. A follower replaying the open (usually with no worker
        list) picks ITSELF — exactly the owner it must be if it is
        ever promoted, so failover needs no table rewrite."""
        if self._replica():
            return self._me()
        live = sorted(self._live_workers())
        if not live:
            return self._me()
        h = int(hashlib.sha1(sid.encode()).hexdigest(), 16)
        return live[h % len(live)]

    # --- devcache/arena state movement --------------------------------
    # (the ONLY call sites of the devcache session_* mutators — the
    # session-state-mutation lint rule pins this)
    def _cache(self):
        return self._ctl.library.store.device_cache()

    def _slab(self, db: str) -> SessionSlab:
        """``db``'s slab, allocated (zeroed, whole) on first use."""
        with self._slabs_mu:
            slab = self._slabs.get(db)
        if slab is None:
            # made and installed outside _slabs_mu: the spill callback
            # takes that lock UNDER the cache's (first install wins)
            slab = self._cache().slab_install(db, SessionSlab(
                db, self.runtime.new_slab(db), self.runtime.slots(db),
                self.runtime.slot_nbytes(db)))
            with self._slabs_mu:
                self._slabs[db] = slab
            self.batcher.set_max_batch(
                db, max(self.batcher.max_batch, slab.slots))
        return slab

    def _on_spill(self, sid: str, model: str, layer: str,
                  value: Any) -> None:
        """Devcache eviction/expiry escape hatch — LEAF (runs under
        the cache lock): copy the lease's slot to the host, every layer
        tagged with the lease's step, into the arena, and free the
        slot."""
        try:
            with self._slabs_mu:
                slab = self._slabs.get(model)
            if slab is None or layer != LEASE:
                return
            with slab.mu:
                host = self.runtime.read_slot(model, slab.arrays,
                                              int(value["slot"]))
                slab.give(int(value["slot"]))
            self._to_arena(sid, model, host, int(value.get("step", 0)))
            obs.REGISTRY.counter("session.slab.spills").inc()
        except Exception as e:  # noqa: BLE001 — spill must never
            # take the cache down with it; the arena just misses
            # this copy (counted, last fault kept for stats())
            self._last_spill_fault = repr(e)
            obs.REGISTRY.counter("session.spill_errors").inc()

    def _to_arena(self, sid: str, db: str, host: Dict[str, np.ndarray],
                  step: int) -> None:
        for layer, v in host.items():
            self.arena.merge_layer(sid, db, layer, step, v,
                                   steps_hint=self.table.steps(sid))
        obs.REGISTRY.counter("session.state_host_bytes").inc(
            sum(v.nbytes for v in host.values()))

    def _take_slot(self, db: str, slab: SessionSlab) -> Optional[int]:
        """A free slot of ``db``'s slab; where none is free, the least
        recently used lease of a session that is not in a live turn is
        evicted (spilled) to make one. None when every slot is in a
        live turn."""
        with slab.mu:
            slot = slab.take()
        if slot is None:
            busy = set(self._lanes.get(db, {}).get("turns", ()))
            if self._cache().session_evict_one(db, skip=busy) is None:
                return None
            with slab.mu:
                slot = slab.take()
        return slot

    def _seat(self, sid: str, db: str, ttl_s: float
              ) -> Optional[Tuple[int, int, bool]]:
        """The session's CURRENT state in a slot: ``(slot, step,
        leased)``, or None when no slot can be had right now (every
        slot is in a live turn). Newest copy wins — the resident
        lease's slot, unless the arena's spill is NEWER (then the arena
        copy revives into the slot). A resident copy can legitimately
        be stale: a mirror follower replays ``op=open`` owning the
        session itself and seats init state at step 0, while a
        worker-owned session's durability arrives only via mirrored
        ``op=spill`` merges into the arena — after promotion the step-0
        resident slot would otherwise silently rewind the session. All
        layers of a revive must carry one step — a mixed assembly is a
        torn state and raises rather than decoding garbage. ``leased``
        is False when the device cache's budget cannot hold one slot:
        the slot is then the frame's alone and goes back to the arena
        when the frame retires."""
        slab = self._slab(db)
        nbytes = slab.slot_nbytes
        lease = self._cache().session_get(sid, db, LEASE)
        # the arena's high-water step, read WITHOUT a read tick: on a
        # warm step the resident slot is at least this new, so the
        # zero-warm-arena-reads gate still holds
        arena_steps = self.arena.steps(sid, db)
        if lease is not None and int(lease["step"]) >= arena_steps:
            self.table.set_steps(sid, int(lease["step"]))
            return int(lease["slot"]), int(lease["step"]), True
        slot = int(lease["slot"]) if lease is not None \
            else self._take_slot(db, slab)
        if slot is None:
            return None
        layers = {name: self.arena.get_layer(sid, db, name)
                  for name in self.runtime.state_layout(db)}
        found = [r for r in layers.values() if r is not None]
        try:
            if not found:
                if self.table.steps(sid) != 0 or arena_steps != 0:
                    raise SessionUnknown(
                        f"session {sid!r} state lost (not resident, "
                        f"no arena spill)")
                step = 0
                with slab.mu:
                    slab.arrays = self.runtime.zero_slot(
                        db, slab.arrays, slot)
            else:
                steps_seen = {int(r["step"]) for r in found}
                if len(found) != len(layers) or len(steps_seen) > 1:
                    raise ServeFault(
                        f"session {sid!r} state torn across steps "
                        f"{sorted(steps_seen)} ({len(found)} of "
                        f"{len(layers)} layers)")
                step = steps_seen.pop()
                values = {n: r["v"] for n, r in layers.items()}
                with slab.mu:
                    slab.arrays = self.runtime.write_slot(
                        db, slab.arrays, slot, values)
                obs.REGISTRY.counter("session.slab.revives").inc()
                obs.REGISTRY.counter("session.state_host_bytes").inc(
                    sum(v.nbytes for v in values.values()))
        except BaseException:
            if lease is None:
                with slab.mu:
                    slab.give(slot)
            raise
        rec = {"slot": slot, "step": step}
        leased = self._cache().session_put(sid, db, LEASE, rec, ttl_s,
                                           nbytes=nbytes)
        obs.REGISTRY.gauge("session.slab.slots_live").set(slab.live())
        self.table.set_steps(sid, step)
        return slot, step, bool(leased)

    def _unseat(self, sid: str, db: str, slot: int, step: int) -> None:
        """A frame's own slot (no lease: the budget holds none) back to
        the arena and the free list — the advanced state must still
        land somewhere durable, so the next frame revives it instead of
        raising SessionUnknown over silently-dropped state."""
        slab = self._slab(db)
        with slab.mu:
            host = self.runtime.read_slot(db, slab.arrays, slot)
            slab.give(slot)
        self._to_arena(sid, db, host, step)
        obs.REGISTRY.counter("session.budget_spills").inc()

    def _release(self, sid: str) -> int:
        """Drop the session's leases WITHOUT a spill and free their
        slots (close, move, handoff: the state went where it had to go,
        or nowhere)."""
        row = self.table.get(sid)
        if row is not None:
            lease = self._cache().session_get(sid, row["db"], LEASE,
                                              touch=False)
            with self._slabs_mu:
                slab = self._slabs.get(row["db"])
            if lease is not None and slab is not None:
                with slab.mu:
                    slab.give(int(lease["slot"]))
                obs.REGISTRY.gauge("session.slab.slots_live").set(
                    slab.live())
        return self._cache().session_drop(sid)

    def _install_state(self, sid: str, db: str, ttl_s: float) -> None:
        """A fresh session's init state into a slot (open, adopt)."""
        seat = self._seat(sid, db, ttl_s)
        if seat is not None and not seat[2]:
            # no lease to hold the slot between frames: init state is
            # what the next frame's seat makes again
            slab = self._slab(db)
            with slab.mu:
                slab.give(seat[0])

    def _pack(self, sid: str, db: str) -> Dict[str, Any]:
        """The session's full host-side state (the resident slot first,
        arena fallback) — the op=spill/handoff payload. The
        last-applied idempotency record rides along so the dedup
        guarantee survives the relocation."""
        layers: Dict[str, Dict[str, Any]] = {}
        lease = self._cache().session_get(sid, db, LEASE, touch=False)
        if lease is not None \
                and int(lease["step"]) >= self.arena.steps(sid, db):
            slab = self._slab(db)
            with slab.mu:
                host = self.runtime.read_slot(db, slab.arrays,
                                              int(lease["slot"]))
            obs.REGISTRY.counter("session.state_host_bytes").inc(
                sum(v.nbytes for v in host.values()))
            layers = {n: {"step": int(lease["step"]), "v": v}
                      for n, v in host.items()}
        else:
            for layer in self.runtime.state_layout(db):
                rec = self.arena.get_layer(sid, db, layer)
                if rec is not None:
                    layers[layer] = {"step": int(rec["step"]),
                                     "v": _host(rec["v"])}
        out = {"layers": layers,
               "steps": max([self.table.steps(sid),
                             self.arena.steps(sid, db)]
                            + [r["step"] for r in layers.values()]
                            or [0])}
        applied = self._applied_record(sid)
        if applied is not None:
            out["applied"] = applied
        return out

    # --- the per-session applied-token record -------------------------
    def _applied_record(self, sid: str) -> Optional[Dict[str, Any]]:
        """Host-copied wire form of the session's last-applied step
        record, or None."""
        with self._applied_mu:
            last = self._applied.get(sid)
            if last is None:
                return None
            return {"token": last["token"],
                    "steps": int(last["steps"]),
                    "out": {k: _host(v)
                            for k, v in last["out"].items()}}

    def _note_applied(self, sid: str,
                      rec: Optional[Dict[str, Any]]) -> None:
        """Adopt a shipped applied-token record (move/handoff/spill
        push) — newest step wins, so a stale straggler push can never
        roll the dedup horizon backwards."""
        if not rec or not rec.get("token"):
            return
        with self._applied_mu:
            cur = self._applied.get(sid)
            if cur is None \
                    or int(rec.get("steps", 0)) >= int(cur["steps"]):
                self._applied[sid] = {"token": rec["token"],
                                      "steps": int(rec.get("steps", 0)),
                                      "out": dict(rec["out"])}

    # --- the batched decode step --------------------------------------
    def _sid_lock(self, sid: str) -> TrackedLock:
        with self._sid_locks_mu:
            return self._sid_locks.setdefault(
                sid, TrackedLock("SessionManager._sid_locks[]"))

    def _run_batch(self, db: str,
                   reqs: List[Dict[str, Any]]) -> List[Any]:
        locks = [self._sid_lock(s)
                 for s in sorted({str(r["sid"]) for r in reqs})]
        for lk in locks:
            lk.acquire()
        try:
            return self._run_batch_locked(db, reqs)
        finally:
            for lk in reversed(locks):
                lk.release()

    def _run_batch_locked(self, db: str,
                          reqs: List[Dict[str, Any]]) -> List[Any]:
        """ONE iteration over the live frames of ``db`` (module
        docstring): admit, one prefill chunk, one decode step, harvest
        and retire."""
        lane = self._lanes.setdefault(
            db, {"turns": {}, "steps": collections.deque(),
                 "chunk_tokens": 0})
        results: List[Any] = [CONTINUE] * len(reqs)
        for i, r in enumerate(reqs):
            try:
                if "_turn" not in r:
                    results[i] = self._admit(db, lane, r)
                else:
                    self._reseat(db, r["_turn"])
            except (ServeFault, SessionMoved, SessionUnknown) as e:
                results[i] = e
                self._drop_turn(db, lane, r)
        turns = [r["_turn"] for i, r in enumerate(reqs)
                 if results[i] is CONTINUE and "_turn" in r]
        dispatched = False
        if turns:
            slab = self._slab(db)
            joining = [t for t in turns if t["chunks"]]
            if joining:
                self._prefill_chunk(db, slab, lane, joining)
            ready = [t for t in turns if not t["chunks"] and t["n"] > 0]
            if ready:
                self._decode_step(db, slab, lane, ready)
                dispatched = True
        # the outputs of every dispatched step but the newest: the
        # device has that one queued while the host reads these
        while len(lane["steps"]) > (1 if dispatched else 0):
            self._harvest(lane["steps"].popleft())
        for i, r in enumerate(reqs):
            t = r.get("_turn")
            if results[i] is CONTINUE and t is not None \
                    and not t["chunks"] and t["n"] == 0 \
                    and t["unread"] == 0:
                results[i] = self._retire(db, lane, r)
        return results

    def _admit(self, db: str, lane: Dict[str, Any],
               r: Dict[str, Any]) -> Any:
        """Seat one joining frame's session and lay out its turn.
        Returns CONTINUE (admitted, or no slot yet: it is offered again
        next iteration), a recorded reply (a retry of an applied
        frame), or raises the frame's own typed fault."""
        t0 = time.perf_counter()
        sid = r["sid"]
        row = self.table.get(sid)
        if row is None:
            raise SessionUnknown(f"unknown session {sid!r}")
        if row["owner"] != self._me():
            # a handoff/move won the sid lock while this frame sat in
            # the queue: bounce ONLY this frame typed-retryable, keep
            # the rest batched
            raise SessionMoved(
                f"session {sid!r} moved to {row['owner']}",
                owner_addr=row["owner"])
        tok = r.get("tok")
        if tok:
            with self._applied_mu:
                last = self._applied.get(sid)
            if last is not None and last["token"] == tok:
                # retry of an applied-but-unanswered frame whose
                # record travelled here with the state (the
                # daemon-local idempotency cache can't have seen this
                # token): replay the recorded reply, never advance
                # the state twice under one token
                return dict(last["out"], steps=int(last["steps"]))
        ttl = float(row["ttl_s"])
        seat = self._seat(sid, db, ttl)
        if seat is None:
            return CONTINUE
        slot, step, leased = seat
        turn = {"sid": sid, "slot": slot, "leased": leased, "ttl": ttl,
                "step": step, "tok": tok, "trace": r.get("trace"),
                "admitted": time.perf_counter(), "chunks": [], "n": 1,
                "x": None, "unread": 0, "outs": [], "logits": None,
                # the turn's timeline (module docstring): when its
                # current phase began, and what its phases count
                "at": 0.0, "prompt_tokens": 0, "prompt_chunks": 0,
                "chunks_ahead": 0, "later_steps": 0, "chunk_steps": 0,
                "chunk_step_s": 0.0}
        if self.runtime.takes_x(db):
            turn["x"] = np.asarray(r["x"], np.float32)
            turn["advance"] = 1
        else:
            tokens = np.asarray(r.get("tokens", ()), np.int32).reshape(-1)
            turn["n"] = int(r.get("new_tokens", 0))
            turn["advance"] = len(tokens) + turn["n"]
            try:
                turn["chunks"] = self._lay_out(db, step, tokens, turn["n"])
            except ServeFault:
                if not leased:
                    self._unseat(sid, db, slot, step)
                raise
        r["_turn"] = turn
        lane["turns"][sid] = turn
        # the frame's wait for the decode scheduler: from its handler's
        # submit to the iteration that seated it (a step boundary, and
        # a free slot); a ``server.sched.*`` span like the lanes' own
        now = turn["at"] = time.perf_counter()
        _span(turn["trace"], "server.sched.session_wait",
              min(r.get("queued", t0), t0), t0)
        _span(turn["trace"], "session.admit", t0, now)
        return CONTINUE

    def _lay_out(self, db: str, step: int, tokens: np.ndarray,
                 n: int) -> List[Tuple[np.ndarray, int, int]]:
        """The prefill chunks of a language-model turn: ``[(ids of one
        chunk length, how many count, the id that becomes the slot's
        next input or -1, the tokens the slot holds before the
        chunk)]``. The sequence to consume is the id the
        last frame left unconsumed (-1 stands for it: the program reads
        it from the slab) and then ``tokens``; all of it but its last id
        goes through prefill, the last is the first decode step's
        input. ``step`` is the session's history length so far."""
        spec = self.runtime.spec(db)
        seq = np.concatenate([np.full(1 if step else 0, -1, np.int32),
                              tokens])
        if len(seq) == 0:
            raise ServeFault("a session's first frame must append "
                             "token ids")
        if len(tokens) and (tokens.min() < 0
                            or tokens.max() >= spec["vocab"]):
            raise ServeFault(f"token ids outside [0, {spec['vocab']})")
        if step + len(tokens) + n - 1 > spec["cache_tokens"]:
            raise ServeFault(
                f"the frame would take the session to "
                f"{step + len(tokens) + n} tokens; a slot caches "
                f"{spec['cache_tokens']}")
        body, last = seq[:-1], int(seq[-1])
        plan = self.runtime.plan_prefill(db, len(body))
        if not plan and last >= 0:
            plan = [(spec["prefill_chunks"][0], 0)]   # only sets tok
        # the slot holds the history but the id left unconsumed
        chunks, at, held = [], 0, max(step - 1, 0)
        for j, (size, count) in enumerate(plan):
            ids = np.zeros(size, np.int32)
            ids[:count] = body[at:at + count]
            chunks.append((ids, count, last if j == len(plan) - 1 else -1,
                           held + at))
            at += count
        return chunks

    def _reseat(self, db: str, turn: Dict[str, Any]) -> None:
        """A live turn whose lease was evicted or expired under it (the
        cache spilled its slot, as it stood, to the arena): take a slot
        again and go on from exactly there. Refreshes the lease's
        recencies otherwise."""
        if not turn["leased"]:
            return
        rec = {"slot": turn["slot"], "step": turn["step"]}
        if self._cache().session_update(turn["sid"], db, LEASE, rec):
            return
        seat = self._seat(turn["sid"], db, turn["ttl"])
        if seat is None:
            raise ServeFault(f"session {turn['sid']!r} lost its slot "
                             f"mid-frame and none is free")
        turn["slot"], _, turn["leased"] = seat

    def _prefill_chunk(self, db: str, slab: SessionSlab,
                       lane: Dict[str, Any],
                       joining: List[Dict[str, Any]]) -> None:
        """One chunk of the oldest joining turn's prompt; the others
        count it as a chunk ahead of theirs."""
        turn = min(joining, key=lambda t: t["admitted"])
        ids, count, next_tok, pos0 = turn["chunks"].pop(0)
        t0 = time.perf_counter()
        with slab.mu:
            slab.arrays = self.runtime.prefill(
                db, slab.arrays, turn["slot"], ids, count, next_tok)
        obs.REGISTRY.counter("session.prefill_tokens").inc(count)
        read, held = self.runtime.prefill_blocks_read(db, len(ids), pos0,
                                                      count)
        obs.REGISTRY.counter("prefill.attn.key_blocks_read").inc(read)
        obs.REGISTRY.counter("prefill.attn.key_blocks_held").inc(held)
        # the host's dispatch only: the program runs behind it, and its
        # device seconds are the device trace's to give
        now = time.perf_counter()
        _span(turn["trace"], "session.prefill", t0, now, tokens=count)
        lane["chunk_tokens"] += count
        for t in joining:
            if t is not turn:
                t["chunks_ahead"] += 1
        turn["prompt_tokens"] += count
        turn["prompt_chunks"] += 1
        if not turn["chunks"]:
            _span(turn["trace"], "session.turn.prefill", turn["at"], now,
                  tokens=turn["prompt_tokens"], chunks=turn["prompt_chunks"],
                  chunks_ahead=turn["chunks_ahead"])
            turn["at"] = now

    def _decode_step(self, db: str, slab: SessionSlab,
                     lane: Dict[str, Any],
                     ready: List[Dict[str, Any]]) -> None:
        t0 = time.perf_counter()
        active = np.zeros(slab.slots, bool)
        xs = None
        if self.runtime.takes_x(db):
            xs = np.zeros((slab.slots, self.runtime.spec(db)["hidden"]),
                          np.float32)
        for t in ready:
            active[t["slot"]] = True
            if xs is not None:
                xs[t["slot"]] = t["x"]
        with slab.mu:
            slab.arrays, outs = self.runtime.step(db, slab.arrays,
                                                  active, xs)
        if xs is None:
            # a live slot sees its history at the frame's start and what
            # the frame has consumed and generated since
            fetched, held = self.runtime.cache_rows_read(
                db, [t["step"] + t["advance"] - t["n"] for t in ready])
            obs.REGISTRY.counter("decode.attn.rows_fetched").inc(fetched)
            obs.REGISTRY.counter("decode.attn.rows_held").inc(held)
        for t in ready:
            t["n"] -= 1
            t["unread"] += 1
        # the chunks dispatched since the step before this one run on
        # the device between the two
        lane["steps"].append({"outs": outs, "turns": list(ready),
                              "t0": t0, "lane": lane,
                              "counts": self.runtime.step_counts(db),
                              "prefill_tokens": lane["chunk_tokens"]})
        lane["chunk_tokens"] = 0
        obs.REGISTRY.counter("session.decode_steps").inc(len(ready))
        if xs is None:
            obs.REGISTRY.counter("session.decode_tokens").inc(len(ready))

    def _harvest(self, step: Dict[str, Any]) -> None:
        """Read one dispatched step's outputs to the host (this is
        where the host waits for the device) and hand each turn its
        row."""
        outs, turns, lane = step["outs"], step["turns"], step["lane"]
        key = "y" if "y" in outs else "ids"
        # the wait is a poll of the array's readiness, not a blocking
        # copy, and that is for a profiler's sake, not the request's: a
        # blocking read is one step-long host event a step in a trace of
        # the runtime's calls, thousands a minute, and the benchmark's
        # reader of idle gaps walks them all for every gap (a traced
        # window then took 1,000 s to reduce for 620; chip runs, PR 27).
        # It costs a step up to half a millisecond of its 22 to 27; a
        # plain ``np.asarray`` is the right read once that reader is
        # repaired (PERF.md section 7)
        while not outs[key].is_ready():
            time.sleep(0.0005)
        host = np.asarray(outs[key])
        counts = step["counts"]
        if counts:
            # an expert model's routing counts ride after its ids
            moe = dict(zip(counts["names"], host[-len(counts["names"]):]))
            obs.REGISTRY.counter("decode.moe.pairs").inc(int(moe["pairs"]))
            obs.REGISTRY.counter("decode.moe.experts_touched").inc(
                int(moe["experts_touched"]))
            obs.REGISTRY.counter("decode.moe.experts_held").inc(
                counts["experts_held"])
            obs.REGISTRY.gauge("decode.moe.max_load").set(
                int(moe["max_load"]))
        # a step's span runs from when the device was free for it (the
        # step before it was read, or its own dispatch if later) to its
        # outputs on the host: consecutive steps tile the timeline, and
        # a prefill chunk dispatched between two steps falls into the
        # later one's span
        now = time.perf_counter()
        start = max(step["t0"], lane.get("read_at", 0.0))
        lane["read_at"] = now
        chunk = step["prefill_tokens"]
        for t in turns:
            t["outs"].append(host[t["slot"]])
            t["unread"] -= 1
            if "logits" in outs and t["n"] == 0 and t["unread"] == 0:
                t["logits"] = outs["logits"]
            self._turn_row(t, now, now - start, chunk)
        _span(turns[0]["trace"], "session.step", start, now,
              rows=len(turns), prefill_tokens=chunk)

    @staticmethod
    def _turn_row(t: Dict[str, Any], now: float, took: float,
                  chunk: int) -> None:
        """A turn's row of a step reached the host at ``now``: its first
        ends the wait for the first token, a later one is a decode step
        (one ``took`` seconds long that held ``chunk`` tokens of another
        turn's prompt), its last ends the decode."""
        if len(t["outs"]) == 1:
            _span(t["trace"], "session.turn.first_token", t["at"], now)
            t["at"] = now
        else:
            t["later_steps"] += 1
            if chunk:
                t["chunk_steps"] += 1
                t["chunk_step_s"] += took
        if t["n"] == 0 and t["unread"] == 0:
            _span(t["trace"], "session.turn.decode", t["at"], now,
                  steps=t["later_steps"], chunk_steps=t["chunk_steps"],
                  chunk_step_s=t["chunk_step_s"])

    def _drop_turn(self, db: str, lane: Dict[str, Any],
                   r: Dict[str, Any]) -> None:
        turn = r.pop("_turn", None)
        if turn is not None:
            lane["turns"].pop(turn["sid"], None)

    def _retire(self, db: str, lane: Dict[str, Any],
                r: Dict[str, Any]) -> Dict[str, Any]:
        """A turn whose last output is on the host: advance the step
        tag, write the logits row, record the applied token, answer."""
        t0 = time.perf_counter()
        turn = r["_turn"]
        sid = turn["sid"]
        step = turn["step"] + turn["advance"]
        if turn["x"] is not None:
            out = {"y": turn["outs"][-1]}
        else:
            out = {"ids": np.asarray(turn["outs"], np.int32).reshape(-1)}
            if turn["logits"] is not None:
                self._write_logits(db, sid, turn["logits"], turn["slot"])
        if turn["leased"]:
            rec = {"slot": turn["slot"], "step": step}
            if not self._cache().session_update(sid, db, LEASE, rec):
                # evicted between the last step and now: the arena holds
                # the state under the old tag; tag it forward
                self._retag(sid, db, step)
        else:
            self._unseat(sid, db, turn["slot"], step)
        self.table.set_steps(sid, step)
        if turn["tok"]:
            with self._applied_mu:
                self._applied[sid] = {"token": turn["tok"], "steps": step,
                                      "out": out}
        self._drop_turn(db, lane, r)
        now = r["retired"] = time.perf_counter()
        _span(turn["trace"], "session.retire", t0, now)
        return dict(out, steps=step)

    def _retag(self, sid: str, db: str, step: int) -> None:
        slot = self.arena.snapshot_slot(sid, db)
        if slot is not None:
            self.arena.merge_state(
                sid, db, {n: {"step": step, "v": rec["v"]}
                          for n, rec in slot["layers"].items()}, step,
                dirty=True)

    def _write_logits(self, db: str, sid: str, logits, slot: int) -> None:
        """The frame's last float32 logits row into the session's
        output set (device to store, no host copy)."""
        lib = self._ctl.library
        name = output_set(sid)
        if not lib.set_exists(db, name):
            lib.create_set(db, name, type_name="matrix")
        row = self.runtime.row_of(logits, slot)
        lib.send_matrix(db, name, row, self.runtime.block_for(db, row.shape))

    # --- frame handlers (called from ServeController) ------------------
    def handle_open(self, p: Dict[str, Any]):
        op = p.get("op", "open")
        if op == "open":
            return self._op_open(p)
        if op == "adopt":
            return self._op_adopt(p)
        if op == "spill":
            return self._op_spill(p)
        if op == "lookup":
            return self._op_lookup(p)
        if op == "move":
            return self._op_move(p)
        if op == "handoff":
            return self._op_handoff(p)
        raise ServeFault(f"unknown SESSION_OPEN op {op!r}")

    def _op_open(self, p):
        sid = str(p["sid"])
        db = str(p["db"])
        kind = str(p.get("kind", "lstm"))
        ttl_s = float(p.get("ttl_s") or self.ttl_s)
        heads = p.get("heads")
        spec = self.runtime.register_model(
            db, kind, client=p.get("client"), heads=heads)
        nbytes = self.runtime.slot_nbytes(db)
        # the daemon's own cap binds the kinds whose state it sizes; a
        # model whose database states its slots and cache is held to
        # what the device cache can lease instead
        if nbytes > self.state_cap and not self.runtime.stores_spec(db):
            raise ServeFault(
                f"session state ({nbytes}B) exceeds "
                f"session_state_bytes ({self.state_cap}B)")
        self._slab(db)
        existing = self.table.get(sid)
        if existing is not None:  # idempotent re-open
            return MsgType.OK, {"sid": sid, "owner": existing["owner"],
                                "spec": spec, "state_nbytes": nbytes,
                                "steps": existing["steps"]}
        owner = self._pick_owner(sid)
        if owner != self._me() and not self._replica():
            try:
                self._push_adopt(owner, sid, db, kind, spec, ttl_s)
            except Exception as e:  # noqa: BLE001 — placement is
                # best-effort; a dead worker falls back to local
                # ownership (the client never sees the bounce)
                self._last_place_fault = repr(e)
                owner = self._me()
        self.table.open(sid, db, kind, owner, ttl_s, home=self._me())
        if owner == self._me():
            self._install_state(sid, db, ttl_s)
        obs.REGISTRY.counter("session.opened").inc()
        self._ensure_housekeeping(ttl_s)
        return MsgType.OK, {"sid": sid, "owner": owner, "spec": spec,
                            "state_nbytes": nbytes, "steps": 0}

    def _push_adopt(self, owner: str, sid: str, db: str, kind: str,
                    spec: Dict[str, Any], ttl_s: float,
                    state: Optional[Dict[str, Any]] = None,
                    steps: int = 0) -> None:
        payload = {"op": "adopt", "sid": sid, "db": db, "kind": kind,
                   "heads": spec.get("heads"), "ttl_s": ttl_s,
                   "home": self._me(), "steps": int(steps)}
        if state is not None:
            payload["state"] = state
        with self._shipped_mu:
            shipped = (owner, db) in self._shipped
        if not shipped:
            # two concurrent opens may both ship — benign: the ingest
            # is idempotent; what must never happen is a weight-LESS
            # adopt at an owner that doesn't hold the model
            payload["weights"] = self._export_weights(db)
            if self.runtime.stores_spec(db):
                payload["spec"] = dict(spec)
        self._ctl.shards.peer_request(owner, MsgType.SESSION_OPEN,
                                      payload, codec=CODEC_PICKLE)
        with self._shipped_mu:
            self._shipped.add((owner, db))

    def forget_owner(self, addr: str) -> None:
        """Invalidate the weights-already-shipped record for one pool
        member (called by the pool's degrade/readmit bookkeeping): a
        dead or restarted worker no longer holds the model, so the
        next session placed there must ship weights again."""
        with self._shipped_mu:
            self._shipped = {e for e in self._shipped if e[0] != addr}

    def _export_weights(self, db: str) -> Dict[str, np.ndarray]:
        out = {}
        for n in self.runtime.weight_names(db):
            t = self._ctl.library.get_tensor(db, n)
            out[n] = np.array(t.data[:t.meta.shape[0],
                                     :t.meta.shape[1]])
        return out

    def _op_adopt(self, p):
        sid = str(p["sid"])
        db = str(p["db"])
        kind = str(p.get("kind", "lstm"))
        ttl_s = float(p.get("ttl_s") or self.ttl_s)
        if p.get("weights"):
            self.runtime.install_model(db, kind, p["weights"],
                                       p.get("spec"))
        self.runtime.register_model(db, kind, heads=p.get("heads"))
        self._slab(db)
        self.table.open(sid, db, kind, self._me(), ttl_s,
                        home=p.get("home"))
        self.table.set_owner(sid, self._me(), home=p.get("home"))
        steps = int(p.get("steps", 0))
        state = p.get("state")
        if state:
            self.arena.merge_state(sid, db, state["layers"],
                                   state.get("steps", steps))
            self.table.set_steps(sid, int(state.get("steps", steps)))
            self._note_applied(sid, state.get("applied"))
        elif steps == 0:
            self._install_state(sid, db, ttl_s)
        self._ensure_housekeeping(ttl_s)
        return MsgType.OK, {"sid": sid, "owner": self._me(),
                            "steps": self.table.steps(sid)}

    def _op_spill(self, p):
        sid = str(p["sid"])
        db = str(p["db"])
        state = p.get("state") or {}
        self.arena.merge_state(sid, db, state.get("layers", {}),
                               int(state.get("steps", 0)))
        self.table.set_steps(sid, int(state.get("steps", 0)))
        self._note_applied(sid, state.get("applied"))
        return MsgType.OK, {"sid": sid,
                            "steps": self.arena.steps(sid, db)}

    def _op_lookup(self, p):
        sid = str(p["sid"])
        row = self.table.get(sid)
        if row is None:
            raise SessionUnknown(f"unknown session {sid!r}")
        owner = row["owner"]
        if owner != self._me() and self._authoritative(row) \
                and owner not in self._live_workers():
            # heal: the recorded owner is gone — adopt here, revive
            # lands lazily from the arena on the next decode step
            self.table.set_owner(sid, self._me(), home=self._me())
            owner = self._me()
        elif self._replica():
            self.table.set_owner(sid, self._me())
            owner = self._me()
        return MsgType.OK, {"sid": sid, "owner": owner,
                            "steps": self.table.steps(sid)}

    def _op_move(self, p):
        """Relocate a LIVE session (the rebalance hook): pack the
        state wherever it currently is, adopt it at the target, and
        re-point the table. In-flight client steps bounce with the
        typed retryable ``SessionMoved`` and land at the target."""
        sid = str(p["sid"])
        to = str(p["to"])
        row = self.table.get(sid)
        if row is None:
            raise SessionUnknown(f"unknown session {sid!r}")
        if self._replica():  # replay: converge to self, no RPC
            self.table.set_owner(sid, self._me())
            return MsgType.OK, {"sid": sid, "owner": self._me()}
        db, kind = row["db"], row["kind"]
        if row["owner"] == self._me():
            with self._sid_lock(sid):
                state = self._pack(sid, db)
                # keep a local arena copy until the adopt lands: a
                # failed push must not leave the packed dict as the
                # state's only holder (ownership stays here on
                # failure, and the next step revives from this copy)
                self.arena.merge_state(sid, db, state["layers"],
                                       state["steps"])
                self._release(sid)
        else:
            rep = self._ctl.shards.peer_request(
                row["owner"], MsgType.SESSION_OPEN,
                {"op": "handoff", "sid": sid}, codec=CODEC_PICKLE)
            state = rep.get("state") or {"layers": {}, "steps": 0}
        if to == self._me():
            self.arena.merge_state(sid, db, state["layers"],
                                   state["steps"])
            self._note_applied(sid, state.get("applied"))
            self.table.set_owner(sid, self._me(), home=self._me())
        else:
            self._push_adopt(to, sid, db, kind,
                             self.runtime.spec(db) or {}, row["ttl_s"],
                             state=state, steps=state["steps"])
            self.table.set_owner(sid, to)
            self.arena.drop(sid)  # the adopt landed; the safety copy
            # (and any older spill) must not linger here
        self.table.set_steps(sid, int(state["steps"]))
        return MsgType.OK, {"sid": sid, "owner": to,
                            "steps": int(state["steps"])}

    def _op_handoff(self, p):
        """Old-owner half of a move: pack, then drop the local copy
        and re-point at home so late frames bounce typed."""
        sid = str(p["sid"])
        row = self.table.get(sid)
        if row is None:
            raise SessionUnknown(f"unknown session {sid!r}")
        with self._sid_lock(sid):
            state = self._pack(sid, row["db"])
            self._release(sid)
            self.arena.drop(sid)
            home = row.get("home") or self._me()
            self.table.set_owner(sid, home)
        with self._applied_mu:
            self._applied.pop(sid, None)  # shipped inside ``state``
        return MsgType.OK, {"sid": sid, "state": state}, CODEC_PICKLE

    def handle_generate(self, p: Dict[str, Any]):
        sid = str(p.get("sid") or p.get("set"))
        row = self.table.get(sid)
        if row is None:
            raise SessionUnknown(f"unknown session {sid!r}")
        owner = row["owner"]
        if owner != self._me():
            if self._replica():
                # mirror replay: the leader applied this — apply the
                # same deterministic step so the replica's state stays
                # warm, and converge ownership to self (the owner this
                # daemon must be the moment it is promoted)
                self.table.set_owner(sid, self._me())
            elif self._authoritative(row) \
                    and owner not in self._live_workers():
                # lazy adoption: the recorded owner died — this
                # daemon takes over, reviving from the arena spill
                self.table.set_owner(sid, self._me(), home=self._me())
            else:
                raise SessionMoved(
                    f"session {sid!r} is owned by {owner}",
                    owner_addr=owner)
        db = row["db"]
        # the in-flight frame's idempotency token (contextvar installed
        # by the dispatcher; local import — server imports this module)
        from netsdb_tpu.serve.server import _idem_token_var

        req = {"sid": sid, "tok": _idem_token_var.get()}
        for key in ("x", "tokens", "new_tokens"):
            if key in p:
                req[key] = p[key]
        with obs.span("session.coalesce", "serve"):
            req["trace"] = obs.capture()
            req["queued"] = time.perf_counter()
            out = self.batcher.submit(db, sid, req)
            if "retired" in req:
                # the leader answers at its iteration's end, after the
                # turns it retires after this one; then this thread
                # wakes (milliseconds on a busy host)
                _span(req["trace"], "session.turn.reply", req["retired"],
                      time.perf_counter())
        return MsgType.OK, dict(out, sid=sid,
                                owner=self._me()), CODEC_PICKLE

    def handle_close(self, p: Dict[str, Any]):
        sid = str(p.get("sid") or p.get("set"))
        row = self.table.get(sid)
        if row is None:
            return MsgType.OK, {"sid": sid, "closed": False}
        if row["owner"] != self._me() and not self._replica() \
                and row["owner"] in self._live_workers():
            try:
                self._ctl.shards.peer_request(
                    row["owner"], MsgType.SESSION_CLOSE, {"sid": sid})
            except Exception as e:  # noqa: BLE001 — the owner's
                del e  # TTL sweep collects what this forward missed
        with self._sid_lock(sid):
            dropped = self._release(sid)
            self.arena.drop(sid)
            closed = self.table.close(sid)
            name = output_set(sid)
            if self._ctl.library.set_exists(row["db"], name):
                self._ctl.library.remove_set(row["db"], name)
        with self._applied_mu:
            self._applied.pop(sid, None)
        # the per-sid lock is deliberately NOT popped: a thread that
        # already fetched the old lock object but not yet acquired it
        # would otherwise share the "exclusive" section with a holder
        # of a fresh object after a same-sid reopen. The map grows by
        # one small object per sid ever opened — the price of the
        # exclusion staying airtight.
        if closed:
            obs.REGISTRY.counter("session.closed").inc()
        return MsgType.OK, {"sid": sid, "closed": closed,
                            "dropped_entries": dropped}

    # --- housekeeping --------------------------------------------------
    def _ensure_housekeeping(self, ttl_s: float) -> None:
        with self._hk_mu:
            if self._hk_thread is not None \
                    and self._hk_thread.is_alive():
                return
            self._hk_stop.clear()
            t = threading.Thread(
                target=self._housekeeping, args=(ttl_s,),
                daemon=True, name="netsdb-session-housekeeping")
            t.start()
            self._hk_thread = t

    def _housekeeping(self, ttl_s: float) -> None:
        interval = max(0.05, min(0.25, float(ttl_s) / 4.0))
        while not self._hk_stop.wait(interval):
            try:
                self._cache().session_sweep()
            except Exception as e:  # noqa: BLE001 — next tick retries
                del e
            self._drain_spill_pushes()

    def _drain_spill_pushes(self) -> None:
        """Ship dirty arena slots of sessions whose home is another
        daemon (a worker's durability push): the home leader merges
        them — and MIRRORS the merge — so a worker death never loses
        more than the not-yet-pushed tail."""
        me = self._me()
        for sid, db in self.arena.take_dirty():
            row = self.table.get(sid)
            home = (row or {}).get("home")
            if not home or home == me:
                continue
            slot = self.arena.snapshot_slot(sid, db)
            if slot is None:
                continue
            applied = self._applied_record(sid)
            if applied is not None:
                slot["applied"] = applied
            try:
                self._ctl.shards.peer_request(
                    home, MsgType.SESSION_OPEN,
                    {"op": "spill", "sid": sid, "db": db,
                     "state": slot},
                    codec=CODEC_PICKLE)
            except Exception as e:  # noqa: BLE001 — re-mark; the
                # next housekeeping tick retries the push
                self._last_spill_fault = repr(e)
                self.arena.mark_dirty(sid, db)
                obs.REGISTRY.counter("session.spill_push_errors").inc()

    def stop(self) -> None:
        self._hk_stop.set()
        t = self._hk_thread
        if t is not None:
            t.join(timeout=2.0)

    # --- introspection -------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = {"open": self.table.count(),
               "sessions": [{k: r[k] for k in
                             ("sid", "db", "owner", "steps")}
                            for r in self.table.sessions()],
               "batcher": self.batcher.snapshot(),
               "arena": self.arena.stats(),
               "decode": _decode.decode_stats(),
               "resident_bytes":
                   self._cache().session_resident_bytes()}
        rep = self.runtime.residency_report()
        if rep.get("models"):
            out["residency"] = rep
        return out
