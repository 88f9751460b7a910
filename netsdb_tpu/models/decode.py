"""Session-serving decode workloads — batched autoregressive steps.

The stateful-serving path (``serve/sessions.py``) turns the one-shot
analytics models in this package into INTERACTIVE workloads: a client
opens a session, the session's recurrent/KV state stays resident in
the device cache between requests, and every ``GENERATE`` advances it
by one (or a few) decode steps. Per *Compiler-First State Space
Duality and Portable O(1) Autoregressive Caching* (arxiv 2603.09555),
the decode loop wants exactly two disciplines:

* **One compiled step program shared by all concurrent sessions.**
  Concurrent ``GENERATE`` requests for the same model coalesce into a
  single padded batch; batch sizes quantize onto the
  ``plan/staging.bucket_rows`` ladder, so batch churn between 1 and
  ``decode_batch_max`` live sessions re-dispatches a cached executable
  instead of retracing. :func:`decode_stats`'s ``traces`` counter is
  the proof: it stops at the number of distinct (model-shape, bucket)
  pairs.
* **O(1) per-step state.** The LSTM carries ``(h, c)``; the
  transformer layer carries a RING-BUFFER KV cache of fixed
  ``kv_max`` entries (position writes at ``pos % kv_max`` — the
  portable O(1) cache: step cost never grows with sequence length).

Every step function is ROW-INDEPENDENT: row ``i`` of the output
depends only on row ``i`` of the inputs and the (shared) weights, so
a session decoded inside a padded batch of 8 produces bit-identical
outputs to the same session decoded alone — the byte-equality gate
``tests/test_sessions.py`` enforces, and the property that lets HA
followers replay mirrored GENERATE frames solo yet converge on the
leader's exact state.

**Multi-model residency** (``config.model_dedup``): model-set ingest
here is the serve-path consumer of the ``dedup/`` package. Each
registered model's weight pages are fingerprinted with
``dedup.detector.block_fingerprints``; once two models of the same
block class are registered, the sets pool through
``Client.dedup_resident`` → ``SetStore.set_pooled`` — byte-identical
pages resident ONCE under a shared device pool, fine-tuned variants
paying only for their deltas — while :meth:`DecodeRuntime.
residency_report` splits every shared page's bytes across its
referents (``page_bytes / refcount``) so per-client attribution stays
exact: the charges sum to the pool, and no client ever pays for
another's private pages.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.dedup import detector as _detector
from netsdb_tpu.plan.staging import bucket_rows
from netsdb_tpu.utils.locks import TrackedLock

#: decode model kinds the runtime can drive. "lstm" reuses the
#: recurrent cell family of ``ops/lstm.py`` (dense, batched);
#: "transformer_layer" is one attention+FFN layer with a ring-buffer
#: KV cache (``models/transformer.py``'s shape, O(1) per step).
DECODE_KINDS = ("lstm", "transformer_layer", "hybrid_lm")

#: weight set names per kind — one store set per tensor, so the dedup
#: detector sees every fine-tuned variant's pages as ordinary
#: BlockedTensor blocks.
LSTM_WEIGHTS = ("w_i", "w_f", "w_c", "w_o",
                "u_i", "u_f", "u_c", "u_o",
                "b_i", "b_f", "b_c", "b_o")
TRANSFORMER_WEIGHTS = ("wq", "wk", "wv", "wo", "w1", "w2")

# process-global counters of the decode programs. The programs
# themselves live in the executor's ONE compiled-program LRU
# (``plan/executor.cached_jit``), so that a trace of a step, prefill
# or slot program ticks the same ``compile.misses`` every other
# compile ticks — the counter a benchmark's ``compiles_in_window``
# reads. (serve/ cannot host this — the scatter-jit-route rule keeps
# compile caches out of the serve layer — so the decode programs live
# with the models they serve.)
_stats = {"traces": 0, "batches": 0, "steps": 0, "pad_rows": 0}
_mu = threading.Lock()
_PROGRAM_PREFIX = "decode::"


def decode_stats() -> Dict[str, int]:
    """Snapshot of the decode counters — ``traces`` counts actual jit
    traces of step and prefill programs (the one-program-per-shape
    proof), ``batches``/``steps``/``pad_rows`` the coalescing
    efficiency (``steps`` live rows, ``pad_rows`` idle rows of the
    dispatched programs)."""
    from netsdb_tpu.plan import executor as _executor

    with _mu:
        out = dict(_stats)
    out["programs"] = sum(k.startswith(_PROGRAM_PREFIX)
                          for k in _executor.compiled_cache_keys())
    return out


def clear_decode_programs() -> None:
    """Drop every cached decode program and zero the counters (test
    isolation — mirrors ``plan/executor.clear_compiled_cache``)."""
    from netsdb_tpu.plan import executor as _executor

    _executor.drop_compiled(_PROGRAM_PREFIX)
    with _mu:
        for k in _stats:
            _stats[k] = 0


obs.REGISTRY.register_collector("decode", decode_stats)


def decode_bucket(n: int) -> int:
    """The slab's slot count for ``n`` resident sessions — the
    ``bucket_rows`` ladder (floor 8, {2^k, 3·2^(k-1)} rungs). A step
    program runs over every slot of the slab (row = slot, idle rows
    masked), so one model has ONE step program however its live
    session count churns."""
    return bucket_rows(int(n))


def _program(key: Tuple, build: Callable, donate: Tuple[int, ...] = (),
             counted: bool = True,
             xla_options: Optional[Dict[str, str]] = None) -> Callable:
    """The jitted program for ``key``, tracing at most once per key
    while it stays in the executor's LRU. The trace counter ticks
    inside the traced python body — it runs at trace time only, so
    ``traces`` counts compilations, not dispatches. ``build`` keeps its
    ``__name__``: it is the XLA module's name in a device trace.
    ``xla_options`` are compiler options a model's spec asks for
    (``spec["xla_options"]``); none by default."""
    from netsdb_tpu.plan import executor as _executor

    def traced(*args):
        if counted:
            with _mu:
                _stats["traces"] += 1
        return build(*args)

    return _executor.cached_jit(
        _PROGRAM_PREFIX + repr(key), traced, donate_argnums=donate,
        name=getattr(build, "__name__", "decode_program"),
        compiler_options=xla_options)


def _put(*values) -> list:
    """Host values on the device, one ``device_put`` each, ahead of the
    program's dispatch; the same avals, so the same program. This is for
    a profiler's sake, as ``serve/sessions.py::_harvest``'s poll is: a
    dispatch copies its host arguments inside its own host event, and a
    copy takes 0.5 to 0.8 ms on a v5e while the chip is busy, so a
    prefill chunk's dispatch (four of them) was one event of 1.2 to
    4 ms. The benchmark's reader of idle gaps walks every host event over
    1 ms for every gap, and a traced window of 64 sessions took 731 s to
    reduce (chip runs; PERF.md section 7)."""
    import jax

    return [jax.device_put(v) for v in values]


# --- slot programs: one slot of a slab, by the layout's slot axis -----

def _slot_index(layout_entry, slot):
    return (slice(None),) * layout_entry["slot_axis"] + (slot,)


def _zero_slot(layout):
    def decode_zero_slot(slab, slot):
        return {name: (arr.at[_slot_index(layout[name], slot)].set(0)
                       if layout[name]["reset"] else arr)
                for name, arr in slab.items()}
    return decode_zero_slot


def _read_slot(layout):
    def decode_read_slot(slab, slot):
        return {name: arr[_slot_index(layout[name], slot)]
                for name, arr in slab.items()}
    return decode_read_slot


def _write_slot(layout):
    def decode_write_slot(slab, slot, values):
        return {name: arr.at[_slot_index(layout[name], slot)].set(
                    values[name].astype(arr.dtype))
                for name, arr in slab.items()}
    return decode_write_slot


# --- step functions (row-independent by construction) -----------------

def _lstm_step(params, h, c, x):
    """One batched LSTM cell step: ``(B, hidden) x (B, in)`` →
    ``(h', c')``. Dense weights (``w``: hidden×in, ``u``:
    hidden×hidden, ``b``: hidden) — the ops/lstm.py gate algebra on a
    session batch axis."""
    import jax.numpy as jnp

    def gate(name, act):
        z = (x @ params["w_" + name].T + h @ params["u_" + name].T
             + params["b_" + name].reshape(-1))
        return act(z)

    import jax.nn as jnn
    i = gate("i", jnn.sigmoid)
    f = gate("f", jnn.sigmoid)
    g = gate("c", jnp.tanh)
    o = gate("o", jnn.sigmoid)
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return h2, c2


def _transformer_step(params, k_cache, v_cache, pos, x, heads, active):
    """One batched transformer-layer decode step with a ring-buffer KV
    cache: write this step's k/v at ``pos % kv_max`` per live row (a
    scatter of one cache row a slot; an idle row's write is dropped),
    attend over the ``min(pos+1, kv_max)`` live entries, add the FFN.
    All ops are per-row, so batch composition never changes any single
    session's bits."""
    import jax.nn as jnn
    import jax.numpy as jnp

    kv_max = k_cache.shape[1]
    embed = x.shape[-1]
    dh = embed // heads
    q = x @ params["wq"].T
    k = x @ params["wk"].T
    v = x @ params["wv"].T
    rows = jnp.arange(k_cache.shape[0])
    at = jnp.where(active, pos % kv_max, kv_max)   # out of range: dropped
    k_cache2 = k_cache.at[rows, at].set(k, mode="drop")
    v_cache2 = v_cache.at[rows, at].set(v, mode="drop")
    live = jnp.minimum(pos + 1, kv_max)  # (B,) valid cache entries
    mask = jnp.arange(kv_max)[None, :] < live[:, None]  # (B, T)
    qh = q.reshape(-1, heads, dh)
    kh = k_cache2.reshape(-1, kv_max, heads, dh)
    vh = v_cache2.reshape(-1, kv_max, heads, dh)
    scores = jnp.einsum("bhd,bthd->bht", qh, kh) / jnp.sqrt(
        jnp.asarray(dh, x.dtype))
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    attn = jnn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,bthd->bhd", attn, vh).reshape(-1, embed)
    y = x + ctx @ params["wo"].T
    ff = jnn.relu(y @ params["w1"].T) @ params["w2"].T
    return k_cache2, v_cache2, jnp.where(active, pos + 1, pos), y + ff


# --- the decode kinds: what each declares to the one state path -------
#
# A kind names its weight sets, derives its spec from the database, and
# declares its session state as a LAYOUT: {array: shape with the slot
# axis in place, dtype, slot_axis, reset}. Everything that moves state
# (the slab in the device cache, spill, revive, move, handoff) works
# from the layout alone. ``step(params, slab, active, x)`` advances
# every slot whose ``active`` is set (row = slot) and returns the new
# slab and the outputs; a kind with ``prefill`` also consumes a chunk
# of ids into one slot. A kind also says how its sets are stored
# (``block_for``, ``stored``) and whether the database holds its spec
# (``stores_spec``: then ``read_spec`` / ``write_spec``), so that the
# serve layer asks the kind and never names one.

class _ToyKind:
    takes_x = True
    #: the spec is derived from the weight shapes and the daemon's
    #: ``kv_max`` / slots, and the daemon's state cap binds a session
    stores_spec = False

    def block_for(self, shape):
        return (32, 1) if shape[1] == 1 else (32, 32)

    def stored(self, w):
        return np.asarray(w, np.float32)


class _LstmKind(_ToyKind):
    name = "lstm"

    def weights(self, spec=None):
        return LSTM_WEIGHTS

    def layout(self, spec):
        s, h = spec["slots"], spec["hidden"]
        return {"h": {"shape": (s, h), "dtype": "float32",
                      "slot_axis": 0, "reset": True},
                "c": {"shape": (s, h), "dtype": "float32",
                      "slot_axis": 0, "reset": True}}

    def build_step(self, spec):
        def decode_lstm_step(params, slab, active, x):
            import jax.numpy as jnp

            h2, c2 = _lstm_step(params, slab["h"], slab["c"], x)
            keep = active[:, None]
            return ({"h": jnp.where(keep, h2, slab["h"]),
                     "c": jnp.where(keep, c2, slab["c"])}, {"y": h2})
        return decode_lstm_step


class _TransformerKind(_ToyKind):
    name = "transformer_layer"

    def weights(self, spec=None):
        return TRANSFORMER_WEIGHTS

    def layout(self, spec):
        s, h, kv = spec["slots"], spec["hidden"], spec["kv_max"]
        cache = {"shape": (s, kv, h), "dtype": "float32", "slot_axis": 0,
                 "reset": True}
        return {"k": dict(cache), "v": dict(cache),
                "pos": {"shape": (s,), "dtype": "int32", "slot_axis": 0,
                        "reset": True}}

    def build_step(self, spec):
        heads = spec["heads"]

        def decode_transformer_step(params, slab, active, x):
            k2, v2, pos2, y = _transformer_step(
                params, slab["k"], slab["v"], slab["pos"], x, heads,
                active)
            return {"k": k2, "v": v2, "pos": pos2}, {"y": y}
        return decode_transformer_step


class _HybridKind:
    name = "hybrid_lm"
    takes_x = False
    #: the model's database holds its spec (slots, cache tokens, ...):
    #: the device cache's leases bound its state, not the daemon's cap
    stores_spec = True

    def block_for(self, shape):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.block_for(shape)

    def stored(self, w):
        return np.asarray(w)

    def read_spec(self, library, db):
        from netsdb_tpu.models import hybrid_lm

        return dict(next(iter(library.get_set_iterator(
            db, hybrid_lm.SPEC_SET))))

    def write_spec(self, library, db, spec):
        from netsdb_tpu.models import hybrid_lm

        if not library.set_exists(db, hybrid_lm.SPEC_SET):
            library.create_set(db, hybrid_lm.SPEC_SET)
        library.clear_set(db, hybrid_lm.SPEC_SET)
        library.send_data(db, hybrid_lm.SPEC_SET, [dict(spec)])

    def weights(self, spec):
        from netsdb_tpu.models import hybrid_lm

        return tuple(hybrid_lm.weight_shapes(spec))

    def layout(self, spec):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.state_layout(spec)

    def build_step(self, spec):
        from netsdb_tpu.models import hybrid_lm

        inner = hybrid_lm.build_step(spec)

        def hybrid_lm_step(params, slab, active, x):
            del x
            slab, ids, logits = inner(params, slab, active)
            return slab, {"ids": ids, "logits": logits}
        return hybrid_lm_step

    def build_prefill(self, spec, chunk):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.build_prefill(spec, chunk)

    def plan_prefill(self, spec, n_tokens):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.plan_chunks(spec, n_tokens)

    def cache_rows_read(self, spec, lengths):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.cache_rows_read(spec, lengths)

    def prefill_blocks_read(self, spec, chunk, pos0, n_valid):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.prefill_blocks_read(spec, chunk, pos0, n_valid)

    def step_counts(self, spec):
        from netsdb_tpu.models import hybrid_lm

        return hybrid_lm.step_counts(spec)


_KINDS = {k.name: k for k in (_LstmKind(), _TransformerKind(),
                              _HybridKind())}


# --- model deployment (the ingest path the dedup detector watches) ----

def _gen_dense(kind: str, hidden: int, heads: int,
               rng: "np.random.Generator") -> Dict[str, np.ndarray]:
    scale = 1.0 / np.sqrt(hidden)
    out: Dict[str, np.ndarray] = {}
    if kind == "lstm":
        for name in LSTM_WEIGHTS:
            if name.startswith("b_"):
                out[name] = np.zeros((hidden, 1), np.float32)
            else:
                out[name] = (rng.standard_normal((hidden, hidden))
                             * scale).astype(np.float32)
    else:
        ffn = 2 * hidden
        for name in ("wq", "wk", "wv", "wo"):
            out[name] = (rng.standard_normal((hidden, hidden))
                         * scale).astype(np.float32)
        out["w1"] = (rng.standard_normal((ffn, hidden))
                     * scale).astype(np.float32)
        out["w2"] = (rng.standard_normal((hidden, ffn))
                     * scale).astype(np.float32)
    return out


def deploy_decode_model(client, db: str, *, kind: str = "lstm",
                        hidden: int = 64, heads: int = 4,
                        seed: int = 0, base_seed: Optional[int] = None,
                        finetune_frac: float = 0.25,
                        block: Tuple[int, int] = (32, 32)) -> Dict:
    """Create ``db`` and load one decode model's weight sets.

    ``base_seed`` models FINE-TUNING: weights generate from the base
    seed, then ``finetune_frac`` of each tensor's block-grid tiles
    (chosen by ``seed``) are perturbed — two variants deployed from
    one base share exactly ``1 - finetune_frac`` of their weight
    pages bit-identically, the sharing the dedup detector collapses.
    Returns the model spec the server's SESSION_OPEN consumes."""
    if kind not in DECODE_KINDS:
        raise ValueError(f"kind must be one of {DECODE_KINDS}, "
                         f"got {kind!r}")
    rng = np.random.default_rng(base_seed if base_seed is not None
                                else seed)
    dense = _gen_dense(kind, hidden, heads, rng)
    if base_seed is not None:
        tune = np.random.default_rng(seed)
        for name, w in dense.items():
            if w.shape[1] == 1:
                continue  # biases stay shared
            bh, bw = block
            gh = max(1, w.shape[0] // bh)
            gw = max(1, w.shape[1] // bw)
            n_tiles = gh * gw
            picked = tune.choice(n_tiles,
                                 size=max(1, int(finetune_frac
                                                 * n_tiles)),
                                 replace=False)
            for t in picked:
                i, j = divmod(int(t), gw)
                w[i * bh:(i + 1) * bh, j * bw:(j + 1) * bw] += (
                    tune.standard_normal((min(bh, w.shape[0] - i * bh),
                                          min(bw, w.shape[1] - j * bw)))
                    * 0.01).astype(np.float32)
    client.create_database(db)
    for name, w in dense.items():
        client.create_set(db, name, type_name="matrix")
        shape = (block[0], 1) if w.shape[1] == 1 else tuple(block)
        client.send_matrix(db, name, w, block_shape=shape)
    return {"kind": kind, "hidden": int(hidden), "heads": int(heads)}


# --- the per-daemon decode runtime ------------------------------------

class DecodeRuntime:
    """Per-daemon model registry + the programs over a model's slab.

    Owns the registration of every decode model (the dense view of its
    weight sets, which IS the stored array when the set's blocks divide
    its shape — one copy of the weights in device memory; fingerprinted
    and shared-pooled only under ``model_dedup``) and runs the step,
    prefill and slot programs. Stateless with respect to SESSIONS — the
    slab (``storage/devcache.SessionSlab``) and which session holds
    which slot live with ``serve/sessions.py``; every method here maps
    ``(slab arrays, ...) → (slab arrays', outputs)`` and donates the
    arrays it is given."""

    def __init__(self, library, *, model_dedup: bool = False,
                 kv_max: int = 64, dedup_bands: int = 16,
                 slots: int = 8):
        self._library = library
        self._model_dedup = bool(model_dedup)
        self._kv_max = int(kv_max)
        self._dedup_bands = int(dedup_bands)
        self._slots = decode_bucket(slots)
        self._mu = TrackedLock("DecodeRuntime._mu")
        # db -> {"spec", "kind" (_KINDS entry), "layout", "params"
        #        (device dense), "client",
        #        "fps" {(set, idx): hash}, "page_bytes" {hash: nbytes}}
        self._models: Dict[str, Dict[str, Any]] = {}
        self._dedup_report: Optional[Dict[str, Any]] = None

    # -- registration / residency -------------------------------------
    def register_model(self, db: str, kind: str,
                       client: Optional[str] = None,
                       heads: Optional[int] = None) -> Dict[str, Any]:
        """Register ``db``'s weight sets (idempotent). The spec comes
        from the database: a kind that stores one (the record of its
        ``spec`` set) is read from there, the others derive theirs from
        the weight shapes. With ``model_dedup`` every weight page is
        fingerprinted with ``dedup.detector`` and, once a second model
        is registered, ALL registered models' sets re-pool through
        ``Client.dedup_resident`` so shared pages install once."""
        with self._mu:
            reg = self._models.get(db)
            if reg is not None:
                return reg["spec"]
        if kind not in _KINDS:
            raise ValueError(f"unknown decode kind {kind!r}")
        impl = _KINDS[kind]
        if impl.stores_spec:
            spec = impl.read_spec(self._library, db)
        else:
            spec = {"kind": kind, "heads": int(heads or 4),
                    "kv_max": self._kv_max, "slots": self._slots}
        names = impl.weights(spec)
        tensors = {n: self._library.get_tensor(db, n) for n in names}
        if "hidden" not in spec:
            spec["hidden"] = int(tensors[names[0]].meta.shape[0])
        fps: Dict[Tuple[str, tuple], str] = {}
        page_bytes: Dict[str, int] = {}
        if self._model_dedup:
            for n, t in tensors.items():
                for idx, h in _detector.block_fingerprints(t).items():
                    fps[(n, idx)] = h
                    bh, bw = t.meta.block_shape
                    page_bytes[h] = bh * bw * t.data.dtype.itemsize
        params = {n: _dense_view(t) for n, t in tensors.items()}
        with self._mu:
            self._models[db] = {"spec": spec, "kind": impl,
                                "layout": impl.layout(spec),
                                "params": params,
                                "client": client, "fps": fps,
                                "page_bytes": page_bytes}
            pool_now = (self._model_dedup and len(self._models) > 1)
            dbs = list(self._models)
        if pool_now:
            sets = [(d, n) for d in dbs
                    for n in self._weight_names(d)]
            report = self._library.dedup_resident(
                sets, bands=self._dedup_bands)
            with self._mu:
                self._dedup_report = report
            obs.REGISTRY.gauge("dedup.page_bytes").set(
                int(report.get("hbm_bytes_pooled", 0)))
        return spec

    def install_model(self, db: str, kind: str,
                      weights: Dict[str, np.ndarray],
                      spec: Optional[Dict[str, Any]] = None) -> None:
        """Ingest shipped dense weights (and the spec, for a kind whose
        database holds one) through this daemon's OWN library
        (create_set + send_matrix), so that ``register_model`` walks the
        same store path here as at the daemon that shipped them:
        fingerprints, and the dedup pooling, trigger alike."""
        impl = _KINDS[kind]
        lib = self._library
        try:
            lib.create_database(db)
        except Exception as e:  # noqa: BLE001 — exists
            del e
        if impl.stores_spec:
            impl.write_spec(lib, db, spec)
        for name, w in weights.items():
            w = impl.stored(w)
            if w.ndim == 1:
                w = w.reshape(-1, 1)
            if not lib.set_exists(db, name):
                lib.create_set(db, name, type_name="matrix")
            lib.send_matrix(db, name, w, block_shape=impl.block_for(w.shape))

    def stores_spec(self, db: str) -> bool:
        """Whether ``db``'s spec is a record of the database (and goes
        with the weights when they are shipped)."""
        return bool(self._reg(db)["kind"].stores_spec)

    def block_for(self, db: str, shape) -> Tuple[int, int]:
        """The block shape ``db``'s kind stores a set of ``shape`` in."""
        return self._reg(db)["kind"].block_for(tuple(shape))

    def weight_names(self, db: str) -> Sequence[str]:
        with self._mu:
            return self._weight_names(db)

    def _weight_names(self, db: str) -> Sequence[str]:
        reg = self._models[db]
        return reg["kind"].weights(reg["spec"])

    def spec(self, db: str) -> Optional[Dict[str, Any]]:
        with self._mu:
            reg = self._models.get(db)
            return dict(reg["spec"]) if reg else None

    def drop_model(self, db: str) -> bool:
        with self._mu:
            return self._models.pop(db, None) is not None

    def residency_report(self) -> Dict[str, Any]:
        """Exact multi-model residency accounting. ``charged`` splits
        every page's bytes across the models referencing it
        (``page_bytes / refcount``) and rolls up per client — the
        charges sum to the unique-page total, so attribution stays
        exact under any degree of sharing."""
        with self._mu:
            refs: Dict[str, int] = {}
            for reg in self._models.values():
                for h in set(reg["fps"].values()):
                    refs[h] = refs.get(h, 0) + 1
            charged: Dict[str, float] = {}
            by_model: Dict[str, float] = {}
            unique_bytes = 0
            sized: Dict[str, int] = {}
            for reg in self._models.values():
                sized.update(reg["page_bytes"])
            for h, n in refs.items():
                unique_bytes += sized.get(h, 0)
            for db, reg in self._models.items():
                share = sum(sized.get(h, 0) / refs[h]
                            for h in set(reg["fps"].values()))
                by_model[db] = share
                who = reg.get("client") or db
                charged[who] = charged.get(who, 0.0) + share
            out = {
                "models": len(self._models),
                "unique_page_bytes": int(unique_bytes),
                "total_page_bytes": int(sum(
                    sum(sized.get(h, 0)
                        for h in set(reg["fps"].values()))
                    for reg in self._models.values())),
                "charged_bytes": {k: int(round(v))
                                  for k, v in charged.items()},
                "charged_by_model": {k: int(round(v))
                                     for k, v in by_model.items()},
                "model_dedup": self._model_dedup,
            }
            if self._dedup_report is not None:
                out["pool"] = dict(self._dedup_report)
        return out

    # -- the slab: layout, allocation, slot programs --------------------
    def _reg(self, db: str) -> Dict[str, Any]:
        with self._mu:
            reg = self._models.get(db)
        if reg is None:
            raise KeyError(f"model {db!r} not registered")
        return reg

    def state_layout(self, db: str) -> Dict[str, Dict[str, Any]]:
        """{array: {shape (slot axis in place), dtype, slot_axis,
        reset}} of ``db``'s slab."""
        return self._reg(db)["layout"]

    def slots(self, db: str) -> int:
        return int(self._reg(db)["spec"]["slots"])

    def slot_nbytes(self, db: str) -> int:
        """Bytes of ONE slot across the slab's arrays."""
        return sum(int(np.prod(e["shape"])) * np.dtype(_np_dtype(
            e["dtype"])).itemsize // e["shape"][e["slot_axis"]]
            for e in self.state_layout(db).values())

    def new_slab(self, db: str) -> Dict[str, Any]:
        """Zeroed device arrays of ``db``'s layout."""
        import jax.numpy as jnp

        return {name: jnp.zeros(e["shape"], e["dtype"])
                for name, e in self.state_layout(db).items()}

    def _slot_program(self, db: str, which: str, build, donate=()):
        layout = self.state_layout(db)
        sig = tuple((n, e["shape"], e["dtype"], e["slot_axis"],
                     e["reset"]) for n, e in sorted(layout.items()))
        return _program((which, sig), build(layout), donate=donate,
                        counted=False)

    def zero_slot(self, db: str, arrays, slot: int):
        """A fresh session's state in ``slot`` (arrays donated)."""
        return self._slot_program(db, "zero", _zero_slot, (0,))(
            arrays, np.int32(slot))

    def read_slot(self, db: str, arrays, slot: int
                  ) -> Dict[str, np.ndarray]:
        """One slot's slices, copied to the host."""
        out = self._slot_program(db, "read", _read_slot)(
            arrays, np.int32(slot))
        return {name: np.array(v) for name, v in out.items()}

    def write_slot(self, db: str, arrays, slot: int,
                   values: Dict[str, np.ndarray]):
        """Host slices into ``slot`` (arrays donated)."""
        layout = self.state_layout(db)
        vals = {n: np.asarray(values[n], _np_dtype(layout[n]["dtype"]))
                for n in layout}
        return self._slot_program(db, "write", _write_slot, (0,))(
            arrays, np.int32(slot), vals)

    # -- the step and prefill programs -----------------------------------
    def takes_x(self, db: str) -> bool:
        """Whether a step takes an input row a session (the toy kinds)
        or reads its input from the slab (a language model's ``tok``)."""
        return bool(self._reg(db)["kind"].takes_x)

    def step(self, db: str, arrays, active: np.ndarray,
             xs: Optional[np.ndarray] = None
             ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Advance every slot whose ``active`` is set by ONE step in a
        single program dispatch over the whole slab (row = slot, so no
        state is gathered, scattered or stacked, on the host or the
        device; arrays donated). Returns the new arrays and the
        outputs, both device arrays, rows indexed by slot. Row
        independence makes a session's result bit-equal to a solo run."""
        reg = self._reg(db)
        spec = reg["spec"]
        active = np.asarray(active, bool)
        if reg["kind"].takes_x:
            xs = np.asarray(xs, np.float32)
        else:
            xs = np.zeros((), np.float32)
        key = ("step", spec["kind"], _spec_sig(spec))
        fn = _program(key, reg["kind"].build_step(spec), donate=(1,),
                      xla_options=spec.get("xla_options"))
        new, outs = fn(reg["params"], arrays, *_put(active, xs))
        live = int(active.sum())
        with _mu:
            _stats["batches"] += 1
            _stats["steps"] += live
            _stats["pad_rows"] += len(active) - live
        return new, outs

    def plan_prefill(self, db: str, n_tokens: int):
        """[(chunk length, tokens that count)] covering ``n_tokens``."""
        reg = self._reg(db)
        return reg["kind"].plan_prefill(reg["spec"], n_tokens)

    def cache_rows_read(self, db: str, lengths) -> Tuple[int, int]:
        """(rows fetched, rows held) of a language model's key/value
        caches by one step whose live slots see ``lengths`` keys."""
        reg = self._reg(db)
        return reg["kind"].cache_rows_read(reg["spec"], lengths)

    def prefill_blocks_read(self, db: str, chunk: int, pos0: int,
                            n_valid: int) -> Tuple[int, int]:
        """(key blocks read, key blocks held) by the attention of one
        prefill chunk of a language model onto a slot at ``pos0``
        tokens."""
        reg = self._reg(db)
        return reg["kind"].prefill_blocks_read(reg["spec"], chunk, pos0,
                                               n_valid)

    def step_counts(self, db: str) -> Dict[str, int]:
        """What a step of ``db`` returns after its slots' ids, by name,
        and ``experts_held``, the held experts its expert layers walk a
        step ({} for a model without expert layers)."""
        reg = self._reg(db)
        counts = getattr(reg["kind"], "step_counts", None)
        return counts(reg["spec"]) if counts else {}

    def prefill(self, db: str, arrays, slot: int, tokens: np.ndarray,
                n_valid: int, next_tok: int):
        """One slot consumes ``n_valid`` of the ``len(tokens)`` ids (a
        length of the spec's ``prefill_chunks``; arrays donated)."""
        reg = self._reg(db)
        spec = reg["spec"]
        chunk = int(len(tokens))
        key = ("prefill", spec["kind"], _spec_sig(spec), chunk)
        fn = _program(key, reg["kind"].build_prefill(spec, chunk),
                      donate=(1,),
                      xla_options=spec.get("xla_options"))
        return fn(reg["params"], arrays,
                  *_put(np.int32(slot), np.asarray(tokens, np.int32),
                        np.int32(n_valid), np.int32(next_tok)))

    def row_of(self, outputs, slot: int):
        """Row ``slot`` of a step's output, (1, width), on the device."""
        fn = _program(("row", tuple(outputs.shape), str(outputs.dtype)),
                      _row_of, counted=False)
        return fn(outputs, np.int32(slot))

    def solo_session(self, db: str) -> "SoloSession":
        """One session alone on a fresh slab of this runtime — the
        unbatched twin the byte-equality gates replay against."""
        return SoloSession(self, db)


class SoloSession:
    """A session in slot 0 of a slab of its own (no daemon, no cache)."""

    def __init__(self, runtime: DecodeRuntime, db: str):
        self._rt = runtime
        self._db = db
        self._arrays = runtime.new_slab(db)
        self._active = np.zeros(runtime.slots(db), bool)
        self._active[0] = True

    def step(self, x) -> np.ndarray:
        """One step on input row ``x``; the output row."""
        hidden = self._rt.spec(self._db)["hidden"]
        xs = np.zeros((len(self._active), hidden), np.float32)
        xs[0] = np.asarray(x, np.float32)
        self._arrays, outs = self._rt.step(self._db, self._arrays,
                                           self._active, xs)
        return np.asarray(outs["y"][0])


def _row_of(outputs, slot):
    from jax import lax

    return lax.dynamic_slice_in_dim(outputs, slot, 1, axis=0)


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(name)


def _spec_sig(spec: Dict[str, Any]) -> Tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in spec.items()))


def _dense_view(t):
    """The dense array of a stored tensor: the stored array itself when
    the set's blocks divide its shape (no second copy in device
    memory), else the unpadded window of it."""
    if tuple(t.data.shape) == tuple(t.meta.shape):
        return t.data
    return t.data[tuple(slice(0, n) for n in t.meta.shape)]
