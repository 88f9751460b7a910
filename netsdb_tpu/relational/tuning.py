"""Measured physical-strategy thresholds, keyed on device kind.

Round-1 froze two crossovers as constants measured once on TPU v5e
(`_DENSE_SEGMENT_LIMIT = 64`, LUT-always joins). This module makes the
thresholds a two-level lookup:

1. a persisted autotune file (``$NETSDB_TPU_HOME/autotune.json``),
   written by :func:`autotune` after actually measuring the crossovers
   on the live backend;
2. a built-in table of measured values per device kind.

A device kind with no built-in table is an error, not a default: a
planner silently running another chip's crossovers hides the device.

The reference's analogue is the compile-time ``-D`` knobs in
``SConstruct:67-100`` (batch sizes, join ratios) that its authors
measured on their cluster and froze; here the same numbers re-measure
themselves per device generation.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Measured tables. "segment_dense_limit": largest group count where the
# broadcast-compare dense segment reduce still beats the scatter-add
# (measured on Q01-shaped data: 6M rows). "join_lut_factor": LUT join
# wins while key_space <= factor * (build_rows + probe_rows); beyond it
# the LUT is mostly padding and the sort path's N log N beats the
# key_space-sized init+scatter. "join_lut_max_bytes": absolute LUT size
# cap so a pathological key range cannot OOM HBM.
_MEASURED: Dict[str, Dict[str, float]] = {
    # v5e, measured via `python -m netsdb_tpu autotune` with scan-slope
    # timing: scatter serializes on colliding updates (55.7 ms vs
    # below-noise dense at 12 groups / 6M rows), and dense keeps
    # winning through the whole measured range (G<=512 @1M rows). The
    # LUT join wins through a 64x-sparse key space (gathers stream;
    # sort+searchsorted serializes); the byte cap retires it beyond.
    "TPU v5 lite": {"segment_dense_limit": 512, "join_lut_factor": 64.0,
                    "join_lut_max_bytes": 1 << 28,
                    # grid one-hot count beats scatter up to 256k groups
                    # (0.67 vs 6.9 ms at 50k; linear in G/128 — kernels.py)
                    "count_grid_limit": float(1 << 18)},
    # CPU (tests, virtual mesh): XLA's CPU scatter is cheap and the
    # dense O(N*G) pass loses earlier. The CPU backend reports no
    # memory limit, so device_hbm_bytes models a test-mesh host share
    # for broadcast-vs-repartition planning (relational/planner.py).
    "cpu": {"segment_dense_limit": 32, "join_lut_factor": 16.0,
            "join_lut_max_bytes": 1 << 27,
            "count_grid_limit": float(1 << 18),
            "device_hbm_bytes": 4 * 1024**3},
}

_cache: Dict[str, Dict[str, float]] = {}


def _tuning_path() -> str:
    root = os.environ.get("NETSDB_TPU_HOME", "/tmp/netsdb_tpu")
    return os.path.join(root, "autotune.json")


def device_kind() -> str:
    return jax.devices()[0].device_kind


def _load(kind: str) -> Dict[str, float]:
    if kind in _cache:
        return _cache[kind]
    if kind not in _MEASURED:
        raise LookupError(
            f"no measured planner thresholds for device kind {kind!r} "
            f"(known: {sorted(_MEASURED)}); measure them with `python -m "
            f"netsdb_tpu autotune` and add a table to "
            f"relational/tuning._MEASURED")
    table = dict(_MEASURED[kind])
    try:
        with open(_tuning_path()) as f:
            persisted = json.load(f)
        table.update(persisted.get(kind, {}))
    except (OSError, ValueError):
        pass
    _cache[kind] = table
    return table


def get(name: str, kind: Optional[str] = None) -> float:
    """Threshold ``name`` for ``kind`` (default: the live backend)."""
    return _load(kind or device_kind())[name]


def set_override(name: str, value: float,
                 kind: Optional[str] = None) -> None:
    """In-process override (tests force strategies through this).

    Thresholds are read at TRACE time, so already-compiled programs
    have the old choice baked in — clear jit caches so the next call
    re-traces under the new threshold.
    """
    kind = kind or device_kind()
    _load(kind)[name] = value
    jax.clear_caches()


def clear_overrides() -> None:
    _cache.clear()
    jax.clear_caches()


# --------------------------------------------------------------- autotune

def _scan_time(step_fn, lo: int = 8, hi: int = 64) -> Optional[float]:
    """Seconds/iteration of ``step_fn(carry) -> carry`` folded inside
    ONE jitted lax.scan (`utils.timing.scan_slope_seconds`): loop
    lengths escalate until the delta clears host timing noise. ``step_fn`` must
    thread a live int32 carry through the computation so XLA can
    neither hoist nor DCE the body. Returns None when the kernel is
    below timing noise even after escalation."""
    import functools

    from netsdb_tpu.utils.timing import device_seconds

    @functools.partial(jax.jit, static_argnums=(0,))
    def loop(n):
        def step(c, _):
            return step_fn(c), None

        c, _ = jax.lax.scan(step, jnp.zeros((), jnp.int32), None, length=n)
        return c

    # autotune sweeps dozens of (strategy, size) points and each
    # escalation recompiles two loop lengths — cap the retries and
    # accept a coarser delta than the default. Per-dispatch walls
    # are not used: sub-millisecond kernels drown in dispatch overhead.
    return device_seconds(lambda n: float(loop(n)), lo=lo, hi=hi,
                          repeats=2, max_escalations=2,
                          min_delta_seconds=0.1)


def _faster(ta: Optional[float], tb: Optional[float]) -> Optional[bool]:
    """Compare two `_scan_time` results where None means BELOW NOISE —
    i.e. faster than the measurement floor, which must count as a WIN,
    not a failure (treating it as undecidable once made autotune record
    'dense never wins' for the strategy that was too fast to time).
    Returns None only when both sides are below noise (undecidable)."""
    if ta is None and tb is None:
        return None
    if ta is None:
        return True
    if tb is None:
        return False
    return ta <= tb


def measure_segment_crossover(n_rows: int = 1 << 20,
                              candidates=(8, 16, 32, 64, 128, 256, 512),
                              ) -> Optional[int]:
    """Measure the dense-vs-scatter segment-sum crossover on the live
    backend: the largest G where dense still wins. 0 means dense LOST
    at the smallest candidate; None means nothing was decidable (both
    strategies below timing noise everywhere) — callers must keep their
    prior threshold rather than record "never wins"."""
    from netsdb_tpu.relational import kernels as K

    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal(n_rows).astype(np.float32))
    best = 0
    for g in candidates:
        seg = jnp.asarray(rng.integers(0, g, n_rows).astype(np.int32))

        def step(method):
            def run(c):
                s_ = (seg + c) % g  # carry-coupled: no hoisting
                out = K.segment_sum(vals, s_, g, method=method)
                return (c + out[0].astype(jnp.int32)) % 127

            return run

        win = _faster(_scan_time(step("dense")), _scan_time(step("scatter")))
        if win is None:
            if best == 0:
                return None  # nothing decidable: caller keeps prior value
            break  # keep the last decidable crossover
        if win:
            best = g
        else:
            break
    # best == 0 ⇒ dense LOST at the smallest G (decided): record "never"
    return best


def measure_count_grid_crossover(n_rows: int = 1 << 20,
                                 candidates=(1 << 12, 1 << 14, 1 << 16,
                                             1 << 18, 1 << 20),
                                 ) -> Optional[int]:
    """Measure the grid-vs-scatter segment-count crossover: the largest
    group count where the one-hot int8 MXU grid formulation still beats
    the scatter-add (`kernels.count_grid`)."""
    from netsdb_tpu.relational import kernels as K

    rng = np.random.default_rng(0)
    best = 0
    for g in candidates:
        seg = jnp.asarray(rng.integers(0, g, n_rows).astype(np.int32))

        def step(method):
            def run(c):
                s_ = (seg + c) % g  # carry-coupled: no hoisting
                out = K.segment_count(s_, g, method=method)
                return (c + out[0]) % 127

            return run

        win = _faster(_scan_time(step("grid")), _scan_time(step("scatter")))
        if win is None:
            if best == 0:
                return None  # undecidable ≠ "grid never wins"
            break
        if win:
            best = g
        else:
            break
    return best


def measure_join_crossover(n_build: int = 1 << 17, n_probe: int = 1 << 19,
                           factors=(2, 4, 8, 16, 32, 64, 128),
                           ) -> Optional[float]:
    """Measure the LUT-vs-sort join crossover: the largest
    ``key_space / (build + probe)`` ratio where the LUT still wins."""
    from netsdb_tpu.relational import kernels as K
    from netsdb_tpu.relational.planner import JoinPlan

    rng = np.random.default_rng(0)
    # never probe a LUT bigger than the byte cap the planner enforces —
    # the probe itself must not OOM measuring the guard
    cap = _load(device_kind())["join_lut_max_bytes"]
    factors = [f for f in factors
               if f * (n_build + n_probe) * 4 <= cap]
    if not factors:  # every probe would breach the cap: LUT never legal
        return 0.0
    best = 0.0  # stays 0 if the LUT never wins, recording "sort always"
    for f in factors:
        ks = int(f * (n_build + n_probe))
        # unique build keys WITHOUT materializing a ks-sized permutation
        # (Generator.choice(replace=False) builds one — ~670 MB at the
        # largest factor): oversample with replacement, dedup, trim.
        # Only uniqueness among the n_build keys matters.
        draw = rng.integers(0, ks, int(n_build * 1.3) + 16)
        pk_u = np.unique(draw)[:n_build]
        while len(pk_u) < n_build:  # sparse-collision retry, ~never loops
            extra = rng.integers(0, ks, n_build)
            pk_u = np.unique(np.concatenate([pk_u, extra]))[:n_build]
        pk = jnp.asarray(rng.permutation(pk_u).astype(np.int32))
        fk = jnp.asarray(rng.integers(0, ks, n_probe).astype(np.int32))

        def step(strategy, ks=ks, pk=pk, fk=fk):
            def run(c):
                probe = (fk + c) % ks  # perturb the probe side only:
                # build keys must stay unique
                idx, hit = K.pk_fk_join(pk, probe,
                                        plan=JoinPlan(strategy, ks))
                return (c + idx[0] + hit[0].astype(jnp.int32)) % 127

            return run

        win = _faster(_scan_time(step("lut")), _scan_time(step("sort")))
        if win is None:
            if best == 0.0:
                return None  # undecidable ≠ "LUT never wins"
            break
        if win:
            best = float(f)
        else:
            break
    return best


def autotune(persist: bool = True) -> Dict[str, float]:
    """Measure both crossovers on the live backend and (optionally)
    persist them for this device kind. Run via
    ``python -m netsdb_tpu autotune``."""
    kind = device_kind()
    raw = {
        "segment_dense_limit": measure_segment_crossover(),
        "count_grid_limit": measure_count_grid_crossover(),
        "join_lut_factor": measure_join_crossover(),
    }
    # None = the sweep was undecidable (everything below timing noise):
    # keep the existing threshold instead of persisting "never wins"
    measured = {k: float(v) for k, v in raw.items() if v is not None}
    measured["join_lut_max_bytes"] = float(_load(kind)["join_lut_max_bytes"])
    _load(kind).update(measured)
    jax.clear_caches()  # compiled programs have the old thresholds baked in
    if persist:
        path = _tuning_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        data[kind] = measured
        with open(path, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
    return measured
