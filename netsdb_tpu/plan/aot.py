"""Ahead-of-time compiled executables — the PreCompiledWorkload cache,
TPU-native.

The reference's master keeps physical plans per job name in memory so a
repeated workload skips planning (``src/queryPlanning/headers/
PreCompiledWorkload.h``, consulted in ``QuerySchedulerServer.cc:
1242-1264``). Two persistent layers replace it here:

1. the **XLA compilation cache** (``config.enable_compilation_cache``):
   every jit this framework compiles lands in an on-disk cache keyed by
   HLO hash, so a FRESH PROCESS re-running the same workload loads the
   compiled executable instead of re-compiling — no code changes at
   call sites, enabled by ``Client.__init__``;
2. **explicit AOT export** (this module): a jitted program serialized
   with ``jax.export`` into a self-contained artifact that a later
   process can load and run without the Python that built it — the
   shippable compiled plan (serve daemons, release bundles).

Both are exercised by tests/test_aot.py; ``chip_smoke.py`` reports the
cache's entry count and the cold/warm spawn-to-first-reply seconds.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict

import jax
from jax import export as jexport


def _register_serializations() -> None:
    """jax.export must know how to serialize the framework's pytree
    auxdata (BlockedTensor's BlockMeta; FFParams is a registered
    dataclass that serializes through its fields). Idempotent."""
    from netsdb_tpu.core.blocked import BlockedTensor, BlockMeta

    try:
        jexport.register_pytree_node_serialization(
            BlockedTensor,
            serialized_name="netsdb_tpu.BlockedTensor",
            serialize_auxdata=lambda meta: json.dumps(
                {"shape": list(meta.shape),
                 "block_shape": list(meta.block_shape)}).encode(),
            deserialize_auxdata=lambda blob: BlockMeta(
                tuple(json.loads(blob)["shape"]),
                tuple(json.loads(blob)["block_shape"])),
        )
    except ValueError:
        pass  # already registered

    from netsdb_tpu.models.ff import FFParams

    try:
        jexport.register_pytree_node_serialization(
            FFParams,
            serialized_name="netsdb_tpu.FFParams",
            serialize_auxdata=lambda aux: json.dumps(aux).encode()
            if aux is not None else b"null",
            deserialize_auxdata=lambda blob: json.loads(blob),
        )
    except ValueError:
        pass


_register_serializations()


def export_jitted(jitted: Callable, *example_args) -> bytes:
    """Serialize a jitted callable, traced+compiled at the example
    arguments' shapes, into a portable executable blob (same platform
    on load — the artifact embeds compiled-for-backend HLO)."""
    exp = jexport.export(jitted)(*example_args)
    return exp.serialize()


def save_exported(path: str, jitted: Callable, *example_args) -> str:
    blob = export_jitted(jitted, *example_args)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "wb") as f:
        f.write(blob)
    return path


def load_exported(path_or_blob) -> Callable:
    """Deserialize an exported executable; returns a callable taking
    the original example-argument structure."""
    if isinstance(path_or_blob, (bytes, bytearray)):
        blob = bytes(path_or_blob)
    else:
        with open(path_or_blob, "rb") as f:
            blob = f.read()
    exp = jexport.deserialize(blob)
    return exp.call


# ------------------------------------------------ suite-level wrappers

def _suite_statics_digest(templates: Dict[str, list]) -> str:
    """Stable digest of the suite's NON-array (compile-time) arguments
    — dictionary codes, key spaces, join plans. The exported program
    baked these in, so a load against tables whose statics differ would
    silently compute wrong answers."""
    import hashlib

    from netsdb_tpu.relational.queries import _SLOT

    canon = {name: [repr(a) for a in t if a is not _SLOT]
             for name, t in templates.items()}
    return hashlib.sha256(json.dumps(canon, sort_keys=True).encode()
                          ).hexdigest()


def export_tpch_suite(tables, path: str) -> str:
    """AOT-compile the ENTIRE fused ten-query TPC-H program
    (``relational.queries.compile_suite``) and serialize it — the whole
    benchmark suite as one shippable executable. A sidecar
    ``<path>.meta`` records the digest of the baked-in statics; the
    loader REQUIRES it, so ship both files together."""
    from netsdb_tpu.relational.queries import compile_suite

    runner = compile_suite(tables)
    with open(path + ".meta", "w") as f:
        json.dump({"statics_digest":
                   _suite_statics_digest(runner.templates)}, f)
    return save_exported(path, runner.jitted, runner.arrays)


def load_tpch_suite(path: str, tables) -> Callable[[], Dict]:
    """Load a serialized suite and re-bind the CURRENT tables' arrays.

    The artifact fixes shapes/dtypes AND the data-dependent statics
    (dictionary codes, key spaces, planner join plans) that were baked
    at export; the loader recomputes them from ``tables`` and REFUSES
    tables whose statics differ — refreshed data must be
    statics-compatible, same as the reference re-running a precompiled
    plan against reloaded sets of the same schema. Fails CLOSED when
    the ``<path>.meta`` sidecar is missing or unreadable (without it
    compatibility cannot be proven, and a silent mismatch computes
    wrong answers)."""
    from netsdb_tpu.relational.queries import suite_args_split

    call = load_exported(path)
    templates, arrays = suite_args_split(tables)
    try:
        with open(path + ".meta") as f:
            want = json.load(f)["statics_digest"]
    except (OSError, ValueError, KeyError) as e:
        raise ValueError(
            f"missing or unreadable statics sidecar {path + '.meta'} "
            "(exported suites must travel with it; re-export if lost)"
        ) from e
    if _suite_statics_digest(templates) != want:
        raise ValueError(
            "exported suite was compiled against different static "
            "arguments (dictionary codes / key spaces / join plans) "
            "than these tables produce; re-export for this data")
    return lambda: call(arrays)


def export_ff_inference(model, params, example_inputs, path: str) -> str:
    """AOT-compile the flagship FF forward (the ``__graft_entry__``
    surface) and serialize it."""
    return save_exported(path, jax.jit(model.forward), params,
                         example_inputs)
