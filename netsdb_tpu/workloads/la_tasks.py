"""Headline LA benchmark tasks — the reference's only published
end-to-end numbers (reference ``selfLearning/documentation.md:5-10``;
see BASELINE.md rows 1-3):

    Gram matrix        X: 200000x1000 (1000x1000 blocks), G = Xt X
                       41.27 s plain -> 22.78 s with self-learning
    Linear regression  same X, ridge normal equations
                       83.45 s -> 43.91 s with self-learning
    Matrix multiply    C = X . W (W: 1000x1000)
                       42.21 s -> 11.41 s best self-learning round

Each task is expressed as a PDML program (the reference drives these
through its LA DSL — ``src/linearAlgebraDSL``, driver
``TestLA21_Instance.cc``) and evaluated over the op layer with inputs
pre-bound in the interpreter environment as device-resident
``BlockedTensor``s — the "data already loaded into sets" starting point
the reference's timings use (its numbers cover the query job, not
dbgen/ingest).

TPU-first design note: the reference executes every DSL statement as a
separate distributed job with materialized intermediates. Here the WHOLE
program is traced into one jaxpr (``compile_pdml``) so XLA fuses across
statements and schedules one program onto the MXU — the per-statement
job boundary, which exists only because the reference's engine needs a
shuffle between stages, disappears.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from netsdb_tpu.core.blocked import BlockMeta, BlockedTensor
from netsdb_tpu.dsl.interp import LAInterpreter
from netsdb_tpu.dsl.parser import parse_program

# Reference numbers (seconds) from selfLearning/documentation.md:5-10:
# plain = no self-learning; best = best self-learning run.
REFERENCE_SECONDS = {
    "gram": {"plain": 41.27, "best": 22.78},
    "linreg": {"plain": 83.45, "best": 43.91},
    "matmul": {"plain": 42.21, "best": 11.41},
}

# The programs. LAMI = lambda*I is pre-bound (PDML has no scalar
# literals in expressions; the reference's sample drivers likewise bind
# scalars by loading pre-scaled matrices).
PROGRAMS = {
    "gram": "G = X '* X",
    "linreg": "w = (X '* X + LAMI) ^-1 %*% (X '* y)",
    "matmul": "C = X %*% W",
}

TASKS = tuple(PROGRAMS)


def compile_pdml(text: str) -> Callable[[Dict[str, BlockedTensor]],
                                        Dict[str, BlockedTensor]]:
    """Trace a whole PDML program into one jit-compiled function
    ``env -> {target: value for each statement}``.

    This is the DSL's compile path: statements become one fused XLA
    program instead of the reference's one-distributed-job-per-statement
    execution (``LAEvaluateFunctions.cc`` calling executeComputations
    per AST node).
    """
    stmts = parse_program(text)

    def run(env: Dict[str, BlockedTensor]) -> Dict[str, BlockedTensor]:
        interp = LAInterpreter()
        interp.env.update(env)
        for stmt in stmts:
            interp.execute(stmt)
        return {stmt.target: interp.env[stmt.target] for stmt in stmts}

    return jax.jit(run)


def make_inputs(task: str, rows: int, cols: int, block: int,
                lam: float = 1.0, dtype=jnp.float32, seed: int = 0,
                ) -> Dict[str, BlockedTensor]:
    """Device-side random inputs at the task's shapes (no host round
    trip — the generator runs on the chip)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)

    def randn(key, shape, bshape):
        meta = BlockMeta(shape, bshape)
        data = jax.random.normal(key, meta.padded_shape, dtype)
        if meta.is_padded:  # honor the zero-margin invariant
            mask_r = jnp.arange(meta.padded_shape[0]) < shape[0]
            mask_c = jnp.arange(meta.padded_shape[1]) < shape[1]
            data = data * (mask_r[:, None] & mask_c[None, :]).astype(dtype)
        return BlockedTensor(data, meta)

    env = {"X": randn(keys[0], (rows, cols), (block, block))}
    if task == "linreg":
        env["y"] = randn(keys[1], (rows, 1), (block, 1))
        eye = jnp.eye(env["X"].meta.padded_shape[1], dtype=dtype)
        n = cols
        eye = eye * (jnp.arange(eye.shape[0]) < n).astype(dtype)[:, None]
        env["LAMI"] = BlockedTensor(lam * eye,
                                    BlockMeta((cols, cols), (block, block)))
    elif task == "matmul":
        env["W"] = randn(keys[2], (cols, cols), (block, block))
    elif task != "gram":
        raise ValueError(f"unknown task {task!r}; have {TASKS}")
    return env


def run_task(task: str, rows: int = 200000, cols: int = 1000,
             block: int = 1000, iters: int = 5, lam: float = 1.0,
             dtype=jnp.float32, seed: int = 0) -> Dict[str, object]:
    """Time one headline task at the reference's scale. Returns timings
    plus the reference baselines and the speedup vs. the reference's
    BEST (self-learned) number."""
    env = make_inputs(task, rows, cols, block, lam, dtype, seed)
    for t in env.values():
        jax.block_until_ready(t.data)
    fn = compile_pdml(PROGRAMS[task])

    def sync(out):
        for v in out.values():
            jax.block_until_ready(v.data)
        return float(jnp.sum(next(iter(out.values())).data))

    t0 = time.perf_counter()
    sync(fn(env))
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(fn(env))
        times.append(time.perf_counter() - t0)
    exec_s = sorted(times)[len(times) // 2]

    # pure device time via the scan-slope protocol (wall includes the
    # controller round-trip, which dominates at these speeds)
    from functools import partial

    from netsdb_tpu.utils.timing import device_seconds

    @partial(jax.jit, static_argnums=1)
    def loop(e, n):
        def step(carry, _):
            e2 = dict(e)
            e2["X"] = e["X"].with_data(e["X"].data + carry)
            out = fn(e2)
            first = next(iter(out.values())).data
            return (jnp.sum(first) * 1e-20).astype(e["X"].data.dtype), None
        c, _ = jax.lax.scan(step, jnp.zeros((), e["X"].data.dtype), None,
                            length=n)
        return c

    dev_s = device_seconds(lambda n: float(loop(env, n)), lo=2, hi=8)

    ref = REFERENCE_SECONDS[task]
    out = {
        "task": task,
        "rows": rows, "cols": cols, "block": block,
        "dtype": str(jnp.dtype(dtype).name),
        "compile_s": round(compile_s, 4),
        "exec_s_median": round(exec_s, 6),
        "exec_s_min": round(min(times), 6),
        "ref_plain_s": ref["plain"],
        "ref_best_s": ref["best"],
        "speedup_vs_ref_best": round(ref["best"] / exec_s, 1),
    }
    if dev_s is not None:
        out["exec_s_device"] = round(dev_s, 6)
        out["speedup_vs_ref_best_device"] = round(ref["best"] / dev_s, 1)
    return out
