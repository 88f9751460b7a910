#!/usr/bin/env python3
"""chip_smoke.py — does the served path start, answer and exit on the chip?

Drives the system's main path ONCE through the entry points a user calls
— a daemon started as ``python -m netsdb_tpu serve``, a ``RemoteClient``,
``models/serving.py`` deploy/score — at the full width of the flagship FF
model (1024 -> 4096 -> 1024, batches of 16,384 rows), then the two other
lanes that share executor, staging and device cache (decode sessions,
paged TPC-H folds), then — after the daemon has exited — the pallas
kernels compiled by Mosaic (``interpret=False``). Every result is checked
against a plain NumPy / reference evaluation.

One process per chip. THIS process never initialises a jax backend (it
is asserted before exit); the daemon child inherits the environment
untouched and is the only process on the chip; the kernel child starts
only after the daemon's exit code has been checked.

Exit code 0 and two stdout lines only when every phase passed on a TPU:
first the report (one JSON object: per-phase ``ok`` and seconds,
``spawn_to_first_reply_s``, compile-cache entries before and after; also
kept as ``chip_smoke_out/summary.json``), then, LAST, the verdict with
exactly these keys: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``, the device as the daemon's jax reports it.
Any phase's failure is the script's failure (logs are kept under
``chip_smoke_out/`` and their tails printed), and then stdout stays
empty. ``--dryrun-cpu`` is the explicit CPU rehearsal (tiny sizes,
pallas interpret mode; its report says ``"dryrun": true``) — without
that flag there is no CPU path: a daemon that reports any platform but
``tpu`` fails the run, naming what it found.

All state (daemon root, NETSDB_TPU_HOME, logs) lives under
``chip_smoke_out/`` next to this file, wiped at start. The compile cache
is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else the repo's fixed
``.jax_compile_cache/``; its entry count is reported before and after.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")

# the flagship FF (1024 -> 4096 -> 1024 over 16,384 rows), and what shares
# its daemon
FULL = dict(
    features=1024, hidden=4096, labels=1024, block=(512, 512),
    batch=16384, ff_requests=3,
    decode_hidden=512, decode_block=(128, 128), decode_steps=8,
    li_rows=2 << 20, pool_mb=16, page_kb=1024,  # 64 MiB table, 16 MiB pool
    flash_bf16=(2, 8, 8192, 128), flash_f32=(2, 8, 2048, 128),
    ring_chunks=4, layer=(2, 1024, 8), layer_seq=8192, layer_ref_seq=2048)
DRYRUN = dict(
    features=64, hidden=128, labels=32, block=(32, 32),
    batch=256, ff_requests=3,
    decode_hidden=64, decode_block=(32, 32), decode_steps=8,
    li_rows=100_000, pool_mb=1, page_kb=64,  # 2.7 MiB table, 1 MiB pool
    flash_bf16=(1, 2, 256, 128), flash_f32=(1, 2, 128, 128),
    ring_chunks=2, layer=(1, 256, 2), layer_seq=256, layer_ref_seq=128)

F32_ATOL = 1e-4
# bf16 mode: inputs/weights round to 8 mantissa bits (2^-9 relative) and
# the hidden activation is kept in bf16, so logits of magnitude ~1 carry
# ~1e-2 absolute error; a softmax over 1024 labels has probabilities
# <~5e-2, which that logit error moves by <~1e-3. 5e-3 leaves headroom
# without admitting a wrong answer (a wrong weight moves probabilities
# by the size of the probabilities themselves).
BF16_ATOL = 5e-3
FOLD_RTOL = 1e-4  # f32 sums over millions of rows, chunk-reassociated


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def tail(path: str, nbytes: int = 6000) -> str:
    if not os.path.exists(path):
        return f"({path} does not exist)"
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - nbytes))
        return f.read().decode(errors="replace")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def cache_dir() -> str:
    from netsdb_tpu.config import COMPILE_CACHE_DIR

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class Phases:
    """Ordered per-phase record; a phase that raises stops the run."""

    def __init__(self) -> None:
        self.record: Dict[str, Dict[str, Any]] = {}

    def run(self, name: str, fn: Callable[[], Any]) -> Any:
        log(f"phase {name} ...")
        t0 = time.perf_counter()
        out = fn()
        dt = round(time.perf_counter() - t0, 3)
        self.record[name] = {"ok": True, "seconds": dt}
        if isinstance(out, dict):
            self.record[name].update(out)
        log(f"phase {name} ok in {dt}s")
        return out


# ---------------------------------------------------------------- daemon

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env() -> Dict[str, str]:
    """The environment untouched, plus a private NETSDB_TPU_HOME so no
    earlier run's /tmp/netsdb_tpu/autotune.json reaches the planner."""
    env = dict(os.environ)
    env["NETSDB_TPU_HOME"] = os.path.join(OUT, "home")
    return env


def spawn_daemon(sz: Dict[str, Any], port: int, log_path: str
                 ) -> subprocess.Popen:
    with open(log_path, "wb") as logf:
        return subprocess.Popen(
            [sys.executable, "-m", "netsdb_tpu", "serve",
             "--port", str(port), "--root", os.path.join(OUT, "root"),
             "--page-pool-mb", str(sz["pool_mb"]),
             "--page-kb", str(sz["page_kb"])],
            env=child_env(), cwd=HERE, stdout=logf,
            stderr=subprocess.STDOUT)


def wait_listening(daemon: subprocess.Popen, port: int,
                   timeout_s: float = 300.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if daemon.poll() is not None:
            raise RuntimeError(
                f"daemon exited with code {daemon.returncode} before "
                f"listening")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"daemon not listening on :{port} after "
                       f"{timeout_s:.0f}s")


# ------------------------------------------------------------- FF scoring

def ff_reference(w1, b1, wo, bo, x) -> np.ndarray:
    """Plain NumPy float32 forward pass, (labels x batch) like the
    served output: relu(w1 x^T + b1) -> wo . + bo -> softmax over
    labels."""
    h = np.maximum(w1 @ x.T + b1[:, None], np.float32(0))
    z = wo @ h + bo[:, None]
    e = np.exp(z - z.max(axis=0, keepdims=True))
    return (e / e.sum(axis=0, keepdims=True)).astype(np.float32)


def ff_weights(sz: Dict[str, Any], seed: int = 1):
    """Random float32 (w1, b1, wo, bo) from a seed, He-scaled like
    FFModel.load_random_weights."""
    rng = np.random.default_rng(seed)
    f, h, l = sz["features"], sz["hidden"], sz["labels"]
    f32 = np.float32
    return (rng.standard_normal((h, f), dtype=f32) * f32(np.sqrt(2.0 / f)),
            rng.standard_normal((h,), dtype=f32) * f32(0.01),
            rng.standard_normal((l, h), dtype=f32) * f32(np.sqrt(2.0 / h)),
            rng.standard_normal((l,), dtype=f32) * f32(0.01))


def compile_counts(client) -> Dict[str, Any]:
    c = client.collect_stats()["metrics"]["compile"]
    return {"misses": c["misses"], "traces": c["traces"],
            "region_traces": dict(c["region_traces"])}


def ff_phase(client, addr: str, sz: Dict[str, Any], compute_dtype,
             atol: float, t_spawn: float) -> Dict[str, Any]:
    from netsdb_tpu.models.ff import FFModel
    from netsdb_tpu.models.serving import ff_serving

    tag = compute_dtype or "f32"
    model = FFModel(db=f"ff_{tag}", block=sz["block"],
                    compute_dtype=compute_dtype)
    weights = ff_weights(sz)

    def load(c):
        model.setup(c)
        model.load_weights(c, *weights)

    srv = ff_serving(model, addr)
    try:
        srv.deploy(load)
        rng = np.random.default_rng(100)
        worst = 0.0
        after_first = None
        for i in range(sz["ff_requests"]):
            x = rng.standard_normal(
                (sz["batch"], sz["features"])).astype(np.float32)
            if i == 0:
                out, forest = srv.score(x, explain=True)
                first_reply_s = time.perf_counter() - t_spawn
                check(bool(forest), "EXPLAIN forest returned")
                for daemon, tree in forest.items():
                    check(tree["mode"] == "whole_plan_jit",
                          f"{daemon}: EXPLAIN mode {tree['mode']!r}, "
                          f"wanted whole_plan_jit")
                    nodes = [n for n in tree["nodes"]
                             if n.get("kind") != "WholePlanJit"]
                    check(nodes and all(n.get("fused") for n in nodes),
                          f"{daemon}: unfused plan nodes "
                          f"{[n['label'] for n in nodes if not n.get('fused')]}")
                after_first = compile_counts(client)
            else:
                out = srv.score(x)
            got = np.asarray(out.to_dense())
            want = ff_reference(*weights, x)
            check(got.shape == want.shape == (sz["labels"], sz["batch"]),
                  f"FF output shape {got.shape}")
            check(np.isfinite(got).all(), "FF output finite")
            err = float(np.abs(got - want).max())
            worst = max(worst, err)
            check(err <= atol,
                  f"FF {tag} batch {i}: max abs err {err:.3e} > {atol}")
        after_last = compile_counts(client)
        check(after_last == after_first,
              f"requests 2..N compiled: {after_first} -> {after_last}")
    finally:
        srv.close()
    return {"max_abs_err": worst, "atol": atol,
            "requests": sz["ff_requests"],
            "first_reply_since_spawn_s": round(first_reply_s, 3)}


# --------------------------------------------------------------- sessions

def sessions_phase(client, addr: str, sz: Dict[str, Any], kind: str
                   ) -> Dict[str, Any]:
    from netsdb_tpu.models.decode import deploy_decode_model
    from netsdb_tpu.serve.client import RemoteClient

    hidden, steps = sz["decode_hidden"], sz["decode_steps"]
    db = f"dec_{kind}"
    deploy_decode_model(client, db, kind=kind, hidden=hidden, seed=7,
                        block=sz["decode_block"])
    rng = np.random.default_rng(7000)
    xs = [rng.standard_normal(hidden).astype(np.float32)
          for _ in range(steps)]
    before = client.collect_stats()["metrics"]["decode"]

    def drive(handle, outs: List[np.ndarray], barrier) -> None:
        for x in xs:
            if barrier is not None:
                barrier.wait(timeout=600)
            # the first step compiles the padded program
            outs.append(handle.generate(x, deadline_s=600.0))

    # two CONCURRENT sessions fed identical inputs (own connections)
    clients = [RemoteClient(addr), RemoteClient(addr)]
    try:
        handles = [c.open_session(db, kind=kind) for c in clients]
        outs: List[List[np.ndarray]] = [[], []]
        errors: List[BaseException] = []
        barrier = threading.Barrier(2)

        def run(i: int) -> None:
            try:
                drive(handles[i], outs[i], barrier)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
                barrier.abort()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        check(not any(t.is_alive() for t in threads),
              "session threads finished")
        if errors:
            raise errors[0]
        mid = client.collect_stats()["metrics"]["decode"]
        # then a third session ALONE, same inputs
        solo = client.open_session(db, kind=kind)
        solo_outs: List[np.ndarray] = []
        drive(solo, solo_outs, None)
        for h in handles + [solo]:
            check(h.steps == steps, f"step counter {h.steps} != {steps}")
            check(h.close(), "session closed")
    finally:
        for c in clients:
            c.close()
    for s in range(steps):
        a, b, c3 = outs[0][s], outs[1][s], solo_outs[s]
        check(a.shape == (hidden,) and np.isfinite(a).all(),
              f"{kind} step {s}: output finite with shape ({hidden},)")
        check(a.tobytes() == b.tobytes(),
              f"{kind} step {s}: identical sessions differ")
        check(a.tobytes() == c3.tobytes(),
              f"{kind} step {s}: batched differs from solo")
    coalesced = ((mid["steps"] - before["steps"])
                 - (mid["batches"] - before["batches"]))
    check(coalesced >= 1,
          f"{kind}: no two-session batch formed in {steps} paired steps")
    return {"steps": steps, "coalesced_steps": coalesced}


# ------------------------------------------------------------- paged fold

def lineitem(rows: int, seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    return {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows,
                                   dtype=np.int32).astype(np.float32),
        "l_extendedprice": rng.uniform(1000, 100000,
                                       rows).astype(np.float32),
        "l_discount": rng.uniform(0, 0.1, rows).astype(np.float32),
        "l_tax": rng.uniform(0, 0.08, rows).astype(np.float32),
    }


def q06_reference(li: Dict[str, np.ndarray]) -> float:
    m = ((li["l_shipdate"] >= 19940101) & (li["l_shipdate"] < 19950101)
         & (li["l_discount"] >= np.float32(0.06 - 0.011))
         & (li["l_discount"] <= np.float32(0.06 + 0.011))
         & (li["l_quantity"] < 24))
    return float((li["l_extendedprice"][m].astype(np.float64)
                  * li["l_discount"][m]).sum())


def q01_reference(li: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    m = li["l_shipdate"] <= 19980902
    seg = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
    f64 = {k: li[k][m].astype(np.float64)
           for k in ("l_quantity", "l_extendedprice", "l_discount",
                     "l_tax")}
    disc_price = f64["l_extendedprice"] * (1.0 - f64["l_discount"])
    out = {"count": np.bincount(seg, minlength=6)}
    for name, v in (("sum_qty", f64["l_quantity"]),
                    ("sum_base_price", f64["l_extendedprice"]),
                    ("sum_disc_price", disc_price),
                    ("sum_charge", disc_price * (1.0 + f64["l_tax"])),
                    ("sum_disc", f64["l_discount"])):
        out[name] = np.bincount(seg, weights=v, minlength=6)
    return out


def fold_span(client) -> Dict[str, Any]:
    """The newest trace profile's executor.fold_stream span."""
    profile = client.get_trace(last=1)["profiles"][-1]
    spans = [s for s in profile["spans"]
             if s["name"] == "executor.fold_stream"]
    check(bool(spans), "trace has no executor.fold_stream span: "
          f"{[s['name'] for s in profile['spans']]}")
    check(spans[0]["counters"]["chunks"] >= 1, "fold_stream chunks >= 1")
    return spans[0]


def paged_phase(client, sz: Dict[str, Any]) -> Dict[str, Any]:
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.table import ColumnTable

    li = lineitem(sz["li_rows"])
    table_bytes = sum(c.nbytes for c in li.values())
    check(table_bytes > sz["pool_mb"] << 20,
          "the table must exceed the page pool")
    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      storage="paged")
    client.send_table("tpch", "lineitem", ColumnTable(
        li, {"l_returnflag": ["A", "N", "R"], "l_linestatus": ["F", "O"]}))
    want06, want01 = q06_reference(li), q01_reference(li)
    chunks = {}
    for temp in ("cold", "warm"):
        t06 = rdag.run_query(client, rdag.q06_sink("tpch"))[0]
        chunks[f"q06_{temp}"] = fold_span(client)["counters"]["chunks"]
        got06 = float(np.asarray(t06["revenue"])[0])
        check(abs(got06 - want06) <= FOLD_RTOL * abs(want06),
              f"q06 {temp}: {got06!r} vs NumPy {want06!r}")
        t01 = rdag.run_query(client, rdag.q01_sink("tpch"))[0]
        chunks[f"q01_{temp}"] = fold_span(client)["counters"]["chunks"]
        check(np.asarray(t01.mask()).all(), "q01: all 6 groups present")
        check(np.array_equal(np.asarray(t01["count"]), want01["count"]),
              f"q01 {temp}: counts {np.asarray(t01['count'])} vs "
              f"{want01['count']}")
        for name in ("sum_qty", "sum_base_price", "sum_disc_price",
                     "sum_charge", "sum_disc"):
            got = np.asarray(t01[name], np.float64)
            check(np.allclose(got, want01[name], rtol=FOLD_RTOL, atol=0),
                  f"q01 {temp} {name}: {got} vs NumPy {want01[name]}")
    store = client.collect_stats().get("page_store")
    check(store is not None and store["native"],
          f"paged set is not on the native page store: {store}")
    check(store["spills"] > 0,
          f"no spills under a {sz['pool_mb']} MiB pool: {store}")
    return {"rows": sz["li_rows"], "table_bytes": table_bytes,
            "spills": store["spills"], "chunks": chunks}


# ------------------------------------------------------- the kernel child

def kernel_child(dryrun: bool) -> int:
    """Runs ALONE on the chip (the daemon has exited): the pallas
    kernels through Mosaic. Prints one JSON line; raises on any
    mismatch."""
    import jax
    import jax.numpy as jnp

    from netsdb_tpu.config import enable_compilation_cache
    from netsdb_tpu.ops.attention import attention
    from netsdb_tpu.ops.pallas_kernels import (NEG_INF, flash_attention,
                                               flash_attention_step)

    enable_compilation_cache()
    sz = DRYRUN if dryrun else FULL
    interpret = dryrun  # the chip run passes interpret=False outright
    platform = jax.devices()[0].platform
    check(platform == ("cpu" if dryrun else "tpu"),
          f"kernel child found platform {platform!r}")
    out: Dict[str, Any] = {"platform": platform}

    def qkv(shape, dtype, seed):
        rng = np.random.default_rng(seed)
        return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                     for _ in range(3))

    @jax.jit
    def reference(q, k, v):
        # ops.attention.attention in f32, one (batch, head) at a time so
        # the (S, S) logits of the 8k case stay a fraction of HBM
        f32 = [t.astype(jnp.float32).reshape((-1, 1, 1) + t.shape[2:])
               for t in (q, k, v)]
        ref = jax.lax.map(lambda t: attention(*t, causal=True), tuple(f32))
        return ref.reshape(q.shape)

    def max_err(a, b) -> float:
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # bf16 tolerances: q is pre-scaled and P rounded in bf16 (2^-9
    # relative each) and the output itself is bf16 (half an ulp of a
    # value of magnitude 2-4 is 8e-3); 1.0e-2 was measured at 8k on a
    # v5e, a masking or carry bug moves outputs by O(1)
    bf16_atol = 5e-2

    # flash_attention, bf16 at its default 1024x1024 blocks, and f32
    q, k, v = qkv(sz["flash_bf16"], jnp.bfloat16, 11)
    ref_bf16 = reference(q, k, v)
    got = flash_attention(q, k, v, causal=True, interpret=interpret)
    check(got.dtype == jnp.bfloat16 and got.shape == q.shape,
          "flash bf16 shape/dtype")
    out["flash_bf16_err"] = max_err(got, ref_bf16)
    check(out["flash_bf16_err"] <= bf16_atol,
          f"flash bf16 vs attention: {out['flash_bf16_err']:.3e}")

    qf, kf, vf = qkv(sz["flash_f32"], jnp.float32, 12)
    got = flash_attention(qf, kf, vf, causal=True, interpret=interpret)
    out["flash_f32_err"] = max_err(got, reference(qf, kf, vf))
    check(out["flash_f32_err"] <= 1e-4,
          f"flash f32 vs attention: {out['flash_f32_err']:.3e}")

    # flash_attention_step: one ring member's fold, chunk by chunk in
    # the order the ring delivers them, over the bf16 case above
    b, h, s, d = sz["flash_bf16"]
    n, sl, me = sz["ring_chunks"], s // sz["ring_chunks"], 1
    flat = [t.reshape(b * h, s, d) for t in (q, k, v)]
    qc = flat[0][:, me * sl:(me + 1) * sl]
    acc = jnp.zeros(qc.shape, jnp.float32)
    l = jnp.zeros((b * h, sl, 128), jnp.float32)
    m = jnp.full((b * h, sl, 128), NEG_INF, jnp.float32)
    for i in range(n):
        src = (me - i) % n
        acc, l, m = flash_attention_step(
            qc, flat[1][:, src * sl:(src + 1) * sl],
            flat[2][:, src * sl:(src + 1) * sl], acc, l, m,
            q_offset=me * sl, k_offset=src * sl, causal=True,
            interpret=interpret)
    ring = (acc / jnp.maximum(l[:, :, :1], 1e-30)).reshape(b, h, sl, d)
    out["ring_step_err"] = max_err(
        ring, ref_bf16[:, :, me * sl:(me + 1) * sl])
    check(out["ring_step_err"] <= bf16_atol,
          f"flash_attention_step chain vs attention: "
          f"{out['ring_step_err']:.3e}")

    # the transformer layer through an in-process Client, its weights
    # read from sets
    from netsdb_tpu.client import Client
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.models.transformer import TransformerLayerModel

    batch, embed, heads = sz["layer"]
    client = Client(Configuration(root_dir=os.path.join(OUT, "kroot")))
    model = TransformerLayerModel(db="tfl", num_heads=heads)
    model.setup(client)
    model.load_random_weights(client, embed=embed, seed=0)
    params = jax.tree_util.tree_map(
        lambda w: jnp.asarray(w, jnp.bfloat16),
        model.params_from_store(client))
    # on the chip impl=None must AUTO-select flash; the CPU rehearsal
    # has to ask for it (auto-selection keeps "full" off-TPU)
    impl = "flash" if dryrun else None
    rng = np.random.default_rng(3)

    def layer_input(seq):
        return jnp.asarray(rng.standard_normal((batch, seq, embed)),
                           jnp.bfloat16)

    def fwd(p, x):
        return model.forward(p, x, impl=impl)

    x = layer_input(sz["layer_seq"])
    check("pallas_call" in str(jax.make_jaxpr(fwd)(params, x)),
          f"layer at seq {sz['layer_seq']} did not take the flash path")
    y = jax.jit(fwd)(params, x)
    check(y.shape == x.shape and bool(jnp.isfinite(y).all()),
          "layer output finite")
    x = layer_input(sz["layer_ref_seq"])
    full = jax.jit(lambda p, xx: model.forward(p, xx, impl="full"))(
        params, x)
    # bf16 activations of magnitude ~8: relative to the output's scale
    # (measured 7e-3 on a v5e — one bf16 ulp at that magnitude)
    out["layer_rel_err"] = (max_err(jax.jit(fwd)(params, x), full)
                            / float(jnp.max(jnp.abs(full))))
    check(out["layer_rel_err"] <= 2e-2,
          f"layer flash vs full at seq {sz['layer_ref_seq']}: "
          f"{out['layer_rel_err']:.3e} of the output scale")
    print(json.dumps(out))
    return 0


# ------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dryrun-cpu", action="store_true",
                    help="CPU rehearsal: tiny sizes, pallas interpret "
                         "mode; never a chip result")
    ap.add_argument("--kernel-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernel_child:
        return kernel_child(args.dryrun_cpu)

    import jax._src.xla_bridge as xla_bridge

    from netsdb_tpu.native.build import library_path
    from netsdb_tpu.serve.client import RemoteClient

    sz = DRYRUN if args.dryrun_cpu else FULL
    want_platform = "cpu" if args.dryrun_cpu else "tpu"

    previous = None  # the run before this one, for the cold/warm report
    if os.path.exists(os.path.join(OUT, "summary.json")):
        with open(os.path.join(OUT, "summary.json")) as f:
            last = json.load(f)
        previous = {k: last.get(k) for k in
                    ("spawn_to_first_reply_s", "compile_cache", "dryrun")}
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "home"))

    cdir = cache_dir()
    entries_before = cache_entries(cdir)
    native_existed = os.path.exists(library_path("pagestore"))
    daemon_log = os.path.join(OUT, "daemon.log")
    kernel_log = os.path.join(OUT, "kernels.log")
    phases = Phases()
    port = free_port()
    addr = f"127.0.0.1:{port}"
    t_spawn = time.perf_counter()
    daemon = spawn_daemon(sz, port, daemon_log)
    try:
        def start():
            wait_listening(daemon, port)
            client = RemoteClient(addr)
            dev = client.ping()["device"]
            if dev["platform"] != want_platform:
                raise RuntimeError(
                    f"the daemon runs on platform {dev['platform']!r} "
                    f"({dev['device_kind']} x{dev['count']}), not "
                    f"{want_platform!r}"
                    + ("" if args.dryrun_cpu else
                       " — chip_smoke.py has no CPU path; "
                       "--dryrun-cpu is the rehearsal"))
            return client, dev

        client, device = phases.run("daemon_start", start)
        phases.run("ff_f32", lambda: ff_phase(
            client, addr, sz, None, F32_ATOL, t_spawn))
        phases.run("ff_bf16", lambda: ff_phase(
            client, addr, sz, "bfloat16", BF16_ATOL, t_spawn))
        for kind in ("lstm", "transformer_layer"):
            phases.run(f"sessions_{kind}", lambda kind=kind: sessions_phase(
                client, addr, sz, kind))
        phases.run("paged_fold", lambda: paged_phase(client, sz))

        def stop() -> None:
            client.shutdown_server()
            client.close()
            code = daemon.wait(timeout=120)
            check(code == 0, f"daemon exit code {code}")

        phases.run("daemon_stop", stop)
    except BaseException:
        log(f"FAILED — daemon log tail ({daemon_log}):\n{tail(daemon_log)}")
        raise
    finally:
        if daemon.poll() is None:  # never leave a process on the chip
            daemon.kill()
            daemon.wait(timeout=60)

    def kernels() -> Dict[str, Any]:
        # only now: the daemon is gone, the chip is free
        argv = [sys.executable, os.path.abspath(__file__), "--kernel-child"]
        if args.dryrun_cpu:
            argv.append("--dryrun-cpu")
        with open(kernel_log, "wb") as logf:
            proc = subprocess.run(argv, env=child_env(), cwd=HERE,
                                  stdout=subprocess.PIPE, stderr=logf,
                                  timeout=1100)
        if proc.returncode != 0:
            log(f"FAILED — kernel log tail ({kernel_log}):\n"
                f"{tail(kernel_log)}")
            raise RuntimeError(
                f"kernel child exit code {proc.returncode}")
        result = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        check(result["platform"] == want_platform,
              f"kernel child ran on {result['platform']!r}")
        return result

    phases.run("kernels", kernels)

    native_lib = library_path("pagestore")
    check(os.path.exists(native_lib),
          f"{native_lib} was not built from native/pagestore.cpp")
    check(not xla_bridge.backends_are_initialized(),
          "chip_smoke.py's own process initialised a jax backend")
    summary = {
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["count"]},
        "dryrun": bool(args.dryrun_cpu),
        "platform": device["platform"],
        "phases": phases.record,
        "spawn_to_first_reply_s":
            phases.record["ff_f32"]["first_reply_since_spawn_s"],
        "compile_cache": {"dir": cdir, "entries_before": entries_before,
                          "entries_after": cache_entries(cdir)},
        "native_pagestore": {
            "file": os.path.basename(native_lib),
            "built_this_run": not native_existed},
        "previous_run": previous,
    }
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    # the report first; the LAST line is the verdict alone, with exactly
    # the keys the driver's check reads
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
