"""The FF scoring deployment: how it is filled, and the requests its clients send.

``fill`` runs in the launcher (the process on the chip) and makes weights and
stored feature sets on the device from the seed. ``Ops`` runs in the harness
process and speaks to the daemon through ``RemoteClient`` only.
"""

from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

DB = "ff"


def stored_name(s: int) -> str:
    return f"x{s}"


def fill(library, cfg, seed):
    """Weights and ``stored_sets`` feature sets, made on the device, into the database."""
    import jax
    import jax.numpy as jnp

    from netsdb_tpu.models.ff import FFModel

    f, h, l = cfg["features"], cfg["hidden"], cfg["labels"]
    block = tuple(cfg["block"])
    scale = cfg["data"]["scale_pow2"]
    model = FFModel(db=DB, block=block, compute_dtype=None)
    t0 = time.time()
    model.setup(library)
    for s in range(cfg["stored_sets"]):
        library.create_set(DB, stored_name(s))

    def make(rows, cols, scale):
        # key and row offset are arguments, not constants: one program for every seed
        return jax.jit(lambda key, row0: datagen.matrix(
            jnp, key, rows, cols, scale, row0=row0, ld=cols))

    zero = jnp.uint32(0)
    for name, (rows, cols), blk in (("w1", (h, f), block), ("b1", (h, 1), (block[0], 1)),
                                    ("wo", (l, h), block), ("bo", (l, 1), (block[0], 1))):
        key = jnp.uint32(datagen.stream_key(seed, name))
        library.send_matrix(DB, name, make(rows, cols, scale[name])(key, zero), blk)
    t1 = time.time()
    rows = cfg["stored_rows"]
    gen_x = make(rows, f, scale["x"])
    key = jnp.uint32(datagen.stream_key(seed, "x"))
    for s in range(cfg["stored_sets"]):
        t = library.send_matrix(DB, stored_name(s), gen_x(key, jnp.uint32(s * rows)), block)
        jax.block_until_ready(t.data)
    jax.block_until_ready(library.get_tensor(DB, "w1").data)
    return {"weights_made_s": t1 - t0, "stored_sets_made_s": time.time() - t1}


class Ops:
    """The client side of the deployment's request kinds (``traffic/*.json`` names one)."""

    def __init__(self, cfg, traffic, seed):
        from netsdb_tpu.models.ff import FFModel

        self.addr = None     # the harness sets it once the daemon listens
        self.cfg = cfg
        self.kind = traffic["request"]
        self.params = traffic.get("params", {})
        self.model = FFModel(db=DB, block=tuple(cfg["block"]), compute_dtype=None)
        self.batches = []
        if self.kind == "score_shipped":
            rows, f = self.params["rows"], cfg["features"]
            stride = self.params["batch_stride_rows"]
            key = datagen.stream_key(seed, "shipped")
            # one seeded block of rows; the distinct batches are windows of it, `stride` rows apart
            base = np.empty((rows + stride * (self.params["distinct_batches"] - 1), f), np.float32)
            step = 8   # rows a task: the hash is NumPy passes that release the lock

            def make(r0):
                n = min(step, len(base) - r0)
                base[r0:r0 + n] = datagen.matrix(np, key, n, f, cfg["data"]["scale_pow2"]["x"],
                                                 row0=r0, ld=f)

            with ThreadPoolExecutor(min(12, os.cpu_count() or 1)) as pool:
                list(pool.map(make, range(0, len(base), step)))
            self.batches = [base[b * stride:b * stride + rows]
                            for b in range(self.params["distinct_batches"])]
            self.first_row = [b * stride for b in range(len(self.batches))]
        elif self.kind != "score_stored":
            raise ValueError(f"the ff deployment has no request kind {self.kind!r}")

    # ---- one client ----------------------------------------------------
    def open_client(self, k: int):
        from netsdb_tpu.models.serving import ff_serving
        from netsdb_tpu.serve.client import RemoteClient

        ctx = {"k": k, "n": 0, "kept": {}}
        if self.kind == "score_stored":
            ctx["client"] = RemoteClient(self.addr)
            ctx["out"] = f"out_c{k}"      # one output set a caller: the scores stay in the database
            ctx["client"].create_set(DB, ctx["out"])
            ctx["sinks"] = [
                self.model.build_inference_dag(input_set=stored_name(s), output_set=ctx["out"])
                for s in range(self.cfg["stored_sets"])]
        else:
            srv = ff_serving(self.model, self.addr, input_set=f"ship_in{k}",
                             output_set=f"ship_out{k}")
            srv.deploy(lambda c: None)
            ctx["srv"] = srv
            # score() ships the batch, then executes; the program traces no span for the first,
            # so it is timed here, on the client's clock (layer_metrics/ship_s_per_req.py)
            client = srv._client()
            send = client.send_matrix

            def timed_send(*a, **kw):
                t = time.time()
                try:
                    return send(*a, **kw)
                finally:
                    counters = ctx.setdefault("counters", {})
                    counters["ship_s"] = counters.get("ship_s", 0.0) + time.time() - t

            client.send_matrix = timed_send
        return ctx

    def close_client(self, ctx) -> None:
        if "client" in ctx:
            ctx["client"].close()
        else:
            ctx["srv"].close()

    def schedule(self, k: int, rng):
        """Endless seeded sequence of requests for client ``k``: shuffled passes over the choices."""
        n = self.cfg["stored_sets"] if self.kind == "score_stored" else len(self.batches)
        while True:
            for choice in rng.permutation(n):
                yield int(choice)

    def warm_requests(self, k: int):
        """Every plan the window uses, twice. The program compiles a plan once for each pair of
        input and output set, so every client warms every stored set into its own output set."""
        if self.kind == "score_stored":
            return list(range(self.cfg["stored_sets"])) * 2
        return [0, 1 % len(self.batches)]

    def issue(self, ctx, choice: int):
        """One request, returning when the daemon has finished it. Returns the rows scored."""
        if self.kind == "score_stored":
            ctx["client"].execute_computations(
                ctx["sinks"][choice], job_name="ff-stored", fetch_results=False)
            ctx["kept"][ctx["out"]] = ("x", choice * self.cfg["stored_rows"], None)
            ctx["n"] += 1
            return self.cfg["stored_rows"]
        value = ctx["srv"].score(self.batches[choice])
        rows = self.params["rows"]
        slot = ctx["n"] % self.params["answers_kept"]
        ctx["kept"][slot] = ("shipped", self.first_row[choice], value)
        ctx["n"] += 1
        np.asarray(value.to_dense())      # the caller reads its scores: part of the request
        return rows

    def answers(self, ctx):
        """What the window's last requests produced: (stream, first row, labels x rows array)."""
        out = []
        for slot, (stream, row0, value) in sorted(ctx["kept"].items(), key=lambda kv: str(kv[0])):
            if value is None:
                value = ctx["client"].get_tensor(DB, slot)
            out.append((stream, row0, np.asarray(value.to_dense())))
        return out
