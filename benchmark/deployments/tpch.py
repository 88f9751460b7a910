"""The TPC-H LINEITEM deployment: how it is filled, and the requests its clients send.

``fill`` runs in the launcher (the process on the chip): it makes the table's
columns on the device from the seed, a block of rows at a time, and appends each
block to a paged set through the controller's in-process ``Client``: the same
``send_table(append=True)`` an application's loader calls. ``Ops`` runs in the
harness process and speaks to the daemon through ``RemoteClient`` only.

The rule from (seed, row) to a row is ``columns`` below; the plain reference
(``configs/tpch-sf30-lineitem_reference.py``) has its own copy of it in NumPy.
"""

from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

DB = "tpch"
SET = "lineitem"
DICTS = {"l_returnflag": ["A", "N", "R"], "l_linestatus": ["F", "O"]}
COLUMNS = ("l_shipdate", "l_returnflag", "l_linestatus",
           "l_quantity", "l_extendedprice", "l_discount", "l_tax")


def columns(xp, keys, row0, rows: int, gen: dict, day_table):
    """``rows`` rows of LINEITEM from global row ``row0``: TPC-H clause 4.2.3's domains.

    ``keys`` are three 32-bit stream keys, ``day_table`` maps a day number from
    ``gen["start_date"]`` to yyyymmdd. Integer arithmetic and one float32 multiply
    by 0.01 a value: the same on the device and on the host.
    """
    u, i32, f32 = xp.uint32, xp.int32, xp.float32
    idx = xp.arange(rows, dtype=u) + xp.asarray(row0, dtype=u)
    h1, h2, h3 = (datagen.mix(xp, idx, k) for k in keys)
    order_day = datagen.scaled(xp, h1, 0, 20, gen["order_days"])
    ship_day = order_day + i32(1) + datagen.scaled(xp, h1, 20, 12, gen["ship_after_max"])
    receipt_day = ship_day + i32(1) + datagen.scaled(xp, h2, 0, 8, gen["receipt_after_max"])
    coin = ((h2 >> u(8)) & u(1)).astype(i32)
    quantity = i32(1) + datagen.scaled(xp, h2, 9, 10, gen["quantity_max"])
    discount = datagen.scaled(xp, h2, 19, 6, gen["discount_max_cents"] + 1)
    tax = datagen.scaled(xp, h2, 25, 7, gen["tax_max_cents"] + 1)
    partkey = (h3 % u(gen["parts"])).astype(i32) + i32(1)
    retail_cents = i32(90000) + (partkey // i32(10)) % i32(20001) + i32(100) * (partkey % i32(1000))
    cent = f32(0.01)
    today = i32(gen["current_day"])
    return {
        "l_shipdate": xp.take(day_table, ship_day),
        "l_returnflag": xp.where(receipt_day <= today, i32(2) * coin, i32(1)).astype(i32),
        "l_linestatus": (ship_day > today).astype(i32),
        "l_quantity": quantity.astype(f32),
        "l_extendedprice": (quantity * retail_cents).astype(f32) * cent,
        "l_discount": discount.astype(f32) * cent,
        "l_tax": tax.astype(f32) * cent,
    }


def day_numbers(gen: dict) -> np.ndarray:
    days = gen["order_days"] + gen["ship_after_max"] + gen["receipt_after_max"] + 2
    return datagen.dates_table(tuple(gen["start_date"]), days)


def stream_keys(seed: int):
    return [datagen.stream_key(seed, f"lineitem.{n}") for n in (1, 2, 3)]


def fill(library, cfg, seed):
    """LINEITEM into a paged set, made on the device block by block and appended."""
    import jax
    import jax.numpy as jnp

    from netsdb_tpu.relational.table import ColumnTable

    gen, rows = cfg["generator"], cfg["rows"]
    block = cfg["fill_block_rows"]
    t0 = time.time()
    library.create_database(DB)
    library.create_set(DB, SET, type_name="table", storage="paged")
    table = jnp.asarray(day_numbers(gen))
    make = jax.jit(lambda keys, row0: columns(jnp, keys, row0, block, gen, table))
    keys = jnp.asarray(stream_keys(seed), dtype=jnp.uint32)

    def to_host(row0):
        """One block's columns as NumPy arrays (the device makes them; this brings them back)."""
        n = min(block, rows - row0)
        return {k: np.asarray(v)[:n] for k, v in make(keys, jnp.uint32(row0)).items()}

    waited_s = appended_s = 0.0
    with ThreadPoolExecutor(1) as pool:     # the next block comes back while this one is appended
        pending = pool.submit(to_host, 0)
        for row0 in range(0, rows, block):
            t = time.time()
            cols = pending.result()
            if row0 + block < rows:
                pending = pool.submit(to_host, row0 + block)
            waited_s += time.time() - t
            t = time.time()
            library.send_table(DB, SET, ColumnTable(cols, DICTS), append=True)
            appended_s += time.time() - t
    return {"table_block_waited_s": waited_s, "table_appended_s": appended_s,
            "table_other_s": time.time() - t0 - waited_s - appended_s}


class Ops:
    """The client side of the deployment's request kinds (``traffic/*.json`` names one)."""

    def __init__(self, cfg, traffic, seed):
        self.addr = None     # the harness sets it once the daemon listens
        self.cfg = cfg
        self.kind = traffic["request"]
        if self.kind != "q01_q06_pair":
            raise ValueError(f"the tpch deployment has no request kind {self.kind!r}")
        self.params = traffic.get("params", {})

    def open_client(self, k: int):
        from netsdb_tpu.relational import dag as rdag
        from netsdb_tpu.serve.client import RemoteClient

        orders = {"q01_q06": ("q01", "q06"), "q06_q01": ("q06", "q01")}
        ctx = {"k": k, "n": 0, "kept": [], "client": RemoteClient(self.addr), "rdag": rdag,
               "orders": [orders[o] for o in self.params["orders"]]}
        ctx["sinks"] = {"q01": rdag.q01_sink(DB, output_set=f"q01_out_c{k}"),
                        "q06": rdag.q06_sink(DB, output_set=f"q06_out_c{k}")}
        return ctx

    def close_client(self, ctx) -> None:
        ctx["client"].close()

    def schedule(self, k: int, rng):
        """Endless seeded sequence: shuffled passes over the orders the pair is sent in."""
        n = len(self.params["orders"])
        while True:
            for choice in rng.permutation(n):
                yield int(choice)

    def warm_requests(self, k: int):
        """One pair: each order runs the same two compiled plans, and the cache holds neither."""
        return [0]

    def issue(self, ctx, choice: int):
        """Both queries, one after the other, answers fetched: one request."""
        answer = {}
        for q in ctx["orders"][choice]:
            t = ctx["rdag"].run_query(ctx["client"], ctx["sinks"][q])[0]
            names = ("revenue",) if q == "q06" else (
                "count", "sum_qty", "sum_base_price", "sum_disc_price", "sum_charge", "sum_disc")
            answer[q] = {n: np.asarray(t[n]) for n in names}
            if q == "q01":
                answer[q]["valid"] = np.asarray(t.mask())
        ctx["kept"].append(answer)
        ctx["n"] += 1
        return 2 * self.cfg["rows"]

    def answers(self, ctx):
        """Every answer of this client since it was opened (warm-up's too: the same table)."""
        return ctx["kept"]
