"""The scale-out workload: a q01-shaped group-by and a revenue join
over INTEGER measures, whose sharded, shuffled, rebalanced or
cache-stitched result must be BYTE-equal to the one-daemon run. The
distributed tests (scale-out, rebalance, HA, distributed fusion,
partial device cache) and the advisors' A/B loop use it as their
oracle.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from netsdb_tpu.plan.computations import Apply, Join, ScanSet, WriteSet
from netsdb_tpu.plan.fold import single_pass, tree_add_states
from netsdb_tpu.relational.table import ColumnTable


def scaleout_table(rows: int, seed: int = 0):
    """The q01-style paged workload with INTEGER measures: partial
    sums stay exactly representable, so the 4-daemon scatter-gather
    result must be BYTE-equal to the 1-daemon run (float q01 differs
    by merge-order reassociation in the last ulp — this workload is
    the acceptance oracle, the shape is identical)."""
    rng = np.random.default_rng(seed)
    cols = {
        "l_shipdate": rng.integers(19920101, 19981231, rows,
                                   dtype=np.int32),
        "l_returnflag": rng.integers(0, 3, rows, dtype=np.int32),
        "l_linestatus": rng.integers(0, 2, rows, dtype=np.int32),
        "l_quantity": rng.integers(1, 51, rows, dtype=np.int32),
        "l_price": rng.integers(1, 1000, rows, dtype=np.int32),
    }
    return ColumnTable(cols, {"l_returnflag": ["A", "N", "R"],
                              "l_linestatus": ["F", "O"]})


def scaleout_q01_sink(db: str, cutoff: int = 19980902,
                      lineitem_set: str = "lineitem",
                      output_set: str = "scale_q01_out"):
    """SCAN(lineitem) → APPLY(int group-by fold) → OUTPUT: per
    (returnflag, linestatus) group, int32 count + sum(qty) +
    sum(price) under a shipdate cutoff. Single-pass fold with a
    declared ``state_merge`` (tree add) — the scatterable q01 shape
    with exact integer accumulators."""
    n_groups = 6  # 3 returnflags x 2 linestatuses

    def init(prev, src):
        z = jnp.zeros((n_groups,), jnp.int32)
        return (z, z, z)

    def step(state, chunk):
        counts, qty, price = state
        ok = chunk.mask() & (chunk["l_shipdate"] <= cutoff)
        gid = jnp.where(ok, chunk["l_returnflag"] * 2
                        + chunk["l_linestatus"], 0)
        one = jnp.where(ok, 1, 0).astype(jnp.int32)
        return (counts.at[gid].add(one),
                qty.at[gid].add(jnp.where(ok, chunk["l_quantity"], 0)),
                price.at[gid].add(jnp.where(ok, chunk["l_price"], 0)))

    def fin(state, src):
        counts, qty, price = state
        gid = jnp.arange(n_groups, dtype=jnp.int32)
        return ColumnTable(
            cols={"l_returnflag": gid // 2, "l_linestatus": gid % 2,
                  "count": counts, "sum_qty": qty, "sum_price": price},
            dicts={"l_returnflag": src.dicts["l_returnflag"],
                   "l_linestatus": src.dicts["l_linestatus"]},
            valid=counts > 0)

    return WriteSet(Apply(ScanSet(db, lineitem_set),
                          fold=single_pass(init, step, fin,
                                           state_merge=tree_add_states),
                          label=f"scaleq01:{cutoff}"),
                    db, output_set)


def scaleout_join_sink(db: str, key_space: int,
                       lineitem_set: str = "lineitem",
                       orders_set: str = "orders",
                       output_set: str = "scale_join_out"):
    """Grace-hash-capable revenue join with INTEGER accumulators:
    per-order sum of lineitem prices via a LUT probe. Declared
    probe/build keys + an output merge make it a distributed-shuffle
    join over a sharded pool; every order's lineitems co-locate on its
    key's shuffle bucket, so the sharded result is byte-equal to the
    single-node run."""
    def init(prev, src, orders):
        return jnp.zeros((orders.num_rows,), jnp.int32)

    def step(acc, li, orders):
        lut = jnp.full((key_space,), -1, jnp.int32).at[
            orders["o_orderkey"]].set(
            jnp.arange(orders.num_rows, dtype=jnp.int32))
        oidx = lut[li["l_orderkey"]]
        ok = (oidx >= 0) & li.mask()
        return acc.at[jnp.where(ok, oidx, 0)].add(
            jnp.where(ok, li["l_price"], 0))

    def fin(acc, src, orders):
        return ColumnTable(cols={"okey": orders["o_orderkey"],
                                 "rev": acc},
                           valid=acc > 0)

    def merge(a, b):
        return ColumnTable(
            cols={"okey": jnp.concatenate([a["okey"], b["okey"]]),
                  "rev": jnp.concatenate([a["rev"], b["rev"]])},
            valid=jnp.concatenate([a.mask(), b.mask()]))

    return WriteSet(
        Join(ScanSet(db, lineitem_set), ScanSet(db, orders_set),
             fold=single_pass(init, step, fin, merge,
                              probe_key="l_orderkey",
                              build_key="o_orderkey",
                              probe_columns=("l_price",)),
             label=f"scalejoin:{key_space}"),
        db, output_set)


def scale_rows(client, db: str, out_set: str):
    """Decoded, canonically-ordered result rows (the byte-equality
    probe)."""
    t = client.get_table(db, out_set)
    ok = np.asarray(t.mask()) if t.valid is not None \
        else np.ones(t.num_rows, bool)
    names = sorted(t.cols)
    rows = [tuple(int(np.asarray(t[n])[i]) for n in names)
            for i in range(t.num_rows) if ok[i]]
    return sorted(rows)
