"""dbgen-shaped TPC-H tables at a scale factor, generated directly as
columns (lineitem ≈ 6M·SF, orders = 1.5M·SF, customer = 150k·SF, part =
200k·SF)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from netsdb_tpu.relational.queries import Tables
from netsdb_tpu.relational.table import ColumnTable


def generate_columnar(sf: float = 0.1, seed: int = 0) -> Tables:
    """dbgen-shaped synthetic tables, built directly as columns (no row
    dicts — row generation at SF≥0.1 would dominate a run).
    Distributions follow dbgen's ranges; string domains are the real
    TPC-H enumerations, dictionary-encoded. Covers all eight tables so
    every columnar query (incl. Q02's five-way join and Q22's
    anti-join) runs at dbgen scale: supplier 10k·SF, partsupp =
    4 suppliers per part, nation 25, region 5."""
    rng = np.random.default_rng(seed)
    n_li = int(6_000_000 * sf)
    n_ord = int(1_500_000 * sf)
    n_cust = int(150_000 * sf)
    n_part = int(200_000 * sf)
    n_sup = max(int(10_000 * sf), 1)

    def dates(n):
        return (rng.integers(1992, 1999, n) * 10000
                + rng.integers(1, 13, n) * 100
                + rng.integers(1, 29, n)).astype(np.int32)

    flags = ["A", "N", "R"]
    status = ["F", "O"]
    modes = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
    prios = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
    segs = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
    brands = sorted(f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6))
    containers = sorted(["SM CASE", "MED BOX", "LG JAR", "WRAP PACK",
                         "JUMBO PKG"])
    types = sorted(["PROMO BURNISHED", "STANDARD POLISHED",
                    "ECONOMY ANODIZED", "PROMO PLATED", "MEDIUM BRUSHED"])

    commit = dates(n_li)
    lineitem = ColumnTable(
        cols={
            "l_orderkey": rng.integers(0, n_ord, n_li).astype(np.int32),
            "l_partkey": rng.integers(0, n_part, n_li).astype(np.int32),
            "l_quantity": rng.integers(1, 51, n_li).astype(np.int32),
            "l_extendedprice": (rng.uniform(1000, 100000, n_li)
                                .astype(np.float32)),
            "l_discount": np.round(rng.uniform(0.0, 0.1, n_li), 2)
            .astype(np.float32),
            "l_tax": np.round(rng.uniform(0.0, 0.08, n_li), 2)
            .astype(np.float32),
            "l_returnflag": rng.integers(0, 3, n_li).astype(np.int32),
            "l_linestatus": rng.integers(0, 2, n_li).astype(np.int32),
            "l_shipmode": rng.integers(0, 7, n_li).astype(np.int32),
            "l_shipdate": dates(n_li),
            "l_commitdate": commit,
            "l_receiptdate": (commit
                              + rng.integers(-5, 15, n_li).astype(np.int32)),
        },
        dicts={"l_returnflag": flags, "l_linestatus": status,
               "l_shipmode": modes},
    )
    orders = ColumnTable(
        cols={
            "o_orderkey": np.arange(n_ord, dtype=np.int32),
            "o_custkey": rng.integers(0, n_cust, n_ord).astype(np.int32),
            "o_orderdate": dates(n_ord),
            "o_orderpriority": rng.integers(0, 5, n_ord).astype(np.int32),
        },
        dicts={"o_orderpriority": prios},
    )
    # dbgen phone country codes are 10..34; Q22 groups by the 2-char
    # prefix, so a 25-entry dictionary of representative numbers suffices
    phones = [f"{cc}-555-{cc:03d}-{cc * 37 % 10000:04d}"
              for cc in range(10, 35)]
    customer = ColumnTable(
        cols={
            "c_custkey": np.arange(n_cust, dtype=np.int32),
            "c_mktsegment": rng.integers(0, 5, n_cust).astype(np.int32),
            "c_acctbal": rng.uniform(-999, 9999, n_cust).astype(np.float32),
            "c_phone": rng.integers(0, len(phones), n_cust).astype(np.int32),
        },
        dicts={"c_mktsegment": segs, "c_phone": phones},
    )
    part = ColumnTable(
        cols={
            "p_partkey": np.arange(n_part, dtype=np.int32),
            "p_brand": rng.integers(0, len(brands), n_part).astype(np.int32),
            "p_container": rng.integers(0, len(containers), n_part)
            .astype(np.int32),
            "p_size": rng.integers(1, 51, n_part).astype(np.int32),
            "p_type": rng.integers(0, len(types), n_part).astype(np.int32),
        },
        dicts={"p_brand": brands, "p_container": containers,
               "p_type": types},
    )
    n_ps = 4 * n_part  # dbgen: four suppliers per part
    partsupp = ColumnTable(cols={
        "ps_partkey": np.repeat(np.arange(n_part, dtype=np.int32), 4),
        "ps_suppkey": rng.integers(0, n_sup, n_ps).astype(np.int32),
        "ps_supplycost": rng.uniform(1, 1000, n_ps).astype(np.float32),
    })
    sup_names = [f"Supplier#{i:09d}" for i in range(n_sup)]
    supplier = ColumnTable(
        cols={
            "s_suppkey": np.arange(n_sup, dtype=np.int32),
            "s_nationkey": rng.integers(0, 25, n_sup).astype(np.int32),
            "s_name": np.arange(n_sup, dtype=np.int32),
        },
        dicts={"s_name": sup_names},
    )
    regions = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
    nat_names = [f"NATION{i:02d}" for i in range(25)]
    nation = ColumnTable(
        cols={
            "n_nationkey": np.arange(25, dtype=np.int32),
            "n_regionkey": (np.arange(25, dtype=np.int32) % 5),
            "n_name": np.arange(25, dtype=np.int32),
        },
        dicts={"n_name": nat_names},
    )
    region = ColumnTable(
        cols={
            "r_regionkey": np.arange(5, dtype=np.int32),
            "r_name": np.arange(5, dtype=np.int32),
        },
        dicts={"r_name": regions},
    )
    tables = {"lineitem": lineitem, "orders": orders, "customer": customer,
              "part": part, "partsupp": partsupp, "supplier": supplier,
              "nation": nation, "region": region}
    for t in tables.values():
        t.cols = {k: jnp.asarray(v) for k, v in t.cols.items()}
    return tables
