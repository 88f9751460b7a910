"""Columnar tpchBench — the nested Customer⋈Order⋈LineItem micro-family
on the device engine.

Round 1 ran this family (``src/tpchBench``) over host dataclasses
through the interpreter plan path (``workloads/tpch_bench.py``). Here
the nested object graph columnarizes at ingest — customers as one
table, the orders→lineItems nesting FLATTENED into a triples table
(customer, supplier, part), which is exactly what the reference's
``CustomerMultiSelection`` → ``CustomerSupplierPartFlat`` computes per
query — and each query shape becomes one jitted kernel:

- int/string selections → masks (``CustomerIntegerSelection[Not].h``,
  ``CustomerStringSelection[Not].h``);
- group-by supplier → segment counts over (supplier, customer) pairs
  (``CustomerSupplierPartGroupBy.h``);
- count aggregation → one reduction (``CountAggregation.h``);
- top-K Jaccard (``TopJaccard.h:17``) → the TPU-native form: the
  customer×part membership matrix is built ONCE with a scatter, then
  every query part-set is a MATVEC on the MXU — intersection sizes for
  all customers in one pass, |union| by inclusion-exclusion, one
  ``lax.top_k``. Set similarity as matmul is the same collapse that
  turned the reference's matmul-as-join into ``dot_general``.

Cross-checked against the host-object pipeline on identical data
(tests/test_tpch_bench_columnar.py).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu.relational import kernels as K
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.workloads.tpch_bench import Customer


# ------------------------------------------------------------- ingest
def columnarize(customers: Sequence[Customer]
                ) -> Dict[str, ColumnTable]:
    """Nested customers → flat columnar tables. The orders→lineItems
    graph flattens into one triples row per line item (the reference
    re-derives these triples inside every query; materializing them
    once at ingest is the columnar engine's scan set)."""
    segs = sorted({c.mktsegment for c in customers})
    seg_code = {s: i for i, s in enumerate(segs)}
    n = len(customers)
    cust = ColumnTable({
        "custKey": jnp.asarray(np.fromiter((c.custKey for c in customers),
                                           np.int32, n)),
        "nationKey": jnp.asarray(np.fromiter(
            (c.nationKey for c in customers), np.int32, n)),
        "mktsegment": jnp.asarray(np.fromiter(
            (seg_code[c.mktsegment] for c in customers), np.int32, n)),
        "accbal": jnp.asarray(np.fromiter(
            (c.accbal for c in customers), np.float32, n)),
    }, dicts={"mktsegment": segs})

    sup_names = sorted({li.supplierName for c in customers
                        for o in c.orders for li in o.lineItems})
    sup_code = {s: i for i, s in enumerate(sup_names)}
    ck, sup, part = [], [], []
    for c in customers:
        for o in c.orders:
            for li in o.lineItems:
                ck.append(c.custKey)
                sup.append(sup_code[li.supplierName])
                part.append(li.partKey)
    triples = ColumnTable({
        "custKey": jnp.asarray(np.asarray(ck, np.int32)),
        "supplier": jnp.asarray(np.asarray(sup, np.int32)),
        "partKey": jnp.asarray(np.asarray(part, np.int32)),
    }, dicts={"supplier": sup_names})
    from netsdb_tpu.relational.stats import analyze_table

    analyze_table(cust)
    analyze_table(triples)
    return {"customers": cust, "triples": triples}


# --------------------------------------------------------- selections
@jax.jit
def _selection_masks(custKey, mktsegment, threshold, seg_code):
    int_sel = custKey > threshold
    str_sel = mktsegment == seg_code
    return int_sel, ~int_sel, str_sel, ~str_sel


def selections(tables: Dict[str, ColumnTable], threshold: int = 0,
               segment: str = "BUILDING"):
    """All four selection variants (int/string × plain/negated) in one
    kernel — masks, the columnar engine's selected sets."""
    cust = tables["customers"]
    return _selection_masks(cust["custKey"], cust["mktsegment"],
                            threshold, cust.code("mktsegment", segment))


# --------------------------------------------------- group-by supplier
@functools.partial(jax.jit, static_argnums=(0, 1))
def _supplier_group_core(n_sup: int, n_cust: int, supplier, custKey):
    pair = supplier * n_cust + custKey
    pair_counts = K.segment_count(pair, n_sup * n_cust)
    per_supplier = K.segment_count(supplier, n_sup)
    return pair_counts, per_supplier


def group_by_supplier(tables: Dict[str, ColumnTable]):
    """supplier → (per-(supplier,customer) part counts, per-supplier
    totals): the fixed-shape aggregate backing ``SupplierInfo`` (the
    variable-length part lists stay derivable from the triples by the
    pair mask; the counts are what the benchmark's checks consume)."""
    from netsdb_tpu.relational.stats import key_space

    t = tables["triples"]
    n_sup = len(t.dicts["supplier"])
    n_cust = key_space(tables["customers"], "custKey")
    pair, per = _supplier_group_core(n_sup, n_cust, t["supplier"],
                                     t["custKey"])
    return pair.reshape(n_sup, n_cust), per


def count_customers(tables: Dict[str, ColumnTable]) -> int:
    return tables["customers"].num_rows


# ------------------------------------------------------ top-K jaccard
@functools.partial(jax.jit, static_argnums=(0, 1))
def _membership_matrix(n_cust: int, n_parts: int, custKey, partKey):
    """(n_cust, n_parts) 0/1 membership — built once, amortized over
    every Jaccard query."""
    flat = custKey * n_parts + jnp.clip(partKey, 0, n_parts - 1)
    m = jnp.zeros((n_cust * n_parts,), jnp.float32).at[flat].max(
        jnp.ones_like(flat, jnp.float32))
    return m.reshape(n_cust, n_parts)


@functools.partial(jax.jit, static_argnums=(2,))
def _jaccard_core(member, query_vec, k: int):
    sizes = member.sum(axis=1)
    inter = member @ query_vec  # MXU matvec: all intersections at once
    union = sizes + query_vec.sum() - inter
    j = jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)
    vals, idx = jax.lax.top_k(j, k)
    return vals, idx


def top_jaccard(tables: Dict[str, ColumnTable],
                query_parts: Sequence[int], k: int = 5
                ) -> List[Tuple[float, int]]:
    """Top-k customers by Jaccard similarity against ``query_parts`` —
    returns [(score, custKey)] best-first (ties broken by custKey
    ascending, matching the host heap's ordering)."""
    from netsdb_tpu.relational.stats import key_space

    t = tables["triples"]
    n_cust = key_space(tables["customers"], "custKey")
    n_parts = max(key_space(t, "partKey"),
                  max(query_parts, default=0) + 1)
    member = _membership_matrix(n_cust, n_parts, t["custKey"],
                                t["partKey"])
    q = np.zeros((n_parts,), np.float32)
    for p in set(query_parts):
        q[p] = 1.0
    vals, idx = _jaccard_core(member, jnp.asarray(q), k)
    out = sorted(zip(np.asarray(vals).tolist(),
                     np.asarray(idx).tolist()),
                 key=lambda si: (-si[0], si[1]))
    return [(float(s), int(i)) for s, i in out]


def queries_on_sets(client, db: str = "tpchbench", threshold: int = 0,
                    segment: str = "BUILDING",
                    query_parts: Sequence[int] = (0,), k: int = 5):
    """Placed-set entry point: the whole micro-family against STORED
    sets — with ``customers``/``triples`` created under a row-sharding
    Placement the same kernels run distributed (XLA inserts the
    segment-psums; placement padding folds to -1 keys and drops by the
    orphan rule). Returns {selections, pair_counts, per_supplier,
    count, top_jaccard} — the shapes the benchmark's checks consume."""
    from netsdb_tpu.relational.dag import _fold_mask
    from netsdb_tpu.relational.stats import analyze_table, inject_stats

    raw = {n: client.get_table(db, n) for n in ("customers", "triples")}
    cust_mask = raw["customers"].mask()
    tables = {n: inject_stats(_fold_mask(t), analyze_table(t))
              for n, t in raw.items()}
    sels = tuple(m & cust_mask
                 for m in selections(tables, threshold, segment))
    pair, per = group_by_supplier(tables)
    return {
        "selections": sels,
        "pair_counts": pair,
        "per_supplier": per,
        "count": int(jnp.sum(cust_mask)),
        "top_jaccard": top_jaccard(tables, list(query_parts), k),
    }
