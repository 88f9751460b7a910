"""Columnar TPC-H queries — the device-side counterparts of
``netsdb_tpu.workloads.tpch``.

Same ten queries as the reference (``src/tpch/source/Query01..22``) and
as the host row engine, but each query body is one (or two) jitted
array programs: filters are masks, group-bys are segment reductions,
joins are searchsorted gathers (see :mod:`netsdb_tpu.relational.kernels`).
String/LIKE predicates are evaluated once on the host dictionary and
broadcast to rows as code lookups — dictionary encoding turns the
reference's per-row string compares into O(|dict|) host work plus an
int gather on device.

Two latency rules shape the code (every host⇄device sync is a round
trip, and compiles cost seconds):

- every jitted core is a **module-level** function, so ``jax.jit``'s
  cache hits across calls — a core defined inside the query wrapper
  would recompile on every invocation (this is the same economics that
  makes the reference cache physical plans in PreCompiledWorkload,
  ``src/queryPlanning/headers/PreCompiledWorkload.h``);
- each core packs its results into as few arrays as possible, because
  every host pull is one round-trip. Scalar predicate parameters
  (dates, codes) are passed as traced scalars, not baked constants, so
  changing a parameter does not retrace.

Every query function takes ``tables`` (dict of ColumnTable) and returns
the same Python result structure as the row engine's query, so the two
engines are cross-checkable on identical data (tests/test_relational.py).

Group cardinalities (static ``num_segments``) come from host-side key
maxima, computed once per table load and cached on the ColumnTable —
the role the reference's ``Statistics`` set-size metadata plays for its
planner (``src/queryPlanning/headers/TCAPAnalyzer.h``).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu.relational import kernels as K
from netsdb_tpu.relational import planner as P
from netsdb_tpu.relational.stats import analyze_table, key_space
from netsdb_tpu.relational.table import ColumnTable, date_to_int, int_to_date

Tables = Dict[str, ColumnTable]

# Join strategies are chosen by the statistics-driven planner
# (`P.plan_join` reading ingest-time column stats), not by per-call
# `key_space=` arguments as in round 1 — the choice follows the data.
# The resulting JoinPlan is a hashable static argument, so each
# (strategy, key_space) pair compiles once and is cached like any other
# static shape.


def _lut(dictionary: List[str], pred: Callable[[str], bool]) -> jnp.ndarray:
    """Host-evaluated string predicate → device bool LUT over codes."""
    return jnp.asarray(np.fromiter((pred(s) for s in dictionary),
                                   np.bool_, len(dictionary)))


def _ct(tables: Tables, name: str) -> ColumnTable:
    """Fetch a table for the direct columnar path, compacting away any
    validity mask first (placement row-padding, applied filters): the
    jitted cores below predate table masks and assume every row is real.
    ``compact()`` is identity for mask-free tables, so the common path
    costs one dict lookup. The set-API DAG path (relational/dag.py)
    instead keeps the mask and ANDs it — static shapes for jit."""
    return tables[name].compact()


# ---------------------------------------------------------------- Q01
def _q01_fold(n_groups, n_ls, rf, ls, qty, price, disc, tax, mask):
    """Shared Q01 reduction body — used by the direct columnar path
    (`_q01_core`) and by the set-API DAG (`relational.dag.q01_sink`),
    which ANDs the table validity mask in (placement row-padding)."""
    seg = rf * n_ls + ls
    qty = qty.astype(jnp.float32)
    disc_price = price * (1.0 - disc)
    charge = disc_price * (1.0 + tax)
    rows = [K.segment_sum(v, seg, n_groups, mask)
            for v in (qty, price, disc_price, charge, disc)]
    # counts stay int32: a float32 count saturates at 2^24 rows/group
    return jnp.stack(rows), K.segment_count(seg, n_groups, mask)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _q01_core(n_groups, n_ls, ship, rf, ls, qty, price, disc, tax, delta):
    return _q01_fold(n_groups, n_ls, rf, ls, qty, price, disc, tax,
                     ship <= delta)


def _args_q01(tables: Tables, delta_date: str = "1998-09-02"):
    li = _ct(tables, "lineitem")
    n_ls = len(li.dicts["l_linestatus"])
    n_groups = len(li.dicts["l_returnflag"]) * n_ls
    return (n_groups, n_ls, li["l_shipdate"], li["l_returnflag"],
            li["l_linestatus"], li["l_quantity"], li["l_extendedprice"],
            li["l_discount"], li["l_tax"], date_to_int(delta_date))


def cq01(tables: Tables, delta_date: str = "1998-09-02"):
    """Pricing summary report. One segment-reduction pass over lineitem."""
    li = _ct(tables, "lineitem")
    n_ls = len(li.dicts["l_linestatus"])
    n_groups = len(li.dicts["l_returnflag"]) * n_ls
    sums, counts = jax.device_get(_q01_core(*_args_q01(tables, delta_date)))
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "sum_disc")
    out = []
    for g in range(n_groups):
        cnt = int(counts[g])
        if cnt == 0:
            continue
        key = (li.decode("l_returnflag", g // n_ls),
               li.decode("l_linestatus", g % n_ls))
        v = {names[i]: float(sums[i, g]) for i in range(5)}
        v["count"] = cnt
        v["avg_qty"] = v["sum_qty"] / cnt
        v["avg_price"] = v["sum_base_price"] / cnt
        v["avg_disc"] = v["sum_disc"] / cnt
        out.append((key, v))
    out.sort(key=lambda kv: kv[0])
    return out


# ---------------------------------------------------------------- Q02
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _q02_core(jp_part, jp_sup, jp_nat, jp_reg,
              p_key, p_size, p_type, ps_part, ps_supp, ps_cost,
              s_key, s_nat, r_key, r_name, n_key, n_reg,
              type_ok, size, region_code):
    n_part = jp_part.key_space
    part_ok = (p_size == size) & jnp.take(type_ok, p_type)
    # partsupp ⋈ part (restrict to qualifying parts)
    _, phit = K.pk_fk_join(p_key, ps_part, part_ok, plan=jp_part)
    # supplier ⋈ nation ⋈ region chain, evaluated on the supplier side;
    # nation columns come through the join's row index (keys need not
    # equal row positions)
    nidx, nhit = K.pk_fk_join(n_key, s_nat, plan=jp_nat)
    sup_region = jnp.take(n_reg, nidx)
    ridx, rhit = K.pk_fk_join(r_key, sup_region, plan=jp_reg)
    in_region = nhit & rhit & (jnp.take(r_name, ridx) == region_code)
    sup_ok = in_region
    # partsupp ⋈ supplier
    sidx, shit = K.pk_fk_join(s_key, ps_supp, sup_ok, plan=jp_sup)
    valid = phit & shit
    # min cost per part, then the first row achieving it (the row
    # engine's combine keeps the earlier row on ties)
    cost_min = K.segment_min(ps_cost, ps_part, n_part, valid)
    at_min = valid & (ps_cost == jnp.take(cost_min, ps_part))
    rows = jnp.arange(ps_part.shape[0], dtype=jnp.int32)
    winner = K.segment_min(rows, ps_part, n_part, at_min)
    has = winner < jnp.iinfo(jnp.int32).max
    winner_c = jnp.clip(winner, 0, ps_part.shape[0] - 1)
    # non-qualifying parts hold deterministic zeros (not clip garbage):
    # the streamed fold produces the same, so whole-table and paged
    # outputs compare array-for-array
    sup_row = jnp.where(has, jnp.take(sidx, winner_c), 0)
    nat_row = jnp.where(has, jnp.take(nidx, sup_row), 0)
    ints = jnp.stack([has.astype(jnp.int32), sup_row, nat_row])
    return ints, cost_min


def _args_q02(tables: Tables, size: int = 15, type_suffix: str = "BRUSHED",
              region: str = "EUROPE"):
    part, ps = _ct(tables, "part"), _ct(tables, "partsupp")
    sup, nat, reg = _ct(tables, "supplier"), _ct(tables, "nation"), _ct(tables, "region")
    type_ok = _lut(part.dicts["p_type"], lambda s: s.endswith(type_suffix))
    return (P.plan_join(part, "p_partkey", ps, "ps_partkey"),
            P.plan_join(sup, "s_suppkey", ps, "ps_suppkey"),
            P.plan_join(nat, "n_nationkey", sup, "s_nationkey"),
            P.plan_join(reg, "r_regionkey", nat, "n_regionkey"),
            part["p_partkey"], part["p_size"], part["p_type"],
            ps["ps_partkey"], ps["ps_suppkey"], ps["ps_supplycost"],
            sup["s_suppkey"], sup["s_nationkey"],
            reg["r_regionkey"], reg["r_name"],
            nat["n_nationkey"], nat["n_regionkey"],
            type_ok, size, reg.code("r_name", region))


def cq02(tables: Tables, size: int = 15, type_suffix: str = "BRUSHED",
         region: str = "EUROPE"):
    """Minimum-cost supplier per qualifying part."""
    sup, nat = _ct(tables, "supplier"), _ct(tables, "nation")
    ints, cost_min = _q02_core(*_args_q02(tables, size, type_suffix, region))
    ints, cost_min = np.asarray(ints), np.asarray(cost_min)
    s_names = np.asarray(sup["s_name"])
    n_names = np.asarray(nat["n_name"])
    out = []
    for pk in np.nonzero(ints[0])[0]:  # only qualifying parts
        pk = int(pk)
        out.append((pk, {"partkey": pk, "cost": float(cost_min[pk]),
                         "s_name": sup.decode(
                             "s_name", int(s_names[ints[1, pk]])),
                         "n_name": nat.decode(
                             "n_name", int(n_names[ints[2, pk]]))}))
    return out


# ---------------------------------------------------------------- Q03
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _q03_core(jp_orders, k, jp_cust, c_key, c_seg, o_key, o_cust, o_date,
              l_okey, l_ship, l_price, l_disc, seg_code, d):
    n_orders = jp_orders.key_space
    cust_ok = c_seg == seg_code
    _, chit = K.pk_fk_join(c_key, o_cust, cust_ok, plan=jp_cust)
    order_ok = chit & (o_date < d)
    oidx, ohit = K.pk_fk_join(o_key, l_okey, order_ok, plan=jp_orders)
    li_ok = ohit & (l_ship > d)
    rev = K.segment_sum(l_price * (1.0 - l_disc), l_okey, n_orders, li_ok)
    odate_per_order = K.segment_min(
        jnp.take(o_date, oidx), l_okey, n_orders, li_ok)
    top_idx, top_ok = K.top_k_masked(rev, k, rev > 0)
    ints = jnp.stack([top_idx, top_ok.astype(jnp.int32),
                      jnp.take(odate_per_order, top_idx)])
    return ints, jnp.take(rev, top_idx)


def _args_q03(tables: Tables, segment: str = "BUILDING",
              date: str = "1995-03-15", k: int = 10):
    cust, orders, li = (_ct(tables, "customer"), _ct(tables, "orders"),
                        _ct(tables, "lineitem"))
    return (P.plan_join(orders, "o_orderkey", li, "l_orderkey"), k,
            P.plan_join(cust, "c_custkey", orders, "o_custkey"),
            cust["c_custkey"],
            cust["c_mktsegment"], orders["o_orderkey"], orders["o_custkey"],
            orders["o_orderdate"], li["l_orderkey"], li["l_shipdate"],
            li["l_extendedprice"], li["l_discount"],
            cust.code("c_mktsegment", segment), date_to_int(date))


def cq03(tables: Tables, segment: str = "BUILDING",
         date: str = "1995-03-15", k: int = 10):
    """Top unshipped orders by revenue."""
    ints, rev = _q03_core(*_args_q03(tables, segment, date, k))
    ints, rev = np.asarray(ints), np.asarray(rev)
    rows = [{"okey": int(ints[0, j]), "odate": int_to_date(int(ints[2, j])),
             "revenue": float(rev[j])}
            for j in range(ints.shape[1]) if ints[1, j]]
    rows.sort(key=lambda r: (-r["revenue"], r["odate"]))
    return rows


# ---------------------------------------------------------------- Q04
@functools.partial(jax.jit, static_argnums=(0, 1))
def _q04_core(n_pri, jp_li, o_key, o_date, o_pri, l_okey, l_commit,
              l_receipt, a, b):
    late = l_commit < l_receipt
    has_late = K.member(l_okey, o_key, late, plan=jp_li)
    in_q = (o_date >= a) & (o_date < b)
    return K.segment_count(o_pri, n_pri, has_late & in_q)


def _args_q04(tables: Tables, d0: str = "1993-07-01",
              d1: str = "1993-10-01"):
    orders, li = _ct(tables, "orders"), _ct(tables, "lineitem")
    n_pri = len(orders.dicts["o_orderpriority"])
    return (n_pri, P.plan_join(li, "l_orderkey", orders, "o_orderkey"),
            orders["o_orderkey"], orders["o_orderdate"],
            orders["o_orderpriority"], li["l_orderkey"], li["l_commitdate"],
            li["l_receiptdate"], date_to_int(d0), date_to_int(d1))


def cq04(tables: Tables, d0: str = "1993-07-01", d1: str = "1993-10-01"):
    """Orders with ≥1 late lineitem, counted per priority."""
    orders = _ct(tables, "orders")
    n_pri = len(orders.dicts["o_orderpriority"])
    counts = np.asarray(_q04_core(*_args_q04(tables, d0, d1)))
    out = [(orders.decode("o_orderpriority", i), int(counts[i]))
           for i in range(n_pri) if counts[i]]
    out.sort(key=lambda kv: kv[0])
    return out


# ---------------------------------------------------------------- Q06
@jax.jit
def _q06_core(ship, discount, quantity, price, a, b, disc, qty):
    mask = ((ship >= a) & (ship < b)
            & (discount >= disc - 0.011) & (discount <= disc + 0.011)
            & (quantity < qty))
    return jnp.sum(jnp.where(mask, price * discount, 0.0))


def _args_q06(tables: Tables, d0: str = "1994-01-01",
              d1: str = "1995-01-01", disc: float = 0.06, qty: int = 24):
    li = _ct(tables, "lineitem")
    return (li["l_shipdate"], li["l_discount"],
            li["l_quantity"], li["l_extendedprice"],
            date_to_int(d0), date_to_int(d1), disc, qty)


def cq06(tables: Tables, d0: str = "1994-01-01", d1: str = "1995-01-01",
         disc: float = 0.06, qty: int = 24):
    """Revenue-change forecast: one fused filtered reduction."""
    rev = float(_q06_core(*_args_q06(tables, d0, d1, disc, qty)))
    return [("revenue", rev)]


# ---------------------------------------------------------------- Q12
@functools.partial(jax.jit, static_argnums=(0, 1))
def _q12_core(n_modes, jp_orders, o_key, o_pri, l_okey, l_mode, l_ship,
              l_commit, l_receipt, hi_lut, m1, m2, a, b):
    mask = (((l_mode == m1) | (l_mode == m2))
            & (l_commit < l_receipt) & (l_ship < l_commit)
            & (l_receipt >= a) & (l_receipt < b))
    oidx, ohit = K.pk_fk_join(o_key, l_okey, plan=jp_orders)
    mask = mask & ohit
    high = jnp.take(hi_lut, jnp.take(o_pri, oidx))
    return jnp.stack([K.segment_count(l_mode, n_modes, mask & high),
                      K.segment_count(l_mode, n_modes, mask & ~high)])


def _args_q12(tables: Tables, mode1: str = "MAIL", mode2: str = "SHIP",
              d0: str = "1994-01-01", d1: str = "1995-01-01"):
    orders, li = _ct(tables, "orders"), _ct(tables, "lineitem")
    n_modes = len(li.dicts["l_shipmode"])
    m1, m2 = li.code("l_shipmode", mode1), li.code("l_shipmode", mode2)
    hi = _lut(orders.dicts["o_orderpriority"],
              lambda s: s in ("1-URGENT", "2-HIGH"))
    return (n_modes, P.plan_join(orders, "o_orderkey", li, "l_orderkey"),
            orders["o_orderkey"], orders["o_orderpriority"],
            li["l_orderkey"], li["l_shipmode"], li["l_shipdate"],
            li["l_commitdate"], li["l_receiptdate"], hi, m1, m2,
            date_to_int(d0), date_to_int(d1))


def cq12(tables: Tables, mode1: str = "MAIL", mode2: str = "SHIP",
         d0: str = "1994-01-01", d1: str = "1995-01-01"):
    """High/low-priority lineitems per ship mode."""
    li = _ct(tables, "lineitem")
    m1, m2 = li.code("l_shipmode", mode1), li.code("l_shipmode", mode2)
    packed = np.asarray(_q12_core(*_args_q12(tables, mode1, mode2, d0, d1)))
    out = [(li.decode("l_shipmode", m),
            {"high": int(packed[0, m]), "low": int(packed[1, m])})
           for m in (m1, m2)
           if m >= 0 and packed[0, m] + packed[1, m] > 0]
    out.sort(key=lambda kv: kv[0])
    return out


# ---------------------------------------------------------------- Q13
# Static histogram domain: per-customer order counts are ~10-40 at any
# dbgen scale factor (orders/customer is fixed by the spec), so a
# generous static cap keeps n_buckets host-static — no mid-query host
# pull of max(counts) and no per-dataset recompile. Overflow (counts
# >= cap) is detected on device and handled by an exact host fallback.
_Q13_CAP = 256


@functools.partial(jax.jit, static_argnums=(0, 1))
def _q13_core(n_cust, cap, o_cust, keep, c_key):
    counts = K.segment_count(o_cust, n_cust, keep)
    per_cust = jnp.take(counts, c_key)
    hist = K.bincount_masked(jnp.minimum(per_cust, cap - 1), cap)
    return hist, jnp.max(per_cust, initial=0)


@functools.partial(jax.jit, static_argnums=(0,))
def _q13_per_cust(n_cust, o_cust, keep, c_key):
    return jnp.take(K.segment_count(o_cust, n_cust, keep), c_key)


def _q13_keep(tables: Tables, word1: str, word2: str) -> jnp.ndarray:
    import re

    orders = _ct(tables, "orders")
    if "o_comment" in orders.dicts:
        pat = re.compile(f"{re.escape(word1)}.*{re.escape(word2)}")
        keep_lut = _lut(orders.dicts["o_comment"],
                        lambda s: not pat.search(s))
        return jnp.take(keep_lut, orders["o_comment"])
    return jnp.ones((orders.num_rows,), jnp.bool_)


def _args_q13(tables: Tables, word1: str = "special",
              word2: str = "requests"):
    cust, orders = _ct(tables, "customer"), _ct(tables, "orders")
    return (key_space(cust, "c_custkey"), _Q13_CAP, orders["o_custkey"],
            _q13_keep(tables, word1, word2), cust["c_custkey"])


def cq13(tables: Tables, word1: str = "special", word2: str = "requests"):
    """Histogram of per-customer order counts (zero included — the
    left-outer-join semantics)."""
    cust, orders = _ct(tables, "customer"), _ct(tables, "orders")
    n_cust = key_space(cust, "c_custkey")
    args = _args_q13(tables, word1, word2)
    keep = args[3]  # reused by the over-cap exact fallback below
    hist, maxc = jax.device_get(_q13_core(*args))
    maxc = int(maxc)
    if maxc >= _Q13_CAP:  # beyond any dbgen shape: exact host fallback
        per = np.asarray(_q13_per_cust(n_cust, orders["o_custkey"], keep,
                                       cust["c_custkey"]))
        hist = np.bincount(per, minlength=maxc + 1)
    return [(i, int(hist[i])) for i in range(maxc + 1) if hist[i]]


# ---------------------------------------------------------------- Q14
@functools.partial(jax.jit, static_argnums=(0,))
def _q14_core(jp_part, p_key, p_type, l_part, l_ship, l_price, l_disc,
              promo_lut, a, b):
    mask = (l_ship >= a) & (l_ship < b)
    pidx, phit = K.pk_fk_join(p_key, l_part, plan=jp_part)
    mask = mask & phit
    rev = jnp.where(mask, l_price * (1.0 - l_disc), 0.0)
    is_promo = jnp.take(promo_lut, jnp.take(p_type, pidx))
    return jnp.stack([jnp.sum(jnp.where(is_promo, rev, 0.0)), jnp.sum(rev)])


def _args_q14(tables: Tables, d0: str = "1995-09-01",
              d1: str = "1995-10-01"):
    li, part = _ct(tables, "lineitem"), _ct(tables, "part")
    promo = _lut(part.dicts["p_type"], lambda s: s.startswith("PROMO"))
    return (P.plan_join(part, "p_partkey", li, "l_partkey"),
            part["p_partkey"], part["p_type"], li["l_partkey"],
            li["l_shipdate"], li["l_extendedprice"], li["l_discount"],
            promo, date_to_int(d0), date_to_int(d1))


def cq14(tables: Tables, d0: str = "1995-09-01", d1: str = "1995-10-01"):
    """% of revenue from promo parts."""
    pr, total = np.asarray(_q14_core(*_args_q14(tables, d0, d1)))
    pct = 100.0 * float(pr) / float(total) if total else 0.0
    return [("promo_revenue_pct", pct)]


# ---------------------------------------------------------------- Q17
@functools.partial(jax.jit, static_argnums=(0,))
def _q17_core(jp_part, p_key, p_brand, p_cont, l_part, l_qty, l_price,
              brand_code, cont_code):
    part_ok = (p_brand == brand_code) & (p_cont == cont_code)
    _, phit = K.pk_fk_join(p_key, l_part, part_ok, plan=jp_part)
    qty = l_qty.astype(jnp.float32)
    avg = K.segment_mean(qty, l_part, jp_part.key_space, phit)
    small = phit & (qty < 0.2 * jnp.take(avg, l_part))
    return jnp.sum(jnp.where(small, l_price, 0.0)) / 7.0


def _args_q17(tables: Tables, brand: str = "Brand#23",
              container: str = "MED BOX"):
    li, part = _ct(tables, "lineitem"), _ct(tables, "part")
    return (P.plan_join(part, "p_partkey", li, "l_partkey"),
            part["p_partkey"],
            part["p_brand"], part["p_container"], li["l_partkey"],
            li["l_quantity"], li["l_extendedprice"],
            part.code("p_brand", brand),
            part.code("p_container", container))


def cq17(tables: Tables, brand: str = "Brand#23", container: str = "MED BOX"):
    """Revenue from small-quantity orders of one brand/container."""
    total = float(_q17_core(*_args_q17(tables, brand, container)))
    return [("avg_yearly", total)] if total else []


# ---------------------------------------------------------------- Q22
@functools.partial(jax.jit, static_argnums=(0, 1))
def _q22_core(n_pref, jp_cust, c_key, c_phone, c_bal, o_cust, code_lut):
    pref = jnp.take(code_lut, c_phone)
    in_pref = pref >= 0
    pos = in_pref & (c_bal > 0)
    avg = (jnp.sum(jnp.where(pos, c_bal, 0.0))
           / jnp.maximum(jnp.sum(pos.astype(jnp.int32)), 1))
    rich = in_pref & (c_bal > avg)
    has_orders = K.member(o_cust, c_key, plan=jp_cust)
    sel = rich & ~has_orders
    seg = jnp.clip(pref, 0, n_pref - 1)
    return jnp.stack([K.segment_count(seg, n_pref, sel).astype(jnp.float32),
                      K.segment_sum(c_bal, seg, n_pref, sel)])


def q22_code_lut(phone_dict: List[str], prefixes: Sequence[str]
                 ) -> Tuple[List[str], jnp.ndarray]:
    """Phone-dictionary → prefix-group code LUT (-1 = no group). Shared
    by the local and sharded Q22 engines so prefix semantics cannot
    diverge."""
    pref_list = sorted(set(prefixes))
    pref_idx = {p: i for i, p in enumerate(pref_list)}
    lut = jnp.asarray(np.fromiter(
        (pref_idx.get(s[:2], -1) for s in phone_dict), np.int32,
        len(phone_dict)))
    return pref_list, lut


def _args_q22(tables: Tables,
              prefixes: Sequence[str] = ("13", "31", "23", "29", "30",
                                         "18", "17")):
    cust, orders = _ct(tables, "customer"), _ct(tables, "orders")
    pref_list, code_lut = q22_code_lut(cust.dicts["c_phone"], prefixes)
    return (len(pref_list),
            P.plan_join(orders, "o_custkey", cust, "c_custkey"),
            cust["c_custkey"], cust["c_phone"],
            cust["c_acctbal"], orders["o_custkey"], code_lut)


def cq22(tables: Tables,
         prefixes: Tuple[str, ...] = ("13", "31", "23", "29", "30", "18",
                                      "17")):
    """Well-funded customers with no orders, grouped by phone prefix."""
    pref_list = sorted(set(prefixes))  # q22_code_lut's group order
    packed = np.asarray(_q22_core(*_args_q22(tables, prefixes)))
    return [(pref_list[i], {"n": int(packed[0, i]),
                            "bal": float(packed[1, i])})
            for i in range(len(pref_list)) if packed[0, i]]


COLUMNAR_QUERIES: Dict[str, Callable] = {
    "q01": cq01, "q02": cq02, "q03": cq03, "q04": cq04, "q06": cq06,
    "q12": cq12, "q13": cq13, "q14": cq14, "q17": cq17, "q22": cq22,
}


def tables_from_rows(data: Dict[str, List[dict]]) -> Tables:
    """Columnarize ``workloads.tpch.generate()`` output and collect
    planner statistics at ingest (the reference's StorageCollectStats
    moment)."""
    out = {}
    for name, rows in data.items():
        if rows:
            out[name] = ColumnTable.from_rows(rows)
            analyze_table(out[name])
    return out


# ------------------------------------------------------- fused suite
_SUITE_CORES: Dict[str, Tuple[Callable, Callable]] = {
    "q01": (_q01_core, _args_q01), "q02": (_q02_core, _args_q02),
    "q03": (_q03_core, _args_q03), "q04": (_q04_core, _args_q04),
    "q06": (_q06_core, _args_q06), "q12": (_q12_core, _args_q12),
    "q13": (_q13_core, _args_q13), "q14": (_q14_core, _args_q14),
    "q17": (_q17_core, _args_q17), "q22": (_q22_core, _args_q22),
}

_SLOT = object()  # placeholder for a device array in an args template


def suite_args_split(tables: Tables):
    """Split every query core's arguments into (templates, arrays):
    the single source of truth for which suite arguments are traced
    device arrays (slots) vs compile-time statics — shared by
    ``compile_suite`` and the AOT loader so they cannot diverge."""
    templates: Dict[str, list] = {}
    arrays: Dict[str, list] = {}
    for name, (_core, args_fn) in _SUITE_CORES.items():
        t, arr = [], []
        for a in args_fn(tables):
            if isinstance(a, (jnp.ndarray, jax.Array)):
                t.append(_SLOT)
                arr.append(a)
            else:
                t.append(a)
        templates[name] = t
        arrays[name] = arr
    return templates, arrays


def compile_suite(tables: Tables) -> Callable[[], Dict[str, object]]:
    """Fuse the ENTIRE ten-query suite into one jitted program.

    The reference must execute each query as its own distributed job
    with materialized intermediates; here the per-query cores are
    inlined into a single XLA program, so the whole benchmark suite
    costs ONE controller round-trip + one device schedule. Returns a
    zero-argument callable producing ``{name: raw core output}`` (the
    same arrays each ``cqNN`` wrapper formats); call it repeatedly —
    the compiled program is cached on the callable.
    """
    templates, arrays = suite_args_split(tables)

    @jax.jit
    def mega(arrs: Dict[str, list]):
        out = {}
        for name, t in templates.items():
            it = iter(arrs[name])
            rebuilt = [next(it) if x is _SLOT else x for x in t]
            out[name] = _SUITE_CORES[name][0](*rebuilt)
        return out

    def runner():
        return mega(arrays)

    runner.jitted = mega  # exposed so tests can assert one compilation
    runner.arrays = arrays  # exposed for AOT export (plan/aot.py)
    runner.templates = templates  # the matching statics, same split
    return runner
