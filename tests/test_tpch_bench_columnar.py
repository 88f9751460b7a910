"""Columnar tpchBench vs the host-object pipeline (VERDICT round-1
item 6): same nested data through both, results must agree."""

import numpy as np
import pytest

from netsdb_tpu.workloads import tpch_bench as TB
from netsdb_tpu.workloads import tpch_bench_columnar as TC


@pytest.fixture(scope="module")
def customers():
    return TB.generate(num_customers=60, seed=7)


@pytest.fixture(scope="module")
def tables(customers):
    return TC.columnarize(customers)


def test_selections_match_host(customers, tables):
    thr = 25
    seg = "BUILDING"
    i_sel, i_not, s_sel, s_not = (np.asarray(m) for m in
                                  TC.selections(tables, thr, seg))
    for i, c in enumerate(customers):
        assert i_sel[i] == (c.custKey > thr)
        assert i_not[i] == (not (c.custKey > thr))
        assert s_sel[i] == (c.mktsegment == seg)
        assert s_not[i] == (c.mktsegment != seg)


def test_group_by_supplier_matches_host(customers, tables):
    pair, per = TC.group_by_supplier(tables)
    pair, per = np.asarray(pair), np.asarray(per)
    sup_names = tables["triples"].dicts["supplier"]
    # host oracle: triples per (supplier, customer)
    from collections import Counter

    w = Counter()
    for c in customers:
        for o in c.orders:
            for li in o.lineItems:
                w[(li.supplierName, c.custKey)] += 1
    for (sname, ck), n in w.items():
        assert pair[sup_names.index(sname), ck] == n
    for s, sname in enumerate(sup_names):
        assert per[s] == sum(n for (nm, _), n in w.items() if nm == sname)


def test_count_customers(customers, tables):
    assert TC.count_customers(tables) == len(customers)


def test_top_jaccard_matches_host(customers, tables):
    query = [1, 3, 5, 7, 11, 13, 17]
    k = 5
    got = TC.top_jaccard(tables, query, k)
    # host oracle — the same scoring the object pipeline's heap keeps
    q = frozenset(query)
    scores = []
    for c in customers:
        parts = frozenset(li.partKey for o in c.orders
                          for li in o.lineItems)
        denom = len(parts | q)
        scores.append(((len(parts & q) / denom) if denom else 0.0,
                       c.custKey))
    scores.sort(key=lambda si: (-si[0], si[1]))
    want = scores[:k]
    assert [ck for _, ck in got] == [ck for _, ck in want]
    for (gs, _), (ws, _) in zip(got, want):
        assert gs == pytest.approx(ws, rel=1e-5)
