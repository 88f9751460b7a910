"""Out-of-core relational execution (VERDICT round-1 item 5): TPC-H
q01/q06 streamed through the paged store under a pool cap smaller than
the table, cross-checked against the in-memory columnar engine."""

import tempfile

import numpy as np
import pytest

from netsdb_tpu.config import Configuration
from netsdb_tpu.relational import outofcore as O
from netsdb_tpu.relational.queries import cq01, cq06, tables_from_rows
from netsdb_tpu.storage.paged import PagedTensorStore
from netsdb_tpu.workloads import tpch


@pytest.fixture(scope="module")
def tables():
    return tables_from_rows(tpch.generate(scale=3, seed=9))


def _store(pool_bytes=None, page_bytes=1 << 14):
    cfg = Configuration(root_dir=tempfile.mkdtemp(prefix="ooc_test_"),
                        page_size_bytes=page_bytes)
    return PagedTensorStore(cfg, pool_bytes=pool_bytes)


def test_paged_columns_roundtrip(tables):
    li = tables["lineitem"]
    store = _store()
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q01_COLUMNS)
    seen = 0
    for cols, valid, _start in pc.stream():
        n = int(np.asarray(valid).sum())
        got = np.asarray(cols["l_quantity"])[:n]
        want = np.asarray(li["l_quantity"])[seen:seen + n]
        np.testing.assert_array_equal(got, want)
        seen += n
    assert seen == li.num_rows
    store.close()


def test_ooc_q01_matches_in_memory(tables):
    li = tables["lineitem"]
    store = _store()
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q01_COLUMNS)
    got = O.ooc_q01(pc)
    want = cq01(tables)
    assert [k for k, _ in got] == [k for k, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g["count"] == w["count"]
        for f in ("sum_qty", "sum_base_price", "sum_disc_price",
                  "sum_charge"):
            assert g[f] == pytest.approx(w[f], rel=1e-4)
    store.close()


def test_ooc_q06_matches_in_memory(tables):
    li = tables["lineitem"]
    store = _store()
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q06_COLUMNS)
    got = O.ooc_q06(pc)
    want = cq06(tables)
    assert got[0][1] == pytest.approx(want[0][1], rel=1e-4, abs=1e-2)
    store.close()


def test_ooc_under_tiny_pool_spills(tables):
    """Pool cap far below the table size: the native arena must spill
    cold pages to disk and the answers must not change — the
    larger-than-memory guarantee."""
    li = tables["lineitem"]
    store = _store(pool_bytes=1 << 15, page_bytes=1 << 12)
    if not store.native:
        pytest.skip("native page store unavailable; spill is native-only")
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q01_COLUMNS)
    got = O.ooc_q01(pc)
    want = cq01(tables)
    assert [k for k, _ in got] == [k for k, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g["count"] == w["count"]
    stats = store.stats()
    assert stats["spills"] > 0, stats  # proof it actually went out of core
    store.close()


# ------------------------------------------------ out-of-core JOIN (r3)
def test_ooc_q03_join_matches_in_memory(tables):
    """Streamed probe (lineitem pages) against a partitioned resident
    build side (customer ⋈ orders LUT), ≥3 key-range partitions — the
    PartitionedHashSet/HashSetManager analogue."""
    from netsdb_tpu.relational.queries import cq03

    li = tables["lineitem"]
    store = _store()
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q03_COLUMNS)
    orders = {n: np.asarray(tables["orders"][n]) for n in
              ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")}
    customer = {n: np.asarray(tables["customer"][n]) for n in
                ("c_custkey", "c_mktsegment")}
    seg = tables["customer"].code("c_mktsegment", "BUILDING")
    from netsdb_tpu.relational.table import date_to_int

    n_keys = int(orders["o_orderkey"].max()) + 1
    key_cap = max(1, n_keys // 3)  # force >= 3 partitions
    parts = O.build_q03_side(store, orders, customer, seg,
                             date_to_int("1995-03-15"), key_cap)
    assert parts >= 3
    got = O.ooc_q03(pc, store)
    want = cq03(tables)
    assert [r["okey"] for r in got] == [r["okey"] for r in want]
    assert [r["odate"] for r in got] == [r["odate"] for r in want]
    for g, w in zip(got, want):
        assert g["revenue"] == pytest.approx(w["revenue"], rel=1e-5)
    store.close()


def test_ooc_q03_join_spills_under_tiny_pool(tables):
    """Join build side + probe stream under a pool cap far below their
    combined size: the arena must spill and the answer must not change."""
    from netsdb_tpu.relational.queries import cq03
    from netsdb_tpu.relational.table import date_to_int

    li = tables["lineitem"]
    store = _store(pool_bytes=1 << 15, page_bytes=1 << 12)
    if not store.native:
        pytest.skip("native page store unavailable; spill is native-only")
    pc = O.PagedColumns.from_table(store, "lineitem", li, O.Q03_COLUMNS)
    orders = {n: np.asarray(tables["orders"][n]) for n in
              ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")}
    customer = {n: np.asarray(tables["customer"][n]) for n in
                ("c_custkey", "c_mktsegment")}
    seg = tables["customer"].code("c_mktsegment", "BUILDING")
    n_keys = int(orders["o_orderkey"].max()) + 1
    O.build_q03_side(store, orders, customer, seg,
                     date_to_int("1995-03-15"), max(1, n_keys // 4))
    got = O.ooc_q03(pc, store)
    want = cq03(tables)
    assert [r["okey"] for r in got] == [r["okey"] for r in want]
    stats = store.stats()
    assert stats["spills"] > 0, stats
    store.close()
