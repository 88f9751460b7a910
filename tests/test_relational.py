"""Columnar device-relational engine vs the host row engine.

The two engines implement the same ten reference queries
(``src/tpch/source/Query01..22``) with independent execution models —
row-at-a-time DAG interpretation vs jitted masked-array programs — so
running both on identical generated data is a strong differential
oracle (the reference has no equivalent; its tests eyeball output).
"""

import numpy as np
import pytest

from netsdb_tpu.relational import ColumnTable, kernels as K
from netsdb_tpu.relational.queries import COLUMNAR_QUERIES, tables_from_rows
from netsdb_tpu.workloads import tpch


@pytest.fixture(scope="module")
def data():
    return tpch.generate(scale=2, seed=3)


@pytest.fixture(scope="module")
def tables(data):
    return tables_from_rows(data)


def _row_engine_client(data):
    """Fresh client loaded with the row tables (query output sets are
    created by run_query itself)."""
    import tempfile

    from netsdb_tpu.client import Client
    from netsdb_tpu.config import Configuration

    client = Client(Configuration(root_dir=tempfile.mkdtemp()))
    client.create_database("tpch")
    for t, rows in data.items():
        client.create_set("tpch", t, type_name="object")
        client.send_data("tpch", t, rows)
    return client


@pytest.fixture(scope="module")
def row_results(data):
    """Run every row-engine query once on a shared client.
    (Platform is pinned to the virtual CPU mesh by conftest.py.)"""
    client = _row_engine_client(data)
    results = {}
    for name in tpch.QUERIES:
        out_rows = tpch.run_query(client, name)
        results[name] = out_rows
    return results


class TestColumnTable:
    def test_round_trip(self, data):
        t = ColumnTable.from_rows(data["orders"])
        back = t.to_rows(date_cols=("o_orderdate",))
        assert back == data["orders"]

    def test_dates_order_isomorphic(self, data):
        t = ColumnTable.from_rows(data["lineitem"])
        ship = np.asarray(t["l_shipdate"])
        raw = [r["l_shipdate"] for r in data["lineitem"]]
        assert (np.argsort(ship, kind="stable").tolist()
                == sorted(range(len(raw)), key=lambda i: raw[i]))

    def test_filter_is_mask_only(self, tables):
        li = tables["lineitem"]
        f = li.filter(li["l_quantity"] > 25)
        assert f.num_rows == li.num_rows  # static shape preserved
        kept = int(np.asarray(f.mask()).sum())
        expect = int((np.asarray(li["l_quantity"]) > 25).sum())
        assert kept == expect

    def test_codes_where(self, tables):
        part = tables["part"]
        codes = part.codes_where("p_type", lambda s: s.startswith("PROMO"))
        for c in codes:
            assert part.decode("p_type", c).startswith("PROMO")


class TestKernels:
    def test_segment_ops_match_numpy(self):
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 13, 300).astype(np.int32)
        vals = rng.standard_normal(300).astype(np.float32)
        mask = rng.random(300) > 0.4
        got = np.asarray(K.segment_sum(vals, ids, 13, mask))
        want = np.zeros(13, np.float32)
        np.add.at(want, ids[mask], vals[mask])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        got_n = np.asarray(K.segment_count(ids, 13, mask))
        want_n = np.bincount(ids[mask], minlength=13)
        assert (got_n == want_n).all()
        got_min = np.asarray(K.segment_min(vals, ids, 13, mask))
        for s in range(13):
            sel = vals[mask & (ids == s)]
            if len(sel):
                assert got_min[s] == pytest.approx(sel.min())
            else:
                assert np.isinf(got_min[s])

    def test_pk_fk_join_matches_dict_join(self):
        rng = np.random.default_rng(1)
        pk = np.arange(50, dtype=np.int32)
        rng.shuffle(pk)
        pk_mask = rng.random(50) > 0.3
        fk = rng.integers(0, 80, 200).astype(np.int32)  # some miss
        idx, hit = K.pk_fk_join(pk, fk, pk_mask)
        idx, hit = np.asarray(idx), np.asarray(hit)
        lookup = {int(k): i for i, k in enumerate(pk) if pk_mask[i]}
        for j in range(200):
            if int(fk[j]) in lookup:
                assert hit[j] and idx[j] == lookup[int(fk[j])]
            else:
                assert not hit[j]

    def test_member_with_duplicates(self):
        build = np.array([5, 5, 9, 2, 2, 2], np.int32)
        bmask = np.array([0, 1, 0, 0, 0, 0], np.bool_)  # only one 5 valid
        probe = np.array([5, 9, 2, 7], np.int32)
        got = np.asarray(K.member(build, probe, bmask))
        assert got.tolist() == [True, False, False, False]

    def test_segment_ops_drop_out_of_range_ids(self):
        """Orphan keys (segment id ≥ num_segments) must be dropped, not
        credited to the last segment."""
        ids = np.array([0, 1, 7, 2, -1], np.int32)  # 7 and -1 orphaned
        vals = np.array([1.0, 2.0, 100.0, 3.0, 50.0], np.float32)
        got = np.asarray(K.segment_sum(vals, ids, 3))
        assert got.tolist() == [1.0, 2.0, 3.0]
        assert np.asarray(K.segment_count(ids, 3)).tolist() == [1, 1, 1]
        assert np.isinf(np.asarray(K.segment_min(vals, ids, 3))).sum() == 0

    def test_top_k_masked(self):
        s = np.array([3.0, 9.0, 1.0, 7.0], np.float32)
        mask = np.array([1, 0, 1, 1], np.bool_)
        idx, ok = K.top_k_masked(s, 3, mask)
        assert np.asarray(idx).tolist() == [3, 0, 2]
        assert np.asarray(ok).all()
        idx, ok = K.top_k_masked(s, 3, np.array([1, 0, 0, 0], np.bool_))
        assert np.asarray(ok).tolist() == [True, False, False]


class TestBenchAndIngestion:
    def test_generated_tables_run_all_queries(self):
        """Every columnar query (including Q02's five-way join and
        Q22's anti-join) executes on the dbgen-shaped generated
        tables."""
        from netsdb_tpu.relational.datagen import generate_columnar

        tables = generate_columnar(sf=0.001)
        assert tables["lineitem"].num_rows == 6000
        for name in sorted(COLUMNAR_QUERIES):
            COLUMNAR_QUERIES[name](tables)

    def test_pickle_round_trip(self, tables):
        import pickle

        t = tables["orders"]
        t2 = pickle.loads(pickle.dumps(t))
        assert t2.dicts == t.dicts
        for name in t.cols:
            np.testing.assert_array_equal(np.asarray(t2[name]),
                                          np.asarray(t[name]))

    def test_load_tbl_dir_columnar(self, tmp_path):
        import tempfile

        from netsdb_tpu.client import Client
        from netsdb_tpu.config import Configuration
        from netsdb_tpu.workloads.tpch import load_tbl_dir_columnar

        (tmp_path / "nation.tbl").write_text(
            "0|ALGERIA|0|haggle after the deposits|\n"
            "1|ARGENTINA|1|al foxes promise|\n")
        client = Client(Configuration(root_dir=tempfile.mkdtemp()))
        counts = load_tbl_dir_columnar(client, str(tmp_path), db="tpchc")
        assert counts == {"nation": 2}
        [ct] = list(client.get_set_iterator("tpchc", "nation_columnar"))
        assert ct.num_rows == 2
        assert ct.decode("n_name", int(np.asarray(ct["n_name"])[1])) \
            == "ARGENTINA"


class TestColumnarVsRowEngine:
    """Differential testing: both engines, same data, same answers."""

    def _close(self, a, b, path=""):
        # same leaf tolerance as the shared engine-parity comparator
        # (utils/compare.py, used by the selftest CLI); the recursion
        # here is kept for the path-annotated assertion messages
        from netsdb_tpu.utils.compare import structurally_close

        if isinstance(a, dict):
            assert set(a) == set(b), (path, a, b)
            for k in a:
                self._close(a[k], b[k], f"{path}.{k}")
        elif isinstance(a, (list, tuple)):
            assert len(a) == len(b), (path, a, b)
            for i, (x, y) in enumerate(zip(a, b)):
                self._close(x, y, f"{path}[{i}]")
        elif isinstance(a, float) or isinstance(b, float):
            assert structurally_close(a, b), (path, a, b)
        else:
            assert a == b, (path, a, b)

    @pytest.mark.parametrize("name", sorted(COLUMNAR_QUERIES))
    def test_query_matches(self, name, tables, row_results):
        got = COLUMNAR_QUERIES[name](tables)
        self._close(got, row_results[name], name)

    def test_q13_empty_customer_table(self, tables):
        """Zero-row customer (reachable via from_columns loaders) must
        yield an empty histogram, not a zero-size reduction error."""
        t2 = dict(tables)
        t2["customer"] = ColumnTable(
            {"c_custkey": np.zeros((0,), np.int32)})
        got = COLUMNAR_QUERIES["q13"](t2)
        assert got == [] or all(cnt == 0 for _, cnt in got)

    def test_q02_independent_of_nation_row_order(self, data, tables,
                                                 row_results):
        """Joins must resolve by key, not row position: shuffling the
        nation table's physical order cannot change Q02."""
        rng = np.random.default_rng(5)
        shuffled = list(data["nation"])
        rng.shuffle(shuffled)
        t2 = dict(tables)
        t2["nation"] = ColumnTable.from_rows(shuffled)
        got = COLUMNAR_QUERIES["q02"](t2)
        self._close(got, row_results["q02"], "q02-shuffled-nation")


class TestFusedSuite:
    def test_suite_matches_solo_cores(self, tables):
        import jax as _jax

        from netsdb_tpu.relational.queries import _SUITE_CORES, compile_suite

        suite = compile_suite(tables)
        res = suite()
        for name, (core, args_fn) in _SUITE_CORES.items():
            solo = core(*args_fn(tables))
            for a, b in zip(_jax.tree_util.tree_leaves(res[name]),
                            _jax.tree_util.tree_leaves(solo)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-3,
                                           err_msg=name)

    def test_suite_is_one_compiled_program(self, tables):
        """Repeated calls reuse ONE jitted program (the whole point:
        one compile + one dispatch for the ten queries)."""
        from netsdb_tpu.relational.queries import compile_suite

        suite = compile_suite(tables)
        r1 = suite()
        r2 = suite()
        assert set(r1) == set(r2) == {"q01", "q02", "q03", "q04", "q06",
                                      "q12", "q13", "q14", "q17", "q22"}
        assert suite.jitted._cache_size() == 1  # no retrace on call 2


@pytest.mark.parametrize("seed", [11, 42, 77])
def test_engines_agree_across_random_datasets(seed):
    """Seed-parametrized differential fuzz: both engines, fresh random
    data, every query (the fixed-seed fixtures above can't catch
    data-shape-dependent divergence, e.g. empty groups or all-miss
    joins under an unlucky draw)."""
    from netsdb_tpu.utils.compare import structurally_close

    # scale=4: at scale=1 these seeds give EMPTY q02/q12/q17 results
    # (an [] == [] comparison exercises nothing)
    data = tpch.generate(scale=4, seed=seed)
    tabs = tables_from_rows(data)
    client = _row_engine_client(data)
    for name in sorted(COLUMNAR_QUERIES):
        rows = sorted(tpch.run_query(client, name), key=str)
        cols = sorted(COLUMNAR_QUERIES[name](tabs), key=str)
        assert structurally_close(cols, rows), (seed, name, cols, rows)
