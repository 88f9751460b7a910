"""Overlapped device staging (plan/staging.py) — buckets, donation,
failure paths.

The staging pipeline inherits ``stream_blocks``'s shutdown/error
discipline and these tests pin it: a reader/staging thread dying
mid-stream surfaces at the consumer (never swallowed), an abandoned
consumer leaves no live thread (asserted via both the staging registry
and the store's ``_readers`` registry), and a store closed under a
live stream errors instead of use-after-free. The shape-bucket tests
pin the two acceptance criteria: bucketed/padded streams match the
unpadded math exactly (masks, not garbage rows), and the recompile
count stays constant across repeated executions with differing ragged
tail sizes.
"""

import threading
import time
import warnings

import numpy as np
import pytest

from netsdb_tpu.plan import staging
from netsdb_tpu.relational.outofcore import PagedColumns
from netsdb_tpu.storage.paged import PagedTensorStore


@pytest.fixture()
def store(config):
    s = PagedTensorStore(config, pool_bytes=1 << 20)
    yield s
    s.close()


def _ingest(store, name="t", n=1000, row_block=128):
    rng = np.random.default_rng(0)
    cols = {"k": rng.integers(0, 7, n, dtype=np.int32),
            "v": rng.uniform(0, 1, n).astype(np.float32)}
    return PagedColumns.ingest(store, name, cols,
                               row_block=row_block), cols


def _wait_no_stagers(timeout=10.0):
    deadline = time.monotonic() + timeout
    while staging.active_count() and time.monotonic() < deadline:
        time.sleep(0.02)
    return staging.active_count()


# ---------------------------------------------------------------- buckets
def test_bucket_rows_ladder():
    # membership: every bucket is 2^k or 3*2^(k-1); floor at 8
    assert staging.bucket_rows(1) == 8
    assert staging.bucket_rows(8) == 8
    assert staging.bucket_rows(9) == 12
    assert staging.bucket_rows(13) == 16
    assert staging.bucket_rows(700) == 768
    assert staging.bucket_rows(1000) == 1024
    for n in range(1, 5000):
        b = staging.bucket_rows(n)
        assert b >= n
        # worst-case pad factor is < 1.5x (the 1.5x rungs of the
        # two-buckets-per-octave ladder), i.e. strictly less than 2x
        assert b <= max(8, (3 * n) // 2 + 2)
        # monotonic
        assert staging.bucket_rows(n + 1) >= b


def test_pad_rows_target_multiple():
    assert staging.pad_rows_target(9, True) == 12
    assert staging.pad_rows_target(9, True, multiple=8) == 16
    assert staging.pad_rows_target(9, False) == 9
    assert staging.pad_rows_target(9, False, multiple=8) == 16


# ---------------------------------------------------------- staged stream
def test_staged_stream_orders_and_joins():
    out = list(staging.stage_stream(iter(range(100)),
                                    lambda x: x * 2, depth=3))
    assert out == [x * 2 for x in range(100)]
    assert _wait_no_stagers() == 0


def test_staged_stream_sync_mode_matches():
    out = list(staging.stage_stream(iter(range(10)),
                                    lambda x: x + 1, depth=0))
    assert out == list(range(1, 11))


def test_source_death_surfaces_at_consumer():
    def source():
        yield 1
        yield 2
        raise OSError("disk gone")

    s = staging.stage_stream(source(), lambda x: x, depth=2)
    got = [next(s), next(s)]
    with pytest.raises(OSError, match="disk gone"):
        next(s)
    assert got == [1, 2]
    assert _wait_no_stagers() == 0


def test_place_death_surfaces_at_consumer():
    def place(x):
        if x == 3:
            raise ValueError("bad block")
        return x

    s = staging.stage_stream(iter(range(10)), place, depth=2)
    assert [next(s), next(s), next(s)] == [0, 1, 2]
    with pytest.raises(ValueError, match="bad block"):
        list(s)
    assert _wait_no_stagers() == 0


def test_abandoned_consumer_joins_threads_and_releases_locks(store):
    pc, _ = _ingest(store, n=4096, row_block=64)  # many pages
    stream = pc.stream_tables()
    next(stream)
    stream.close()
    assert _wait_no_stagers() == 0
    # the store's page-reader registry must also be drained (the
    # staging thread closed the host stream, which joined its reader)
    with store._readers_lock:
        assert all(not t.is_alive() for t, _ in store._readers)
    # and the read lock is released: a mutation proceeds immediately
    pc.append({"k": np.arange(10, dtype=np.int32),
               "v": np.ones(10, np.float32)})


def test_store_closed_while_stream_live(config):
    s = PagedTensorStore(config, pool_bytes=1 << 20)
    pc, _ = _ingest(s, n=4096, row_block=64)
    stream = pc.stream_tables()
    next(stream)
    s.close()  # joins the page readers under the live stream
    with pytest.raises((RuntimeError, KeyError)):
        for _ in range(200):
            next(stream)
    stream.close()
    assert _wait_no_stagers() == 0


# ------------------------------------------------------- padded numerics
def test_bucketed_stream_matches_exact_shapes(store):
    # ragged appends → padded chunks; bucketed and exact-shape paths
    # must produce identical fold results (masks, not garbage rows)
    import jax
    import jax.numpy as jnp

    pc, cols = _ingest(store, n=500, row_block=128)
    extra = {"k": np.arange(37, dtype=np.int32) % 7,
             "v": np.full(37, 0.5, np.float32)}
    pc.append(extra)
    oracle_n = 537
    oracle = float(np.concatenate([cols["v"], extra["v"]]).sum())

    @jax.jit
    def step(acc, v, valid):
        return acc + jnp.where(valid, v, 0.0).sum()

    def run():
        import contextlib

        acc = jnp.zeros((), jnp.float32)
        rows = 0
        with contextlib.closing(pc.stream()) as chunks:
            for ccols, valid, _start in chunks:
                acc = step(acc, ccols["v"], valid)
                rows += int(np.asarray(valid).sum())
        return float(acc), rows

    store.config.shape_bucketing = True
    got_b, rows_b = run()
    store.config.shape_bucketing = False
    got_e, rows_e = run()
    assert rows_b == rows_e == oracle_n
    np.testing.assert_allclose(got_b, oracle, rtol=1e-5)
    np.testing.assert_allclose(got_b, got_e, rtol=0, atol=0)


def test_bucketed_chunk_shapes_are_buckets(store):
    pc, _ = _ingest(store, n=100, row_block=100)
    chunk = next(iter(pc.stream_tables()))
    assert chunk["v"].shape[0] == staging.bucket_rows(100) == 128
    assert int(np.asarray(chunk.mask()).sum()) == 100


def test_matmul_streamed_bucketed_matches_oracle(store):
    rng = np.random.default_rng(1)
    m = rng.standard_normal((333, 16)).astype(np.float32)  # ragged tail
    rhs = rng.standard_normal((16, 8)).astype(np.float32)
    store.put("m", m, row_block=100)
    got = store.matmul_streamed("m", rhs)
    np.testing.assert_allclose(got, m @ rhs, rtol=1e-4, atol=1e-4)
    got_sync = store.matmul_streamed("m", rhs, stage_depth=0)
    np.testing.assert_array_equal(got, got_sync)


# ---------------------------------------------------- recompile stability
def test_recompile_count_constant_across_ragged_tails(config):
    """Three executions over sets with DIFFERING row counts (differing
    ragged tails, same bucket) must not add traces after the first —
    the buckets absorb the shape churn (acceptance criterion)."""
    from netsdb_tpu.client import Client
    from netsdb_tpu.plan import executor
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.table import ColumnTable

    c = Client(config)
    c.create_database("d")
    rng = np.random.default_rng(2)

    def ingest_and_run(n):
        if c.set_exists("d", "lineitem"):
            c.remove_set("d", "lineitem")
        c.create_set("d", "lineitem", type_name="table", storage="paged")
        cols = {
            "l_shipdate": rng.integers(19940101, 19950101, n,
                                       dtype=np.int32),
            "l_discount": np.full(n, 0.06, np.float32),
            "l_quantity": np.full(n, 10.0, np.float32),
            "l_extendedprice": rng.uniform(1000, 2000,
                                           n).astype(np.float32),
        }
        c.send_table("d", "lineitem", ColumnTable(cols, {}))
        out = rdag.run_query(c, rdag.q06_sink("d"))
        ref = float((cols["l_extendedprice"]
                     * cols["l_discount"]).sum(dtype=np.float64))
        np.testing.assert_allclose(float(np.asarray(out["revenue"])[0]),
                                   ref, rtol=1e-4)

    # all three sizes share one bucket (1536): differing ragged tails
    ingest_and_run(1100)
    t1 = executor.compile_stats()["traces"]
    ingest_and_run(1300)
    ingest_and_run(1233)
    t3 = executor.compile_stats()["traces"]
    assert t3 == t1, (f"buckets must absorb the shape churn: traces "
                      f"went {t1} -> {t3}")


# ------------------------------------------------------------- donation
def test_donation_plumbing_preserves_results(config):
    """Force fold-buffer donation on (CPU ignores the donation itself
    but traces the donated signature) — results must be unchanged."""
    from netsdb_tpu.relational.outofcore import ooc_q06

    config.donate_fold_buffers = True
    store = PagedTensorStore(config, pool_bytes=1 << 20)
    try:
        rng = np.random.default_rng(3)
        n = 700
        cols = {
            "l_shipdate": rng.integers(19940101, 19950101, n,
                                       dtype=np.int32),
            "l_discount": np.full(n, 0.06, np.float32),
            "l_quantity": np.full(n, 10.0, np.float32),
            "l_extendedprice": rng.uniform(1000, 2000,
                                           n).astype(np.float32),
        }
        pc = PagedColumns.ingest(store, "li", cols, row_block=128)
        with warnings.catch_warnings():
            # CPU backends warn that donation is unimplemented — the
            # plumbing (donated signature) is what this test pins
            warnings.simplefilter("ignore")
            (rev,) = [v for _, v in ooc_q06(pc)]
        ref = float((cols["l_extendedprice"]
                     * cols["l_discount"]).sum(dtype=np.float64))
        np.testing.assert_allclose(rev, ref, rtol=1e-4)
    finally:
        store.close()


def test_fold_donate_argnums_gating(config):
    config.donate_fold_buffers = True
    assert staging.fold_donate_argnums(config) == (0,)
    config.donate_fold_buffers = False
    assert staging.fold_donate_argnums(config) == ()
    config.donate_fold_buffers = None
    # auto mode: CPU test backend → off
    assert staging.fold_donate_argnums(config) == ()


# ------------------------------------------------- stream lock semantics
def test_staged_stream_holds_read_lock_until_closed(store):
    pc, _ = _ingest(store, n=2048, row_block=64)
    stream = pc.stream_tables()
    next(stream)
    appended = threading.Event()

    def do_append():
        pc.append({"k": np.zeros(5, np.int32),
                   "v": np.ones(5, np.float32)})
        appended.set()

    t = threading.Thread(target=do_append)
    t.start()
    time.sleep(0.15)
    assert not appended.is_set(), "append must wait for the live stream"
    stream.close()
    t.join(timeout=10)
    assert appended.is_set()
