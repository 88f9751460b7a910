"""Distributed model inference serving (PR 17): tensor_chain
scatter-gather, ModelServing deploy/score, routed matrix ingest, the
per-shard ONE-program proof, and sharded ANALYZE_SET fan-out.

The acceptance oracle throughout is the SINGLE-DEVICE ENGINE — a solo
daemon running the same model on the same bytes — never a hand-rolled
numpy reimplementation (the FF tail is a softmax; byte-equality must
pin the engine against itself, as ``tests/test_scaleout.py`` does).
"""

import contextlib

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.core.blocked import batch_block
from netsdb_tpu.models.conv2d import Conv2DModel
from netsdb_tpu.models.ff import FFModel
from netsdb_tpu.models.serving import ModelServing, ff_serving
from netsdb_tpu.relational.table import ColumnTable
from netsdb_tpu.serve import placement as PL
from netsdb_tpu.serve.client import RemoteClient
from netsdb_tpu.serve.errors import RemoteError
from netsdb_tpu.serve.protocol import CODEC_PICKLE, MsgType
from netsdb_tpu.serve.server import ServeController
from netsdb_tpu.storage.store import SetIdentifier


def _counter(name: str) -> int:
    return obs.REGISTRY.counter(name).value


def _int_f32(rng, shape, lo=-4, hi=4):
    """Integer-valued f32: exact under any reassociation, so equality
    checks are BIT-equality checks."""
    return rng.integers(lo, hi, size=shape).astype(np.float32)


@contextlib.contextmanager
def pool(tmp_path, n_workers=2):
    """Leader + N shard workers in-process; yields (leader, workers,
    leader_address). Pool membership = leader + workers, so a
    range-placed set has N+1 slots."""
    daemons = []
    try:
        workers = []
        for i in range(n_workers):
            w = ServeController(
                Configuration(root_dir=str(tmp_path / f"w{i}")), port=0)
            w.start()
            daemons.append(w)
            workers.append(w)
        leader = ServeController(
            Configuration(root_dir=str(tmp_path / "leader")), port=0,
            workers=[f"127.0.0.1:{w.port}" for w in workers])
        leader.start()
        daemons.append(leader)
        yield leader, workers, f"127.0.0.1:{leader.port}"
    finally:
        for d in daemons:
            d.shutdown()


@contextlib.contextmanager
def solo(tmp_path, name="solo"):
    ctl = ServeController(
        Configuration(root_dir=str(tmp_path / name)), port=0)
    ctl.start()
    try:
        yield ctl, f"127.0.0.1:{ctl.port}"
    finally:
        ctl.shutdown()


def _ff_weights(rng, F, H, L):
    return (_int_f32(rng, (H, F)), _int_f32(rng, (H,)),
            _int_f32(rng, (L, H)), _int_f32(rng, (L,)))


def _ff_oracle(tmp_path, weights, batch, block=(4, 4)):
    """The single-device engine's answer for one FF batch."""
    w1, b1, wo, bo = weights
    with solo(tmp_path, "oracle") as (_ctl, addr):
        c = RemoteClient(addr)
        m = FFModel(db="fforacle", block=block)
        m.setup(c)
        m.load_weights(c, w1, b1, wo, bo)
        m.load_inputs(c, batch)
        res = c.execute_computations(m.build_inference_dag(),
                                     job_name="fforacle")
        out = np.asarray(next(iter(res.values())).to_dense())
        c.close()
        return out


# --- FF end to end: deploy, score, byte-equality ----------------------

def test_ff_serving_byte_equal_cold_and_warm(tmp_path):
    """Distributed scoring over a 5-slot pool is byte-equal to the
    single-device engine — cold (first frame compiles per shard) and
    warm (second frame rides every shard's jit + device cache)."""
    rng = np.random.default_rng(7)
    F, H, L, B = 12, 8, 5, 32
    weights = _ff_weights(rng, F, H, L)
    batch = _int_f32(rng, (B, F))
    batch2 = _int_f32(rng, (24, F))  # different rows: re-slices, retraces
    oracle = _ff_oracle(tmp_path, weights, batch)
    oracle2 = _ff_oracle(tmp_path, weights, batch2)

    with pool(tmp_path, n_workers=4) as (_leader, workers, addr):
        model = FFModel(db="ffsrv", block=(4, 4))

        def load(c):
            model.setup(c)
            model.load_weights(c, *weights)

        srv = ff_serving(model, addr, block=model.block)
        addrs = srv.deploy(load)
        assert len(addrs) == 5  # leader + 4 workers

        before = _counter("shard.scatter_queries")
        out = srv.score(batch)
        assert np.array_equal(np.asarray(out.to_dense()), oracle)
        assert _counter("shard.scatter_queries") == before + 1

        # warm: same weights, same pool, new frame
        out2 = srv.score(batch2)
        assert np.array_equal(np.asarray(out2.to_dense()), oracle2)

        # re-score the first batch — fully warm replay
        out3 = srv.score(batch)
        assert np.array_equal(np.asarray(out3.to_dense()), oracle)
        srv.close()


def test_ff_serving_per_shard_one_program_proof(tmp_path):
    """The tentpole's structural claim, pinned: every shard executed
    the WHOLE layer chain as ONE compiled program. The per-shard
    EXPLAIN tree reports mode ``whole_plan_jit`` and marks every plan
    node ``fused`` (the only unfused node is the synthetic
    ``WholePlanJit`` root that carries the program's measured time)."""
    rng = np.random.default_rng(11)
    weights = _ff_weights(rng, 12, 8, 5)
    batch = _int_f32(rng, (20, 12))

    with pool(tmp_path, n_workers=2) as (_leader, _workers, addr):
        model = FFModel(db="ffproof", block=(4, 4))

        def load(c):
            model.setup(c)
            model.load_weights(c, *weights)

        srv = ff_serving(model, addr, block=model.block)
        addrs = srv.deploy(load)
        _out, forest = srv.score(batch, explain=True)
        assert sorted(forest) == sorted(addrs)  # one tree per daemon
        for daemon, tree in forest.items():
            assert tree["mode"] == "whole_plan_jit", daemon
            nodes = tree["nodes"]
            plan_nodes = [n for n in nodes
                          if n.get("kind") != "WholePlanJit"]
            assert plan_nodes and all(n.get("fused") for n in plan_nodes)
            # the chain shape survived: 5 scans, 4 joins per shard
            kinds = sorted(n["kind"] for n in plan_nodes)
            assert kinds.count("Scan") == 5 and kinds.count("Join") == 4
        srv.close()


def test_ff_serving_staged_rows_bounded_per_shard(tmp_path):
    """The ≤1/N structural proof: routed ingest leaves each slot
    holding only its contiguous row range — no daemon ever stages the
    whole batch."""
    rng = np.random.default_rng(13)
    weights = _ff_weights(rng, 12, 8, 5)
    B = 30
    batch = _int_f32(rng, (B, 12))

    with pool(tmp_path, n_workers=3) as (leader, workers, addr):
        model = FFModel(db="ffrows", block=(4, 4))

        def load(c):
            model.setup(c)
            model.load_weights(c, *weights)

        srv = ff_serving(model, addr, block=model.block)
        addrs = srv.deploy(load)
        before = _counter("serve.client.routed_ingests")
        srv.score(batch)
        assert _counter("serve.client.routed_ingests") == before + 1

        slices = PL.range_slices(B, len(addrs))
        bound = max(hi - lo for lo, hi in slices)
        assert bound < B  # the proof is vacuous otherwise
        total = 0
        for ctl in [leader] + workers:
            items = ctl.library.store.get_items(
                SetIdentifier("ffrows", "inputs"))
            for it in items:
                rows = int(np.asarray(it.to_dense()).shape[0]) \
                    if hasattr(it, "to_dense") else 0
                assert rows <= bound
                total += rows
        assert total == B
        srv.close()


# --- a shipped batch is blocked by its own rows ------------------------

@pytest.mark.parametrize("rows, block, want", [
    (1, (512, 512), (128, 512)), (128, (512, 512), (128, 512)),
    (200, (512, 512), (256, 512)), (512, (512, 512), (512, 512)),
    (600, (512, 512), (512, 512)), (3, (4, 4), (4, 4)),
    (200, (4, 4), (4, 4)), (128, (512, 512, 2), (128, 512, 2))])
def test_batch_block_follows_the_batch_rows_in_whole_lanes(rows, block,
                                                          want):
    assert batch_block(rows, block) == want
    # along the output's batch axis, the other axes keep the model's
    assert batch_block(rows, block[::-1], axis=len(block) - 1) \
        == want[::-1]


def _ingested():
    return {k: _counter("store.ingest." + k) for k in ("rows", "pad_rows")}


def _ff_served_block(tmp_path, weights, batch, n_workers, block):
    """Score ``batch`` through ``ff_serving`` over a pool of ``n_workers``
    workers (0: a solo daemon, the input set range-placed over its one
    slot) with the model blocked ``block``; returns the output, each
    slot's stored input block shape and padded rows, and what the
    ingest counters counted."""
    db = "ffbatch"
    model = FFModel(db=db, block=block)

    def load(c):
        model.setup(c)
        model.load_weights(c, *weights)

    with pool(tmp_path, n_workers=n_workers) as (leader, workers, addr):
        srv = ff_serving(model, addr, block=model.block)
        srv.deploy(load)
        before = _ingested()
        out = srv.score(batch)
        counted = {k: v - before[k] for k, v in _ingested().items()}
        stored = [ctl.library.get_tensor(db, "inputs")
                  for ctl in [leader] + workers]
        scores = leader.library.get_tensor(db, "output")
        srv.close()
    return (out, [(t.meta.block_shape, t.data.shape[0]) for t in stored],
            counted, (scores.meta.block_shape, scores.data.shape))


@pytest.mark.parametrize("rows, row_block", [
    (128, 128), (200, 256), (512, 512), (600, 512)])
def test_a_shipped_batch_is_blocked_by_its_own_rows(tmp_path, rows,
                                                    row_block):
    """A batch of fewer rows than the model's block rows is stored with
    a row block of its rows rounded up to a lane (128), its scores are
    byte-equal to the same batch padded to the model's 512 rows, and
    the output's batch axis takes the same block; a batch of 512 rows
    or more keeps (512, 512). The ingest counters count the rows stored
    and the zero rows their blocks add. 600 features are not a whole
    number of 512-column blocks, so the contraction's margin is still
    padded."""
    rng = np.random.default_rng(29)
    F, H, L = 600, 8, 5
    weights = _ff_weights(rng, F, H, L)
    batch = _int_f32(rng, (rows, F))
    oracle = _ff_oracle(tmp_path, weights, batch, block=(512, 512))
    out, stored, counted, scores = _ff_served_block(
        tmp_path, weights, batch, 0, (512, 512))
    padded = -(-rows // row_block) * row_block
    assert stored == [((row_block, 512), padded)]
    assert counted == {"rows": rows, "pad_rows": padded - rows}
    assert scores == ((512, row_block), (512, padded))
    assert np.array_equal(np.asarray(out.to_dense()), oracle)


def test_a_routed_slice_is_blocked_by_its_own_rows(tmp_path):
    """Over a pool, each slot's slice of the batch takes the rule for
    its own rows: 200 rows over three slots are slices of 67, 67 and 66
    rows, each stored in one 128-row block; the assembled scores are
    blocked by the whole batch's 200 rows, 256, and byte-equal to the
    solo engine's at 512."""
    rng = np.random.default_rng(31)
    weights = _ff_weights(rng, 600, 8, 5)
    batch = _int_f32(rng, (200, 600))
    oracle = _ff_oracle(tmp_path, weights, batch, block=(512, 512))
    out, stored, counted, scores = _ff_served_block(
        tmp_path, weights, batch, 2, (512, 512))
    assert stored == [((128, 512), 128)] * 3
    assert counted == {"rows": 200, "pad_rows": 3 * 128 - 200}
    assert scores == ((512, 256), (512, 256))
    assert np.array_equal(np.asarray(out.to_dense()), oracle)


def test_the_ingest_counters_are_catalogued_and_documented():
    import os

    from netsdb_tpu.obs.export import CATALOG
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "METRICS.md")).read()
    for name in ("store.ingest.rows", "store.ingest.pad_rows"):
        assert CATALOG[name][0] == "counter"
        assert f"| `{name}` | counter |" in doc


# --- conv2d: items-mode tensor_chain without ModelServing -------------

def test_conv2d_items_chain_byte_equal(tmp_path):
    """The tensor_chain kind is a plan-level contract, not a
    ModelServing feature: a conv DAG over a range-placed ITEMS set
    (one rank-4 stack per item), stamped with ``mode="items"``,
    scatters per shard and chains per-item outputs in slot order —
    byte-equal to the solo engine."""
    rng = np.random.default_rng(17)
    images = [_int_f32(rng, (1, 3, 8, 8)) for _ in range(6)]
    kernels = _int_f32(rng, (4, 3, 3, 3))
    bias = _int_f32(rng, (4,))

    def load_weights(c, db):
        c.create_set(db, "kernels", type_name="tensor4d")
        c.create_set(db, "bias", type_name="tensor4d")
        c.send_data(db, "kernels", [kernels])
        c.send_data(db, "bias", [bias])

    with solo(tmp_path, "convsolo") as (_ctl, saddr):
        sc = RemoteClient(saddr)
        m = Conv2DModel(db="conv", activation="relu")
        m.setup(sc)
        sc.send_data("conv", "images", list(images))
        load_weights(sc, "conv")
        res = sc.execute_computations(m.build_inference_dag(),
                                      job_name="convsolo")
        oracle = [np.asarray(v) for v in next(iter(res.values()))]
        sc.close()

    with pool(tmp_path, n_workers=2) as (_leader, _workers, addr):
        c = RemoteClient(addr)
        m = Conv2DModel(db="conv", activation="relu")
        c.create_database("conv")
        c.create_set("conv", "images", type_name="tensor4d",
                     placement="range")
        entry = c._placement_entry("conv", "images", refresh=True)
        for sl in entry["slots"]:
            wc = RemoteClient(sl["addr"])
            wc.create_database("conv")
            load_weights(wc, "conv")
            wc.close()
        c.send_data("conv", "images", list(images))

        sink = m.build_inference_dag()
        sink.scatter_gather = {"mode": "items"}
        reply = c._request(
            MsgType.EXECUTE_COMPUTATIONS,
            {"sinks": [sink], "job_name": "convpool",
             "materialize": True, "explain": False},
            codec=CODEC_PICKLE)
        results = c._collect_results(reply["results"], True)
        got = [np.asarray(v) for v in next(iter(results.values()))]
        assert len(got) == len(oracle)
        for g, o in zip(got, oracle):
            assert np.array_equal(g, o)
        c.close()


# --- refusal shape stays typed ----------------------------------------

def test_undeclared_chain_refuses_typed(tmp_path):
    """A sink WITHOUT the scatter_gather declaration over a sharded
    tensor set still refuses with the scatter refusal naming the
    supported shapes — the declaration is the opt-in, never inferred."""
    rng = np.random.default_rng(19)
    weights = _ff_weights(rng, 12, 8, 5)

    with pool(tmp_path, n_workers=2) as (_leader, _workers, addr):
        model = FFModel(db="ffrefuse", block=(4, 4))

        def load(c):
            model.setup(c)
            model.load_weights(c, *weights)

        srv = ModelServing(model, addr, batch_axis=1, block=model.block)
        srv.deploy(load)
        c = RemoteClient(addr)
        c.send_matrix("ffrefuse", "inputs", _int_f32(rng, (12, 12)),
                      (4, 4))
        sink = model.build_inference_dag()  # no scatter_gather stamp
        with pytest.raises(RemoteError, match="scatter_gather"):
            c.execute_computations(sink, job_name="refused")
        c.close()
        srv.close()


# --- sharded ANALYZE_SET fan-out --------------------------------------

def test_analyze_set_sharded_merges(tmp_path):
    """ANALYZE_SET over a partitioned table merges per-shard
    summaries: rows sum, min/max envelope, dictionaries union in slot
    order — matching the solo daemon analyzing the same table."""
    rng = np.random.default_rng(23)
    n = 60
    t = ColumnTable.from_columns({
        "k": rng.integers(0, 9, n).astype(np.int32),
        "cat": np.array([("a", "b", "c")[i]
                         for i in rng.integers(0, 3, n)], dtype=object)})

    with solo(tmp_path, "ansolo") as (_ctl, saddr):
        sc = RemoteClient(saddr)
        sc.create_database("d")
        sc.create_set("d", "t", type_name="table")
        sc.send_table("d", "t", t)
        oracle = sc.analyze_set("d", "t")
        sc.close()

    with pool(tmp_path, n_workers=2) as (_leader, _workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        c.send_table("d", "t", t)
        before = _counter("shard.analyze_fanouts")
        info = c.analyze_set("d", "t")
        assert _counter("shard.analyze_fanouts") == before + 1
        assert info["num_rows"] == oracle["num_rows"] == n
        s, o = info["stats"]["k"], oracle["stats"]["k"]
        assert (s.n_rows, s.min_val, s.max_val) == \
            (o.n_rows, o.min_val, o.max_val)
        assert info["dicts"]["cat"] == oracle["dicts"]["cat"]
        c.close()


def test_analyze_set_local_only_stays_local(tmp_path):
    """local_only analyzes only the coordinator's own pages (the
    worker-facing frame the fan-out itself sends)."""
    with pool(tmp_path, n_workers=2) as (leader, _workers, addr):
        c = RemoteClient(addr)
        c.create_database("d")
        c.create_set("d", "t", type_name="table", placement="range")
        t = ColumnTable.from_columns(
            {"k": np.arange(12, dtype=np.int32)})
        c.send_table("d", "t", t)
        reply = c._request(MsgType.ANALYZE_SET,
                           {"db": "d", "set": "t", "local_only": True})
        assert reply["num_rows"] < 12  # one slot's rows only
        c.close()
