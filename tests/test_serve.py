"""Serve-layer tests — the PDBServer/PDBClient pair.

In-process daemon on an ephemeral localhost port (the reference's
pseudo-cluster runs real processes over real TCP on one machine —
``scripts/startPseudoCluster.py:33-51``; here the listener thread + real
sockets exercise the same protocol with test-speed startup), plus one
true multi-process integration test via the CLI daemon.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
from netsdb_tpu.models.ff import FFModel
from netsdb_tpu.serve.client import RemoteClient, RemoteError
from netsdb_tpu.serve.server import ServeController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def server(tmp_path):
    config = Configuration(root_dir=str(tmp_path / "served"))
    ctl = ServeController(config, port=0)
    port = ctl.start()
    yield ctl, f"127.0.0.1:{port}"
    ctl.shutdown()


def test_hello_ping_and_stats(server):
    ctl, addr = server
    c = RemoteClient(addr)
    info = c.ping()
    assert info["uptime"] >= 0
    stats = c.collect_stats()
    assert "cache" in stats
    c.close()


def test_ping_names_the_device(server):
    """The first thing a client can ask a daemon is where it computes:
    platform, kind and count exactly as jax reports them in the
    daemon's process (here the suite's 8-device virtual CPU mesh)."""
    import jax

    _, addr = server
    c = RemoteClient(addr)
    dev = c.ping()["device"]
    assert dev == {"platform": jax.devices()[0].platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    assert dev["platform"] == "cpu"
    c.close()


def test_client_address_dispatch(server):
    """Client(address=...) returns the thin RPC client — same facade."""
    _, addr = server
    c = Client(address=addr)
    assert isinstance(c, RemoteClient)
    c.create_database("dispatch")
    c.create_set("dispatch", "s")
    assert c.set_exists("dispatch", "s")
    c.close()


def test_matrix_roundtrip(server):
    _, addr = server
    c = RemoteClient(addr)
    c.create_database("db")
    c.create_set("db", "m")
    a = np.arange(30, dtype=np.float32).reshape(5, 6)
    c.send_matrix("db", "m", a, (4, 4))
    back = c.get_tensor("db", "m")
    np.testing.assert_allclose(back.to_dense(), a)
    assert back.shape == (5, 6)
    c.close()


def test_object_roundtrip_and_errors(server):
    _, addr = server
    c = RemoteClient(addr)
    c.create_database("db")
    c.create_set("db", "objs")
    items = [{"k": i, "v": ("x", i)} for i in range(7)]
    c.send_data("db", "objs", items)
    assert list(c.get_set_iterator("db", "objs")) == items
    # server-side KeyError crosses the wire with its message
    with pytest.raises(RemoteError, match="unknown set"):
        c.get_tensor("db", "missing")
    with pytest.raises(RemoteError, match="does not exist"):
        c.create_set("nodb", "s")
    c.close()


def test_auth_token():
    config = Configuration(root_dir="/tmp/netsdb_serve_auth_test")
    ctl = ServeController(config, port=0, token="sekrit")
    port = ctl.start()
    addr = f"127.0.0.1:{port}"
    try:
        with pytest.raises(RemoteError, match="bad token"):
            RemoteClient(addr, token="wrong")
        c = RemoteClient(addr, token="sekrit")
        assert c.ping()["uptime"] >= 0
        c.close()
    finally:
        ctl.shutdown()


def test_pickle_refused_when_disabled(tmp_path):
    config = Configuration(root_dir=str(tmp_path / "nopickle"))
    ctl = ServeController(config, port=0, allow_pickle=False)
    port = ctl.start()
    try:
        c = RemoteClient(f"127.0.0.1:{port}")
        c.create_database("db")
        c.create_set("db", "objs")
        with pytest.raises(RemoteError, match="pickled frame refused"):
            c.send_data("db", "objs", [1, 2, 3])
        c.close()
    finally:
        ctl.shutdown()


def _load_ff(client, db="ffd", block=(16, 16)):
    rng = np.random.default_rng(3)
    feat, hid, lab = 32, 48, 8
    w1 = (rng.standard_normal((hid, feat)) * 0.1).astype(np.float32)
    b1 = (rng.standard_normal((hid,)) * 0.1).astype(np.float32)
    wo = (rng.standard_normal((lab, hid)) * 0.1).astype(np.float32)
    bo = (rng.standard_normal((lab,)) * 0.1).astype(np.float32)
    x = rng.standard_normal((24, feat)).astype(np.float32)
    model = FFModel(db=db, block=block)
    model.setup(client)
    model.load_weights(client, w1, b1, wo, bo)
    model.load_inputs(client, x)
    return model, (w1, b1, wo, bo, x)


def test_remote_ff_inference_matches_local(server, tmp_path):
    """The FFTest scenario through the RPC hop equals the library path."""
    _, addr = server
    remote = RemoteClient(addr)
    model, weights = _load_ff(remote)
    sink = model.build_inference_dag()
    results = remote.execute_computations(sink, job_name="ff-rpc")
    got = next(iter(results.values())).to_dense()

    local = Client(Configuration(root_dir=str(tmp_path / "local")))
    model2, _ = _load_ff(local)
    want = np.asarray(model2.inference(local).to_dense())
    np.testing.assert_allclose(got, want, atol=1e-5)

    jobs = remote.list_jobs()
    assert any(j["name"] == "ff-rpc" and j["status"] == "done" for j in jobs)
    remote.close()


def test_remote_tpch_bench_matches_local(server, tmp_path):
    """tpchBench through the daemon (the round-1 VERDICT's second
    serve workload): nested customers loaded once server-side, the
    selection + flatten pipeline executed remotely, results equal the
    in-process library path."""
    from netsdb_tpu.workloads import tpch_bench as TB

    _, addr = server
    remote = RemoteClient(addr)
    customers = TB.generate(num_customers=30, seed=11)
    TB.load(remote, customers, db="tb_rpc")
    remote.execute_computations(
        TB.customer_int_selection(db="tb_rpc", threshold=10),
        TB.flatten_triples(db="tb_rpc"),
        job_name="tpchbench-rpc")
    sel = list(remote.get_set_iterator("tb_rpc", "selected_int"))
    flat = list(remote.get_set_iterator("tb_rpc", "triples"))
    assert sel and flat

    local = Client(Configuration(root_dir=str(tmp_path / "tb_local")))
    TB.load(local, customers, db="tb_rpc")
    local.execute_computations(
        TB.customer_int_selection(db="tb_rpc", threshold=10),
        TB.flatten_triples(db="tb_rpc"), job_name="tpchbench-local")
    want_sel = list(local.get_set_iterator("tb_rpc", "selected_int"))
    want_flat = list(local.get_set_iterator("tb_rpc", "triples"))
    assert sorted(c.custKey for c in sel) == \
        sorted(c.custKey for c in want_sel)
    assert sorted((t.customerName, t.supplierName, t.partKey)
                  for t in flat) == \
        sorted((t.customerName, t.supplierName, t.partKey)
               for t in want_flat)
    remote.close()


def test_execute_plan_text_no_pickle(tmp_path):
    """The TCAP path: plan text + entry-point registry, pickle disabled
    end-to-end — remote execution without any code shipping."""
    config = Configuration(root_dir=str(tmp_path / "plan"))
    ctl = ServeController(config, port=0, allow_pickle=False)
    port = ctl.start()
    try:
        c = RemoteClient(f"127.0.0.1:{port}")
        c.create_database("db")
        c.create_set("db", "m")
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        c.send_matrix("db", "m", a, (2, 2))
        plan = "\n".join([
            "in <= SCAN('db', 'm')",
            "t <= APPLY(in, 'transpose')",
            "out <= OUTPUT(t, 'db', 'mt')",
        ])
        results = c.execute_plan(
            plan, {"transpose": "netsdb_tpu.ops.linalg:transpose"},
            job_name="plan-job")
        got = next(iter(results.values())).to_dense()
        np.testing.assert_allclose(got, a.T)
        c.close()
    finally:
        ctl.shutdown()


def test_concurrent_clients_shared_weights(server):
    """N threads, one resident model: private input/output sets, shared
    weight sets — the served-inference pattern. All results must match
    the per-client NumPy oracle."""
    _, addr = server
    setup = RemoteClient(addr)
    model, (w1, b1, wo, bo, _) = _load_ff(setup, db="shared")
    setup.close()

    errs = []

    def one_client(i):
        try:
            c = RemoteClient(addr)
            rng = np.random.default_rng(100 + i)
            x = rng.standard_normal((16, w1.shape[1])).astype(np.float32)
            c.create_set("shared", f"in_{i}")
            c.create_set("shared", f"out_{i}")
            c.send_matrix("shared", f"in_{i}", x, (16, 16))
            sink = model.build_inference_dag(input_set=f"in_{i}",
                                             output_set=f"out_{i}")
            for _ in range(3):
                res = c.execute_computations(sink, job_name=f"client{i}")
            got = next(iter(res.values())).to_dense()
            h = np.maximum(w1 @ x.T + b1[:, None], 0)
            logits = wo @ h + bo[:, None]
            e = np.exp(logits - logits.max(axis=0, keepdims=True))
            want = e / e.sum(axis=0, keepdims=True)
            np.testing.assert_allclose(got, want, atol=1e-5)
            c.close()
        except Exception as e:  # surfaced in the main thread
            errs.append((i, e))

    threads = [threading.Thread(target=one_client, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs


def test_weights_stay_resident_across_sessions(server):
    """Reconnect: the daemon still holds the sets a prior session
    loaded — data resident across client sessions (the defining serve
    property; the library client reloads per process)."""
    _, addr = server
    c1 = RemoteClient(addr)
    c1.create_database("persist")
    c1.create_set("persist", "w")
    a = np.ones((8, 8), np.float32) * 7
    c1.send_matrix("persist", "w", a, (4, 4))
    c1.close()

    c2 = RemoteClient(addr)
    np.testing.assert_allclose(c2.get_tensor("persist", "w").to_dense(), a)
    c2.close()


def test_execute_plan_with_shipped_udf_source(tmp_path):
    """Code shipping on registerType (round-3 item 7): the plan's UDF
    module does NOT exist on the server's import path — its source
    rides the catalog (the reference replicating user-type .so files,
    PDBCatalog.h:45-50) and the daemon execs it at bind time."""
    import sys

    mod_name = "udf_shipped_square_xyz"
    assert mod_name not in sys.modules  # genuinely not installed
    src = "\n".join([
        "import jax.numpy as jnp",
        "def square(t):",
        "    return t.with_data(t.data * t.data)",
    ])
    config = Configuration(root_dir=str(tmp_path / "ship"))
    ctl = ServeController(config, port=0, allow_pickle=False)
    port = ctl.start()
    try:
        c = RemoteClient(f"127.0.0.1:{port}")
        c.create_database("db")
        c.create_set("db", "m")
        a = np.arange(8, dtype=np.float32).reshape(2, 4)
        c.send_matrix("db", "m", a, (2, 2))
        c.register_type("SquareOp", f"{mod_name}:square", source=src)
        plan = "\n".join([
            "in <= SCAN('db', 'm')",
            "sq <= APPLY(in, 'square')",
            "out <= OUTPUT(sq, 'db', 'sq')",
        ])
        results = c.execute_plan(plan, {"square": "SquareOp"},
                                 job_name="shipped-udf")
        got = next(iter(results.values())).to_dense()
        np.testing.assert_allclose(got, a * a)
        c.close()
    finally:
        ctl.shutdown()
        sys.modules.pop(mod_name, None)
