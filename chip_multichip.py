#!/usr/bin/env python3
"""chip_multichip.py — distribution through the set API on four real chips.

ONE process owns all four chips (a daemon in-process, its client on a
loopback socket), so nothing here competes for a device:

1. a live daemon: ``create_set(placement=Placement.data_parallel(ndim=1))``
   + ``send_table`` + ``q01_sink`` over the smoke's synthetic lineitem plus
   one row (2,097,153: the row axis is padded) — the stored column's
   shards sit on 4 distinct devices and the result equals ``cq01`` (one
   device) and a NumPy evaluation of the same rows;
2. the same daemon: FF inference under ``dryrun_multichip``'s placements
   (data x model = 2 x 2) at the flagship width equals the unplaced
   single-device output;
3. ``__graft_entry__.dryrun_multichip(4)`` on the real chips;
4. ``parallel.ring.ring_attention(impl=None)`` at S 8192 over the four
   chips takes the flash-carry kernel and equals the reference;
5. (recorded, not judged) on which device the arrays of a 4-daemon
   in-process pool (a leader and three shard workers) land: the set-up
   binds no daemon to a device.

Run by a builder on a four-chip host: ``python chip_multichip.py``. It is
not part of the driver's check (``chip_smoke.py`` is). ``--dryrun-cpu``
rehearses at tiny sizes on four virtual CPU devices
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``;
``tests/test_chip_smoke.py`` runs that).
Last stdout line: one JSON object; exit code 0 only if 1-4 passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import Any, Dict

import numpy as np

import chip_smoke
from chip_smoke import FOLD_RTOL, check, lineitem, q01_reference

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out", "multichip")

# the smoke's sizes (FF width, lineitem rows) plus what only this script runs
FULL = dict(chip_smoke.FULL, ring=(1, 8, 8192, 128),
            pool_ff=(256, 512, 64, 1024))
DRYRUN = dict(chip_smoke.DRYRUN, ring=(1, 2, 512, 128),
              pool_ff=(32, 64, 16, 64))


def placed_q01(client, ctl, sz: Dict[str, Any]) -> Dict[str, Any]:
    """The smoke's synthetic lineitem (2M rows at full size) plus one row
    in a set placed over the four chips: q01_sink == cq01 on one device
    == NumPy."""
    import jax

    from netsdb_tpu.parallel.placement import Placement
    from netsdb_tpu.relational import dag as rdag
    from netsdb_tpu.relational.queries import cq01
    from netsdb_tpu.relational.table import ColumnTable

    rows = sz["li_rows"] + 1  # not a multiple of 4: the table is padded
    li = lineitem(rows)
    table = ColumnTable(li, {"l_returnflag": ["A", "N", "R"],
                             "l_linestatus": ["F", "O"]})
    client.create_database("tpch")
    client.create_set("tpch", "lineitem", type_name="table",
                      placement=Placement.data_parallel(ndim=1))
    client.send_table("tpch", "lineitem", table)
    col = ctl.library.get_table("tpch", "lineitem")["l_quantity"]
    check(col.shape[0] == rows + 3,
          f"stored column has {col.shape[0]} rows, not {rows} + 3 padding")
    shards = [(s.device.id, s.data.shape[0])
              for s in col.addressable_shards]
    devices = sorted(d for d, _ in shards)
    check(len(set(devices)) == len(jax.devices()) == 4,
          f"lineitem shards sit on devices {devices}")
    check(all(n == (rows + 3) // 4 for _, n in shards),
          f"uneven shards (device, rows): {shards}")
    got = rdag.run_query(client, rdag.q01_sink("tpch"))[0]
    check(np.asarray(got.mask()).all(), "q01: all 6 groups present")
    keys = [(got.dicts["l_returnflag"][int(rf)],
             got.dicts["l_linestatus"][int(ls)])
            for rf, ls in zip(np.asarray(got["l_returnflag"]),
                              np.asarray(got["l_linestatus"]))]
    single = cq01({"lineitem": table})  # one device, unplaced
    check(sorted(keys) == [k for k, _ in single], "q01 group keys vs cq01")
    numpy_ref = q01_reference(li)  # float64, group = returnflag*2+linestatus
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "sum_disc")
    worst = 0.0
    for key, vals in single:
        i = keys.index(key)
        g = ["A", "N", "R"].index(key[0]) * 2 + ["F", "O"].index(key[1])
        count = int(np.asarray(got["count"])[i])
        check(count == vals["count"] == numpy_ref["count"][g],
              f"q01 {key} count {count} vs cq01 {vals['count']} vs NumPy "
              f"{numpy_ref['count'][g]}")
        for name in names:
            v = float(np.asarray(got[name])[i])
            for what, want in (("cq01", vals[name]),
                               ("NumPy", float(numpy_ref[name][g]))):
                rel = abs(v - want) / abs(want)
                worst = max(worst, rel)
                check(rel <= FOLD_RTOL,
                      f"q01 {key} {name}: {v} vs {what} {want}")
    return {"rows": rows, "table_bytes": sum(c.nbytes for c in li.values()),
            "shard_rows": [n for _, n in shards],
            "shard_devices": devices, "groups": len(single),
            "max_rel_err": worst}


def oversized_placement(dryrun: bool) -> Dict[str, Any]:
    """A placement that names more devices than the process holds raises
    on the chips; only the CPU backend collapses it to one device."""
    from netsdb_tpu.parallel.placement import Placement

    big = Placement((("data", 64),), ("data",))
    if dryrun:
        check(big.resolved_axes() == (("data", 1),), "CPU collapse")
        return {"raised": False}
    try:
        axes = big.resolved_axes()
    except ValueError as e:
        return {"raised": True, "message": str(e)}
    raise AssertionError(f"64-device placement resolved to {axes} on 4 chips")


def ff_2x2(client, sz: Dict[str, Any]) -> Dict[str, Any]:
    from netsdb_tpu.models.ff import FFModel
    from netsdb_tpu.parallel.placement import Placement

    axes = (("data", 2), ("model", 2))
    placements = {
        "inputs": Placement(axes, ("data", None)),
        "w1": Placement(axes, ("model", None)),
        "b1": Placement(axes, (None, None)),
        "wo": Placement(axes, (None, "model")),
        "bo": Placement(axes, (None, None)),
        "output": Placement(axes, (None, "data")),
    }
    x = np.random.default_rng(5).standard_normal(
        (sz["batch"], sz["features"])).astype(np.float32)
    outs = {}
    for db, pl in (("ff_2x2", placements), ("ff_single", None)):
        model = FFModel(db=db, block=sz["block"])
        model.setup(client, placements=pl)
        model.load_random_weights(client, sz["features"], sz["hidden"],
                                  sz["labels"], seed=1)
        model.load_inputs(client, x)
        outs[db] = np.asarray(model.inference(client).to_dense())
    check(outs["ff_2x2"].shape == (sz["labels"], sz["batch"]),
          f"FF output shape {outs['ff_2x2'].shape}")
    check(np.isfinite(outs["ff_2x2"]).all(), "2x2 FF output finite")
    err = float(np.abs(outs["ff_2x2"] - outs["ff_single"]).max())
    # the model axis splits wo's contraction: partial sums + psum
    # reassociate f32 adds, nothing more
    check(err <= 1e-6, f"2x2 FF vs single-device: max abs {err:.3e}")
    return {"max_abs_err": err}


def placed_weight_devices(ctl) -> Dict[str, Any]:
    """Where the 2x2 model's stored w1 really sits."""
    w1 = ctl.library.get_tensor("ff_2x2", "w1").data
    devices = sorted(s.device.id for s in w1.addressable_shards)
    check(len(set(devices)) == 4, f"2x2 w1 shards on devices {devices}")
    return {"w1_shard_devices": devices}


def ring(sz: Dict[str, Any], dryrun: bool) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from netsdb_tpu.ops.attention import attention
    from netsdb_tpu.parallel.ring import ring_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    # impl=None must auto-select the flash-carry kernel on the chips;
    # off-TPU auto-selection keeps the naive fold, so the rehearsal asks
    impl = "flash" if dryrun else None
    out: Dict[str, Any] = {}
    for dtype, tol in ((jnp.bfloat16, 5e-2), (jnp.float32, 1e-4)):
        rng = np.random.default_rng(21)
        q, k, v = (jax.device_put(
            jnp.asarray(rng.standard_normal(sz["ring"]), dtype),
            NamedSharding(mesh, P(None, None, "data", None)))
            for _ in range(3))

        def run(q, k, v):
            return ring_attention(q, k, v, mesh, axis="data", causal=True,
                                  impl=impl)

        check("pallas_call" in str(jax.make_jaxpr(run)(q, k, v)),
              "ring_attention did not take the flash-carry kernel")
        got = jax.jit(run)(q, k, v)
        check(len(got.sharding.device_set) == 4, "ring output on 4 chips")
        ref = jax.jit(lambda q, k, v: attention(
            *(t.astype(jnp.float32) for t in (q, k, v)), causal=True))(
                q, k, v)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
        check(err <= tol, f"ring {dtype.__name__} vs attention: {err:.3e}")
        out[f"{dtype.__name__}_err"] = err
    return out


def pool_landing(sz: Dict[str, Any]) -> Dict[str, Any]:
    """OBSERVATION ONLY: a 4-daemon in-process pool (leader + 3 shard
    workers, each a ``ServeController`` on a loopback port) scores one
    FF batch; report which device each daemon's stored
    weights and routed batch slice sit on. No daemon binds a device, so the
    expectation from the code is: all on device 0."""
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.models.ff import FFModel
    from netsdb_tpu.models.serving import ff_serving
    from netsdb_tpu.serve.server import ServeController

    f, h, l, batch = sz["pool_ff"]
    daemons = []
    try:
        for i in range(3):
            w = ServeController(Configuration(
                root_dir=os.path.join(OUT, f"pool_w{i}")), port=0)
            w.start()
            daemons.append(w)
        leader = ServeController(
            Configuration(root_dir=os.path.join(OUT, "pool_leader")),
            port=0, workers=[w.advertise_addr for w in daemons])
        leader.start()
        daemons.append(leader)
        model = FFModel(db="ffpool", block=(64, 64))

        def load(c):
            model.setup(c)
            model.load_random_weights(c, f, h, l, seed=2)

        srv = ff_serving(model, leader.advertise_addr)
        try:
            srv.deploy(load)
            x = np.random.default_rng(6).standard_normal(
                (batch, f)).astype(np.float32)
            out = np.asarray(srv.score(x).to_dense())
            check(out.shape == (l, batch) and np.isfinite(out).all(),
                  "pool FF output")
        finally:
            srv.close()
        landing = {}
        for d in daemons:
            w1 = d.library.get_tensor("ffpool", "w1").data
            xs = d.library.get_tensor("ffpool", "inputs").data
            landing[d.advertise_addr] = {
                "w1_devices": sorted(x.id for x in w1.devices()),
                "batch_slice_devices": sorted(x.id for x in xs.devices())}
        return {"daemons": len(daemons), "landing": landing}
    finally:
        for d in daemons:
            d.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dryrun-cpu", action="store_true",
                    help="rehearse on 4 virtual CPU devices, tiny sizes")
    args = ap.parse_args()
    sz = DRYRUN if args.dryrun_cpu else FULL

    import jax

    import __graft_entry__ as graft
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.serve.client import RemoteClient
    from netsdb_tpu.serve.server import ServeController

    devices = jax.devices()
    want = "cpu" if args.dryrun_cpu else "tpu"
    if devices[0].platform != want or len(devices) < 4:
        raise RuntimeError(
            f"need 4 {want} devices; jax reports {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind})")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    record: Dict[str, Any] = {}

    def run(name, fn):
        t0 = time.perf_counter()
        out = fn()
        record[name] = dict(out or {}, ok=True,
                            seconds=round(time.perf_counter() - t0, 3))
        print(f"[chip_multichip] {name} ok {record[name]}",
              file=sys.stderr, flush=True)

    ctl = ServeController(Configuration(
        root_dir=os.path.join(OUT, "root")), port=0)
    ctl.start()
    try:
        client = RemoteClient(ctl.advertise_addr)
        check(client.ping()["device"]["count"] == len(devices),
              "the daemon sees every chip")
        run("oversized_placement",
            lambda: oversized_placement(args.dryrun_cpu))
        run("placed_q01", lambda: placed_q01(client, ctl, sz))
        run("ff_2x2", lambda: dict(ff_2x2(client, sz),
                                   **placed_weight_devices(ctl)))
        client.close()
    finally:
        ctl.shutdown()
    run("dryrun_multichip_4", lambda: graft.dryrun_multichip(4))
    run("ring_attention", lambda: ring(sz, args.dryrun_cpu))
    run("pool_landing_observation", lambda: pool_landing(sz))
    print(json.dumps({
        "ok": True, "dryrun": bool(args.dryrun_cpu),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "checks": record}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
