"""Transformer layer model: sets round-trip, single-chip vs
sequence-parallel equivalence, training step, graft-entry dryrun."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from netsdb_tpu.models.transformer import (
    TransformerLayerModel, TransformerLayerParams)
from netsdb_tpu.parallel.mesh import make_mesh

RNG = np.random.default_rng(3)


@pytest.fixture(scope="module")
def model_and_params():
    embed = 32
    tl = TransformerLayerModel(num_heads=4)
    p = TransformerLayerParams(
        w_qkv=jnp.asarray(RNG.standard_normal((embed, 3 * embed)),
                          jnp.float32) * 0.1,
        w_out=jnp.asarray(RNG.standard_normal((embed, embed)),
                          jnp.float32) * 0.1,
        w_up=jnp.asarray(RNG.standard_normal((embed, 4 * embed)),
                         jnp.float32) * 0.1,
        w_down=jnp.asarray(RNG.standard_normal((4 * embed, embed)),
                           jnp.float32) * 0.1,
    )
    return tl, p, embed


def test_sets_roundtrip(client):
    tl = TransformerLayerModel(db="tf1", num_heads=4)
    tl.setup(client)
    tl.load_random_weights(client, embed=32, seed=0)
    p = tl.params_from_store(client)
    assert p.w_qkv.shape == (32, 96) and p.w_down.shape == (128, 32)
    x = jnp.asarray(RNG.standard_normal((2, 16, 32)), jnp.float32)
    out = tl.forward(p, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


def test_sequence_parallel_matches_single_chip(model_and_params):
    tl, p, embed = model_and_params
    mesh = make_mesh((8,), ("sp",))
    x = jnp.asarray(RNG.standard_normal((1, 64, embed)), jnp.float32)
    expect = tl.forward(p, x)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "sp", None)))
    out = jax.jit(lambda pp, xx: tl.forward_sp(pp, xx, mesh, "sp"))(p, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-3, atol=1e-4)


def test_train_step_reduces_loss(model_and_params):
    tl, p, embed = model_and_params
    x = jnp.asarray(RNG.standard_normal((2, 16, embed)), jnp.float32)
    y = jnp.asarray(RNG.standard_normal((2, 16, embed)), jnp.float32)
    step = jax.jit(tl.train_step)
    losses = []
    for _ in range(5):
        p, l = step(p, x, y)
        losses.append(float(l))
    assert losses[-1] < losses[0]


def test_graft_entry_dryrun_all_sizes():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 16)
    for n in (1, 2, 4, 8):
        g.dryrun_multichip(n)


def test_transformer_sp_through_set_api(tmp_path):
    """Long-context through the database API (round 3): weights in
    replicated placed sets, activations sharded on the SEQUENCE axis,
    and the forward DAG runs ring attention over the placement's mesh —
    results match the single-device forward from unplaced sets."""
    import numpy as np

    from netsdb_tpu.client import Client
    from netsdb_tpu.config import Configuration
    from netsdb_tpu.models.transformer import TransformerLayerModel
    from netsdb_tpu.parallel.placement import Placement

    embed, seq, heads = 64, 64, 4
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, seq, embed)).astype(np.float32)

    def run(client, placements, x_placement):
        m = TransformerLayerModel(db="tl", num_heads=heads)
        m.setup(client, placements=placements)
        m.load_random_weights(client, embed, seed=5)
        m.load_inputs(client, x, placement=x_placement)
        return np.asarray(m.serve_forward(client))

    axes = (("sp", 8),)
    dist = run(Client(Configuration(root_dir=str(tmp_path / "a"))),
               {s: Placement(axes, (None, None))
                for s in TransformerLayerModel.SETS},
               Placement(axes, (None, "sp", None)))
    solo = run(Client(Configuration(root_dir=str(tmp_path / "b"))),
               None, None)
    np.testing.assert_allclose(dist, solo, rtol=2e-3, atol=2e-3)
