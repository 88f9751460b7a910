"""Transformer layer serving — the long-context flagship.

No reference analogue exists (netsDB predates attention, SURVEY §5);
this model completes the framework's long-context story: a transformer
block whose weights live in database sets like every other model's, a
single-chip forward, and a sequence-parallel forward where activations
are sharded on the sequence axis and attention runs as ring attention
over the mesh (``netsdb_tpu.parallel.ring``) — the capability that
subsumes the reference's "scale the big dimension" relational SUMMA.

Layer = pre-LN MHA + residual, pre-LN MLP (gelu) + residual.
x: (batch, seq, embed).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from netsdb_tpu.client import Client
from netsdb_tpu.ops.attention import mha_forward
from netsdb_tpu.parallel.ring import ring_attention

_HI = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TransformerLayerParams:
    w_qkv: jax.Array   # (E, 3E)
    w_out: jax.Array   # (E, E)
    w_up: jax.Array    # (E, 4E)
    w_down: jax.Array  # (4E, E)


class TransformerLayerModel:
    SETS = ("w_qkv", "w_out", "w_up", "w_down")

    def __init__(self, db: str = "transformer", num_heads: int = 8):
        self.db = db
        self.num_heads = num_heads

    def setup(self, client: Client, placements=None,
              storages=None) -> None:
        """``placements`` maps set name → Placement (weights typically
        replicated; the activation set sharded on the sequence axis) —
        the long-context model declared distributed the same way the
        relational sets are (round 3). ``storages`` maps set name →
        "memory"|"paged": paged weight sets stream through the staged
        DAG (``build_forward_dag_staged``)."""
        client.create_database(self.db)
        for s in self.SETS:
            client.create_set(self.db, s,
                              placement=(placements or {}).get(s),
                              storage=(storages or {}).get(s, "memory"))

    def load_random_weights(self, client: Client, embed: int,
                            seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        scale = embed ** -0.5
        for name, shape in (("w_qkv", (embed, 3 * embed)),
                            ("w_out", (embed, embed)),
                            ("w_up", (embed, 4 * embed)),
                            ("w_down", (4 * embed, embed))):
            client.send_matrix(self.db, name,
                               rng.standard_normal(shape).astype(np.float32)
                               * scale, (min(512, shape[0]), min(512, shape[1])))

    def params_from_store(self, client: Client) -> TransformerLayerParams:
        g = lambda n: client.get_tensor(self.db, n).to_dense()
        return TransformerLayerParams(w_qkv=g("w_qkv"), w_out=g("w_out"),
                                      w_up=g("w_up"), w_down=g("w_down"))

    # --- math ---------------------------------------------------------
    @staticmethod
    def _ln(x):
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5)

    def _mlp(self, x, p: TransformerLayerParams):
        h = jax.nn.gelu(jnp.einsum("bse,ef->bsf", x, p.w_up, precision=_HI))
        return jnp.einsum("bsf,fe->bse", h, p.w_down, precision=_HI)

    def forward(self, p: TransformerLayerParams, x: jax.Array,
                causal: bool = True, impl: Optional[str] = None
                ) -> jax.Array:
        """Single-chip forward. ``impl`` picks the attention core
        (``ops.attention.attention_dispatch``; None auto-selects)."""
        a = mha_forward(self._ln(x), p.w_qkv, p.w_out, self.num_heads,
                        causal=causal, impl=impl)
        x = x + a
        return x + self._mlp(self._ln(x), p)

    def forward_sp(self, p: TransformerLayerParams, x: jax.Array, mesh: Mesh,
                   axis: str = "data", causal: bool = True) -> jax.Array:
        """Sequence-parallel forward: x sharded (None, axis, None). The
        projections/MLP are per-position (XLA keeps them local); the
        attention core rotates k/v around the ring."""
        from netsdb_tpu.ops.attention import merge_project, qkv_project

        q, k, v = qkv_project(self._ln(x), p.w_qkv, self.num_heads)
        spec = NamedSharding(mesh, P(None, None, axis, None))
        q, k, v = (jax.lax.with_sharding_constraint(t, spec)
                   for t in (q, k, v))
        out = ring_attention(q, k, v, mesh, axis=axis, causal=causal)
        x = x + merge_project(out, p.w_out)
        return x + self._mlp(self._ln(x), p)

    # --- set-API serving (round 3) ------------------------------------
    def load_inputs(self, client: Client, x: np.ndarray,
                    input_set: str = "x", placement=None) -> None:
        """Store an activation batch (batch, seq, embed) as a raw-array
        set through the public data path (works with the in-process
        client AND the RemoteClient — and therefore fans out to
        follower daemons in multi-host mode). With a placement whose
        spec shards dim 1 (the sequence axis), ingest shards the
        sequence over the mesh — long-context inputs live distributed
        in the database like any other set. Unplaced inputs get a
        trivial replicated placement so the stored item is a device
        array either way (the executor's traced-scan path takes
        jax.Arrays; bare numpy items stay host objects by design)."""
        from netsdb_tpu.parallel.placement import Placement

        if placement is None:
            placement = Placement((("data", 1),),
                                  (None,) * np.asarray(x).ndim)
        client.create_set(self.db, input_set, placement=placement)
        client.clear_set(self.db, input_set)
        client.send_data(self.db, input_set,
                         [np.asarray(x, np.float32)])

    def build_forward_dag(self, client: Client, input_set: str = "x",
                          output_set: str = "y", causal: bool = True,
                          placement=None):
        """SCAN(x) ⋈ SCAN(weights...) → forward → OUTPUT. When the
        input set's placement shards the sequence axis, the traced body
        runs the ring-attention sequence-parallel forward over that
        placement's mesh; unplaced sets run the single-chip forward —
        the SAME DAG, distribution decided by how the sets were created
        (netsdb_tpu round-3 rule).

        ``placement``: the input set's placement. Defaults to looking
        it up in the client's store; a RemoteClient has no store, so
        remote callers pass the placement they created the set with."""
        from netsdb_tpu.plan.computations import Join, ScanSet, WriteSet
        from netsdb_tpu.storage.store import SetIdentifier

        if placement is None and hasattr(client, "store"):
            placement = client.store.placement_of(
                SetIdentifier(self.db, input_set))
        mesh = axis = None
        if placement is not None:
            sharded_axes = [a for a in placement.spec if a is not None]
            if sharded_axes:
                mesh = placement.mesh()
                ax = sharded_axes[0]
                axis = ax[0] if isinstance(ax, tuple) else ax
                if mesh.shape[axis] == 1:
                    mesh = axis = None  # degraded single-device mesh

        def fwd(gathered, w_down_bt):
            x, wq, wo, wu = gathered
            p = TransformerLayerParams(
                w_qkv=wq.to_dense(), w_out=wo.to_dense(),
                w_up=wu.to_dense(), w_down=w_down_bt.to_dense())
            if mesh is not None:
                return self.forward_sp(p, x, mesh, axis, causal=causal)
            return self.forward(p, x, causal=causal)

        g1 = Join(ScanSet(self.db, input_set), ScanSet(self.db, "w_qkv"),
                  fn=lambda a, b: (a, b), label="gather:w_qkv",
                  passthrough=True)
        g2 = Join(g1, ScanSet(self.db, "w_out"),
                  fn=lambda a, b: a + (b,), label="gather:w_out",
                  passthrough=True)
        g3 = Join(g2, ScanSet(self.db, "w_up"),
                  fn=lambda a, b: a + (b,), label="gather:w_up",
                  passthrough=True)
        # the traced body CLOSES OVER the mesh, so the compiled-plan
        # cache key (built from labels) must pin the mesh identity —
        # axis names, shape AND device ids — or a same-shaped DAG built
        # for a different/reinitialized mesh would reuse a stale closure
        mesh_tag = (None if mesh is None else
                    (tuple(mesh.shape.items()),
                     tuple(d.id for d in mesh.devices.flat)))
        out = Join(g3, ScanSet(self.db, "w_down"), fn=fwd,
                   label=f"transformer-fwd:{self.num_heads}:{causal}:"
                         f"{axis}:{mesh_tag}")
        return WriteSet(out, self.db, output_set)

    def build_forward_dag_staged(self, input_set: str = "x",
                                 output_set: str = "y",
                                 causal: bool = True):
        """Forward as STAGED Computation nodes (ln → qkv-proj →
        attention core → out-proj → residual → ln → MLP-up → MLP-down
        → residual) instead of one fused fn, so EVERY weight matrix
        (w_qkv, w_out, w_up, w_down) may live in a ``storage="paged"``
        set and STREAM through the DAG: each weight's row blocks are
        contraction slices accumulated by a reduce-mode
        :class:`~netsdb_tpu.plan.fold.TensorFold` (the reference's
        page-fed weight scans, ``SimpleFF.cc:94-290``, applied to the
        transformer layer). With resident sets the same DAG evaluates
        the plain fns — storage stays a property of the set, not the
        query."""
        from netsdb_tpu.plan.computations import (Apply, Join, ScanSet,
                                                  WriteSet)
        from netsdb_tpu.plan.fold import TensorFold

        heads, db = self.num_heads, self.db

        def contract_partial(eq):
            def partial(carry, start, block, acts):
                sl = jax.lax.dynamic_slice_in_dim(
                    acts, start, block.shape[0], axis=-1)
                p = jnp.einsum(eq, sl, block, precision=_HI)
                return p if carry is None else carry + p
            return partial

        def proj_fold():  # (B,S,E') @ paged (E',F): rows = contraction
            return TensorFold(mode="reduce",
                              partial=contract_partial("bse,ef->bsf"))

        from netsdb_tpu.ops.attention import (attention_dispatch,
                                              merge_heads,
                                              split_qkv_heads)

        ln1 = Apply(ScanSet(db, input_set), fn=self._ln, label="ln1")
        # qkv projection: w_qkv (E,3E) may be paged — its row blocks
        # are contraction slices of ln(x)
        qkv = Join(ln1, ScanSet(db, "w_qkv"),
                   fn=lambda xs, w: jnp.einsum("bse,ef->bsf", xs,
                                               w.to_dense(),
                                               precision=_HI),
                   tensor_fold=proj_fold(), label="qkv-proj")

        def attn_core(q_k_v):
            q, k, v = split_qkv_heads(q_k_v, heads)
            return merge_heads(attention_dispatch(q, k, v,
                                                  causal=causal))

        core = Apply(qkv, fn=attn_core,
                     label=f"attn-core:{heads}:{causal}")
        # out projection: w_out (E,E) may be paged the same way
        proj = Join(core, ScanSet(db, "w_out"),
                    fn=lambda os, w: jnp.einsum("bse,ef->bsf", os,
                                                w.to_dense(),
                                                precision=_HI),
                    tensor_fold=proj_fold(), label="out-proj")
        a1 = Join(ScanSet(db, input_set), proj,
                  fn=lambda x, a: x + a, label="residual1")
        ln2 = Apply(a1, fn=self._ln, label="ln2")

        h = Join(ln2, ScanSet(db, "w_up"),
                 fn=lambda xs, wu: jax.nn.gelu(jnp.einsum(
                     "bse,ef->bsf", xs, wu.to_dense(), precision=_HI)),
                 tensor_fold=TensorFold(
                     mode="reduce", partial=contract_partial("bse,ef->bsf"),
                     finalize=lambda c, xs: jax.nn.gelu(c)),
                 label="mlp-up")
        mlp = Join(h, ScanSet(db, "w_down"),
                   fn=lambda hs, wd: jnp.einsum(
                       "bsf,fe->bse", hs, wd.to_dense(), precision=_HI),
                   tensor_fold=TensorFold(
                       mode="reduce",
                       partial=contract_partial("bsf,fe->bse")),
                   label="mlp-down")
        out = Join(a1, mlp, fn=lambda a, m2: a + m2, label="residual2")
        return WriteSet(out, db, output_set)

    def serve_forward(self, client: Client, input_set: str = "x",
                      output_set: str = "y", causal: bool = True,
                      placement=None) -> jax.Array:
        sink = self.build_forward_dag(client, input_set, output_set,
                                      causal, placement=placement)
        results = client.execute_computations(
            sink, job_name=f"{self.db}-forward")
        return next(iter(results.values()))

    def loss(self, p: TransformerLayerParams, x: jax.Array,
             targets: jax.Array) -> jax.Array:
        """Simple next-step regression loss for the training dry-run."""
        out = self.forward(p, x)
        return jnp.mean((out - targets) ** 2)

    def train_step(self, p, x, targets, lr: float = 1e-2):
        l, g = jax.value_and_grad(self.loss)(p, x, targets)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), l
