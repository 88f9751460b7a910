"""Feed-forward NN inference in the database — the flagship workload.

Mirrors the reference FF application end to end
(``src/FF/source/SimpleFF.cc``, driver ``src/tests/source/FFTest.cc``):

- ``setup``/``create_sets`` ≙ ``ff::setup`` registering the 12 UDF .so
  libs + ``ff::createSet`` of {inputs, w1, b1, wo, bo, y1, yo, output}
  (``SimpleFF.cc:60-82``);
- ``load_random_weights`` ≙ ``ff::loadMatrix`` (random blocked matrices);
- ``inference`` ≙ ``ff::inference_unit`` (``SimpleFF.cc:331-424``):
  stage A  y1 = relu(w1·inputsᵀ + b1); yo = wo·y1 + bo
  stage B  output = softmax over labels (exp → row-sum → normalize);
- the DAG built here is scan→join→agg→map→write Computations, so the
  plan dump shows the same relational shape as the reference's TCAP.

Layout convention follows the reference: inputs are (batch x features),
weights (out x in), activations flow as (features x batch).

``train_step`` has no reference analogue as a fused op (netsDB trains
offline in TF/PyTorch and imports weights) but is required for the
multi-chip dry-run and completes the framework: cross-entropy + SGD via
``jax.grad`` over the same blocked tensors.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from netsdb_tpu.client import Client
from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.ops import nn as nn_ops
from netsdb_tpu.ops.matmul import matmul, matmul_t
from netsdb_tpu.plan.computations import Apply, Join, ScanSet, WriteSet


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FFParams:
    w1: BlockedTensor  # (hidden x features)
    b1: BlockedTensor  # (hidden x 1)
    wo: BlockedTensor  # (labels x hidden)
    bo: BlockedTensor  # (labels x 1)


class FFModel:
    """One-hidden-layer FF classifier stored as database sets."""

    SETS = ("inputs", "w1", "b1", "wo", "bo", "y1", "yo", "output")

    def __init__(self, db: str = "ff", block: Tuple[int, int] = (512, 512),
                 compute_dtype: Optional[str] = None):
        self.db = db
        self.block = block
        self.compute_dtype = compute_dtype

    # --- setup (ref ff::setup + createSet, SimpleFF.cc:60-82) ---------
    def setup(self, client: Client,
              placements: Optional[Dict[str, object]] = None,
              storages: Optional[Dict[str, str]] = None) -> None:
        """``placements`` maps set name → Placement: declare at createSet
        how each model set shards over the mesh (inputs/activations on
        ``data``, weight rows/cols on ``model``, biases replicated) —
        the reference's per-set PartitionPolicy, upgraded from "which
        worker" to "which mesh axis". Execution then distributes with no
        further client involvement: the executor's jit sees the stored
        shardings.

        ``storages`` maps set name → "memory"|"paged": weight sets
        declared ``paged`` live as arena pages and STREAM through the
        inference DAG (larger-than-HBM weights, the reference's
        storage-managed weight scans — ``SimpleFF.cc:94-290``)."""
        client.create_database(self.db)
        for s in self.SETS:
            client.create_set(self.db, s,
                              placement=(placements or {}).get(s),
                              storage=(storages or {}).get(s, "memory"))
        client.register_type("FFMatrixBlock", "netsdb_tpu.core.blocked:BlockedTensor")
        # a live placement advisor (client.set_placement_advisor) may
        # have chosen the block shape at create_set — adopt it so the
        # whole model blocks consistently with its sets' placement.
        # (RemoteClient has no local catalog; placement is decided
        # daemon-side there.)
        catalog = getattr(client, "catalog", None)
        if catalog is not None:
            placed = (catalog.get_set(self.db, "w1") or {}).get(
                "meta", {}).get("block_shape")
            if placed:
                self.block = tuple(placed)

    def load_weights(self, client: Client, w1, b1, wo, bo) -> None:
        br = self.block[0]
        client.send_matrix(self.db, "w1", w1, self.block)
        client.send_matrix(self.db, "b1", np.asarray(b1).reshape(-1, 1), (br, 1))
        client.send_matrix(self.db, "wo", wo, self.block)
        client.send_matrix(self.db, "bo", np.asarray(bo).reshape(-1, 1), (br, 1))

    def load_random_weights(self, client: Client, features: int, hidden: int,
                            labels: int, seed: int = 0) -> None:
        """ref ff::loadMatrix with random data (FFTest.cc:100-117)."""
        rng = np.random.default_rng(seed)
        scale1 = np.sqrt(2.0 / features)
        scale2 = np.sqrt(2.0 / hidden)
        self.load_weights(
            client,
            rng.standard_normal((hidden, features), dtype=np.float32) * scale1,
            rng.standard_normal((hidden,), dtype=np.float32) * 0.01,
            rng.standard_normal((labels, hidden), dtype=np.float32) * scale2,
            rng.standard_normal((labels,), dtype=np.float32) * 0.01,
        )

    def load_inputs(self, client: Client, inputs: np.ndarray) -> None:
        client.send_matrix(self.db, "inputs", inputs, self.block)

    # --- inference (ref ff::inference_unit, SimpleFF.cc:331-424) ------
    def build_inference_dag(self, dropout_rate: float = 0.0,
                            key: Optional[jax.Array] = None,
                            input_set: str = "inputs",
                            output_set: str = "output") -> WriteSet:
        """Computation DAG with the reference's relational shape.

        ``input_set``/``output_set`` let concurrent clients share the
        resident weight sets while scanning/writing private sets — the
        served-inference pattern (many QueryClients, one loaded model,
        reference ``QueryClient.h:160-224``)."""
        cd = self.compute_dtype
        inputs = ScanSet(self.db, input_set)
        w1 = ScanSet(self.db, "w1")
        b1 = ScanSet(self.db, "b1")
        wo = ScanSet(self.db, "wo")
        bo = ScanSet(self.db, "bo")
        # both weight matmuls are row-decomposable in the weight: when
        # the weight set is storage="paged", the executor streams its
        # row-block pages through the same fn and concatenates output
        # rows (out_block pins the assembled meta to the resident
        # path's) — the reference's page-fed weight scans
        # (SimpleFF.cc:94-290 + FFMatrixBlockScanner.h); resident sets
        # ignore the fold entirely
        from netsdb_tpu.plan.fold import TensorFold

        def _dense(v):
            return np.asarray(v.to_dense()) \
                if isinstance(v, BlockedTensor) else np.asarray(v)

        # the SUMMA declarations (fn(block, x) == block @ rhs(x)) make
        # both weight streams routable through the distributed engine
        # under config.distributed_matmul — declared ONLY under full-
        # precision compute: SUMMA's k-panel accumulation reassociates
        # the contraction (exact for f32 HIGHEST over integer-valued
        # operands, last-ulp for reduced precision epilogues)
        wfold = TensorFold(mode="rows",
                           out_block=(self.block[0], self.block[0]),
                           summa_rhs=(lambda x: _dense(x).T)
                           if cd is None else None)
        rfold = TensorFold(mode="rows",
                           out_block=(self.block[0], self.block[0]),
                           summa_rhs=(lambda y: _dense(y))
                           if cd is None else None)
        # FFTransposeMult + FFAggMatrix: w1 · inputsᵀ → (hidden x batch)
        h = Join(w1, inputs, fn=lambda w, x: matmul_t(w, x, cd,
                                                      accum_dtype=cd),
                 label="FFTransposeMult", tensor_fold=wfold)
        # FFReluBiasSum
        y1 = Join(h, b1,
                  fn=lambda hh, bb: nn_ops.bias_relu(hh, bb, dropout_rate, key),
                  label="FFReluBiasSum")
        # FFInputLayerJoin + FFAggMatrix: wo · y1 → (labels x batch)
        yo_lin = Join(wo, y1, fn=lambda w, y: matmul(w, y, cd),
                      label="FFInputLayerJoin", tensor_fold=rfold)
        # FFTransposeBiasSum → FFRowAggregate → FFOutputLayer, fused
        out = Join(yo_lin, bo,
                   fn=lambda y, b: nn_ops.ff_output_layer(y, b, axis=0),
                   label="FFOutputLayer")
        return WriteSet(out, self.db, output_set)

    def inference(self, client: Client, dropout_rate: float = 0.0,
                  key: Optional[jax.Array] = None) -> BlockedTensor:
        sink = self.build_inference_dag(dropout_rate, key)
        results = client.execute_computations(sink, job_name=f"{self.db}-inference")
        return next(iter(results.values()))

    def build_fused_inference_dag(self, params: "FFParams",
                                  out_mode: str = "softmax") -> WriteSet:
        """Whole network inside ONE computation — the reference's
        ``src/FF_proj`` variant (``FullyConnectedNetwork.h:18-127``): a
        single SelectionComp holding all weights as members, scanning
        only the input set. ``out_mode="label"`` mirrors FF_proj's head
        (sigmoid then 0.5-threshold ``outLabel`` —
        ``FullyConnectedNetwork.cc:13-25``); "softmax" uses the standard
        inference tail."""
        if out_mode not in ("softmax", "label"):
            raise ValueError(
                f"out_mode must be 'softmax' or 'label', got {out_mode!r}")
        cd = self.compute_dtype

        def whole_network(x: BlockedTensor) -> BlockedTensor:
            h = nn_ops.bias_relu(matmul_t(params.w1, x, cd, accum_dtype=cd),
                                 params.b1)
            yo = matmul(params.wo, h, cd)
            if out_mode == "label":
                p = nn_ops.bias_sigmoid(yo, params.bo)
                # padding margins are sigmoid-remasked to 0 → stay 0
                return p.with_data((p.data > 0.5).astype(p.data.dtype))
            return nn_ops.ff_output_layer(yo, params.bo, axis=0)

        net = Apply(ScanSet(self.db, "inputs"), whole_network,
                    label="FullyConnectedNetwork")
        return WriteSet(net, self.db, "output")

    def inference_fused(self, client: Client,
                        out_mode: str = "softmax") -> BlockedTensor:
        """FF_proj-style single-UDF inference over stored weights."""
        sink = self.build_fused_inference_dag(self.params_from_store(client),
                                              out_mode)
        results = client.execute_computations(
            sink, job_name=f"{self.db}-inference-fused-{out_mode}")
        return next(iter(results.values()))

    # --- pure-function forms (for jit/sharding) -----------------------
    def params_from_store(self, client: Client) -> FFParams:
        return FFParams(
            w1=client.get_tensor(self.db, "w1"),
            b1=client.get_tensor(self.db, "b1"),
            wo=client.get_tensor(self.db, "wo"),
            bo=client.get_tensor(self.db, "bo"),
        )

    def forward(self, params: FFParams, inputs: BlockedTensor) -> BlockedTensor:
        """(batch x features) → softmax probs (labels x batch). Same math
        as the DAG, one traced function. When reduced precision is opted
        in (``compute_dtype``) the hidden activation also stays in that
        dtype (accum_dtype), halving its HBM traffic; the output layer
        always accumulates f32 for the softmax."""
        cd = self.compute_dtype
        h = nn_ops.bias_relu(matmul_t(params.w1, inputs, cd, accum_dtype=cd),
                             params.b1)
        yo = matmul(params.wo, h, cd)
        return nn_ops.ff_output_layer(yo, params.bo, axis=0)

    def logits(self, params: FFParams, inputs: BlockedTensor) -> BlockedTensor:
        cd = self.compute_dtype
        h = nn_ops.bias_relu(matmul_t(params.w1, inputs, cd, accum_dtype=cd),
                             params.b1)
        return matmul(params.wo, h, cd)

    # --- training (TPU-first extension; powers dryrun_multichip) ------
    def loss(self, params: FFParams, inputs: BlockedTensor,
             labels_onehot: BlockedTensor) -> jax.Array:
        """Masked softmax cross-entropy. ``labels_onehot``: (labels x batch)
        blocked like the output."""
        lg = self.logits(params, inputs)
        logits_masked = jnp.where(lg.mask(jnp.bool_), lg.data, -jnp.inf)
        logp = jax.nn.log_softmax(logits_masked, axis=0)
        logp = jnp.nan_to_num(logp, nan=0.0, neginf=0.0)
        batch = inputs.shape[0]
        return -jnp.sum(labels_onehot.data * logp) / batch

    def train_step(self, params: FFParams, inputs: BlockedTensor,
                   labels_onehot: BlockedTensor,
                   lr: float = 0.1) -> Tuple[FFParams, jax.Array]:
        loss, grads = jax.value_and_grad(self.loss)(params, inputs, labels_onehot)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return new, loss
