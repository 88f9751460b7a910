"""Model serving over the sharded daemon pool — distributed inference.

The reference serves model inference by storing the model as blocked
matrix sets and scoring batches through the relational engine
(``SimpleFF.cc`` + ``QueryClient.h:160-224``: many query clients, one
loaded model). This module is that pattern over the horizontal
scale-out pool (``serve/shard.py``), in three pieces:

* **model-as-blocked-sets ingest** — :meth:`ModelServing.deploy`
  creates the batch-partitioned input tensor set
  (``placement="range"``) on the pool leader and mirrors the model's
  weight sets onto EVERY pool member: weights replicated, activations
  data-parallel by batch — the canonical inference-serving placement.
* **layer-chain plan builder** — the model's inference DAG is built
  against the served input/output sets and stamped with the
  ``scatter_gather`` declaration that opts it into the
  ``tensor_chain`` scatter kind (``plan/scatter.py``): each shard then
  executes the WHOLE chain over its local batch partition through its
  own executor, which compiles it as ONE program per shard — the
  whole-plan jit for resident weight sets (every EXPLAIN node marked
  ``fused``), the region mapper (``plan/fusion.py``) when weights are
  ``storage="paged"`` and must stream.
* **batched scoring frames** — :meth:`ModelServing.score` routed-
  ingests one batch (contiguous row slices to the owning shards, in
  parallel) and executes the chain pool-wide; the coordinator
  concatenates per-shard outputs in slot order, byte-equal to a
  single-daemon run (every output element is computed from exactly
  one shard's rows, never summed across shards).

``explain=True`` scoring returns the per-layer EXPLAIN decomposition:
the coordinator slot's annotated operator tree plus the full
per-shard forest, every node marked with the daemon that executed it.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.core.blocked import batch_block


class ModelServing:
    """Serve one layer-chain model (FF-style: ``build_inference_dag``
    + a ``db``/``block`` surface) over a leader + shard-worker pool.

    ``batch_axis`` is the axis of the model's OUTPUT along which the
    batch runs (1 for FF's ``(labels x batch)`` activations);
    ``gather_mode="items"`` instead concatenates per-shard item LISTS
    (the conv2d shape — one output tensor per input image).
    ``sink_builder`` overrides the default
    ``model.build_inference_dag(input_set=..., output_set=...)`` for
    models whose builder takes no set arguments."""

    def __init__(self, model, leader_addr: str,
                 input_set: str = "inputs", output_set: str = "output",
                 batch_axis: int = 1, gather_mode: str = "concat",
                 block: Optional[Tuple[int, int]] = None,
                 sink_builder: Optional[Callable[[], Any]] = None):
        self.model = model
        self.leader_addr = leader_addr
        self.input_set = input_set
        self.output_set = output_set
        self.batch_axis = int(batch_axis)
        self.gather_mode = gather_mode
        self.block = tuple(block) if block is not None \
            else tuple(getattr(model, "block", ()) or ()) or None
        self.sink_builder = sink_builder
        self.addrs: List[str] = []
        self._leader = None

    # --- lifecycle ----------------------------------------------------
    def _client(self):
        if self._leader is None:
            from netsdb_tpu.serve.client import RemoteClient

            self._leader = RemoteClient(self.leader_addr)
        return self._leader

    def close(self) -> None:
        if self._leader is not None:
            self._leader.close()
            self._leader = None

    def deploy(self, load_model: Callable[[Any], None]) -> List[str]:
        """Model-as-blocked-sets ingest: create the batch-partitioned
        input set on the leader (one slot per pool member), then run
        ``load_model(client)`` against EVERY member — each daemon ends
        up holding the full weight sets locally, which is exactly what
        the tensor_chain subplan's weight ScanSets read shard-side.
        ``load_model`` is typically ``model.setup`` + weight loading;
        set creation is idempotent, so re-deploy refreshes weights in
        place. Returns the pool's slot addresses in slot order."""
        from netsdb_tpu.serve.client import RemoteClient

        c = self._client()
        db = self.model.db
        c.create_database(db)
        c.create_set(db, self.input_set, type_name="tensor",
                     placement="range")
        entry = c._placement_entry(db, self.input_set, refresh=True)
        addrs = [sl["addr"] for sl in entry["slots"]]
        for addr in addrs:
            wc = RemoteClient(addr)
            try:
                load_model(wc)
            finally:
                wc.close()
        self.addrs = addrs
        obs.REGISTRY.counter("models.deploys").inc()
        return addrs

    # --- the layer-chain plan ----------------------------------------
    def _sink(self, rows: int):
        if self.sink_builder is not None:
            sink = self.sink_builder()
        else:
            sink = self.model.build_inference_dag(
                input_set=self.input_set, output_set=self.output_set)
        # the tensor_chain opt-in: declares the chain batch-
        # decomposable along `axis` (plan/scatter.py module docstring);
        # the assembled output's batch axis is blocked by the batch's
        # own rows, as the shards' programs block it
        block = batch_block(rows, self.block, self.batch_axis) \
            if self.block else self.block
        sink.scatter_gather = {"axis": self.batch_axis,
                               "block": block,
                               "mode": self.gather_mode}
        return sink

    # --- batched scoring ---------------------------------------------
    def score(self, batch, explain: bool = False):
        """One scoring frame: routed batch ingest + pool-wide chain
        execution. Returns the assembled output (a BlockedTensor when
        ``block`` is declared); with ``explain=True`` returns
        ``(output, shard_operators)`` — the per-shard EXPLAIN forest,
        every node annotated with its executing daemon."""
        from netsdb_tpu.serve.protocol import CODEC_PICKLE, MsgType

        c = self._client()
        db = self.model.db
        batch = np.asarray(batch, np.float32)
        rows = int(batch.shape[0])
        # a batch under the model's block rows is blocked by its own
        # rows (core/blocked.py::batch_block): the device then pads and
        # computes no rows that nobody asked for
        block = batch_block(rows, self.block) if self.block else None
        # ONE trace for the whole request: its three frames (ship the
        # batch, execute, read the scores back) carry one query id, so
        # the daemon's profile of each joins it; the span's self time
        # is this side's work between and after the frames
        with c.request_trace("models.score"):
            c.send_matrix(db, self.input_set, batch, block)
            reply = c._request(
                MsgType.EXECUTE_COMPUTATIONS,
                {"sinks": [self._sink(rows)], "job_name": f"{db}-serve",
                 "materialize": True, "explain": bool(explain)},
                codec=CODEC_PICKLE)
            results = c._collect_results(reply["results"], True)
        value = next(iter(results.values()))
        obs.REGISTRY.counter("models.batches_scored").inc()
        obs.REGISTRY.counter("models.rows_scored").inc(rows)
        if explain:
            return value, reply.get("shard_operators")
        return value

    def score_batches(self, batches):
        """Score an iterable of batches in arrival order (the serving
        loop — one routed frame per batch over the same deployed
        pool)."""
        for batch in batches:
            yield self.score(batch)


def ff_serving(model, leader_addr: str, **kw) -> ModelServing:
    """FF convenience: batch runs along axis 1 of the ``(labels x
    batch)`` output; the model's own block shape re-blocks the
    assembly."""
    kw.setdefault("batch_axis", 1)
    return ModelServing(model, leader_addr, **kw)
