"""Client facade — the ``PDBClient`` equivalent.

The reference's ``PDBClient`` aggregates CatalogClient, DispatcherClient,
DistributedStorageManagerClient and QueryClient behind one object
(``src/mainClient/headers/PDBClient.h:28-295``): createDatabase/createSet/
sendData/registerType/executeComputations/getSetIterator. In
single-controller JAX there is no client⇄master RPC hop — the "client" IS
the controller — so this facade talks directly to the catalog, the set
store, and the query executor. The API surface is kept deliberately close
so every reference test driver has a line-for-line analogue.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from netsdb_tpu import obs
from netsdb_tpu.catalog.catalog import Catalog
from netsdb_tpu.config import Configuration, DEFAULT_CONFIG
from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.storage.store import SetIdentifier, SetStore


def _ident(db: str, set_name: str) -> SetIdentifier:
    return SetIdentifier(db, set_name)


def table_info(table) -> Dict[str, Any]:
    """The analyze-set summary for one resident ColumnTable — the ONE
    place its shape is defined (Client.analyze_set and the daemon's
    ANALYZE_SET handler both build it here, so they cannot diverge)."""
    from netsdb_tpu.relational.stats import analyze_table

    return {"stats": dict(analyze_table(table)),
            "dicts": dict(table.dicts), "num_rows": table.num_rows}


class Client:
    """Facade over catalog + storage + execution.

    Mirrors ``PDBClient`` (reference ``src/mainClient/headers/PDBClient.h``):

    ===========================  =======================================
    reference                    here
    ===========================  =======================================
    createDatabase               :meth:`create_database`
    createSet<T>(db,set,...)     :meth:`create_set`
    sendData<T>(pair, vector)    :meth:`send_data` / :meth:`send_matrix`
    flushData                    :meth:`flush_data`
    registerType(.so)            :meth:`register_type` (Python entry point)
    executeComputations          :meth:`execute_computations`
    getSetIterator<T>            :meth:`get_set_iterator`
    removeSet / clearSet         :meth:`remove_set` / :meth:`clear_set`
    addSharedMapping (dedup)     :meth:`add_shared_mapping`
    ===========================  =======================================
    """

    def __new__(cls, config: Configuration = DEFAULT_CONFIG,
                catalog_path: Optional[str] = None,
                address: Optional[str] = None,
                token: Optional[str] = None,
                replicas=None):
        if address is not None:
            # thin RPC mode — talk to a resident daemon instead of
            # owning the store (reference: PDBClient always works this
            # way; here the in-process library is the default and
            # ``Client(address="host:port")`` is the served form).
            # ``replicas``: other daemon addresses holding the same
            # data — enables client-side hedged reads (tail latency;
            # see RemoteClient).
            from netsdb_tpu.serve.client import RemoteClient

            return RemoteClient(address, token=token, replicas=replicas)
        return super().__new__(cls)

    def __init__(self, config: Configuration = DEFAULT_CONFIG,
                 catalog_path: Optional[str] = None,
                 address: Optional[str] = None,
                 token: Optional[str] = None,
                 replicas=None):
        del address, token, replicas  # consumed by __new__ (RemoteClient)
        self.config = config
        config.ensure_dirs()
        from netsdb_tpu.config import enable_compilation_cache

        enable_compilation_cache()  # PreCompiledWorkload analogue
        self.catalog = Catalog(catalog_path or ":memory:")
        self.store = SetStore(config)
        # mesh of the most recent placement applied via create_set — the
        # cluster this controller is currently distributing over (the
        # reference's ResourceManager serverlist role)
        self._mesh = None
        self._advisor = None  # Lachesis-lite (set_placement_advisor)
        self._advisor_key = "default"
        self._advisor_arm = None  # arm applied by this session's DDL

    @property
    def mesh(self):
        """The device mesh of the last placement-carrying ``create_set``
        (None while every set is single-device)."""
        return self._mesh

    # --- self-learning placement (Lachesis) ---------------------------
    def set_placement_advisor(self, advisor, key: str = "default") -> None:
        """Install a :class:`~netsdb_tpu.learning.advisor.PlacementAdvisor`
        the DDL and query paths consult — the reference's self-learning
        hooks at set creation and scheduling
        (``QuerySchedulerServer.cc:246-330``, dispatcher placement
        optimizers). ``key`` names the workload whose measured history
        drives set placement: ``create_set`` picks block shape from the
        best-known arm for ``key``, and ``execute_computations`` runs
        each job under the advisor's choice for that job, recording
        elapsed time back to the history DB — the reference's
        first-run-slow, later-runs-fast loop (documentation.md:5-10)."""
        self._advisor = advisor
        self._advisor_key = key

    # --- DDL ----------------------------------------------------------
    def create_database(self, db: str) -> None:
        self.catalog.create_database(db)

    def create_set(
        self,
        db: str,
        set_name: str,
        type_name: str = "tensor",
        persistence: str = "transient",
        eviction: str = "lru",
        partition_lambda: Optional[str] = None,
        placement=None,
        storage: str = "memory",
    ) -> SetIdentifier:
        """``partition_lambda`` mirrors createSet-with-dispatch-computation
        (reference ``PDBClient.h:79-103``): a named key function the
        dispatcher/placement layer may use to route data.

        ``storage="paged"`` backs the set with the shared page arena
        instead of RAM: ingest pages the relation in row-chunks, and
        Computation DAGs over the set run STREAMED — the executor folds
        each fold-bearing stage over the page stream under the arena's
        pool cap (the reference's PageScanner-fed out-of-core execution,
        ``src/storage/headers/PageScanner.h:25-34``). Composes with
        ``placement``: streamed chunks are mesh-sharded per chunk.
        Durability: the arena's spill files are capacity, not
        durability — a paged set persists via ``flush``/``flush_data``
        (snapshot of the materialized relation; reload re-ingests into
        the arena, coming back paged).

        ``placement`` (:class:`~netsdb_tpu.parallel.placement.Placement`
        or its ``to_meta`` dict) declares the set's mesh sharding — the
        createSet-time PartitionPolicy (``PartitionPolicy.h:27-50``):
        every tensor/table ingested into the set is placed with it, and
        query jits over the set inherit the sharding, so XLA distributes
        the job the way the reference scheduler broadcast stages to all
        workers."""
        if not self.catalog.database_exists(db):
            raise KeyError(f"database {db!r} does not exist; create_database first")
        if storage not in ("memory", "paged"):
            # validate BEFORE the catalog write — a late store-side
            # rejection would leave a dangling catalog row
            raise ValueError(f"storage must be 'memory' or 'paged', "
                             f"got {storage!r}")
        from netsdb_tpu.parallel.placement import Placement

        if isinstance(placement, dict):
            placement = Placement.from_meta(placement)
        meta: Dict[str, Any] = {}
        if partition_lambda:
            meta["partition_lambda"] = partition_lambda
        arm = (self._advisor.choose(self._advisor_key)
               if self._advisor is not None else None)
        if placement is None and arm is not None:
            # an advisor arm may carry a sharding decision (the DRL /
            # rule-based optimizers choose *distribution*, not just
            # page size — Lachesis' decision variable on TPU): specs
            # values may be Placement objects keyed by set role
            spec = arm.specs.get("placement") or arm.specs.get(set_name)
            if isinstance(spec, Placement):
                placement = spec
                # the arm's placement is the configuration actually in
                # force for this DDL — stash it so job timings record
                # against it (same discipline as the block-shape arms
                # below) and audit the decision
                self._advisor_arm = arm
                self._advisor.db.record(
                    f"{self._advisor_key}:decisions",
                    plan_key=f"set:{db}.{set_name}", elapsed_s=0.0,
                    config_label=arm.label)
        if placement is not None:
            meta["sharding"] = placement.to_meta()
            self._mesh = placement.mesh()
            # placement-history row: the sharding actually applied by
            # DDL, auditable by the advisor/judge (the reference logs
            # its placement decisions to the self-learning DB)
            from netsdb_tpu.learning.history import get_history_db

            get_history_db().record(
                f"{db}.{set_name}:placement", plan_key=f"set:{db}.{set_name}",
                elapsed_s=0.0, config_label=placement.label())
        if arm is not None and type_name == "tensor" \
                and "block" in arm.specs:
            # live Lachesis decision: the chosen placement (block shape
            # = the reference's page-size knob) lands in the catalog and
            # the history DB, and send_matrix defaults to it. Decision
            # rows live under "<key>:decisions" so they audit the live
            # choices without polluting the reward means.
            # Stashed ONLY when the arm actually decided something for
            # THIS set: a model's later sets consulting the advisor
            # must not overwrite the arm a placement decision applied
            # (job timings would then record against the wrong arm)
            meta["placement"] = arm.label
            meta["block_shape"] = list(arm.specs["block"])
            self._advisor_arm = arm  # the placement actually in force
            self._advisor.db.record(f"{self._advisor_key}:decisions",
                                    plan_key=f"set:{db}.{set_name}",
                                    elapsed_s=0.0,
                                    config_label=arm.label)
        if storage != "memory":
            meta["storage"] = storage
        self.catalog.create_set(db, set_name, type_name, meta, persistence)
        ident = _ident(db, set_name)
        self.store.create_set(ident, persistence=persistence, eviction=eviction,
                              placement=placement, storage=storage)
        return ident

    def remove_set(self, db: str, set_name: str) -> None:
        self.catalog.remove_set(db, set_name)
        self.store.remove_set(_ident(db, set_name))

    def clear_set(self, db: str, set_name: str) -> None:
        self.store.clear_set(_ident(db, set_name))

    def set_exists(self, db: str, set_name: str) -> bool:
        return self.catalog.set_exists(db, set_name)

    # --- types --------------------------------------------------------
    def register_type(self, type_name: str, entry_point: str,
                      source: Optional[str] = None,
                      ship_module: bool = False) -> None:
        """Register an op/model implementation by dotted import path
        (ref registerType / VTableMap dynamic loading,
        ``src/objectModel/headers/VTableMap.h:36-80``).

        ``source`` ships the module's code through the catalog so a
        daemon that has never installed it can still execute the type —
        the reference replicating user-type .so binaries
        (``PDBCatalog.h:45-50``). ``ship_module=True`` reads the source
        off the locally-importable module instead."""
        if ship_module and source is None:
            from netsdb_tpu.catalog.catalog import read_module_source

            source = read_module_source(entry_point)
        self.catalog.register_type(type_name, entry_point, source=source)

    # --- data path ----------------------------------------------------
    def send_data(self, db: str, set_name: str, items: Sequence[Any]) -> None:
        """Sets created with ``type_name="objects"`` columnarize at
        ingest: records flow through ``autojoin.table_from_objects``
        into ONE dictionary-encoded ColumnTable (string keys become
        device codes), so ``Join(on=...)`` DAGs over the set run on the
        device engine — the reference's dispatcher building typed pages
        from raw records (``JoinPairArray.h:122`` re-priced). All other
        sets store items as-is (the host-record path)."""
        ident = _ident(db, set_name)
        info = self.catalog.get_set(db, set_name)
        if info is not None and info.get("type") == "objects":
            if not items:
                return  # empty batch: same no-op as the object path
            from netsdb_tpu.relational.autojoin import (concat_tables,
                                                        table_from_objects)
            from netsdb_tpu.relational.table import ColumnTable

            new = table_from_objects(list(items))

            def append(existing_items):
                tables = [i for i in existing_items
                          if isinstance(i, ColumnTable)]
                # append = device concat + dictionary remap; runs
                # atomically under the store lock (update_set), so
                # concurrent senders cannot lose each other's batch
                return [concat_tables(tables[0], new) if tables else new]

            self.store.update_set(ident, append)
            return
        self.store.add_data(ident, list(items))

    def send_matrix(
        self,
        db: str,
        set_name: str,
        dense: Union[np.ndarray, "Any"],
        block_shape: Optional[Tuple[int, int]] = None,
        dtype=None,
    ) -> BlockedTensor:
        """Load a dense matrix as one blocked tensor into a set — the
        analogue of ``FFMatrixUtil::load_matrix`` generating a
        ``Vector<Handle<FFMatrixBlock>>`` and sendData'ing it.

        Block shape resolution: explicit argument > the set's
        advisor-chosen placement (catalog meta, written by
        ``create_set`` under a PlacementAdvisor) > config default.

        A ``storage="paged"`` set takes the HOST array straight into
        the arena — no BlockedTensor, nothing device-resident (the
        whole point is matrices larger than HBM; consume them with
        :meth:`paged_matmul`). Returns None in that case."""
        ident = _ident(db, set_name)
        if self.store.storage_of(ident) == "paged":
            dense_np = np.ascontiguousarray(
                np.asarray(dense, dtype or np.float32))
            self.store.add_data(ident, [dense_np])
            cat = self.catalog.get_set(db, set_name)
            if cat is not None:
                cat["meta"].update(shape=list(dense_np.shape),
                                   dtype=str(dense_np.dtype))
                self.catalog.update_set_meta(db, set_name, cat["meta"])
            return None
        if block_shape is None:
            info = self.catalog.get_set(db, set_name)
            placed = (info or {}).get("meta", {}).get("block_shape")
            if placed:
                block_shape = tuple(placed)
        block_shape = block_shape or self.config.default_block_shape
        t = BlockedTensor.from_dense(dense, block_shape, dtype=dtype)
        # the rows placed in HBM, and the zero rows their blocks add
        rows = t.shape[0]
        obs.REGISTRY.counter("store.ingest.rows").inc(rows)
        obs.REGISTRY.counter("store.ingest.pad_rows").inc(
            t.meta.padded_shape[0] - rows)
        self.store.put_tensor(ident, t)
        cat = self.catalog.get_set(db, set_name)
        if cat is not None:
            cat["meta"].update(
                shape=list(t.shape), block_shape=list(t.meta.block_shape),
                dtype=str(t.dtype),
            )
            self.catalog.update_set_meta(db, set_name, cat["meta"])
        return t

    def send_table(self, db: str, set_name: str, rows_or_table,
                   date_cols: Sequence[str] = (),
                   append: bool = False) -> "Any":
        """Ingest a relation as ONE ColumnTable (dictionary-encoding
        string columns on the way in — weak-typed rows become device
        columns, the reference's dispatcher page-building role). If the
        set carries a placement, the store shards the table's rows over
        the mesh (PartitionPolicy applied at ingest,
        ``src/dispatcher/headers/PartitionPolicy.h:27-50``).

        ``append=True`` adds the batch to the stored relation instead
        of replacing it — the reference's addData continuously
        appending pages (``StorageAddData``): paged sets write
        additional arena pages, memory sets concat with dictionary
        remap; both atomic under the store lock."""
        from netsdb_tpu.relational.table import ColumnTable

        table = (rows_or_table if isinstance(rows_or_table, ColumnTable)
                 else ColumnTable.from_rows(list(rows_or_table), date_cols))
        ident = _ident(db, set_name)
        if append:
            self.store.append_table(ident, table)
            cat = self.catalog.get_set(db, set_name)
            if cat is not None:  # catalog reflects the TOTAL after append
                info = self.analyze_set(db, set_name)
                cat["meta"].update(num_rows=info["num_rows"],
                                   columns=sorted(table.cols))
                self.catalog.update_set_meta(db, set_name, cat["meta"])
            return table
        self.store.clear_set(ident)
        self.store.add_data(ident, [table])
        cat = self.catalog.get_set(db, set_name)
        if cat is not None:
            cat["meta"].update(num_rows=table.num_rows,
                               columns=sorted(table.cols))
            self.catalog.update_set_meta(db, set_name, cat["meta"])
        return table

    def get_table(self, db: str, set_name: str):
        from netsdb_tpu.relational.outofcore import PagedColumns
        from netsdb_tpu.relational.table import ColumnTable

        items = self.store.get_items(_ident(db, set_name))
        tables = [i for i in items if isinstance(i, ColumnTable)]
        if not tables:
            paged = [i for i in items if isinstance(i, PagedColumns)]
            if len(paged) == 1:
                # compatibility materialization — HOST-side assembly
                # (numpy columns, nothing touches HBM): the set was
                # paged because it does not fit; queries should go
                # through the DAG path, which folds over the stream
                return paged[0].to_host_table()
        if len(tables) != 1:
            raise ValueError(
                f"set {db}:{set_name} holds {len(tables)} tables; expected 1")
        return tables[0]

    def analyze_set(self, db: str, set_name: str) -> Dict[str, Any]:
        """Planner statistics for a stored relation WITHOUT
        materializing it: resident tables analyze in place (cached);
        paged sets return their ingest-time stats. This is the
        reference's collect-stats-where-the-data-lives surface
        (``StorageCollectStats``, ``PangeaStorageServer.h:48``) — the
        DAG builders consume these summaries instead of pulling tables
        (``relational/dag.py``)."""
        from netsdb_tpu.relational.outofcore import PagedColumns

        items = self.store.get_items(_ident(db, set_name))
        if len(items) == 1 and isinstance(items[0], PagedColumns):
            pc = items[0]
            return {"stats": dict(pc.stats), "dicts": dict(pc.dicts),
                    "num_rows": pc.num_rows}
        return table_info(self.get_table(db, set_name))

    def get_tensor(self, db: str, set_name: str) -> BlockedTensor:
        return self.store.get_tensor(_ident(db, set_name))

    def paged_matmul(self, db: str, set_name: str, rhs) -> np.ndarray:
        """``stored @ rhs`` with the stored matrix STREAMED page by
        page through the device — the larger-than-HBM weight pattern
        as a set property: ``create_set(storage="paged")`` +
        ``send_matrix`` pages the matrix into the arena, and only one
        page + ``rhs`` are device-resident at a time."""
        return self.store.paged_matmul(_ident(db, set_name), rhs)

    def get_set_iterator(self, db: str, set_name: str) -> Iterator[Any]:
        return self.store.scan(_ident(db, set_name))

    def flush_data(self) -> None:
        """Durably flush all persistent sets (ref flushData →
        StorageCleanup broadcast, ``PDBClient.h:141``). Paged sets
        snapshot as their materialized relation and re-ingest into the
        arena on reload (``SetStore.flush``)."""
        for ident in self.store.list_sets():
            info = self.catalog.get_set(ident.db, ident.set)
            if info and info.get("persistence") == "persistent":
                self.store.flush(ident)

    def dedup_resident(self, sets: Sequence[Tuple[str, str]],
                       bands: int = 16, seed: int = 0) -> Dict[str, Any]:
        """Dedup device-resident model weight sets at block level: LSH
        groups near-duplicate blocks across the sets, byte-identical
        group members collapse into one shared device pool, and each
        set keeps a slot grid (``dedup/pool.py``) — fine-tuned variants
        share HBM the way the reference's models share physical pages
        (``SharedTensorBlockSet.h:25``, ``PDBClient.h:113-138``).
        Inference is bit-unchanged; returns the pooling report. Sets
        are partitioned by (block_shape, dtype) class; classes with one
        member still pool (dedup within a single model's repeated
        blocks)."""
        from netsdb_tpu.dedup.pool import pool_models

        tensors: Dict[str, BlockedTensor] = {}
        for db, set_name in sets:
            tensors[f"{db}:{set_name}"] = self.get_tensor(db, set_name)
        by_class: Dict[Any, Dict[str, BlockedTensor]] = {}
        for name, t in tensors.items():
            by_class.setdefault((t.meta.block_shape, str(t.dtype)),
                                {})[name] = t
        total: Dict[str, Any] = {"classes": len(by_class), "models": 0,
                                 "total_blocks": 0, "unique_blocks": 0,
                                 "shared_block_refs": 0,
                                 "hbm_bytes_before": 0,
                                 "hbm_bytes_pooled": 0}
        for cls, group in by_class.items():
            pooled, report = pool_models(group, bands=bands, seed=seed)
            for name, pt in pooled.items():
                db, set_name = name.split(":", 1)
                self.store.set_pooled(_ident(db, set_name), pt)
            for k in ("models", "total_blocks", "unique_blocks",
                      "shared_block_refs", "hbm_bytes_before",
                      "hbm_bytes_pooled"):
                total[k] += report[k]
        total["hbm_savings_pct"] = round(
            100 * (1 - total["hbm_bytes_pooled"]
                   / max(total["hbm_bytes_before"], 1)), 1)
        return total

    # --- dedup (ref PDBClient::addSharedPage/addSharedMapping) --------
    def add_shared_mapping(
        self, private_db: str, private_set: str, shared_db: str, shared_set: str,
        mapping: Optional[Dict] = None,
    ) -> None:
        self.store.add_shared_mapping(
            _ident(private_db, private_set), _ident(shared_db, shared_set), mapping
        )

    # --- query execution ----------------------------------------------
    def execute_computations(self, *sinks, job_name: str = "job",
                             materialize: bool = True,
                             explain: bool = False):
        """Plan + run a Computation DAG — ``QueryClient::executeComputations``
        (reference ``src/queries/headers/QueryClient.h:160-224``) without the
        client→master RPC hop. ``sinks`` are Write computations from
        :mod:`netsdb_tpu.plan.computations`.

        ``explain=True`` is the in-process EXPLAIN ANALYZE: the
        executor records every plan node's wall/device time, rows and
        cache/compile counters (``obs/operators.py``) and the return
        becomes ``(results, operators_tree)``.

        With a placement advisor installed, the job's elapsed time is
        recorded against the arm whose placement this session's DDL
        actually APPLIED (``create_set`` stashes it) — never against an
        arm that was merely chosen, so per-arm means measure real
        physical configurations (the scheduler-side self-learning hook,
        ``QuerySchedulerServer.cc:246-330``)."""
        from netsdb_tpu.plan.executor import execute_computations

        def run():
            if self._advisor is not None and self._advisor_arm is not None:
                from netsdb_tpu.learning.history import set_config_label

                set_config_label(self._advisor_arm.label)
                try:
                    return execute_computations(self, list(sinks),
                                                job_name=job_name,
                                                materialize=materialize)
                finally:
                    set_config_label("")  # no stale-arm tagging
            return execute_computations(self, list(sinks),
                                        job_name=job_name,
                                        materialize=materialize)

        if not explain:
            return run()
        with obs.operators.explain_capture() as cap:
            results = run()
        return results, cap.get("operators")

    # --- stats --------------------------------------------------------
    def collect_stats(self) -> Dict[str, Any]:
        """Per-set storage stats (ref StorageCollectStats → ``Statistics``
        used by the cost-based planner)."""
        return {
            str(i): self.store.set_stats(i) for i in self.store.list_sets()
        }
