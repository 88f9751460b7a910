"""Computation DAG — the user-facing query API (reference layer 9).

The reference's ``Computation`` subclasses (SelectionComp /
MultiSelectionComp / JoinComp / AggregateComp / PartitionComp / ScanSet /
SetWriter — ``src/lambdas/headers/Computation.h:21-97``) carry ``Lambda``
trees of per-tuple C++ logic and compile themselves to TCAP strings.
Here each node carries a traced-Python function over set values
(``BlockedTensor``s or host objects); "compiling" is composing those
functions into jit stages (``netsdb_tpu.plan.planner``), with XLA as the
physical optimizer. The node vocabulary is kept 1:1 so every reference
query has a structural analogue, and ``to_plan_string`` emits a
TCAP-like textual dump (debuggability + test surface, standing in for
``src/logicalPlan``'s IR).
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, List, Optional, Sequence

_ids = itertools.count()

#: Labels of the suite's AUDITED row-decomposable chunk transforms —
#: the DERIVED ``rowwise`` set (PR 10 follow-on). An :class:`Apply`
#: whose label matches an entry (exact match, or prefix match for
#: entries ending in ``:``) auto-derives ``rowwise=True`` instead of
#: requiring a per-node declaration; call sites must NOT additionally
#: pass ``rowwise=True`` for these labels (the ``rowwise-shadow`` lint
#: rule flags the shadowing declaration — one source of truth).
#:
#: Membership is a CORRECTNESS contract, audited like a FoldSpec
#: decomposition: every listed label names a per-row transform that
#: (a) maps any row-slice to exactly the matching row-slice of the
#: whole-input result and (b) preserves the chunk contract AND the
#: schema surface (see the ``rowwise`` docstring below). The in-repo
#: members are the suite's pre-chain transforms under the
#: ``pre:`` namespace: affine per-row column maps (``pre:affine``),
#: column projections/renames-free selections (``pre:project``) and
#: per-row scaling (``pre:scale``).
ROWWISE_SAFE_LABELS = ("pre:affine", "pre:project", "pre:scale")


def rowwise_safe(label: str) -> bool:
    """True when ``label`` is in the derived rowwise set (exact entry,
    or namespace entry ending in ``:`` matched as a prefix)."""
    lab = str(label or "")
    return any(lab.startswith(entry) if entry.endswith(":")
               else lab == entry for entry in ROWWISE_SAFE_LABELS)


class Computation:
    """DAG node. ``inputs`` are upstream Computations; ``op_kind`` mirrors
    the reference class name it replaces."""

    op_kind = "Computation"

    def __init__(self, inputs: Sequence["Computation"]):
        self.inputs: List[Computation] = list(inputs)
        self.node_id = next(_ids)
        self.output_name = f"{self.op_kind}_{self.node_id}"

    # --- evaluation hook (overridden) --------------------------------
    def evaluate(self, *args: Any) -> Any:
        raise NotImplementedError

    # --- TCAP-like dump ----------------------------------------------
    def plan_atom(self) -> str:
        ins = ", ".join(i.output_name for i in self.inputs)
        return f"{self.output_name} <= {self.op_kind.upper()}({ins})"

    def __repr__(self):
        return f"<{self.op_kind} #{self.node_id}>"


class ScanSet(Computation):
    """Read a stored set — reference ``ScanUserSet``/``ScanSet``
    (``src/lambdas/headers/ScanSet.h``). Leaf node."""

    op_kind = "Scan"

    def __init__(self, db: str, set_name: str):
        super().__init__([])
        self.db = db
        self.set_name = set_name
        self.output_name = f"scan_{db}_{set_name}_{self.node_id}"

    def plan_atom(self) -> str:
        return f"{self.output_name} <= SCAN('{self.db}', '{self.set_name}')"


class Apply(Computation):
    """1-in selection/projection — reference ``SelectionComp``
    (``src/lambdas/headers/SelectionComp.h``): projection lambda only."""

    op_kind = "Apply"

    def __init__(self, input_: Computation, fn: Optional[Callable[[Any], Any]] = None,
                 label: str = "", traceable: bool = True, fold=None,
                 tensor_fold=None, rowwise: Optional[bool] = None):
        """``traceable=False`` marks a host-side projection (numpy / Python
        object work) that must run eagerly outside jit — the reference
        analogue is a C++ lambda that touches non-tensor state.

        ``fold`` (:class:`netsdb_tpu.plan.fold.FoldSpec`) gives the node
        a streamable decomposition; when the scanned set is paged, the
        executor folds the node over the page stream instead of calling
        ``fn``. With ``fn=None`` the whole-table path is derived from
        the fold, so the two cannot diverge.

        ``tensor_fold`` (:class:`netsdb_tpu.plan.fold.TensorFold`) is
        the same for a paged TENSOR input: the executor streams the
        matrix's row-block pages through the node (in-DB inference over
        storage-managed weights, ref ``SimpleFF.cc:94-290``).

        **Label contract (jit-cache correctness).** On the streamed
        executor path, a traceable node's ``fn`` is compiled ONCE per
        ``(job_name, canonical plan, topo position, label)`` and
        REUSED across executions. Parameters ``fn`` bakes into its
        closure (thresholds, constants, captured arrays) are traced
        into that first compilation as constants — so two DAGs that
        differ ONLY in closure values but share job name, plan shape
        and label will silently reuse the first DAG's stale constants.
        Either reflect every closure parameter in ``label`` (what the
        in-repo builders do: ``label=f"filter>{cutoff}"``) or vary
        ``job_name`` per parameterization. Non-traceable
        (``traceable=False``) nodes evaluate fresh every time and are
        exempt. See README "Execution pipeline".

        ``rowwise=True`` declares ``fn`` ROW-DECOMPOSABLE: applied to
        any row-slice of its input it produces exactly the matching
        row-slice of the whole-input result, and it preserves the
        chunk contract (a ColumnTable in → a ColumnTable out, row for
        row, validity mask and ``_rowid`` untouched or forwarded) AND
        the table's SCHEMA SURFACE — column names and dictionary
        encodings a downstream fold's ``init``/``finalize`` may read.
        The fusion mapper (``plan/fusion.py``) uses the declaration to
        fuse the node into a downstream fold's per-chunk step when the
        scanned set is paged — the chunk is transformed and reduced in
        one compiled program instead of materializing the whole set
        for the transform. Under that fusion only the STEPS see
        transformed chunks; ``init(state, src, ...)`` and
        ``finalize(state, src, ...)`` still receive the raw scan
        handle, which is why a rename or dictionary re-encoding
        (schema the fold could observe via ``src``) disqualifies the
        declaration. Declaring ``rowwise`` for a fn that mixes rows
        (sorts, global statistics, cross-row joins) or reshapes the
        schema surface silently computes the wrong answer on paged
        inputs — the same class of contract as a FoldSpec's
        decomposition.

        ``rowwise=None`` (the default) DERIVES the declaration from
        the audited label registry (:data:`ROWWISE_SAFE_LABELS`): the
        suite's known-safe pre-chain transforms fuse without per-node
        declarations, and a label outside the registry stays
        non-rowwise. Passing an explicit True/False always wins —
        but an explicit ``rowwise=True`` on a registry label shadows
        the derived set and is flagged by the ``rowwise-shadow`` lint
        rule (drop the argument; the registry is the one source of
        truth for those labels)."""
        super().__init__([input_])
        self.fold = fold
        self.tensor_fold = tensor_fold
        if fn is None:
            if fold is None:
                raise ValueError("Apply needs fn or fold")
            fn = fold.whole
        self.fn = fn
        self.traceable = traceable
        self.label = label or getattr(fn, "__name__", "fn")
        # None = derive from the audited registry; an explicit
        # declaration (True OR False) always wins over derivation
        self.rowwise_declared = rowwise is not None
        self.rowwise = (bool(rowwise) if rowwise is not None
                        else rowwise_safe(self.label))

    def evaluate(self, x):
        return self.fn(x)

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= APPLY({self.inputs[0].output_name}, "
                f"'{self.label}')")


class Filter(Computation):
    """Selection predicate — reference ``SelectionComp::getSelection``
    (FILTER atom in TCAP, ``src/logicalPlan/source/Lexer.l``). For host
    object sets; tensor pipelines express filtering as masks."""

    op_kind = "Filter"

    def __init__(self, input_: Computation, pred: Callable[[Any], bool],
                 label: str = ""):
        super().__init__([input_])
        self.pred = pred
        self.label = label or getattr(pred, "__name__", "pred")

    def evaluate(self, items):
        return [x for x in items if self.pred(x)]

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= FILTER({self.inputs[0].output_name}, "
                f"'{self.label}')")


class MultiApply(Computation):
    """1-in → many-out flatten — reference ``MultiSelectionComp``
    (FLATTEN atom). ``fn`` returns a list per input value."""

    op_kind = "Flatten"

    def __init__(self, input_: Computation, fn: Callable[[Any], List[Any]],
                 label: str = ""):
        super().__init__([input_])
        self.fn = fn
        self.label = label or getattr(fn, "__name__", "fn")

    def evaluate(self, items):
        out: List[Any] = []
        for x in items:
            out.extend(self.fn(x))
        return out

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= FLATTEN({self.inputs[0].output_name}, "
                f"'{self.label}')")


class Join(Computation):
    """2-in combine — reference ``JoinComp`` (``src/lambdas/headers/
    JoinComp.h``). For tensor pipelines the join-on-block-index +
    projection collapses into one traced fn (e.g. ``ops.matmul_t``); for
    host sets an equi-join on key fns (hash join, as the reference's
    broadcast/partitioned hash joins)."""

    op_kind = "Join"

    def __init__(self, left: Computation, right: Computation,
                 fn: Optional[Callable[[Any, Any], Any]] = None,
                 left_key: Optional[Callable] = None,
                 right_key: Optional[Callable] = None,
                 project: Optional[Callable[[Any, Any], Any]] = None,
                 label: str = "", fold=None, fold_src: int = 0,
                 on: Optional[tuple] = None,
                 take: Optional[Sequence[str]] = None,
                 tensor_fold=None, passthrough: bool = False):
        """``fold`` + ``fold_src``: streamable decomposition (see
        :class:`netsdb_tpu.plan.fold.FoldSpec`); ``fold_src`` says which
        input (0=left, 1=right) is the probe/fact side the page stream
        replaces — the other input's value is passed to the fold as
        resident state (gather-chain tuples flattened).

        ``on=(left_col, right_col)`` declares the equi-join key by
        COLUMN NAME (the reference's attribute-naming join lambdas,
        ``JoinComp::getKeySelection``) and lowers evaluation to the
        device LUT/sort join (``relational.autojoin.equijoin``):
        object-record inputs columnarize automatically, string keys
        ride dictionary unification, and the probe is one device
        gather — the automatic form of what round 3 exposed only as
        hand calls. ``take`` limits which right columns are gathered.
        Callable ``left_key``/``right_key`` stay the interpreter
        fallback for keys no column expresses.

        **Label contract**: a traceable ``fn``-bearing Join on the
        streamed executor path shares one compiled program per
        ``(job_name, plan shape, topo position, label)`` — closure
        constants inside ``fn`` must be reflected in ``label`` (or a
        distinct ``job_name``) or a structurally identical DAG reuses
        this one's baked-in values. See :class:`Apply` for the full
        contract."""
        super().__init__([left, right])
        self.fold = fold
        self.fold_src = fold_src
        # streamable decomposition over a paged TENSOR input (weight
        # scans — see Apply docstring / plan.fold.TensorFold)
        self.tensor_fold = tensor_fold
        # passthrough=True: fn only re-shapes its inputs (the gather-
        # chain tuple append) — the streamed executor forwards paged
        # handles through it UNMATERIALIZED so a downstream fold can
        # stream them (grace-hash build sides behind a gather chain)
        self.passthrough = passthrough
        self.on = tuple(on) if on else None
        self.take = take
        if fn is None and fold is not None and left_key is None:
            from netsdb_tpu.plan.fold import flatten_resident

            if fold_src == 0:
                fn = lambda a, b: fold.whole(a, *flatten_resident((b,)))
            else:
                fn = lambda a, b: fold.whole(b, *flatten_resident((a,)))
        self.fn = fn
        self.left_key = left_key
        self.right_key = right_key
        self.project = project
        self.label = label or (getattr(fn, "__name__", "join") if fn else "equijoin")

    def evaluate(self, left, right):
        if self.fn is not None:
            return self.fn(left, right)
        if self.on is not None:
            # device path: columnarize records if needed, then one
            # LUT/sort equi-join gather (string keys unify host-side)
            from netsdb_tpu.relational.autojoin import (equijoin,
                                                        table_from_objects)
            from netsdb_tpu.relational.table import ColumnTable

            lt = (left if isinstance(left, ColumnTable)
                  else table_from_objects(left))
            rt = (right if isinstance(right, ColumnTable)
                  else table_from_objects(right))
            return equijoin(lt, self.on[0], rt, self.on[1], take=self.take)
        # host-side hash equi-join (reference broadcast join: build small
        # side hash table, probe the large side)
        table = {}
        for r in right:
            table.setdefault(self.right_key(r), []).append(r)
        out = []
        proj = self.project or (lambda a, b: (a, b))
        for l in left:
            for r in table.get(self.left_key(l), ()):
                out.append(proj(l, r))
        return out

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= JOIN({self.inputs[0].output_name}, "
                f"{self.inputs[1].output_name}, '{self.label}')")


class Aggregate(Computation):
    """Group-by/reduce — reference ``AggregateComp``/``ClusterAggregateComp``
    (``src/lambdas/headers/AggregateComp.h``). Tensor pipelines pass a
    traced reduction fn; host sets pass key/value fns + combiner (the
    CombinerProcessor/AggregationProcessor pair collapses into one dict
    fold — the cross-node shuffle it implemented is XLA's problem now)."""

    op_kind = "Aggregate"

    def __init__(self, input_: Computation,
                 fn: Optional[Callable[[Any], Any]] = None,
                 key: Optional[Callable] = None,
                 value: Optional[Callable] = None,
                 combine: Optional[Callable[[Any, Any], Any]] = None,
                 label: str = ""):
        super().__init__([input_])
        self.fn = fn
        self.key = key
        self.value = value
        self.combine = combine
        self.label = label or (getattr(fn, "__name__", "agg") if fn else "groupby")

    def evaluate(self, x):
        if self.fn is not None:
            return self.fn(x)
        acc = {}
        for item in x:
            k = self.key(item)
            v = self.value(item)
            acc[k] = self.combine(acc[k], v) if k in acc else v
        return acc

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= AGGREGATE({self.inputs[0].output_name}, "
                f"'{self.label}')")


class Partition(Computation):
    """Repartition by key — reference ``PartitionComp``
    (``src/lambdas/headers/PartitionComp.h``, TCAP APPLY-PARTITION atom
    ``AtomicComputationClasses.h:497``): route each item to one of
    ``num_partitions`` by its partition-lambda key. Routing uses the
    dispatcher's stable hash, so a set materialized from this node is
    co-partitioned with any set ingested via
    ``HashPolicy`` with the same key fn (the reference's co-located
    join setup). Output is {partition_id: [items]}."""

    op_kind = "Partition"

    def __init__(self, input_: Computation, key_fn,
                 num_partitions: int, label: str = "",
                 slack: float = 2.0):
        """``key_fn`` may be a callable (host-object routing) or a
        COLUMN NAME string: over a placed ColumnTable input, the node
        then lowers to the device all_to_all row shuffle
        (``relational.shuffle.hash_repartition``) on the mesh the
        set's placement put the columns on — the reference's
        partition stage shipping rows to their owning workers
        (``PipelineStage.cc:1652-1728``), output a ShardedRows a
        downstream ``local_join``/aggregate stage consumes."""
        super().__init__([input_])
        if num_partitions < 1:
            raise ValueError(f"num_partitions must be >= 1, got "
                             f"{num_partitions}")
        self.key_fn = key_fn
        self.num_partitions = num_partitions
        self.slack = slack
        self.traceable = False  # host routing / shard_map progs run eager
        self.label = label or (key_fn if isinstance(key_fn, str)
                               else getattr(key_fn, "__name__", "partition"))

    def evaluate(self, items):
        if isinstance(self.key_fn, str):
            from netsdb_tpu.relational.shuffle import hash_repartition
            from netsdb_tpu.relational.table import ColumnTable

            if not isinstance(items, ColumnTable):
                raise TypeError(
                    f"Partition on column {self.key_fn!r} needs a "
                    f"ColumnTable input; got {type(items).__name__}")
            mesh, axis = _mesh_of_table(items)
            if mesh.shape[axis] != self.num_partitions:
                raise ValueError(
                    f"Partition declared {self.num_partitions} "
                    f"partitions but the set's placement meshes "
                    f"{mesh.shape[axis]} shards on {axis!r}")
            return hash_repartition(mesh, axis, dict(items.cols),
                                    self.key_fn, self.slack,
                                    valid=items.valid)
        from netsdb_tpu.storage.dispatcher import HashPolicy

        # same routing as the dispatcher by construction (the
        # co-partitioning guarantee in the class docstring)
        parts = HashPolicy(self.key_fn).partition(items,
                                                  self.num_partitions)
        return dict(enumerate(parts))

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= PARTITION("
                f"{self.inputs[0].output_name}, '{self.label}')")


def _mesh_of_table(table):
    """(mesh, axis) a placed ColumnTable's columns live on — read off
    the arrays' NamedSharding, so DAG nodes never take a hand mesh."""
    import jax

    for col in table.cols.values():
        sh = getattr(col, "sharding", None)
        if sh is not None and hasattr(sh, "mesh") and sh.spec:
            for entry in sh.spec:
                if entry is not None:
                    ax = entry if isinstance(entry, str) else entry[0]
                    return sh.mesh, ax
    raise ValueError(
        "device Partition needs a placed (mesh-sharded) input set — "
        "create the set with a row-sharding Placement")


class WriteSet(Computation):
    """Materialize into a set — reference ``SetWriter``/``WriteUserSet``.
    Sink node; stage boundary (the reference's pipeline breaker)."""

    op_kind = "Write"

    def __init__(self, input_: Computation, db: str, set_name: str):
        super().__init__([input_])
        self.db = db
        self.set_name = set_name

    def evaluate(self, x):
        return x

    def plan_atom(self) -> str:
        return (f"{self.output_name} <= OUTPUT({self.inputs[0].output_name}, "
                f"'{self.db}', '{self.set_name}')")
