"""ColumnTable: a relation as a struct of device arrays.

Design rules (all driven by XLA's static-shape compilation model):

- **Numeric columns** are ``int32`` / ``float32`` device arrays.
- **String columns** are dictionary-encoded at ingest: an ``int32``
  code array plus a host-side ``list[str]`` dictionary. Predicates on
  strings become integer compares on device; the strings themselves
  never leave the host.
- **Dates** are ``int32`` yyyymmdd (order-isomorphic to ISO strings, so
  range predicates are int compares — same trick the reference's
  drivers use with encoded ints, ``src/tpch/source/Query06/``).
- **Filters never shrink arrays.** A filtered table keeps every row and
  carries a boolean ``valid`` mask; aggregations apply the mask. This
  keeps every intermediate shape static so one jit covers all
  selectivities. (The reference's row pipeline has the same structure
  inverted: its FilterExecutor emits a bitmap consumed downstream —
  ``src/lambdas/headers/FilterExecutor.h``.)

Row↔column conversion accepts the row dicts produced by
``workloads.tpch.generate``/``parse_tbl`` so the columnar engine can be
golden-tested against the host row engine on identical data.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

import jax.tree_util

_DATE_RE = re.compile(r"^(\d{4})-(\d{2})-(\d{2})$")


def date_to_int(s: str) -> int:
    """ISO date string → yyyymmdd int32."""
    m = _DATE_RE.match(s)
    if not m:
        raise ValueError(f"not an ISO date: {s!r}")
    y, mo, d = m.groups()
    return int(y) * 10000 + int(mo) * 100 + int(d)


def int_to_date(v: int) -> str:
    v = int(v)
    return f"{v // 10000:04d}-{(v // 100) % 100:02d}-{v % 100:02d}"


def _encode_strings(values: List[str], is_date: bool):
    """Shared string-column encoder: ISO dates → yyyymmdd int32 (no
    dictionary), anything else → dictionary codes. Returns
    ``(codes, dictionary_or_None)``. Single definition so both ingestion
    paths (from_rows / from_columns) stay type-identical on the same
    data."""
    if is_date:
        return jnp.asarray(np.fromiter((date_to_int(v) for v in values),
                                       np.int32, len(values))), None
    uniq = sorted(set(values))
    code = {s: i for i, s in enumerate(uniq)}
    return jnp.asarray(np.fromiter((code[v] for v in values),
                                   np.int32, len(values))), uniq


class _TableAuxKey:
    """Hashable static metadata of a ColumnTable (column names + string
    dictionaries) with the hash precomputed once — jit cache lookups on
    table arguments stay O(1) after the first (identity fast path), not
    O(total dictionary bytes) per call.

    Deliberately carries NOTHING derived from column DATA: jax reuses
    treedefs (and thus aux objects) across equal-schema tables, so any
    per-data payload here would alias between distinct tables — see
    relational/stats.py for why the stats cache is per-instance."""

    __slots__ = ("names", "dicts", "_hash")

    def __init__(self, names, dicts):
        self.names = names
        self.dicts = dicts
        self._hash = hash((names, dicts))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        if self is other:
            return True
        return (isinstance(other, _TableAuxKey) and self._hash == other._hash
                and self.names == other.names and self.dicts == other.dicts)


@dataclasses.dataclass
class ColumnTable:
    """A relation: named device columns + optional validity mask.

    ``dicts[name]`` present ⇒ ``cols[name]`` holds int32 codes into it.
    ``valid`` of None means "all rows valid" (saves a mask op on the
    common unfiltered scan).
    """

    cols: Dict[str, jnp.ndarray]
    dicts: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    valid: Optional[jnp.ndarray] = None

    # --- construction -------------------------------------------------
    @staticmethod
    def from_rows(rows: Sequence[Dict[str, Any]],
                  date_cols: Sequence[str] = ()) -> "ColumnTable":
        """Build from row dicts. Column kinds are inferred from the first
        row: str → dictionary-encoded (unless named in ``date_cols`` or
        shaped like an ISO date, then yyyymmdd int32), int → int32,
        float → float32."""
        if not rows:
            raise ValueError("from_rows needs at least one row")
        names = list(rows[0].keys())
        cols: Dict[str, jnp.ndarray] = {}
        dicts: Dict[str, List[str]] = {}
        for name in names:
            v0 = rows[0][name]
            values = [r[name] for r in rows]
            if isinstance(v0, str):
                is_date = name in date_cols or bool(_DATE_RE.match(v0))
                cols[name], uniq = _encode_strings(values, is_date)
                if uniq is not None:
                    dicts[name] = uniq
            elif isinstance(v0, bool):
                cols[name] = jnp.asarray(np.asarray(values, np.bool_))
            elif isinstance(v0, int):
                cols[name] = jnp.asarray(np.asarray(values, np.int32))
            else:
                cols[name] = jnp.asarray(np.asarray(values, np.float32))
        return ColumnTable(cols, dicts)

    @staticmethod
    def from_columns(cols: Dict[str, np.ndarray],
                     dicts: Optional[Dict[str, List[str]]] = None,
                     date_cols: Sequence[str] = ()) -> "ColumnTable":
        """Build from the columnar parser's output
        (``workloads.tpch.parse_tbl_columnar``): numeric numpy arrays
        and object arrays of strings."""
        out: Dict[str, jnp.ndarray] = {}
        dd: Dict[str, List[str]] = dict(dicts or {})
        for name, arr in cols.items():
            a = np.asarray(arr)
            if a.dtype.kind in "OUS":
                vals = [str(x) for x in a.tolist()]
                is_date = name in date_cols or bool(
                    len(vals) and _DATE_RE.match(vals[0]))
                out[name], uniq = _encode_strings(vals, is_date)
                if uniq is not None:
                    dd[name] = uniq
            elif a.dtype.kind == "i":
                out[name] = jnp.asarray(a.astype(np.int32))
            elif a.dtype.kind == "f":
                out[name] = jnp.asarray(a.astype(np.float32))
            else:
                out[name] = jnp.asarray(a)
        return ColumnTable(out, dd)

    # --- shape / access ----------------------------------------------
    @property
    def num_rows(self) -> int:
        return int(next(iter(self.cols.values())).shape[0])

    def __getitem__(self, name: str) -> jnp.ndarray:
        return self.cols[name]

    def mask(self) -> jnp.ndarray:
        """Validity as a bool array (materializes all-true if unset)."""
        if self.valid is not None:
            return self.valid
        n = self.num_rows
        return jnp.ones((n,), jnp.bool_)

    def code(self, name: str, value: str) -> int:
        """Dictionary code of ``value`` in string column ``name``; -1 if
        absent (compares false against every row on device)."""
        try:
            return self.dicts[name].index(value)
        except ValueError:
            return -1

    def codes_where(self, name: str, pred) -> List[int]:
        """All dictionary codes whose string satisfies ``pred`` — for
        LIKE-style predicates evaluated once on the host dictionary
        instead of per row (e.g. Q02 'ends with BRUSHED', Q13 comment
        NOT LIKE)."""
        return [i for i, s in enumerate(self.dicts[name]) if pred(s)]

    def decode(self, name: str, code: int) -> str:
        return self.dicts[name][int(code)]

    def compact(self) -> "ColumnTable":
        """Materialize validity: drop invalid rows (placement padding,
        applied filters) and return a mask-free table. Host-side dynamic
        shape — call OUTSIDE jit; traced code uses the mask algebra
        instead. This is the bridge from a placement-padded stored table
        back to the direct columnar query path, which assumes every row
        is real."""
        if self.valid is None:
            return self
        cached = self.__dict__.get("_compacted")
        if cached is not None:
            return cached
        keep = np.asarray(self.valid)
        if bool(keep.all()):
            out = ColumnTable(self.cols, self.dicts, None)
        else:
            idx = jnp.asarray(np.flatnonzero(keep))
            out = ColumnTable({n: jnp.take(c, idx, axis=0)
                               for n, c in self.cols.items()},
                              self.dicts, None)
        # memoized: repeated direct-path queries over one stored table
        # must not re-gather per call (and downstream per-table caches —
        # column stats, join plans — key on the compacted instance)
        self.__dict__["_compacted"] = out
        return out

    # --- relational verbs (mask algebra) ------------------------------
    def filter(self, mask: jnp.ndarray) -> "ColumnTable":
        """AND a predicate mask into validity. Shapes unchanged."""
        new = mask if self.valid is None else (self.valid & mask)
        return ColumnTable(self.cols, self.dicts, new)

    def select(self, names: Sequence[str]) -> "ColumnTable":
        return ColumnTable({n: self.cols[n] for n in names},
                           {n: d for n, d in self.dicts.items() if n in names},
                           self.valid)

    def with_column(self, name: str, arr: jnp.ndarray,
                    dictionary: Optional[List[str]] = None) -> "ColumnTable":
        cols = dict(self.cols)
        cols[name] = arr
        dicts = dict(self.dicts)
        if dictionary is not None:
            dicts[name] = dictionary
        return ColumnTable(cols, dicts, self.valid)

    # --- persistence (store spill / checkpoint) -----------------------
    def __getstate__(self):
        """Pickle via host numpy (device arrays aren't spill-portable);
        lets a ColumnTable live in a SetStore set like any object and
        survive ``flush``/``load_set``."""
        return {"cols": {n: np.asarray(c) for n, c in self.cols.items()},
                "dicts": self.dicts,
                "valid": None if self.valid is None else np.asarray(self.valid)}

    def __setstate__(self, state):
        # columns stay HOST numpy: unpickling happens in client
        # processes too (a fetched result table), and a client must
        # never initialise a jax backend — on a TPU host the daemon
        # owns the chip. Device placement is the consumer's step
        # (jit arguments / the staging pipeline upload on use).
        self.cols = dict(state["cols"])
        self.dicts = state["dicts"]
        self.valid = state["valid"]

    # --- pytree protocol ----------------------------------------------
    # Registered below: a ColumnTable is a jit-traceable value (columns
    # and validity are leaves; names and string dictionaries are static
    # metadata). This is what lets a table stored in a set become a
    # *traced argument* of a compiled query plan — and, when its columns
    # carry a NamedSharding from a set placement, what lets XLA
    # partition the whole query and insert the collectives
    # (netsdb_tpu.parallel.placement).
    def tree_flatten(self):
        names = tuple(sorted(self.cols))
        children = tuple(self.cols[n] for n in names) + (self.valid,)
        # Dictionaries can be huge (e.g. a comment column ≈ one string
        # per row); a query executes on every call but the dict content
        # never changes after construction, so the aux key — tuple copy
        # AND its hash — is built once per table, not per flatten
        # (protects the executor's compiled-plan fast path).
        key = self.__dict__.get("_aux_key")
        if key is None or key.names != names:
            key = _TableAuxKey(
                names, tuple((k, tuple(v))
                             for k, v in sorted(self.dicts.items())))
            self.__dict__["_aux_key"] = key
        return children, key

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = cls.__new__(cls)
        obj.cols = dict(zip(aux.names, children[:-1]))
        obj.dicts = {k: list(v) for k, v in aux.dicts}
        obj.valid = children[-1]
        obj.__dict__["_aux_key"] = aux
        return obj

    # --- host materialization ----------------------------------------
    def to_rows(self, date_cols: Sequence[str] = ()) -> List[Dict[str, Any]]:
        """Decode to row dicts (drops invalid rows). Host-side; for
        tests and result iteration, not the hot path."""
        host = {n: np.asarray(c) for n, c in self.cols.items()}
        ok = np.asarray(self.mask())
        out = []
        for i in range(len(ok)):
            if not ok[i]:
                continue
            row = {}
            for n, c in host.items():
                v = c[i]
                if n in self.dicts:
                    row[n] = self.dicts[n][int(v)]
                elif n in date_cols:
                    row[n] = int_to_date(int(v))
                elif c.dtype.kind == "f":
                    row[n] = float(v)
                elif c.dtype.kind == "b":
                    row[n] = bool(v)
                else:
                    row[n] = int(v)
            out.append(row)
        return out


jax.tree_util.register_pytree_node(
    ColumnTable,
    ColumnTable.tree_flatten,
    ColumnTable.tree_unflatten,
)
