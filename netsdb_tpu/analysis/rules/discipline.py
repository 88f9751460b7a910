"""Ports of the pre-framework static checks, one typed rule each.

Every check that lived as a bespoke scanner in
``tests/test_static_checks.py`` (clock discipline, exception hierarchy,
zero-copy framing, pickle confinement, staging/device-upload
discipline, print ban, qid minting, obs counter discipline) is now a
:class:`~netsdb_tpu.analysis.lint.Rule` with the same scope and the
same failure text intent — plus per-rule inline suppressions, which
the old scanners could not express (their exemptions were hardwired
file lists; those lists live on here as rule scope).
"""

from __future__ import annotations

import ast
from typing import Iterable

from netsdb_tpu.analysis.lint import (Diagnostic, Module, Rule,
                                      register, terminal_name)

_SERVE = "netsdb_tpu/serve/"
_OBS = "netsdb_tpu/obs/"
_PLAN = "netsdb_tpu/plan/"
_STORAGE = "netsdb_tpu/storage/"
_OOC = "netsdb_tpu/relational/outofcore.py"

#: the staging module owns the (background-thread) device_put calls
_STAGING_EXEMPT = ("netsdb_tpu/plan/staging.py",)
#: the two modules allowed to name device_put on storage/plan paths
_UPLOAD_EXEMPT = ("netsdb_tpu/plan/staging.py",
                  "netsdb_tpu/storage/devcache.py")
#: protocol.py metadata codec — the only pickle-allowed functions
_PICKLE_OK_FUNCS = {"encode_body", "decode_body"}
#: print() is the OUTPUT of these (the operator CLI and its re-exec)
_PRINT_EXEMPT = ("netsdb_tpu/cli.py", "netsdb_tpu/_reexec.py")

_LOOP_NODES = (ast.For, ast.While, ast.AsyncFor, ast.ListComp,
               ast.SetComp, ast.DictComp, ast.GeneratorExp)


@register
class WallClockRule(Rule):
    """``time.time()`` in deadline-bearing layers (serve/, obs/)."""

    id = "wall-clock"
    rationale = ("wall clocks jump (NTP); every deadline must be "
                 "time.monotonic(), display stamps via "
                 "utils.timing.wall_now")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith((_SERVE, _OBS))

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "time" \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "time":
                yield self.diag(
                    mod, node,
                    "time.time() in a deadline-bearing layer — use "
                    "time.monotonic() (display: utils.timing.wall_now)")
            if isinstance(node, ast.ImportFrom) and node.module == "time" \
                    and any(a.name == "time" for a in node.names):
                yield self.diag(
                    mod, node,
                    "'from time import time' hides wall-clock reads "
                    "from review")


@register
class BroadExceptRule(Rule):
    """Broad except handlers that neither bind nor re-raise."""

    id = "broad-except"
    rationale = ("an opaque except erases the typed error hierarchy — "
                 "bind ('as e') and forward, or re-raise")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith((_SERVE, _OBS))

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or (
                isinstance(node.type, ast.Name)
                and node.type.id in ("Exception", "BaseException"))
            reraises = any(isinstance(sub, ast.Raise)
                           for sub in ast.walk(node))
            if broad and node.name is None and not reraises:
                yield self.diag(
                    mod, node,
                    "broad except that neither binds ('as e') nor "
                    "re-raises — type it or forward it "
                    "(serve/errors.py)")


@register
class ToBytesRule(Rule):
    """``.tobytes()`` on the serve data path (breaks zero-copy v3)."""

    id = "tobytes"
    rationale = ("tensor bytes ride out-of-band memoryview segments; "
                 "one .tobytes() reintroduces the full-payload copy")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith(_SERVE)

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "tobytes":
                yield self.diag(
                    mod, node,
                    ".tobytes() on the serve data path — ship the "
                    "buffer as an out-of-band segment (memoryview), "
                    "never a copy")


@register
class PickleProtocolRule(Rule):
    """pickle/cloudpickle outside protocol.py's metadata codec."""

    id = "pickle-protocol"
    rationale = ("tensor bytes must never ride a pickle stream; the "
                 "wire's pickle use is confined to the metadata codec")

    def select(self, mod: Module) -> bool:
        return mod.rel == _SERVE + "protocol.py"

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name in _PICKLE_OK_FUNCS:
                    continue
                if self._mentions_pickle(node):
                    yield self.diag(
                        mod, node,
                        f"pickle use in {node.name}() — allowed only "
                        f"in the metadata codec "
                        f"({', '.join(sorted(_PICKLE_OK_FUNCS))})")
            elif self._mentions_pickle(node):
                yield self.diag(
                    mod, node,
                    "module-level pickle reference in the wire "
                    "protocol — allowed only inside the metadata "
                    "codec functions")

    @staticmethod
    def _mentions_pickle(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) \
                    and sub.id in ("pickle", "cloudpickle"):
                return True
            if isinstance(sub, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in sub.names]
                if isinstance(sub, ast.ImportFrom) and sub.module:
                    names.append(sub.module)
                if any(n.split(".")[0] in ("pickle", "cloudpickle")
                       for n in names):
                    return True
        return False


@register
class DevicePutLoopRule(Rule):
    """Synchronous ``device_put`` inside loop bodies on the streamed
    hot paths (plan/, outofcore)."""

    id = "device-put-loop"
    rationale = ("per-chunk uploads go through plan/staging."
                 "stage_stream so the copy overlaps compute")

    def select(self, mod: Module) -> bool:
        if mod.rel in _STAGING_EXEMPT:
            return False
        return mod.rel.startswith(_PLAN) or mod.rel == _OOC

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for loop in mod.walk():
            if not isinstance(loop, _LOOP_NODES):
                continue
            for sub in ast.walk(loop):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "device_put":
                    yield self.diag(
                        mod, sub,
                        "synchronous device_put inside a loop body — "
                        "stage uploads through plan/staging."
                        "stage_stream so the copy overlaps the "
                        "consumer's compute")


@register
class DevicePutDirectRule(Rule):
    """Any ``device_put`` mention on storage/plan paths outside the
    sanctioned upload modules (cache bypass)."""

    id = "device-put-direct"
    rationale = ("store-owned block uploads go through storage/"
                 "devcache.to_device or the cross-query cache is "
                 "silently bypassed and its counters lie")

    def select(self, mod: Module) -> bool:
        if mod.rel in _UPLOAD_EXEMPT:
            return False
        return mod.rel.startswith((_STORAGE, _PLAN)) or mod.rel == _OOC

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            hit = None
            if isinstance(node, ast.Call):
                if terminal_name(node.func) == "device_put":
                    hit = "call"
            elif isinstance(node, ast.ImportFrom):
                if any(a.name == "device_put" for a in node.names):
                    hit = "import"
            if hit:
                yield self.diag(
                    mod, node,
                    f"direct device_put ({hit}) on a store/plan path "
                    f"— upload set blocks via storage/devcache."
                    f"to_device (inside a stage_stream place "
                    f"function) so the device cache cannot be "
                    f"silently bypassed")


@register
class ModuleDictCounterRule(Rule):
    """Module-level dict literals in obs/ (counters belong to the
    registry)."""

    id = "module-dict-counter"
    rationale = ("a bare module dict is invisible to COLLECT_STATS "
                 "and un-resettable; counters go through "
                 "MetricsRegistry")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith(_OBS)

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.tree.body:
            targets, value = [], None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            if value is not None \
                    and isinstance(value, (ast.Dict, ast.DictComp)):
                names = ", ".join(getattr(t, "id", "?") for t in targets)
                yield self.diag(
                    mod, node,
                    f"module-level dict {names!r} in obs/ — counters "
                    f"go through MetricsRegistry, not bare module "
                    f"dicts")


@register
class PrintBanRule(Rule):
    """``print()`` outside cli.py / _reexec."""

    id = "print-ban"
    rationale = ("daemons and libraries report through the logger or "
                 "the metrics registry, never stdout")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith("netsdb_tpu/") \
            and mod.rel not in _PRINT_EXEMPT

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                yield self.diag(
                    mod, node,
                    "print() outside cli.py — use "
                    "utils.profiling.get_logger or a registry counter")


@register
class RowwiseShadowRule(Rule):
    """Manual ``rowwise=True`` declarations on Apply labels the
    derived registry already covers."""

    id = "rowwise-shadow"
    rationale = ("plan/computations.ROWWISE_SAFE_LABELS is the one "
                 "source of truth for the suite's audited "
                 "row-decomposable transforms; a per-node re-"
                 "declaration shadows it and drifts when the registry "
                 "is re-audited")

    def select(self, mod: Module) -> bool:
        return mod.rel.endswith(".py") \
            and not mod.rel.startswith("tests/fixtures/")

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        # the registry lives in a jax-free module, importable from the
        # lint process (the framework bans jax imports at lint time)
        from netsdb_tpu.plan.computations import rowwise_safe

        for node in mod.walk():
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "Apply"):
                continue
            kw = {k.arg: k.value for k in node.keywords
                  if k.arg is not None}
            rw = kw.get("rowwise")
            label = kw.get("label")
            if (isinstance(rw, ast.Constant) and rw.value is True
                    and isinstance(label, ast.Constant)
                    and isinstance(label.value, str)
                    and rowwise_safe(label.value)):
                yield self.diag(
                    mod, node,
                    f"rowwise=True on label {label.value!r} shadows "
                    f"the derived registry (plan/computations."
                    f"ROWWISE_SAFE_LABELS) — drop the argument; the "
                    f"declaration is auto-derived")


#: the two modules allowed to touch per-session device-cache state:
#: the owner (serve/sessions.py drives every install/update/spill
#: decision) and the cache that implements the primitives
_SESSION_STATE_EXEMPT = ("netsdb_tpu/serve/sessions.py",
                         "netsdb_tpu/storage/devcache.py")
#: the session-state mutators (devcache session API + spill wiring)
_SESSION_STATE_CALLS = ("session_put", "session_update",
                        "session_drop", "session_sweep",
                        "session_evict_one", "slab_install",
                        "slab_drop", "set_session_spill")


@register
class SessionStateMutationRule(Rule):
    """Per-session device-cache state mutated outside the session
    manager (breaks step-tag consistency and the TTL accounting)."""

    id = "session-state-mutation"
    rationale = ("session state carries step tags and TTL/LRU "
                 "accounting that only serve/sessions.py maintains "
                 "coherently; a stray session_put desyncs the "
                 "devcache copy from the arena spill and tears "
                 "revived state")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith("netsdb_tpu/") \
            and mod.rel not in _SESSION_STATE_EXEMPT

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            name = None
            if isinstance(node, ast.Call):
                t = terminal_name(node.func)
                if t in _SESSION_STATE_CALLS:
                    name = t
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    if a.name in _SESSION_STATE_CALLS:
                        name = a.name
                        break
            if name:
                yield self.diag(
                    mod, node,
                    f"{name}() outside serve/sessions.py — session "
                    f"state mutations (step tags, TTL, spill wiring) "
                    f"are the session manager's alone; route through "
                    f"SessionManager so devcache and arena stay "
                    f"consistent")


@register
class QidMintRule(Rule):
    """``new_query_id`` outside obs/ (unsampled tracing on hot
    paths)."""

    id = "qid-mint"
    rationale = ("hot paths mint through obs.sample_qid so tracing "
                 "cost follows config.obs_trace_sample")

    def select(self, mod: Module) -> bool:
        return mod.rel.startswith("netsdb_tpu/") \
            and not mod.rel.startswith(_OBS)

    def check_module(self, mod: Module) -> Iterable[Diagnostic]:
        for node in mod.walk():
            hit = False
            if isinstance(node, ast.Call):
                hit = terminal_name(node.func) == "new_query_id"
            elif isinstance(node, ast.ImportFrom):
                hit = any(a.name == "new_query_id" for a in node.names)
            if hit:
                yield self.diag(
                    mod, node,
                    "new_query_id outside obs/ — unsampled qid "
                    "minting pays full tracing per request; mint "
                    "through obs.sample_qid (config.obs_trace_sample)")
