"""reprolint rules RL001-RL010: the repo's standing policies, mechanically.

Each rule enforces one policy from ROADMAP.md "Standing policies" (the rule
code is cross-referenced there and in README "Static analysis"):

* RL001 compat-drift          — drifted JAX APIs only through repro.compat
* RL002 engine-seam-ownership — Parareal math only in repro.core.engine,
                                frontier/window control only in repro.core.window
* RL003 host-sync-discipline  — no implicit device->host syncs inside
                                ``@hot_loop`` functions outside the
                                ``_host_fetch`` seam
* RL004 donation-after-use    — a buffer passed in a ``donate_argnums``
                                position of a jitted callable is dead; rule
                                flags later reads in the same function
* RL005 fused-path-gating     — Pallas dispatch via
                                ``kernels.ops.fused_default()`` /
                                ``engine.resolve_fused``, not ad-hoc
                                ``jax.default_backend() == "tpu"`` checks
* RL006 test-tier-markers     — subprocess-spawning / multi-device tests
                                carry ``slow``/``distributed`` markers
* RL007 tracked-artifacts     — build caches and dry-run outputs are never
                                tracked in git
* RL008 model-eval-seam       — drivers and the serving engine evaluate the
                                backbone only through the
                                ``repro.core.denoiser.Denoiser`` seam, never
                                by calling a bare ``model_fn(x, t)``
* RL009 accel-seam-ownership  — Anderson/secant mixing math (dense linalg
                                solves, gamma systems) lives only in
                                ``repro.core.accel``; drivers consume the
                                ``Accelerator`` seam
* RL010 kernel-tile-literals  — kernel tile/block/chunk sizes come from the
                                ``repro.kernels.tuning`` seam; hardcoded
                                integer tile kwargs (``block_q=32``) at call
                                sites outside ``repro.kernels`` are flagged

All rules are pure-AST (no JAX import anywhere in this package): they see
through import aliases via :func:`repro.analysis.core.qualname`, which is
what lets RL001 catch ``from jax import tree_map`` and ``from
jax.experimental import shard_map as sm`` — the false-negative class the
old ``check.sh`` grep shipped with.
"""
from __future__ import annotations

import ast
import os
import subprocess
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.core import (Finding, ModuleInfo, module_rule,
                                 project_rule, qualname)


def _find(mod: ModuleInfo, node: ast.AST, code: str, rule: str,
          message: str) -> Finding:
    return Finding(code=code, message=message, path=mod.path,
                   line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0), rule=rule)


def _in(path: str, *suffixes: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(p.endswith(s) for s in suffixes)


# ==========================================================================
# RL001 — compat drift
# ==========================================================================

# Exact drifted callables (resolved through the import graph).
_DRIFTED_EXACT = {
    "jax.tree_map": "repro.compat.tree.map",
    "jax.make_mesh": "repro.compat.make_mesh",
    "jax.shard_map": "repro.compat.shard_map",
    "jax.lax.axis_size": "repro.compat.axis_size",
}
# Legacy jax.tree_util spellings with a compat.tree equivalent.
_DRIFTED_TREE_UTIL = {
    "tree_map": "repro.compat.tree.map",
    "tree_map_with_path": "repro.compat.tree.map_with_path",
    "tree_flatten": "repro.compat.tree.flatten",
    "tree_unflatten": "repro.compat.tree.unflatten",
    "tree_leaves": "repro.compat.tree.leaves",
    "tree_structure": "repro.compat.tree.structure",
    "tree_all": "repro.compat.tree (extend the shim)",
    "tree_reduce": "repro.compat.tree (extend the shim)",
}
# Any touch of the old experimental shard_map module is drifted.
_DRIFTED_PREFIXES = ("jax.experimental.shard_map",)

_RL001_ALLOWED = ("src/repro/compat.py",)


def _drifted_target(qn: Optional[str]) -> Optional[str]:
    if not qn:
        return None
    if qn in _DRIFTED_EXACT:
        return _DRIFTED_EXACT[qn]
    if qn.startswith("jax.tree_util."):
        leaf = qn.split(".")[-1]
        if leaf in _DRIFTED_TREE_UTIL:
            return _DRIFTED_TREE_UTIL[leaf]
    for pref in _DRIFTED_PREFIXES:
        if qn == pref or qn.startswith(pref + "."):
            return "repro.compat.shard_map"
    return None


@module_rule("RL001", "compat-drift",
             "drifted JAX APIs (shard_map/make_mesh/tree_map/cost_analysis/"
             "axis_size) called outside repro.compat")
def rl001_compat_drift(mod: ModuleInfo) -> Iterable[Finding]:
    if _in(mod.path, *_RL001_ALLOWED):
        return
    seen: Set[Tuple[int, int]] = set()

    def emit(node, qn, blessed):
        key = (node.lineno, node.col_offset)
        if key in seen:
            return None
        seen.add(key)
        return _find(mod, node, "RL001", "compat-drift",
                     f"drifted JAX API `{qn}` outside repro.compat — use "
                     f"`{blessed}` (ROADMAP standing policy: drifting JAX "
                     f"APIs are named only in repro.compat)")

    for node in ast.walk(mod.tree):
        # import statements that bind a drifted name (aliased or not)
        if isinstance(node, ast.Import):
            for a in node.names:
                blessed = _drifted_target(a.name)
                if blessed:
                    f = emit(node, a.name, blessed)
                    if f:
                        yield f
        elif isinstance(node, ast.ImportFrom) and not node.level:
            base = node.module or ""
            for a in node.names:
                full = f"{base}.{a.name}" if base else a.name
                blessed = _drifted_target(full) or _drifted_target(base)
                if blessed:
                    f = emit(node, full, blessed)
                    if f:
                        yield f
        # use sites: attribute chains and bare aliased names
        elif isinstance(node, ast.Attribute):
            qn = qualname(node, mod.aliases)
            blessed = _drifted_target(qn)
            if blessed:
                f = emit(node, qn, blessed)
                if f:
                    yield f
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            qn = mod.aliases.get(node.id)
            blessed = _drifted_target(qn)
            if blessed:
                f = emit(node, qn, blessed)
                if f:
                    yield f
        # `.cost_analysis()` drifted list[dict] -> dict: only the compat
        # wrapper may call the raw method
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "cost_analysis":
            qn = qualname(node.func, mod.aliases)
            if qn is None or not qn.startswith("repro.compat"):
                f = emit(node, "<compiled>.cost_analysis()",
                         "repro.compat.cost_analysis(compiled)")
                if f:
                    yield f


# ==========================================================================
# RL002 — engine-seam ownership
# ==========================================================================

_ENGINE = "repro.core.engine"
_WINDOW = "repro.core.window"
_RL002_OWNERS = ("src/repro/core/engine.py", "src/repro/core/window.py")
# kernels implement the update math itself (ref oracle + Pallas bodies)
_RL002_KERNEL_EXEMPT = "/repro/kernels/"

# Names whose *definition* outside the owner module is a re-derivation of
# the Parareal seam (ROADMAP: "Parareal math lives in exactly one module").
_ENGINE_OWNED_DEFS = frozenset({
    "parareal_update", "corrector_sweep", "coarse_init_sweep",
    "suffix_refinement", "run_parareal", "convergence_norm",
    "blockwise_norm", "still_refining", "has_converged", "prefix_frontier",
})

_FINE_TOKENS = ("y", "y_i", "yi", "fine")
_COARSE_TOKENS = ("cur", "prev", "coarse", "g_cur", "g_prev", "g_new",
                  "g_old", "gcur", "gprev")


def _leaf_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id.lower()
    if isinstance(node, ast.Attribute):
        return node.attr.lower()
    return None


def _is_fine(tok: str) -> bool:
    return tok in _FINE_TOKENS or "fine" in tok


def _is_coarse(tok: str) -> bool:
    return tok in _COARSE_TOKENS or "coarse" in tok or tok.startswith("g_")


def _parareal_shape(node: ast.BinOp) -> bool:
    """``a + b - c`` / ``a - b + c`` whose operand names spell the
    predictor-corrector update (one fine term, two coarse terms)."""
    ops: List[ast.AST] = []
    if isinstance(node.op, ast.Sub) and isinstance(node.left, ast.BinOp) \
            and isinstance(node.left.op, ast.Add):
        ops = [node.left.left, node.left.right, node.right]
    elif isinstance(node.op, ast.Add) and isinstance(node.right, ast.BinOp) \
            and isinstance(node.right.op, ast.Sub):
        ops = [node.left, node.right.left, node.right.right]
    else:
        return False
    toks = [_leaf_name(o) for o in ops]
    if any(t is None for t in toks):
        return False
    return (sum(1 for t in toks if _is_fine(t)) >= 1
            and sum(1 for t in toks if _is_coarse(t)) >= 2)


@module_rule("RL002", "engine-seam-ownership",
             "Parareal math / frontier control re-derived outside "
             "repro.core.engine / repro.core.window")
def rl002_engine_seam(mod: ModuleInfo) -> Iterable[Finding]:
    if _in(mod.path, *_RL002_OWNERS):
        return
    kernel_exempt = _RL002_KERNEL_EXEMPT in mod.path.replace(os.sep, "/")

    for node in ast.walk(mod.tree):
        # (a) private-helper access through the seam boundary
        if isinstance(node, ast.ImportFrom) and not node.level and \
                node.module in (_ENGINE, _WINDOW):
            for a in node.names:
                if a.name.startswith("_"):
                    yield _find(
                        mod, node, "RL002", "engine-seam-ownership",
                        f"private engine-seam helper `{node.module}."
                        f"{a.name}` imported outside its owner module — "
                        f"consume the public seam instead")
        elif isinstance(node, ast.Attribute) and node.attr.startswith("_"):
            qn = qualname(node, mod.aliases)
            if qn and (qn.startswith(_ENGINE + "._")
                       or qn.startswith(_WINDOW + "._")):
                yield _find(
                    mod, node, "RL002", "engine-seam-ownership",
                    f"private engine-seam helper `{qn}` referenced outside "
                    f"its owner module — consume the public seam instead")
        # (b) re-derivation by name: defining an engine-owned function
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.name in _ENGINE_OWNED_DEFS and not kernel_exempt:
            yield _find(
                mod, node, "RL002", "engine-seam-ownership",
                f"`def {node.name}` outside repro.core.engine re-derives "
                f"the Parareal seam — import it from repro.core.engine "
                f"(ROADMAP: Parareal math lives in exactly one module)")
        # (c) re-implementation of parareal_update by expression shape
        elif isinstance(node, ast.BinOp) and not kernel_exempt and \
                _parareal_shape(node):
            yield _find(
                mod, node, "RL002", "engine-seam-ownership",
                "predictor-corrector update re-derived by shape "
                "(`fine + G_cur - G_prev`) — call "
                "repro.core.engine.parareal_update instead")


# ==========================================================================
# RL003 — host-sync discipline inside @hot_loop
# ==========================================================================

_HOST_MODULES = ("np", "numpy", "math")
_HOST_BUILTINS = frozenset({
    "len", "min", "max", "sum", "sorted", "enumerate", "range", "list",
    "tuple", "dict", "set", "zip", "abs", "any", "all", "str", "repr",
    "print", "isinstance", "getattr", "hasattr", "float", "int", "bool",
    "round", "divmod", "reversed", "map", "filter",
})
_DEVICE_MODULES = ("jnp", "jax", "lax")
_CONVERTERS = frozenset({"float", "int", "bool"})
_NP_CONVERTERS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                            "numpy.array"})


def _is_hot_loop(dec: ast.AST, aliases: Dict[str, str]) -> bool:
    target = dec.func if isinstance(dec, ast.Call) else dec
    qn = qualname(target, aliases)
    return bool(qn) and qn.split(".")[-1] == "hot_loop"


def _is_host_fetch(func: ast.AST, aliases: Dict[str, str]) -> bool:
    qn = qualname(func, aliases)
    return bool(qn) and qn.split(".")[-1].endswith("host_fetch")


def _target_keys(node: ast.AST) -> List[str]:
    """Assignment-target taint keys: plain names and `self.x`-style dotted
    attributes (the serving engine mutates device state through self)."""
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, ast.Attribute):
        base = _expr_key(node)
        return [base] if base else []
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in node.elts:
            out.extend(_target_keys(e))
        return out
    if isinstance(node, ast.Starred):
        return _target_keys(node.value)
    return []


def _expr_key(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Taint:
    """Forward host/device taint over one @hot_loop function body.

    Conservative in the device direction: a call whose callee isn't a known
    host producer (numpy/math/builtins/`_host_fetch`) and takes no
    host-tainted argument is assumed to return device values — exactly the
    posture that protects the one-sync-per-refinement contract."""

    def __init__(self, aliases: Dict[str, str]):
        self.aliases = aliases
        self.host: Set[str] = set()
        self.device: Set[str] = set()

    def classify(self, node: ast.AST) -> str:           # host|device|unknown
        if isinstance(node, ast.Constant):
            return "host"
        if isinstance(node, (ast.List, ast.Tuple, ast.Set, ast.Dict,
                             ast.ListComp, ast.SetComp, ast.DictComp,
                             ast.GeneratorExp, ast.JoinedStr)):
            return "host"
        key = _expr_key(node)
        if key is not None:
            if key in self.device:
                return "device"
            if key in self.host:
                return "host"
            # attribute of a tainted base inherits the base's taint
            parts = key.split(".")
            for i in range(len(parts) - 1, 0, -1):
                base = ".".join(parts[:i])
                if base in self.device:
                    return "device"
                if base in self.host:
                    return "host"
            return "unknown"
        if isinstance(node, ast.Subscript):
            return self.classify(node.value)
        if isinstance(node, ast.UnaryOp):
            return self.classify(node.operand)
        if isinstance(node, (ast.BinOp, ast.BoolOp, ast.Compare, ast.IfExp)):
            kids = [self.classify(k) for k in ast.iter_child_nodes(node)
                    if isinstance(k, ast.expr)]
            if "device" in kids:
                return "device"
            if kids and all(k == "host" for k in kids):
                return "host"
            return "unknown"
        if isinstance(node, ast.Call):
            return self.classify_call(node)
        return "unknown"

    def classify_call(self, node: ast.Call) -> str:
        if _is_host_fetch(node.func, self.aliases):
            return "host"
        qn = qualname(node.func, self.aliases)
        root = qn.split(".")[0] if qn else None
        args = list(node.args) + [kw.value for kw in node.keywords]
        arg_taints = [self.classify(a) for a in args]
        if root in _HOST_MODULES or \
                (isinstance(node.func, ast.Name)
                 and node.func.id in _HOST_BUILTINS):
            return "host"
        # a host-side method of a host object stays host
        if isinstance(node.func, ast.Attribute) and \
                self.classify(node.func.value) == "host":
            return "host"
        if root in _DEVICE_MODULES or (qn and qn.startswith("jax.")):
            return "device"
        # pragmatic: feeding a host value in marks the result host (the
        # serving engine's `policy.advance(lo, fetched_block_resid, B)`)
        if "host" in arg_taints:
            return "host"
        return "device"

    def assign(self, targets: Sequence[ast.AST], value: ast.AST) -> None:
        taint = self.classify(value)
        for t in targets:
            for key in _target_keys(t):
                self.host.discard(key)
                self.device.discard(key)
                if taint == "host":
                    self.host.add(key)
                elif taint == "device":
                    self.device.add(key)


def _iter_stmts(body: Sequence[ast.stmt]) -> Iterable[ast.stmt]:
    """Statements of one scope in source order, recursing into compound
    statements but NOT into nested function/class scopes."""
    for stmt in body:
        yield stmt
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if sub:
                yield from _iter_stmts(sub)
        for h in getattr(stmt, "handlers", []) or []:
            yield from _iter_stmts(h.body)


def _stmt_exprs(stmt: ast.stmt) -> Iterable[ast.AST]:
    """The expressions evaluated by ``stmt`` itself (compound statements
    contribute only their headers — their bodies are separate statements)."""
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        yield stmt.target
        yield stmt.iter
    elif isinstance(stmt, (ast.While, ast.If)):
        yield stmt.test
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            yield item.context_expr
    elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
        for d in stmt.decorator_list:
            yield d
    elif isinstance(stmt, ast.Try):
        return
    else:
        yield stmt


@module_rule("RL003", "host-sync-discipline",
             "implicit device->host sync inside a @hot_loop function "
             "outside the _host_fetch seam")
def rl003_host_sync(mod: ModuleInfo) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(_is_hot_loop(d, mod.aliases) for d in node.decorator_list):
            continue
        taint = _Taint(mod.aliases)
        for stmt in _iter_stmts(node.body):
            # flag sync-inducing calls in this statement first (reads
            # happen before the statement's own stores take effect)
            for expr in _stmt_exprs(stmt):
                for sub in ast.walk(expr):
                    if isinstance(sub, ast.Call):
                        yield from _rl003_check_call(mod, sub, taint)
            if isinstance(stmt, ast.Assign):
                taint.assign(stmt.targets, stmt.value)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                taint.assign([stmt.target], stmt.value)
            elif isinstance(stmt, ast.AugAssign):
                taint.assign([stmt.target], stmt.value)
            elif isinstance(stmt, ast.For):
                taint.assign([stmt.target], stmt.iter)


def _rl003_check_call(mod: ModuleInfo, call: ast.Call,
                      taint: _Taint) -> Iterable[Finding]:
    qn = qualname(call.func, mod.aliases)
    # device_get anywhere in a hot loop bypasses the blessed seam
    if qn and qn.split(".")[-1] == "device_get":
        yield _find(mod, call, "RL003", "host-sync-discipline",
                    "`jax.device_get` inside a @hot_loop — route the "
                    "fetch through the blessed `_host_fetch` seam (one "
                    "sync per refinement)")
        return
    args = call.args
    if isinstance(call.func, ast.Name) and call.func.id in _CONVERTERS \
            and len(args) == 1:
        if taint.classify(args[0]) == "device":
            yield _find(mod, call, "RL003", "host-sync-discipline",
                        f"`{call.func.id}()` of a device value inside a "
                        f"@hot_loop forces an implicit sync — fetch through "
                        f"`_host_fetch` once per refinement instead")
    elif qn in _NP_CONVERTERS and args:
        if taint.classify(args[0]) == "device":
            yield _find(mod, call, "RL003", "host-sync-discipline",
                        f"`{qn}()` of a device value inside a @hot_loop "
                        f"forces an implicit sync — fetch through "
                        f"`_host_fetch` once per refinement instead")
    elif isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
            and not args:
        if taint.classify(call.func.value) == "device":
            yield _find(mod, call, "RL003", "host-sync-discipline",
                        "`.item()` on a device value inside a @hot_loop "
                        "forces an implicit sync — fetch through "
                        "`_host_fetch` once per refinement instead")


# ==========================================================================
# RL004 — donation-after-use
# ==========================================================================

def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Literal donate_argnums of a jax.jit(...) call (None when dynamic)."""
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, int)
                    for e in v.elts):
                return tuple(e.value for e in v.elts)
            return None   # dynamic (e.g. self._donate): not statically known
    return None


def _is_jit(func: ast.AST, aliases: Dict[str, str]) -> bool:
    qn = qualname(func, aliases)
    return bool(qn) and qn.split(".")[-1] in ("jit", "pjit")


def _module_donated(mod: ModuleInfo) -> Dict[str, Tuple[int, ...]]:
    """Functions donated via decorator: @jax.jit(donate_argnums=...) or
    @functools.partial(jax.jit, donate_argnums=...)."""
    out: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            is_partial_jit = (
                qualname(dec.func, mod.aliases) in
                ("functools.partial", "partial")
                and dec.args and _is_jit(dec.args[0], mod.aliases))
            if _is_jit(dec.func, mod.aliases) or is_partial_jit:
                pos = _donate_positions(dec)
                if pos:
                    out[node.name] = pos
    return out


@module_rule("RL004", "donation-after-use",
             "buffer passed in a donate_argnums position and read "
             "afterwards in the same function")
def rl004_donation(mod: ModuleInfo) -> Iterable[Finding]:
    decorated = _module_donated(mod)
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from _rl004_scan_scope(mod, list(node.body), decorated)
    yield from _rl004_scan_scope(mod, list(mod.tree.body), decorated)


def _rl004_scan_scope(mod: ModuleInfo, body: List[ast.stmt],
                      decorated: Dict[str, Tuple[int, ...]]
                      ) -> Iterable[Finding]:
    """Linear forward scan of one scope: record jit-with-donation bindings,
    mark donated argument names dead at each call, flag loads of dead names,
    resurrect names on store (``x, s = step(x, y)`` is the safe idiom)."""
    donated: Dict[str, Tuple[int, ...]] = dict(decorated)
    dead: Dict[str, int] = {}       # donated name -> donating call's line

    def stores_of(stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        for t in getattr(stmt, "targets", []) or []:
            out.update(_target_keys(t))
        if isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
            out.update(_target_keys(stmt.target))
        return out

    for stmt in _iter_stmts(body):
        # record jitted-with-donation callables bound in this scope
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Assign) and isinstance(sub.value, ast.Call) \
                    and _is_jit(sub.value.func, mod.aliases):
                pos = _donate_positions(sub.value)
                if pos:
                    for key in _target_keys(sub.targets[0]):
                        donated[key] = pos

        # loads of already-dead names: dead was filled by EARLIER
        # statements, so the donating statement's own arg use never
        # self-flags — but passing a dead buffer to a second call does
        for expr in _stmt_exprs(stmt):
            for sub in ast.walk(expr):
                if isinstance(sub, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(sub, "ctx", None), ast.Load):
                    key = _expr_key(sub)
                    if key in dead:
                        yield _find(
                            mod, sub, "RL004", "donation-after-use",
                            f"`{key}` was donated to a jitted callable "
                            f"(donate_argnums) at line {dead[key]} and read "
                            f"afterwards — donated buffers are dead; rebind "
                            f"the result (`x, ... = fn(x, ...)`) or drop "
                            f"the donation")
                        dead.pop(key, None)   # one report per donation

        # this statement's donating calls mark their args dead ...
        for expr in _stmt_exprs(stmt):
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Call):
                    callee = _expr_key(sub.func)
                    if callee in donated:
                        for i in donated[callee]:
                            if i < len(sub.args):
                                key = _expr_key(sub.args[i])
                                if key is not None:
                                    dead[key] = sub.lineno
        # ... and its stores resurrect rebound names
        for key in stores_of(stmt):
            dead.pop(key, None)


# ==========================================================================
# RL005 — fused-path gating
# ==========================================================================

_RL005_ALLOWED = ("src/repro/kernels/ops.py", "src/repro/compat.py")


@module_rule("RL005", "fused-path-gating",
             "direct backend/platform string check gating the Pallas path "
             "instead of kernels.ops.fused_default()/engine.resolve_fused")
def rl005_fused_gating(mod: ModuleInfo) -> Iterable[Finding]:
    if _in(mod.path, *_RL005_ALLOWED):
        return

    def const_strs(n: ast.AST) -> List[str]:
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            return [n.value]
        if isinstance(n, (ast.Tuple, ast.List, ast.Set)):
            out: List[str] = []
            for e in n.elts:
                out.extend(const_strs(e))
            return out
        return []

    def is_backend_probe(n: ast.AST) -> bool:
        if isinstance(n, ast.Call):
            qn = qualname(n.func, mod.aliases)
            return bool(qn) and qn.split(".")[-1] in (
                "default_backend", "get_backend")
        if isinstance(n, ast.Attribute) and n.attr == "platform":
            return True
        return False

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Compare):
            continue
        sides = [node.left] + list(node.comparators)
        if not any(is_backend_probe(s) for s in sides):
            continue
        strs: List[str] = []
        for s in sides:
            strs.extend(const_strs(s))
        if "tpu" in strs:
            yield _find(
                mod, node, "RL005", "fused-path-gating",
                "backend==\"tpu\" string check gates the fused Pallas path "
                "— use repro.kernels.ops.fused_default() / "
                "repro.core.engine.resolve_fused(None) so dispatch policy "
                "lives in one place (ROADMAP item 5: GPU parity)")


# ==========================================================================
# RL006 — test-tier markers
# ==========================================================================

_SUBPROCESS_FUNCS = frozenset({"run", "Popen", "call", "check_call",
                               "check_output"})
_TIER_MARKS = frozenset({"slow", "distributed"})


def _marks_of(exprs: Sequence[ast.AST], aliases: Dict[str, str]) -> Set[str]:
    marks: Set[str] = set()
    for e in exprs:
        target = e.func if isinstance(e, ast.Call) else e
        qn = qualname(target, aliases)
        if qn and qn.startswith("pytest.mark."):
            marks.add(qn.split(".")[2])
    return marks


def _mesh_devices(call: ast.Call) -> int:
    """Literal device count of a make_mesh((a, b, ...), ...) call, or 0."""
    if not call.args:
        return 0
    shape = call.args[0]
    if isinstance(shape, (ast.Tuple, ast.List)) and all(
            isinstance(e, ast.Constant) and isinstance(e.value, int)
            for e in shape.elts):
        n = 1
        for e in shape.elts:
            n *= e.value
        return n
    if isinstance(shape, ast.Constant) and isinstance(shape.value, int):
        return shape.value
    return 0


def _rl006_trigger(fn: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    # a test taking the `monkeypatch` fixture and building a mesh is
    # presumed to be faking the mesh constructor (compat-branch tests do
    # exactly this) — subprocess spawns can't be faked that way and are
    # still flagged
    fakes_mesh = any(a.arg == "monkeypatch"
                     for a in getattr(fn, "args").args)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        qn = qualname(node.func, aliases)
        leaf = qn.split(".")[-1] if qn else None
        if leaf == "run_subprocess":
            return "spawns a subprocess (run_subprocess)"
        if qn and qn.startswith("subprocess.") and \
                leaf in _SUBPROCESS_FUNCS:
            return f"spawns a subprocess ({qn})"
        if leaf == "make_mesh" and _mesh_devices(node) > 1 \
                and not fakes_mesh:
            return f"builds a {_mesh_devices(node)}-device mesh"
    return None


@module_rule("RL006", "test-tier-markers",
             "subprocess-spawning or multi-device tests must carry "
             "slow/distributed markers so check.sh --fast stays honest")
def rl006_test_tiers(mod: ModuleInfo) -> Iterable[Finding]:
    if not mod.is_test_file:
        return
    module_marks: Set[str] = set()
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "pytestmark"
                for t in node.targets):
            vals = node.value.elts if isinstance(
                node.value, (ast.List, ast.Tuple)) else [node.value]
            module_marks |= _marks_of(vals, mod.aliases)
    if module_marks & _TIER_MARKS:
        return

    def check_fn(fn, extra_marks: Set[str]):
        if not fn.name.startswith("test"):
            return
        marks = extra_marks | _marks_of(fn.decorator_list, mod.aliases)
        if marks & _TIER_MARKS:
            return
        why = _rl006_trigger(fn, mod.aliases)
        if why:
            yield _find(
                mod, fn, "RL006", "test-tier-markers",
                f"`{fn.name}` {why} but carries no slow/distributed "
                f"marker — the check.sh --fast tier would run it "
                f"(register intent with @pytest.mark.slow / "
                f"@pytest.mark.distributed)")

    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from check_fn(node, set())
        elif isinstance(node, ast.ClassDef):
            cls_marks = _marks_of(node.decorator_list, mod.aliases)
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from check_fn(sub, cls_marks)


# ==========================================================================
# RL007 — tracked build/experiment artifacts (project rule)
# ==========================================================================

def artifact_violations(tracked: Iterable[str]) -> List[str]:
    """Offending paths among an iterable of tracked repo paths (the pure
    core of RL007 — unit-testable without git)."""
    bad: List[str] = []
    for p in tracked:
        parts = p.replace(os.sep, "/").split("/")
        if "__pycache__" in parts or ".pytest_cache" in parts \
                or p.endswith(".pyc") \
                or p.replace(os.sep, "/").startswith("experiments/dryrun"):
            bad.append(p)
    return bad


def _git_tracked(root: str) -> Optional[List[str]]:
    try:
        out = subprocess.run(["git", "-C", root, "ls-files"],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    return out.stdout.splitlines()


@project_rule("RL007", "tracked-artifacts",
              "build caches (__pycache__/.pyc/.pytest_cache) and dry-run "
              "experiment outputs must never be tracked in git")
def rl007_artifacts(root: str, modules) -> Iterable[Finding]:
    tracked = _git_tracked(root)
    if tracked is None:       # not a git checkout: nothing to assert
        return
    for p in artifact_violations(tracked):
        # message preserved from the scripts/check.sh shell-grep era
        yield Finding(
            code="RL007", rule="tracked-artifacts", path=p, line=1, col=0,
            message="artifact lint FAILED: build/experiment artifacts are "
                    "tracked in git — git rm --cached it and keep "
                    ".gitignore covering the pattern")


# ==========================================================================
# RL008 — model-eval seam (drivers/serve call the Denoiser, not model_fn)
# ==========================================================================

# Only drivers and the serving engine are in scope — models may of course
# call their own forward, and tests/benchmarks call whatever they probe.
# Fixture files keep the rule's natural scope by name (the RL006 precedent:
# naming places a fixture inside the scope the rule derives structurally).
_RL008_SCOPES = ("src/repro/core/", "src/repro/serve/")
# solvers.py is the seam's one consumer (it receives the eval callable the
# driver composed); denoiser.py is the seam itself.
_RL008_ALLOWED = ("src/repro/core/solvers.py", "src/repro/core/denoiser.py")


def _rl008_in_scope(path: str) -> bool:
    p = path.replace(os.sep, "/")
    if any(s in p for s in _RL008_SCOPES):
        return True
    return os.path.basename(p).startswith("rl008")


@module_rule("RL008", "model-eval-seam",
             "direct model_fn(x, t)-shaped call in a driver or the serving "
             "engine instead of the repro.core.denoiser.Denoiser seam")
def rl008_model_eval_seam(mod: ModuleInfo) -> Iterable[Finding]:
    if not _rl008_in_scope(mod.path) or _in(mod.path, *_RL008_ALLOWED):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        leaf = _leaf_name(node.func)
        if leaf is None or "model_fn" not in leaf:
            continue
        if len(node.args) != 2 or any(
                isinstance(a, ast.Starred) for a in node.args):
            continue
        yield _find(
            mod, node, "RL008", "model-eval-seam",
            f"direct model eval `{leaf}(x, t)` outside the Denoiser seam — "
            f"adapt via repro.core.denoiser.as_denoiser and evaluate "
            f"through the Denoiser (standalone call, or .inner_eval() "
            f"inside a driver shard_map) so time/data/model parallelism "
            f"compose driver-free")


# ==========================================================================
# RL009 — accel-seam ownership (mixing math lives in repro.core.accel)
# ==========================================================================

# Same scope story as RL008: only drivers and the serving engine must
# consume the Accelerator seam — models/tests/benchmarks do whatever they
# probe.  Fixture files opt into the scope by name (the RL006/RL008
# precedent).
_ACCEL = "repro.core.accel"
_RL009_OWNER = ("src/repro/core/accel.py",)
_RL009_SCOPES = ("src/repro/core/", "src/repro/serve/")
# Names whose *definition* outside the owner is a re-derivation of the
# mixing seam (leading underscores stripped before matching).
_ACCEL_OWNED_DEFS = frozenset({"resolve_accel", "solve_gamma",
                               "anderson_mix"})
# Dense linear-algebra entry points: the secant/normal-equations solve is
# the acceleration seam's signature — no other core/serve module does
# dense linalg (frontier control, sweeps and solvers are all elementwise
# or reductions).
_LINALG_SOLVERS = frozenset({"solve", "lstsq", "inv", "pinv", "cholesky",
                             "qr", "svd"})


def _rl009_in_scope(path: str) -> bool:
    p = path.replace(os.sep, "/")
    if any(s in p for s in _RL009_SCOPES):
        return True
    return os.path.basename(p).startswith("rl009")


@module_rule("RL009", "accel-seam-ownership",
             "Anderson/secant mixing math (dense linalg solves, gamma "
             "systems) re-derived outside repro.core.accel")
def rl009_accel_seam(mod: ModuleInfo) -> Iterable[Finding]:
    if not _rl009_in_scope(mod.path) or _in(mod.path, *_RL009_OWNER):
        return
    for node in ast.walk(mod.tree):
        # (a) private-helper access across the seam boundary
        if isinstance(node, ast.ImportFrom) and not node.level and \
                node.module == _ACCEL:
            for a in node.names:
                if a.name.startswith("_"):
                    yield _find(
                        mod, node, "RL009", "accel-seam-ownership",
                        f"private accel-seam helper `{_ACCEL}.{a.name}` "
                        f"imported outside its owner module — consume the "
                        f"public seam (Accelerator.apply/init_state/"
                        f"reset_lanes, resolve_accel) instead")
        elif isinstance(node, ast.Attribute) and node.attr.startswith("_"):
            qn = qualname(node, mod.aliases)
            if qn and qn.startswith(_ACCEL + "._"):
                yield _find(
                    mod, node, "RL009", "accel-seam-ownership",
                    f"private accel-seam helper `{qn}` referenced outside "
                    f"its owner module — consume the public seam instead")
        # (b) re-derivation by name: defining a seam-owned function
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.name.lstrip("_") in _ACCEL_OWNED_DEFS:
            yield _find(
                mod, node, "RL009", "accel-seam-ownership",
                f"`def {node.name}` outside repro.core.accel re-derives "
                f"the acceleration seam — import it from repro.core.accel "
                f"(mixing math lives in exactly one module)")
        # (c) re-implementation by shape: a dense least-squares/secant
        # solve in a driver or the serving engine IS mixing math
        elif isinstance(node, ast.Call):
            qn = qualname(node.func, mod.aliases)
            if qn and ".linalg." in qn and \
                    qn.split(".")[-1] in _LINALG_SOLVERS:
                yield _find(
                    mod, node, "RL009", "accel-seam-ownership",
                    f"dense linear-algebra solve `{qn}` in a driver/serve "
                    f"module — Anderson/secant mixing math belongs to "
                    f"repro.core.accel; select an Accelerator "
                    f"(SRDSConfig(accel=...)) and let the engine apply it")


# ==========================================================================
# RL010 — kernel tile literals (launch sizes come from the tuning seam)
# ==========================================================================

# Kernel launch-shape kwargs owned by repro.kernels.tuning.  A hardcoded
# integer for any of these at a call site outside the kernels package is a
# size that silently stops tracking the tuner's per-backend tables — the
# exact drift the seam exists to prevent.  Names passed *as variables*
# (resolved configs, sweep candidates) are fine; only integer literals are
# flagged.
_RL010_TILE_KWARGS = frozenset({"block_q", "block_k", "block_rows",
                                "tile_rows", "chunk", "chunk_target",
                                "num_warps", "num_stages"})
# The kernels package is the seam's owner: its heuristics, wrappers and
# raw pallas_call entry points ARE where the defaults live.
_RL010_OWNER = "src/repro/kernels/"


def _rl010_exempt(path: str) -> bool:
    return _RL010_OWNER in path.replace(os.sep, "/")


def _int_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, int) and \
            not isinstance(node.value, bool)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return _int_literal(node.operand)
    return False


@module_rule("RL010", "kernel-tile-literals",
             "hardcoded kernel tile/block/chunk integer kwarg outside "
             "repro.kernels — sizes come from the tuning seam")
def rl010_tile_literals(mod: ModuleInfo) -> Iterable[Finding]:
    if _rl010_exempt(mod.path):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg in _RL010_TILE_KWARGS and _int_literal(kw.value):
                yield _find(
                    mod, node, "RL010", "kernel-tile-literals",
                    f"hardcoded kernel tile size `{kw.arg}=...` at a call "
                    f"site outside repro.kernels — launch shapes resolve "
                    f"through repro.kernels.tuning (pass tuner= / "
                    f"KernelTuner(overrides=...) so per-backend tables "
                    f"and heuristics stay authoritative)")
