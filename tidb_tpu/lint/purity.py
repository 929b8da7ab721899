"""Hot-path purity lint: AST pass over the engine directories.

"Query Processing on Tensor Computation Runtimes" (PAPERS.md) makes the
case that the hot path must stay inside the compiled graph; every host
sync (device_get, np.asarray on a device array, .block_until_ready) or
Python-interpreted row loop is a graph break that turns a multi-GB/s scan
into a per-row interpreter crawl.  These hazards are syntactically
recognizable, so they are linted — sites that are genuinely host
boundaries (result readback after the device program finishes) live in
baseline.json with a justification.

Rules
-----
host-sync        np.asarray / numpy.asarray / jax.device_get calls and
                 .block_until_ready() method calls in engine code —
                 gated on device-array PROVENANCE: (a) a module that
                 never imports jax cannot hold a device array (device
                 values are only created by jax APIs, and the engine
                 contract keeps Chunk columns host-resident), so its
                 np.asarray calls are host normalizations, not syncs;
                 (b) np.asarray applied to the direct result of a
                 jit-bound callable (``out = jitted(...)`` then
                 ``np.asarray(out)``) is the DESIGNED readback boundary
                 — the program completed, the sync is the single
                 intended result transfer.  Both used to need baseline
                 allowlist entries.
tracer-coercion  float()/int()/bool() on a value inside a jitted function
                 (concretizes a tracer -> recompile or TracerError).
row-loop         for-loops / comprehensions iterating chunk rows
                 (`.to_pylist()`, `.iter_rows()`, `range(.. .num_rows ..)`)
                 — per-row Python in engine code.
time-in-jit      time.time()/perf_counter()/datetime.now() inside a jitted
                 function (bakes a constant at trace time, silently wrong).
rng-in-jit       `random.*` / `np.random.*` inside a jitted function (host
                 RNG at trace time = constant folded; use jax.random).
static-unhashable  jax.jit static_argnums/static_argnames whose call sites
                 pass list/dict/set literals (unhashable -> TypeError at
                 call time, or a recompile per identity if wrapped).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set

from . import Finding

ENGINE_DIRS = ("tidb_tpu/coord", "tidb_tpu/copr", "tidb_tpu/executor",
               "tidb_tpu/expr", "tidb_tpu/layout", "tidb_tpu/lifecycle",
               "tidb_tpu/mpp", "tidb_tpu/ops", "tidb_tpu/planner",
               "tidb_tpu/serving")

HOST_SYNC_DOTTED = {"np.asarray", "numpy.asarray", "jax.device_get"}
HOST_SYNC_METHODS = {"block_until_ready"}
TRACER_COERCIONS = {"float", "int", "bool"}
TIME_DOTTED = {"time.time", "time.perf_counter", "time.monotonic",
               "datetime.now", "datetime.datetime.now"}
ROW_ITER_METHODS = {"to_pylist", "iter_rows"}
ROW_COUNT_ATTRS = {"num_rows"}
#: factories whose return value IS a jitted callable: assignment from
#: one opens a readback-boundary name (`out = jitted(...)` then
#: `np.asarray(out)`).  `_demote_encoder` (layout/coldtier) memoizes
#: jax.jit closures per column class so demotions never retrace.
JIT_WRAPPERS = {"jax.jit", "jit", "_packed_jit", "_demote_encoder"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.device_get' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _imports_jax(tree: ast.Module) -> bool:
    """True when the module imports jax in any form.  Device arrays are
    created only by jax APIs; a module that never names jax can only
    hold host values (the engine contract keeps Chunk columns numpy),
    so host-sync hazards cannot occur there."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name == "jax" or a.name.startswith("jax.")
                   for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "jax" or mod.startswith("jax."):
                return True
    return False


_SCOPE_STOPS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


def _scan_boundary(node, visible: Set[str]) -> Set[str]:
    """Readback-boundary names bound in ONE scope's immediate body
    (nested defs excluded — they compute their own set with this one
    visible, matching closure capture): names bound to jitted callables
    (`jitted = jax.jit(fn)`) and names assigned from calling one
    (`out = jitted(*args)`) — the finished device program's output,
    whose np.asarray is the designed readback boundary.  Scoped per
    function so an unrelated `out` elsewhere is never whitelisted."""
    out: Set[str] = set()

    def walk(n):
        for child in ast.iter_child_nodes(n):
            if isinstance(child, _SCOPE_STOPS):
                continue
            if isinstance(child, ast.Assign) \
                    and isinstance(child.value, ast.Call):
                d = _dotted(child.value.func)
                if d in JIT_WRAPPERS or d in visible or d in out:
                    for tgt in child.targets:
                        if isinstance(tgt, ast.Name):
                            out.add(tgt.id)
            walk(child)

    walk(node)
    return out


def _jitted_names(tree: ast.Module) -> Set[str]:
    """Function names that get jitted in this module: decorated with a jit
    wrapper, or passed as the first argument to one (`jax.jit(fn, ...)`,
    `_packed_jit(fn)`) anywhere in the file."""
    jitted: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                d = _dotted(target)
                if d in JIT_WRAPPERS:
                    jitted.add(node.name)
                elif (isinstance(dec, ast.Call)
                      and _dotted(dec.func) in ("partial", "functools.partial")
                      and dec.args and _dotted(dec.args[0]) in JIT_WRAPPERS):
                    jitted.add(node.name)
        elif isinstance(node, ast.Call):
            if _dotted(node.func) in JIT_WRAPPERS and node.args:
                first = _dotted(node.args[0])
                if first is not None and "." not in first:
                    jitted.add(first)
    return jitted


class _PurityVisitor(ast.NodeVisitor):
    def __init__(self, relpath: str, jitted: Set[str],
                 has_jax: bool = True,
                 module_boundary: Optional[Set[str]] = None):
        self.relpath = relpath
        self.jitted = jitted
        self.has_jax = has_jax  # module can hold device arrays at all
        # readback-boundary names, one set per lexical scope (closures
        # see enclosing scopes' names; siblings never see each other's)
        self.boundary_stack: List[Set[str]] = [module_boundary or set()]
        self.scope: List[str] = []
        self.jit_depth = 0  # >0 while inside a jitted function body
        self.findings: List[Finding] = []

    # -- scope bookkeeping ------------------------------------------------
    def _visible_boundary(self) -> Set[str]:
        return set().union(*self.boundary_stack)

    def _enter(self, node, is_jitted: bool):
        self.scope.append(node.name)
        self.boundary_stack.append(
            _scan_boundary(node, self._visible_boundary()))
        if is_jitted:
            self.jit_depth += 1
        self.generic_visit(node)
        if is_jitted:
            self.jit_depth -= 1
        self.boundary_stack.pop()
        self.scope.pop()

    def visit_FunctionDef(self, node):
        self._enter(node, node.name in self.jitted)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.scope.append(node.name)
        self.boundary_stack.append(set())
        self.generic_visit(node)
        self.boundary_stack.pop()
        self.scope.pop()

    def _emit(self, rule: str, node: ast.AST, token: str, message: str):
        self.findings.append(Finding(
            rule=rule, path=self.relpath, line=node.lineno,
            scope=".".join(self.scope), token=token, message=message))

    # -- rules ------------------------------------------------------------
    def _is_readback_boundary(self, node: ast.Call) -> bool:
        """np.asarray on the direct result of a jit-bound callable: the
        designed single readback after the program completed.  Names
        resolve through the lexical boundary-scope stack."""
        if not node.args:
            return False
        visible = self._visible_boundary()
        a = node.args[0]
        if isinstance(a, ast.Call) and _dotted(a.func) in visible:
            return True
        return isinstance(a, ast.Name) and a.id in visible

    def visit_Call(self, node: ast.Call):
        d = _dotted(node.func)
        if not self.has_jax:
            pass  # no jax import: no device arrays, no syncs possible
        elif d in HOST_SYNC_DOTTED:
            if not self._is_readback_boundary(node):
                self._emit("host-sync", node, d,
                           f"{d}() forces a device->host sync: the "
                           "host waits out a full device round trip")
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in HOST_SYNC_METHODS):
            self._emit("host-sync", node, f".{node.func.attr}",
                       f".{node.func.attr}() blocks the host on device "
                       "completion inside engine code")
        if self.jit_depth:
            if d in TRACER_COERCIONS and node.args:
                self._emit("tracer-coercion", node, f"{d}()",
                           f"{d}() on a value inside a jitted function "
                           "concretizes the tracer (TracerError or a "
                           "recompile per value)")
            elif d in TIME_DOTTED:
                self._emit("time-in-jit", node, d,
                           f"{d}() inside a jitted function is evaluated "
                           "once at trace time and baked in as a constant")
            elif d is not None and (d.startswith("np.random.")
                                    or d.startswith("numpy.random.")
                                    or d.startswith("random.")):
                self._emit("rng-in-jit", node, d,
                           f"{d}() inside a jitted function is host RNG "
                           "frozen at trace time; use jax.random with an "
                           "explicit key")
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in ROW_ITER_METHODS):
            self._emit(
                "row-loop", node, f".{node.func.attr}",
                f".{node.func.attr}() materializes rows into Python "
                "objects in engine code — per-row interpreter work on "
                "the hot path; stay on column arrays")
        self.generic_visit(node)

    def visit_For(self, node: ast.For):
        self._check_row_iter(node, node.iter)
        self.generic_visit(node)

    def _visit_comp(self, node):
        for gen in node.generators:
            self._check_row_iter(node, gen.iter)
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    def _check_row_iter(self, node, it: ast.AST):
        for sub in ast.walk(it):
            if (isinstance(sub, ast.Call)
                    and _dotted(sub.func) == "range"
                    and any(isinstance(a, ast.Attribute)
                            and a.attr in ROW_COUNT_ATTRS
                            for arg in sub.args
                            for a in ast.walk(arg))):
                self._emit(
                    "row-loop", node, "range(num_rows)",
                    "Python loop over per-row range(.num_rows) in "
                    "engine code; vectorize over column arrays")
                return


def _static_spec(keywords):
    nums, names = (), ()
    for kw in keywords:
        if kw.arg == "static_argnums":
            try:
                v = ast.literal_eval(kw.value)
                nums = tuple(v) if isinstance(v, (tuple, list)) else (v,)
            except (ValueError, SyntaxError):
                pass
        elif kw.arg == "static_argnames":
            try:
                v = ast.literal_eval(kw.value)
                names = tuple(v) if isinstance(v, (tuple, list)) else (v,)
            except (ValueError, SyntaxError):
                pass
    return nums, names


def _lint_static_args(tree: ast.Module, relpath: str,
                      findings: List[Finding]):
    """jax.jit static args fed unhashable literals.  The spec attaches to
    the name the JITTED callable is bound to — the Assign target of
    `g = jax.jit(f, static_argnums=...)` or the def name for decorator
    forms — because calling the unjitted original with a list is legal;
    only the jitted binding raises at call time."""
    # jitted binding name -> (static positions, static names)
    specs = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _dotted(node.value.func) in JIT_WRAPPERS):
            nums, names = _static_spec(node.value.keywords)
            if nums or names:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        specs[tgt.id] = (nums, names)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                d = _dotted(dec.func)
                if d not in JIT_WRAPPERS and not (
                        d in ("partial", "functools.partial") and dec.args
                        and _dotted(dec.args[0]) in JIT_WRAPPERS):
                    continue
                nums, names = _static_spec(dec.keywords)
                if nums or names:
                    specs[node.name] = (nums, names)
    if not specs:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        if fn not in specs:
            continue
        nums, names = specs[fn]
        bad = []
        for i, arg in enumerate(node.args):
            if i in nums and isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                bad.append(f"arg {i}")
        for kw in node.keywords:
            if kw.arg in names and isinstance(
                    kw.value, (ast.List, ast.Dict, ast.Set)):
                bad.append(f"arg {kw.arg!r}")
        if bad:
            findings.append(Finding(
                rule="static-unhashable", path=relpath, line=node.lineno,
                scope="", token=fn,
                message=(f"{fn}() is jitted with static args but "
                         f"{', '.join(bad)} passes an unhashable "
                         "list/dict/set literal — TypeError at call time; "
                         "pass a tuple")))


def lint_source(src: str, relpath: str) -> List[Finding]:
    """Lint one module's source text (also the negative-test entry)."""
    tree = ast.parse(src)
    visitor = _PurityVisitor(relpath, _jitted_names(tree),
                             has_jax=_imports_jax(tree),
                             module_boundary=_scan_boundary(tree, set()))
    visitor.visit(tree)
    _lint_static_args(tree, relpath, visitor.findings)
    return visitor.findings


def lint_tree(repo_root: str,
              dirs: tuple = ENGINE_DIRS) -> List[Finding]:
    findings: List[Finding] = []
    for d in dirs:
        absdir = os.path.join(repo_root, d)
        if not os.path.isdir(absdir):
            continue
        for base, _subdirs, files in sorted(os.walk(absdir)):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                p = os.path.join(base, fn)
                rel = os.path.relpath(p, repo_root)
                with open(p, "r", encoding="utf-8") as f:
                    findings += lint_source(f.read(), rel)
    return findings
