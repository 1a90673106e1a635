"""AST lint for the serving stack: host-sync, assert, jit, and Pallas rules.

Pure ``ast``/stdlib — no jax import — so the CI gate runs in milliseconds
and needs no accelerator stack.  Run via ``python -m repro.analysis``.

Rule catalog (ids are what suppressions and the baseline reference):

* ``host-sync`` — a host-device synchronizing call (``np.asarray`` /
  ``np.array`` on device values, ``.item()``, ``.block_until_ready()``,
  ``jax.device_get``) in a function reachable from the engine's hot
  plan/launch/commit path.  Every step gets exactly ONE sync (committing
  the sampled tokens); anything else serializes host against device and
  kills the async loop's overlap.  Host-sync callables passed by reference
  (e.g. into an executor) are flagged too.
* ``bare-assert`` — an ``assert`` statement in library code (``src/``).
  Asserts vanish under ``python -O``; invariants must raise typed
  exceptions (``BlockPoolError`` / ``ValueError``), the PR-4 allocator
  precedent.
* ``jit-static-unhashable`` — a ``static_argnames`` parameter of a jitted
  function with an unhashable (list/dict/set) default, or an unhashable
  literal passed for one at a call site: jit would raise at call time, or
  worse, retrace per call once "fixed" with a tuple-of-varying-contents.
* ``jit-traced-control-flow`` — Python ``if``/``while`` on a *non-static*
  parameter inside a directly-jitted function: either a tracer error, or —
  for call-site Python scalars — a silent retrace per distinct value.
* ``pallas-arity`` — ``pallas_call`` plumbing mismatches: in_specs (+
  scalar-prefetch operands) vs call operand count, out_specs vs out_shape,
  ``input_output_aliases`` indices out of range.
* ``pallas-alias`` — an out_shape entry aliasing a whole input buffer
  (``X.shape`` of a kernel parameter, the in-place scatter pattern) that is
  NOT covered by ``input_output_aliases``: XLA would materialize a full
  copy of the pool every step.
* ``pallas-align`` — a literal BlockSpec block dimension misaligned with
  the TPU tile: last dim must be 1 or a multiple of 128 (lane), second-to-
  last a multiple of 8 (fp32 sublane) — or equal to the array's dim.  A
  literal 1 there is fine only where the array's dim is 1, which the lint
  reads off the index map: a lambda that walks that axis (returns anything
  but the literal 0 there) means the array's dim exceeds 1 and is flagged.
  The lint cannot see array shapes; the compile tests
  (tests/test_chip_compile.py) are the real guard.
* ``pallas-grid-div`` — a grid extent computed with floor division ``//``
  instead of ``pl.cdiv``: silently drops the ragged tail unless the
  divisor provably divides (suppress with a justification where it does).
* ``kernel-ref-parity`` — every public ``*_kernel`` in a
  ``kernels/<pkg>/kernel.py`` must have a ``*_ref`` in the sibling
  ``ref.py`` whose parameter names are an ordered subsequence of the
  kernel's (tiling/interpret knobs may be kernel-only): the parity tests
  assume the two are call-compatible.
* ``telemetry-alloc`` — an allocating argument (container literal,
  comprehension, f-string, or a list/dict/set/tuple/sorted call) passed
  to a telemetry call — a method on a ``tracer`` / ``recorder`` /
  ``metrics`` receiver — in a function reachable from the hot
  plan/launch/commit path.  Telemetry on the hot path must pass scalars
  the instrumented code already holds (O(1) per event); building
  containers per token/step turns "always-on-cheap" into allocation
  pressure.

Suppression: ``# lint: allow(rule-id)`` (optionally with a reason after
the closing paren) on the offending line or the line directly above.

Baseline: ``analysis/baseline.json`` grandfathers pre-existing violations
by ``(rule, path, symbol)`` count — line-number independent, so unrelated
edits don't churn it.  New violations beyond the baselined count fail the
gate; regenerate with ``python -m repro.analysis --write-baseline``.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "host-sync": "host-device sync reachable from the hot serving path",
    "bare-assert": "bare assert in library code (vanishes under python -O)",
    "jit-static-unhashable": "unhashable value for a static jit argument",
    "jit-traced-control-flow": "Python control flow on a traced jit param",
    "pallas-arity": "pallas_call spec/operand/alias arity mismatch",
    "pallas-alias": "scatter output not covered by input_output_aliases",
    "pallas-align": "literal BlockSpec dim misaligned with the TPU tile",
    "pallas-grid-div": "grid extent uses // instead of pl.cdiv",
    "kernel-ref-parity": "kernel.py/ref.py signature mismatch",
    "telemetry-alloc": "allocating argument to a hot-path telemetry call",
}

# the engine's hot path: one step = plan -> launch -> commit (plan_spec is
# the speculative variant), plus the async loop that drives them
HOT_ROOTS = {("Engine", "step"), ("Engine", "plan_step"),
             ("Engine", "plan_spec"), ("Engine", "launch_step"),
             ("Engine", "commit_step"), ("AsyncEngine", "_loop")}

# packages whose functions participate in hot-path reachability (the hot
# path never leaves host-side bookkeeping code; jitted bodies are traced,
# where a host sync would be a tracer error, not a silent stall)
HOT_PACKAGES = ("serving", "analysis")

NUMPY_SYNC_FUNCS = {"asarray", "array"}
SYNC_METHODS = {"item", "block_until_ready"}

# receivers whose method calls count as telemetry, and builtins whose call
# as a telemetry argument allocates a container per event
TELEMETRY_RECEIVERS = {"tracer", "recorder", "metrics"}
ALLOC_BUILTINS = {"list", "dict", "set", "tuple", "sorted"}

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([^)]*)\)")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, e.g. src/repro/serving/engine.py
    line: int
    symbol: str        # enclosing Class.func / func / <module>
    message: str

    @property
    def baseline_key(self) -> str:
        return f"{self.rule}::{self.path}::{self.symbol}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.symbol}: " \
               f"{self.message}"


@dataclasses.dataclass
class FuncInfo:
    module: "ModuleInfo"
    node: ast.AST                  # FunctionDef / AsyncFunctionDef
    name: str
    cls: Optional[str]

    @property
    def qualname(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclasses.dataclass
class ModuleInfo:
    path: pathlib.Path
    rel: str
    tree: ast.Module
    lines: List[str]
    numpy_names: Set[str] = dataclasses.field(default_factory=set)
    jax_names: Set[str] = dataclasses.field(default_factory=set)
    functions: List[FuncInfo] = dataclasses.field(default_factory=list)


def _collect_module(path: pathlib.Path, rel: str) -> Optional[ModuleInfo]:
    try:
        src = path.read_text()
        tree = ast.parse(src)
    except (OSError, SyntaxError):
        return None
    mod = ModuleInfo(path=path, rel=rel, tree=tree, lines=src.splitlines())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                top = a.name.split(".")[0]
                bound = a.asname or top
                if top == "numpy":
                    mod.numpy_names.add(bound)
                elif a.name == "jax":
                    mod.jax_names.add(bound)
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] == "numpy":
                for a in node.names:
                    if a.name in NUMPY_SYNC_FUNCS:
                        mod.numpy_names.add("")   # bare-name from-import
    # index top-level functions and class methods (nested defs are scanned
    # as part of their parent's body, not resolved as call targets)
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod.functions.append(FuncInfo(mod, node, node.name, None))
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    mod.functions.append(
                        FuncInfo(mod, sub, sub.name, node.name))
    return mod


def _walks_axis(spec: ast.Call, pos: int) -> bool:
    """Whether a BlockSpec's lambda index map returns anything but the
    literal 0 at block position ``pos`` (the kernel then steps through that
    axis, so the array's dim there exceeds the block's).  Named index maps
    are not resolved and count as not walking."""
    fn = spec.args[1] if len(spec.args) > 1 else None
    if not (isinstance(fn, ast.Lambda) and isinstance(fn.body, ast.Tuple)):
        return False
    elts = fn.body.elts
    if len(elts) < abs(pos):
        return False
    e = elts[pos]
    return not (isinstance(e, ast.Constant) and e.value == 0)


class Linter:
    """One lint run over a source tree (default: the repro package that
    contains this file)."""

    def __init__(self, src_root: Optional[pathlib.Path] = None):
        if src_root is None:
            src_root = pathlib.Path(__file__).resolve().parents[1]
        self.src_root = pathlib.Path(src_root)
        # repo-relative display prefix: .../repo/src/repro -> src/repro
        try:
            self.rel_base = self.src_root.relative_to(
                self.src_root.parents[1])
        except (IndexError, ValueError):
            self.rel_base = pathlib.Path(self.src_root.name)
        self.modules: List[ModuleInfo] = []
        for p in sorted(self.src_root.rglob("*.py")):
            mod = _collect_module(p, str(self.rel_base /
                                         p.relative_to(self.src_root)))
            if mod is not None:
                self.modules.append(mod)
        self.findings: List[Finding] = []

    # -- shared helpers --------------------------------------------------------

    def _emit(self, rule: str, mod: ModuleInfo, node: ast.AST, symbol: str,
              message: str) -> None:
        self.findings.append(Finding(rule=rule, path=mod.rel,
                                     line=getattr(node, "lineno", 0),
                                     symbol=symbol, message=message))

    @staticmethod
    def _enclosing(mod: ModuleInfo, node: ast.AST) -> str:
        """Qualname of the innermost indexed function containing ``node``
        (by line span), or <module>."""
        line = getattr(node, "lineno", 0)
        best, best_span = "<module>", None
        for fn in mod.functions:
            lo = fn.node.lineno
            hi = getattr(fn.node, "end_lineno", lo)
            if lo <= line <= hi and (best_span is None or hi - lo < best_span):
                best, best_span = fn.qualname, hi - lo
        return best

    # -- rule: bare-assert -----------------------------------------------------

    def check_asserts(self) -> None:
        for mod in self.modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Assert):
                    self._emit(
                        "bare-assert", mod, node, self._enclosing(mod, node),
                        "assert vanishes under python -O; raise a typed "
                        "exception (BlockPoolError / ValueError) instead")

    # -- rule: host-sync (call-graph reachability) -----------------------------

    def _sync_sites(self, mod: ModuleInfo, root: ast.AST
                    ) -> List[Tuple[ast.AST, str]]:
        """Host-sync expressions inside ``root``: sync calls, and sync
        callables passed by reference (e.g. into run_in_executor)."""
        call_funcs = {id(n.func) for n in ast.walk(root)
                      if isinstance(n, ast.Call)}
        sites: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(root):
            if isinstance(node, ast.Attribute):
                base = node.value
                is_np = (isinstance(base, ast.Name)
                         and base.id in mod.numpy_names)
                is_jax = (isinstance(base, ast.Name)
                          and base.id in mod.jax_names)
                label = None
                if is_np and node.attr in NUMPY_SYNC_FUNCS:
                    label = f"np.{node.attr}"
                elif is_jax and node.attr == "device_get":
                    label = "jax.device_get"
                elif node.attr in SYNC_METHODS and id(node) in call_funcs:
                    label = f".{node.attr}()"
                if label is None:
                    continue
                if id(node) in call_funcs:
                    sites.append((node, f"{label} call"))
                else:
                    sites.append((node, f"{label} passed by reference"))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    "" in mod.numpy_names and \
                    node.func.id in NUMPY_SYNC_FUNCS:
                sites.append((node, f"{node.func.id} call"))
        return sites

    def _hot_reachable(self) -> List[FuncInfo]:
        """Functions reachable from HOT_ROOTS by bare-name call resolution
        over the HOT_PACKAGES modules (shared by the host-sync and
        telemetry-alloc rules)."""
        hot = [m for m in self.modules
               if any(f"/{pkg}/" in m.rel.replace("\\", "/")
                      for pkg in HOT_PACKAGES)]
        by_name: Dict[str, List[FuncInfo]] = {}
        for mod in hot:
            for fn in mod.functions:
                by_name.setdefault(fn.name, []).append(fn)

        def edges(fn: FuncInfo) -> List[FuncInfo]:
            out: List[FuncInfo] = []
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Name):
                    out.extend(by_name.get(node.func.id, []))
                elif isinstance(node.func, ast.Attribute):
                    out.extend(by_name.get(node.func.attr, []))
            return out

        roots = [fn for mod in hot for fn in mod.functions
                 if (fn.cls, fn.name) in HOT_ROOTS]
        seen: Set[Tuple[str, str]] = set()
        stack = list(roots)
        reached: List[FuncInfo] = []
        while stack:
            fn = stack.pop()
            key = (fn.module.rel, fn.qualname)
            if key in seen:
                continue
            seen.add(key)
            reached.append(fn)
            stack.extend(edges(fn))
        return reached

    def check_host_sync(self) -> None:
        for fn in self._hot_reachable():
            for node, what in self._sync_sites(fn.module, fn.node):
                self._emit(
                    "host-sync", fn.module, node, fn.qualname,
                    f"{what} is reachable from the hot plan/launch/commit "
                    "path; each step budgets exactly one device sync")

    # -- rule: telemetry-alloc -------------------------------------------------

    @staticmethod
    def _allocating_arg(node: ast.AST) -> Optional[str]:
        """Why ``node`` allocates a container per call, or None."""
        if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.Tuple)):
            return f"{type(node).__name__.lower()} literal"
        if isinstance(node, (ast.ListComp, ast.DictComp, ast.SetComp,
                             ast.GeneratorExp)):
            return "comprehension"
        if isinstance(node, ast.JoinedStr):
            return "f-string"
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ALLOC_BUILTINS:
            return f"{node.func.id}() call"
        return None

    def check_telemetry_alloc(self) -> None:
        """Telemetry calls on the hot path must pass scalars the caller
        already holds: flag container-building arguments to any method
        call on a tracer / recorder / metrics receiver in a hot-reachable
        function."""
        for fn in self._hot_reachable():
            for node in ast.walk(fn.node):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                recv = node.func.value
                recv_name = None
                if isinstance(recv, ast.Name):
                    recv_name = recv.id
                elif isinstance(recv, ast.Attribute):
                    recv_name = recv.attr
                if recv_name not in TELEMETRY_RECEIVERS:
                    continue
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    why = self._allocating_arg(arg)
                    if why is not None:
                        self._emit(
                            "telemetry-alloc", fn.module, arg, fn.qualname,
                            f"{why} passed to {recv_name}.{node.func.attr}() "
                            "on the hot path — telemetry must record "
                            "scalars the caller already holds")

    # -- rules: jit hygiene ----------------------------------------------------

    @staticmethod
    def _static_names(call: ast.Call) -> Optional[Set[str]]:
        """static_argnames from a jax.jit / functools.partial(jax.jit, ...)
        call node; None when the call carries none."""
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    return {v.value}
                if isinstance(v, (ast.Tuple, ast.List)):
                    return {e.value for e in v.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)}
        return None

    @staticmethod
    def _is_jax_jit(node: ast.AST, mod: ModuleInfo) -> bool:
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id in mod.jax_names)

    def _jitted_defs(self, mod: ModuleInfo
                     ) -> List[Tuple[FuncInfo, Set[str]]]:
        """Directly-jitted defs in a module with their static-name sets:
        @jax.jit and @functools.partial(jax.jit, static_argnames=...)."""
        out = []
        for fn in mod.functions:
            for dec in fn.node.decorator_list:
                if self._is_jax_jit(dec, mod):
                    out.append((fn, set()))
                elif isinstance(dec, ast.Call):
                    if self._is_jax_jit(dec.func, mod):
                        out.append((fn, self._static_names(dec) or set()))
                    elif dec.args and self._is_jax_jit(dec.args[0], mod) and \
                            isinstance(dec.func, ast.Attribute) and \
                            dec.func.attr == "partial":
                        out.append((fn, self._static_names(dec) or set()))
        return out

    @staticmethod
    def _unhashable_literal(node: ast.AST) -> bool:
        return isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                 ast.DictComp, ast.SetComp))

    def check_jit_hygiene(self) -> None:
        jitted_statics: Dict[str, Set[str]] = {}
        jitted_mods: List[Tuple[ModuleInfo, FuncInfo, Set[str]]] = []
        for mod in self.modules:
            for fn, statics in self._jitted_defs(mod):
                jitted_statics[fn.name] = statics
                jitted_mods.append((mod, fn, statics))

        for mod, fn, statics in jitted_mods:
            args = fn.node.args
            params = [a.arg for a in args.posonlyargs + args.args +
                      args.kwonlyargs]
            # unhashable defaults on static params
            defaults = dict(zip(params[len(params) - len(args.defaults):],
                                args.defaults))
            for name in statics:
                d = defaults.get(name)
                if d is not None and self._unhashable_literal(d):
                    self._emit(
                        "jit-static-unhashable", mod, d, fn.qualname,
                        f"static arg {name!r} defaults to an unhashable "
                        "literal; jit hashes statics per call")
            # Python control flow on traced (non-static) params
            traced = {p for p in params if p not in statics and p != "self"}
            for node in ast.walk(fn.node):
                if isinstance(node, (ast.If, ast.While)):
                    used = {n.id for n in ast.walk(node.test)
                            if isinstance(n, ast.Name)}
                    bad = sorted(used & traced)
                    if bad:
                        self._emit(
                            "jit-traced-control-flow", mod, node, fn.qualname,
                            f"Python {type(node).__name__.lower()} on traced "
                            f"param(s) {', '.join(bad)}: a tracer error, or "
                            "a retrace per distinct call-site value — mark "
                            "static or use lax.cond/select")

        # unhashable literals passed for static params at call sites
        for mod in self.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = None
                if isinstance(node.func, ast.Name):
                    name = node.func.id
                elif isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                statics = jitted_statics.get(name)
                if not statics:
                    continue
                for kw in node.keywords:
                    if kw.arg in statics and \
                            self._unhashable_literal(kw.value):
                        self._emit(
                            "jit-static-unhashable", mod, kw.value,
                            self._enclosing(mod, node),
                            f"unhashable literal for static arg "
                            f"{kw.arg!r} of jitted {name}()")

    # -- rules: Pallas kernels -------------------------------------------------

    @staticmethod
    def _resolve(name_node: ast.AST, fn_node: ast.AST) -> ast.AST:
        """Resolve a Name to its (last) assignment value within the
        enclosing function, else return the node unchanged."""
        if not isinstance(name_node, ast.Name):
            return name_node
        val = name_node
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == name_node.id:
                val = node.value
        return val

    @staticmethod
    def _as_list(node: Optional[ast.AST]) -> Optional[List[ast.AST]]:
        if isinstance(node, (ast.List, ast.Tuple)):
            return list(node.elts)
        return None

    def check_pallas(self) -> None:
        for mod in self.modules:
            if "/kernels/" not in mod.rel.replace("\\", "/") or \
                    not mod.rel.endswith("kernel.py"):
                continue
            for fn in mod.functions:
                self._check_pallas_fn(mod, fn)

    def _check_pallas_fn(self, mod: ModuleInfo, fn: FuncInfo) -> None:
        params = {a.arg for a in fn.node.args.args}
        for node in ast.walk(fn.node):
            # the pattern: pl.pallas_call(kernel, **kw)(operand, ...)
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Call)
                    and isinstance(node.func.func, ast.Attribute)
                    and node.func.func.attr == "pallas_call"):
                continue
            operands = node.args
            pc = node.func
            kw = {k.arg: k.value for k in pc.keywords if k.arg}
            prefetch = 0
            in_specs = kw.get("in_specs")
            out_specs = kw.get("out_specs")
            grids: List[ast.AST] = []
            if "grid" in kw:
                grids.append(self._resolve(kw["grid"], fn.node))
            gs = kw.get("grid_spec")
            gs = self._resolve(gs, fn.node) if gs is not None else None
            if isinstance(gs, ast.Call):
                gkw = {k.arg: k.value for k in gs.keywords if k.arg}
                if isinstance(gkw.get("num_scalar_prefetch"), ast.Constant):
                    prefetch = gkw["num_scalar_prefetch"].value
                in_specs = in_specs or gkw.get("in_specs")
                out_specs = out_specs or gkw.get("out_specs")
                if "grid" in gkw:
                    grids.append(self._resolve(gkw["grid"], fn.node))
            in_list = self._as_list(in_specs)
            out_list = self._as_list(out_specs)
            shp = kw.get("out_shape")
            shp_list = self._as_list(shp)
            aliases = kw.get("input_output_aliases")
            alias_pairs: List[Tuple[int, int]] = []
            if isinstance(aliases, ast.Dict):
                for k, v in zip(aliases.keys, aliases.values):
                    if isinstance(k, ast.Constant) and \
                            isinstance(v, ast.Constant):
                        alias_pairs.append((k.value, v.value))

            # arity: specs vs operands vs out_shape vs alias index ranges
            if in_list is not None and operands and \
                    len(in_list) + prefetch != len(operands):
                self._emit("pallas-arity", mod, node, fn.qualname,
                           f"{len(in_list)} in_specs + {prefetch} scalar-"
                           f"prefetch operands != {len(operands)} call "
                           "operands")
            if out_list is not None and shp_list is not None and \
                    len(out_list) != len(shp_list):
                self._emit("pallas-arity", mod, node, fn.qualname,
                           f"{len(out_list)} out_specs != {len(shp_list)} "
                           "out_shape entries")
            n_out = (len(shp_list) if shp_list is not None
                     else (1 if shp is not None else None))
            for k, v in alias_pairs:
                if operands and not 0 <= k < len(operands):
                    self._emit("pallas-arity", mod, node, fn.qualname,
                               f"input_output_aliases key {k} out of range "
                               f"for {len(operands)} operands")
                if n_out is not None and not 0 <= v < n_out:
                    self._emit("pallas-arity", mod, node, fn.qualname,
                               f"input_output_aliases value {v} out of "
                               f"range for {n_out} outputs")

            # alias coverage: out_shape entries that mirror a whole input
            # parameter's shape are in-place scatters and must be aliased
            if shp_list is not None:
                aliased_outs = {v for _, v in alias_pairs}
                for i, entry in enumerate(shp_list):
                    if not (isinstance(entry, ast.Call) and entry.args):
                        continue
                    a0 = entry.args[0]
                    if isinstance(a0, ast.Attribute) and \
                            a0.attr == "shape" and \
                            isinstance(a0.value, ast.Name) and \
                            a0.value.id in params and i not in aliased_outs:
                        self._emit(
                            "pallas-alias", mod, entry, fn.qualname,
                            f"out_shape[{i}] mirrors {a0.value.id}.shape "
                            "(in-place scatter output) but is not in "
                            "input_output_aliases — XLA will copy the "
                            "whole buffer every call")

            # BlockSpec literal-dim alignment (TPU: lane=128, sublane=8)
            for spec in (in_list or []) + (out_list or []) + \
                    ([out_specs] if out_list is None and
                     out_specs is not None else []):
                if not (isinstance(spec, ast.Call) and spec.args and
                        isinstance(spec.args[0], ast.Tuple)):
                    continue
                dims = spec.args[0].elts
                for pos, want, label in ((-1, 128, "last (lane)"),
                                         (-2, 8, "second-to-last (sublane)")):
                    if len(dims) < abs(pos):
                        continue
                    d = dims[pos]
                    if isinstance(d, ast.Constant) and \
                            isinstance(d.value, int) and \
                            d.value != 1 and d.value % want != 0:
                        self._emit(
                            "pallas-align", mod, spec, fn.qualname,
                            f"literal {label} block dim {d.value} is "
                            f"neither 1 nor a multiple of {want}")
                    elif pos == -2 and isinstance(d, ast.Constant) and \
                            d.value == 1 and _walks_axis(spec, pos):
                        self._emit(
                            "pallas-align", mod, spec, fn.qualname,
                            "second-to-last (sublane) block dim 1 over an "
                            "axis the index map walks: the array's dim "
                            "exceeds 1, so the block is neither a multiple "
                            "of 8 nor the whole dim")

            # grid extents built with // drop the ragged tail
            for g in grids:
                for sub in ast.walk(g):
                    if isinstance(sub, ast.BinOp) and \
                            isinstance(sub.op, ast.FloorDiv):
                        self._emit(
                            "pallas-grid-div", mod, sub, fn.qualname,
                            "grid extent uses // — a non-dividing extent "
                            "silently skips the tail; use pl.cdiv (or "
                            "suppress with proof the divisor divides)")

    # -- rule: kernel/ref parity -----------------------------------------------

    def check_kernel_ref_parity(self) -> None:
        kernels: Dict[str, ModuleInfo] = {}
        refs: Dict[str, ModuleInfo] = {}
        for mod in self.modules:
            rel = mod.rel.replace("\\", "/")
            if "/kernels/" not in rel:
                continue
            pkg = rel.rsplit("/", 2)[-2]
            if rel.endswith("/kernel.py"):
                kernels[pkg] = mod
            elif rel.endswith("/ref.py"):
                refs[pkg] = mod
        for pkg, kmod in kernels.items():
            rmod = refs.get(pkg)
            for fn in kmod.functions:
                if fn.cls or fn.name.startswith("_") or \
                        not fn.name.endswith("_kernel"):
                    continue
                ref_name = fn.name[:-len("_kernel")] + "_ref"
                rfn = None
                if rmod is not None:
                    rfn = next((f for f in rmod.functions
                                if f.name == ref_name and f.cls is None),
                               None)
                if rfn is None:
                    self._emit(
                        "kernel-ref-parity", kmod, fn.node, fn.qualname,
                        f"no {ref_name}() in kernels/{pkg}/ref.py — every "
                        "public kernel needs an interpretable reference")
                    continue
                kp = [a.arg for a in fn.node.args.args]
                rp = [a.arg for a in rfn.node.args.args]
                it = iter(kp)
                if not all(any(p == q for q in it) for p in rp):
                    self._emit(
                        "kernel-ref-parity", kmod, fn.node, fn.qualname,
                        f"{ref_name}({', '.join(rp)}) is not an ordered "
                        f"subsequence of {fn.name}({', '.join(kp)}) — the "
                        "parity tests assume call compatibility")

    # -- driver ----------------------------------------------------------------

    def run(self) -> List[Finding]:
        self.findings = []
        self.check_asserts()
        self.check_host_sync()
        self.check_telemetry_alloc()
        self.check_jit_hygiene()
        self.check_pallas()
        self.check_kernel_ref_parity()
        self.findings.sort(key=lambda f: (f.path, f.line, f.rule))
        return self.findings

    # -- suppression / baseline ------------------------------------------------

    def is_suppressed(self, f: Finding) -> bool:
        mod = next((m for m in self.modules if m.rel == f.path), None)
        if mod is None or f.line < 1:
            return False
        for ln in (f.line, f.line - 1):
            if 1 <= ln <= len(mod.lines):
                m = _ALLOW_RE.search(mod.lines[ln - 1])
                if m and f.rule in [s.strip() for s in
                                    m.group(1).split(",")]:
                    return True
        return False


@dataclasses.dataclass
class LintResult:
    active: List[Finding]
    suppressed: List[Finding]
    baselined: List[Finding]

    @property
    def ok(self) -> bool:
        return not self.active


def load_baseline(path: pathlib.Path) -> Dict[str, int]:
    if not path.exists():
        return {}
    data = json.loads(path.read_text())
    return {str(k): int(v) for k, v in data.get("entries", {}).items()}


def default_baseline_path() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent / "baseline.json"


def run_lint(src_root: Optional[pathlib.Path] = None,
             baseline_path: Optional[pathlib.Path] = None) -> LintResult:
    linter = Linter(src_root)
    findings = linter.run()
    baseline = load_baseline(baseline_path or default_baseline_path())
    remaining = dict(baseline)
    active: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    for f in findings:
        if linter.is_suppressed(f):
            suppressed.append(f)
        elif remaining.get(f.baseline_key, 0) > 0:
            remaining[f.baseline_key] -= 1
            baselined.append(f)
        else:
            active.append(f)
    return LintResult(active=active, suppressed=suppressed,
                      baselined=baselined)


def write_baseline(path: Optional[pathlib.Path] = None,
                   src_root: Optional[pathlib.Path] = None) -> pathlib.Path:
    """Grandfather every current unsuppressed finding: the gate then fails
    only on NEW violations.  Checked in so CI and local runs agree."""
    path = path or default_baseline_path()
    linter = Linter(src_root)
    entries: Dict[str, int] = {}
    for f in linter.run():
        if not linter.is_suppressed(f):
            entries[f.baseline_key] = entries.get(f.baseline_key, 0) + 1
    path.write_text(json.dumps(
        {"comment": "grandfathered lint findings by rule::path::symbol; "
                    "regenerate with python -m repro.analysis "
                    "--write-baseline",
         "entries": dict(sorted(entries.items()))}, indent=1) + "\n")
    return path
