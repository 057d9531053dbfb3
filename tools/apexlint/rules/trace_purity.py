"""APX001 — trace purity: no host effects reachable from traced code.

PR 2's "no-callback jaxpr" asserts protect two jitted functions; this
rule protects all of them. It builds an intra-package call graph and
walks reachability from every **traced root**:

- functions decorated with ``jax.jit`` (bare, or via
  ``functools.partial(jax.jit, ...)``),
- callables passed to ``jax.jit(...)`` / ``shard_map(...)`` /
  ``jax.lax.scan(...)`` / ``pl.pallas_call(...)`` (by name, ``self.``
  method, lambda, or through ``functools.partial``).

Any function reachable from a root may not perform a **host effect**:

- clock reads (``time.*`` — a ``perf_counter()`` inside traced code is
  constant-folded at trace time and stamps every step with the same
  value),
- bus/log output (``publish_event``/``structured_warning``/
  ``one_time_warning``/``print`` — fires once per *trace*, not per step,
  which is exactly the misleading telemetry PR 2 banned),
- file I/O (``open``),
- host syncs (``.item()`` — the decidable spelling of the
  ``.item()``/``float()``-on-traced-value class; bare ``float(x)`` is
  statically indistinguishable from legal trace-time coercion of static
  config and is not flagged),
- callback escapes (``io_callback``/``pure_callback``/
  ``jax.debug.print``/``jax.debug.callback`` — the "no-callback jaxpr"
  invariant itself),
- live-metrics mutations (``.record()``/``.observe()``/``.inc()`` — a
  monitor.export registry sample taken inside traced code lands once per
  trace, not per step; record around the jitted call).

The traversal stops at *sanctioned trace-time boundaries* — functions
whose whole purpose is host-side static resolution during trace
(:data:`BOUNDARY_FUNCS`, e.g. the autotuner's ``tuned_params``: it reads
the tune cache and publishes provenance events once per trace by
design). Resolution is static and conservative: bare names lexically,
``self.m`` within the class, ``mod.f``/from-imports across apex_tpu
modules; calls through values it cannot resolve (flax ``.apply``,
callables passed as arguments) are not followed.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import LintContext, Rule, SourceFile, Violation, register

# sanctioned trace-time host work: static geometry/config resolution that
# must run during trace and is documented to do so. Crossing one of these
# names ends the traversal — their internals are host code by design.
BOUNDARY_FUNCS = frozenset({
    "tuned_params",     # tune.api: cache lookup + autotune provenance
    "_pallas_ok",       # normalization: kernel-or-jnp route from the
    #                     static hidden size, announced once per process
})

EFFECT_NAME_CALLS = frozenset({
    "publish_event", "structured_warning", "one_time_warning",
    "deprecated_warning", "print", "open", "input",
    "io_callback", "pure_callback",
})
EFFECT_ATTR_CALLS = frozenset({"item", "io_callback", "pure_callback"})
# live-metrics mutation verbs (monitor.export registry: Counter.inc,
# Histogram.record/observe). Inside traced code these fire once per
# TRACE, not per step — the same silently-wrong-telemetry class as
# publish_event. ``.set`` is deliberately absent: ``x.at[i].set(v)`` is
# the jnp functional-update idiom all over legitimately traced code
# (its subscripted chain never resolves here, but the name must not
# invite the confusion either).
METRIC_ATTR_CALLS = frozenset({"record", "observe", "inc"})
TRACE_WRAPPERS = ("jit", "pallas_call", "shard_map")


def _attr_chain(node: ast.AST) -> List[str]:
    """``jax.lax.scan`` → ["jax", "lax", "scan"]; [] when not a plain
    dotted chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return []


def _unwrap_partial(node: ast.AST) -> ast.AST:
    """``functools.partial(f, ...)`` → ``f`` (recursively)."""
    while isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] == "partial" and node.args:
            node = node.args[0]
        else:
            break
    return node


def _is_trace_wrapper(func: ast.AST) -> Optional[str]:
    """'jit' / 'pallas_call' / 'shard_map' / 'scan' when ``func`` is a
    call target that traces its first callable argument."""
    chain = _attr_chain(func)
    if not chain:
        return None
    tail = chain[-1]
    if tail in TRACE_WRAPPERS:
        return tail
    if tail == "scan" and (len(chain) == 1 or chain[-2] == "lax"):
        return "scan"
    return None


class _FuncInfo:
    """One function/method/lambda node in the call graph."""

    def __init__(self, key: Tuple[str, ...], node: ast.AST, sf: SourceFile,
                 module: str, scope: Tuple[str, ...],
                 class_name: Optional[str]):
        self.key = key
        self.node = node
        self.sf = sf
        self.module = module
        self.scope = scope          # lexical scope path above this def
        self.class_name = class_name
        self.name = key[-1]
        self.is_root = False
        self.root_why = ""
        self.calls: List[Tuple] = []            # resolvable call refs
        self.effects: List[Tuple[int, str]] = []
        self.loads: Set[str] = set()            # bare names read in body

    @property
    def line(self) -> int:
        return getattr(self.node, "lineno", 0)


class _ModuleIndex:
    def __init__(self, module: str):
        self.module = module
        # bare alias → ("module", dotted) | ("from", module, original)
        self.imports: Dict[str, Tuple] = {}


class _Indexer:
    """Pass 1 over one module: register every function node, record its
    calls/effects/loads, note imports and traced-root sites."""

    def __init__(self, rule: "TracePurityRule", sf: SourceFile,
                 module: str):
        self.rule = rule
        self.sf = sf
        self.module = module
        self.idx = _ModuleIndex(module)
        self.lambda_count = 0

    # ---- top-level drive ------------------------------------------------
    def index(self, tree: ast.Module) -> None:
        # module-level statements form a synthetic scope: they can carry
        # roots (`step = jax.jit(fn)` at import time) but are not
        # themselves traced
        mod_info = _FuncInfo((self.module, "<module>"), tree, self.sf,
                             self.module, (), None)
        for stmt in tree.body:
            if isinstance(stmt, (ast.Import, ast.ImportFrom)):
                self._record_import(stmt)
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._index_func(stmt, (), None)
            elif isinstance(stmt, ast.ClassDef):
                self._index_class(stmt, ())
            else:
                self._scan_stmt(mod_info, stmt, set(), (), None,
                                effects=False)

    def _record_import(self, node) -> None:
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.asname:
                    self.idx.imports[alias.asname] = ("module", alias.name)
                else:
                    root = alias.name.split(".")[0]
                    self.idx.imports[root] = ("module", root)
            return
        if node.level:  # relative: resolve against this module's package
            parts = self.module.split(".")
            base = ".".join(parts[:len(parts) - node.level])
            mod = f"{base}.{node.module}" if node.module else base
        else:
            mod = node.module or ""
        for alias in node.names:
            name = alias.asname or alias.name
            # alias may be a function in `mod` or the submodule
            # `mod.name`; resolution tries both at lookup time
            self.idx.imports[name] = ("from", mod, alias.name)

    # ---- registration ---------------------------------------------------
    def _register(self, name: str, node: ast.AST, scope: Tuple[str, ...],
                  cls: Optional[str], parent_is_class: bool) -> _FuncInfo:
        key = (self.module,) + scope + (name,)
        info = _FuncInfo(key, node, self.sf, self.module, scope, cls)
        self.rule.funcs[key] = info
        self.rule.by_module_scope.setdefault(
            (self.module, scope), {})[name] = info
        if parent_is_class and cls is not None:
            self.rule.methods.setdefault(
                (self.module, cls), {})[name] = info
        return info

    def _index_class(self, node: ast.ClassDef,
                     scope: Tuple[str, ...]) -> None:
        inner = scope + (node.name,)
        for stmt in node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._index_func(stmt, inner, node.name,
                                 parent_is_class=True)
            elif isinstance(stmt, ast.ClassDef):
                self._index_class(stmt, inner)

    def _index_func(self, node, scope: Tuple[str, ...],
                    cls: Optional[str],
                    parent_is_class: bool = False) -> None:
        info = self._register(node.name, node, scope, cls, parent_is_class)
        for dec in node.decorator_list:
            chain = _attr_chain(_unwrap_partial(dec))
            if chain and chain[-1] == "jit":
                info.is_root = True
                info.root_why = "@jit"
        params = self._params(node)
        inner = scope + (node.name,)
        for stmt in node.body:
            self._index_nested_or_scan(info, stmt, params, inner, cls)

    def _index_lambda(self, node: ast.Lambda, scope: Tuple[str, ...],
                      cls: Optional[str]) -> _FuncInfo:
        self.lambda_count += 1
        name = f"<lambda:{node.lineno}:{self.lambda_count}>"
        info = self._register(name, node, scope, cls, False)
        self._scan_expr_tree(info, node.body, self._params(node),
                             scope + (name,), cls)
        return info

    def _index_nested_or_scan(self, info: _FuncInfo, stmt: ast.AST,
                              params: Set[str], scope: Tuple[str, ...],
                              cls: Optional[str]) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._index_func(stmt, scope, cls)
            return
        if isinstance(stmt, ast.ClassDef):
            self._index_class(stmt, scope)
            return
        self._scan_stmt(info, stmt, params, scope, cls, effects=True)

    @staticmethod
    def _params(node) -> Set[str]:
        a = node.args
        out = {arg.arg for arg in a.posonlyargs + a.args + a.kwonlyargs}
        if a.vararg:
            out.add(a.vararg.arg)
        if a.kwarg:
            out.add(a.kwarg.arg)
        out.discard("self")
        return out

    # ---- body scan ------------------------------------------------------
    def _scan_stmt(self, info: _FuncInfo, stmt: ast.AST, params: Set[str],
                   scope: Tuple[str, ...], cls: Optional[str],
                   effects: bool) -> None:
        """Scan one statement, descending into control flow but treating
        nested defs/lambdas as separate graph nodes."""
        if isinstance(stmt, (ast.Import, ast.ImportFrom)):
            # function-local imports (the repo's cycle-avoidance idiom)
            # merge into the module's table — resolution is name-based
            self._record_import(stmt)
        for node in ast.iter_child_nodes(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._index_func(node, scope, cls)
                continue
            if isinstance(node, ast.ClassDef):
                self._index_class(node, scope)
                continue
            if isinstance(node, ast.Lambda):
                self._index_lambda(node, scope, cls)
                continue
            self._scan_stmt(info, node, params, scope, cls, effects)
        if isinstance(stmt, ast.Name) and isinstance(stmt.ctx, ast.Load):
            info.loads.add(stmt.id)
        if isinstance(stmt, ast.Call):
            self._scan_call(info, stmt, params, scope, cls,
                            effects=effects)

    def _scan_expr_tree(self, info: _FuncInfo, expr: ast.AST,
                        params: Set[str], scope: Tuple[str, ...],
                        cls: Optional[str]) -> None:
        """Lambda bodies: scan the expression tree itself."""
        self._scan_stmt(info, expr, params, scope, cls, effects=True)
        if isinstance(expr, ast.Call):
            self._scan_call(info, expr, params, scope, cls, effects=True)
        if isinstance(expr, ast.Name) and isinstance(expr.ctx, ast.Load):
            info.loads.add(expr.id)

    def _scan_call(self, info: _FuncInfo, node: ast.Call,
                   params: Set[str], scope: Tuple[str, ...],
                   cls: Optional[str], effects: bool) -> None:
        f = node.func
        chain = _attr_chain(f)
        wrapper = _is_trace_wrapper(f)
        if wrapper and node.args:
            arg = _unwrap_partial(node.args[0])
            if isinstance(arg, ast.Lambda):
                target = self._find_lambda(arg)
            else:
                target = None
            self.rule.root_args.append(
                (self.module, scope, cls, arg, target, wrapper))
        if effects:
            self._scan_effects(info, node, chain, params)
        if isinstance(f, ast.Name):
            info.calls.append(("name", f.id))
        elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id == "self":
                info.calls.append(("self", f.attr))
            else:
                info.calls.append(("mod", f.value.id, f.attr))

    def _find_lambda(self, node: ast.Lambda) -> Optional[_FuncInfo]:
        for info in self.rule.funcs.values():
            if info.node is node:
                return info
        return None

    def _scan_effects(self, info: _FuncInfo, node: ast.Call,
                      chain: List[str], params: Set[str]) -> None:
        f = node.func
        if isinstance(f, ast.Name):
            if f.id in EFFECT_NAME_CALLS:
                info.effects.append(
                    (node.lineno, f"{f.id}() is a host effect"))
            # NOTE: float(x)/int(x) on a *traced* value is also a host
            # sync, but statically indistinguishable from the legal (and
            # pervasive) trace-time coercion of static python config
            # (eps, scale, dropout_p) — .item() below is the decidable
            # spelling of that bug class
            return
        if not chain:
            return
        if chain[0] == "time":
            info.effects.append(
                (node.lineno,
                 f"{'.'.join(chain)}() reads the host clock (frozen at "
                 f"trace time inside traced code)"))
        elif chain[-1] in EFFECT_ATTR_CALLS:
            info.effects.append(
                (node.lineno, f".{chain[-1]}() is a host effect"))
        elif chain[-1] in METRIC_ATTR_CALLS:
            info.effects.append(
                (node.lineno,
                 f".{chain[-1]}() mutates a host-side metrics sink "
                 f"(fires once per trace, not per step — record around "
                 f"the jitted call, never inside it)"))
        elif "debug" in chain[:-1] and \
                chain[-1] in ("print", "callback", "breakpoint"):
            info.effects.append(
                (node.lineno,
                 f"{'.'.join(chain)}() is a callback escape (the "
                 f"no-callback-jaxpr invariant)"))


@register
class TracePurityRule(Rule):
    RULE_ID = "APX001"
    SUMMARY = ("no host effects (clocks, events, prints, file I/O, "
               ".item(), callbacks) reachable from traced code")

    SCOPE = "apex_tpu"

    def check(self, ctx: LintContext) -> Iterator[Violation]:
        self.funcs: Dict[Tuple[str, ...], _FuncInfo] = {}
        self.by_module_scope: Dict[Tuple, Dict[str, _FuncInfo]] = {}
        self.methods: Dict[Tuple[str, str], Dict[str, _FuncInfo]] = {}
        # (module, scope, class, arg_expr, pre-resolved lambda, wrapper)
        self.root_args: List[Tuple] = []
        self.module_index: Dict[str, _ModuleIndex] = {}

        for sf in ctx.iter_files(under=self.SCOPE):
            if sf.tree is None:
                continue
            module = os.path.splitext(sf.path)[0].replace(os.sep, ".")
            if module.endswith(".__init__"):
                module = module[:-len(".__init__")]
            indexer = _Indexer(self, sf, module)
            indexer.index(sf.tree)
            self.module_index[module] = indexer.idx

        roots: List[_FuncInfo] = [i for i in self.funcs.values()
                                  if i.is_root]
        for module, scope, cls, arg, lam, wrapper in self.root_args:
            info = lam if lam is not None else \
                self._resolve_expr(module, scope, cls, arg)
            if info is not None and not info.is_root:
                info.is_root = True
                info.root_why = wrapper
                roots.append(info)

        # DFS reachability with provenance paths for the report
        seen: Dict[Tuple[str, ...], List[str]] = {}
        frontier: List[_FuncInfo] = []
        for r in sorted(roots, key=lambda i: i.key):
            if r.key not in seen:
                seen[r.key] = [f"{r.name}[{r.root_why}]"]
                frontier.append(r)
        while frontier:
            cur = frontier.pop()
            path = seen[cur.key]
            for ref in self._edges(cur):
                if ref.name in BOUNDARY_FUNCS:
                    continue
                if ref.key not in seen:
                    seen[ref.key] = path + [ref.name]
                    frontier.append(ref)

        reported: Set[Tuple[str, int]] = set()
        for key in sorted(seen):
            info = self.funcs.get(key)
            if info is None:
                continue
            via = " -> ".join(seen[key])
            for lineno, desc in info.effects:
                site = (info.sf.path, lineno)
                if site in reported:
                    continue
                reported.add(site)
                yield self.violation(
                    info.sf, lineno,
                    f"{desc}; reachable from traced code via {via}")

    # ---- resolution -----------------------------------------------------
    def _edges(self, info: _FuncInfo) -> List[_FuncInfo]:
        out: List[_FuncInfo] = []
        inner_scope = (info.module, info.scope + (info.name,))
        for name, nested in self.by_module_scope.get(inner_scope,
                                                     {}).items():
            # a nested def referenced by name in the body is assumed
            # called (or passed onward into traced code)
            if name in info.loads:
                out.append(nested)
        for ref in info.calls:
            target: Optional[_FuncInfo] = None
            if ref[0] == "name":
                target = self._resolve_name(
                    info.module, info.scope + (info.name,), ref[1])
            elif ref[0] == "self" and info.class_name is not None:
                target = self.methods.get(
                    (info.module, info.class_name), {}).get(ref[1])
            elif ref[0] == "mod":
                target = self._resolve_attr(info.module, ref[1], ref[2])
            if target is not None:
                out.append(target)
        return out

    def _resolve_name(self, module: str, scope: Tuple[str, ...],
                      name: str) -> Optional[_FuncInfo]:
        """Lexical: innermost enclosing scope outward to module level,
        then from-imports within apex_tpu."""
        for i in range(len(scope), -1, -1):
            hit = self.by_module_scope.get((module, scope[:i]),
                                           {}).get(name)
            if hit is not None:
                return hit
        imp = self.module_index.get(module)
        if imp is not None:
            ref = imp.imports.get(name)
            if ref is not None and ref[0] == "from":
                return self.by_module_scope.get((ref[1], ()),
                                                {}).get(ref[2])
        return None

    def _resolve_attr(self, module: str, alias: str,
                      attr: str) -> Optional[_FuncInfo]:
        imp = self.module_index.get(module)
        if imp is None:
            return None
        ref = imp.imports.get(alias)
        if ref is None:
            return None
        if ref[0] == "module":
            return self.by_module_scope.get((ref[1], ()), {}).get(attr)
        # from-import of a submodule: `from apex_tpu.serve import kv_cache`
        sub = f"{ref[1]}.{ref[2]}"
        return self.by_module_scope.get((sub, ()), {}).get(attr)

    def _resolve_expr(self, module: str, scope: Tuple[str, ...],
                      cls: Optional[str], arg: ast.AST
                      ) -> Optional[_FuncInfo]:
        """Resolve a callable expression passed to a trace wrapper."""
        if isinstance(arg, ast.Name):
            return self._resolve_name(module, scope, arg.id)
        if isinstance(arg, ast.Attribute) and \
                isinstance(arg.value, ast.Name) and \
                arg.value.id == "self" and cls is not None:
            return self.methods.get((module, cls), {}).get(arg.attr)
        return None
