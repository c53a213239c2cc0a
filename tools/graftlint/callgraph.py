"""Whole-program call graph + interprocedural facts for graftlint.

The per-module engine (engine.ModuleAnalysis) can only see a trace boundary
that sits in the same file: `# graftlint: traced` pragmas existed purely to
paper over that. This module lifts the analysis to the PROJECT level:

- **module graph**: every linted file becomes a dotted module
  (`raft_stereo_tpu/train/trainer.py` -> `raft_stereo_tpu.train.trainer`);
  `import`/`from ... import` (absolute and relative, including lazy imports
  inside function bodies) resolve names across files.
- **call graph**: each function's call sites resolve to project functions —
  bare names, imported symbols, `module.attr` access, `self.method`, and
  methods on instances whose constructor is a project class
  (`coord = HostCoordinator(); coord.sync()` resolves to the method).
- **cross-module traced-ness**: a tracing entry point whose argument is a
  call into a factory (`jax.jit(make_train_step(...))`) marks the functions
  the factory RETURNS as traced — in whatever module they live; and every
  resolvable callee of a traced function is traced transitively (worklist,
  so call-graph cycles converge). Most `# graftlint: traced` pragmas become
  inferable; `stale_traced_pragmas()` names the ones the inference obsoleted.
- **cross-module jit registry**: jit bindings travel to importing modules
  (bare imported names, `module.f` access) and `self.<attr>` bindings are
  visible project-wide, so `trainer.train_step(...)` is a recognized
  compiled call in chip_smoke.py, not just in trainer.py.
- **function summaries** feeding the interprocedural rules:
  * returns-device-value (GL005): a function whose return flows from a
    compiled call taints its callers everywhere;
  * returns-jit-callable: factories like `_cached_init_fn(cfg)` whose
    product is itself a compiled callable (`F(cfg)(rng, x)` is a device
    value);
  * donates-parameter (GL010): a helper that passes its parameter at a
    donated position of a jit donates its caller's argument;
  * reaches-collective (GL008): a function that (transitively) calls a
    compiled callable or a multihost collective is a pod-wide program no
    host may skip.

Stdlib-only (ast + os.path), like the rest of graftlint.
"""

from __future__ import annotations

import ast
import os
import threading
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.graftlint.engine import (
    PALLAS_CALLEES,
    TRACING_CALLEES,
    JitBinding,
    ModuleAnalysis,
    TaintScope,
    _is_partial_call,
    callee_matches,
    dotted_name,
)

# Host-level multihost collectives: every process must enter these together.
MULTIHOST_COLLECTIVE_CALLEES = {
    "sync_global_devices",
    "process_allgather",
    "broadcast_one_to_all",
}

_FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)
_ANY_FN = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def module_name_for(path: str, root: str = ".") -> str:
    """Dotted module name for a file path, relative to the project root
    (`raft_stereo_tpu/train/trainer.py` -> `raft_stereo_tpu.train.trainer`,
    `chip_smoke.py` -> `chip_smoke`, a package `__init__.py` -> the package name)."""
    rel = os.path.relpath(os.path.abspath(path), os.path.abspath(root))
    if rel.endswith(".py"):
        rel = rel[:-3]
    # Files OUTSIDE the root (tmp fixtures, absolute one-offs) produce ".."
    # segments — drop them so the tail still forms a usable dotted name.
    parts = [p for p in rel.split(os.sep) if p and p not in (".", "..")]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else "__root__"


class Project:
    """Cross-module facts over a set of ModuleAnalysis instances. Building
    one AUGMENTS each analysis in place (traced sets grow, external jit
    bindings appear) and leaves `analysis.project` pointing here for the
    interprocedural queries the rules make."""

    def __init__(self, analyses: Iterable[ModuleAnalysis], root: str = "."):
        self.analyses: List[ModuleAnalysis] = list(analyses)
        self.by_module: Dict[str, ModuleAnalysis] = {}
        for a in self.analyses:
            a.project = self
            a.module_name = module_name_for(a.path, root)
            self.by_module.setdefault(a.module_name, a)
            for b in a.jit_bindings.values():
                if b.owner is None:
                    b.owner = a
        # path-keyed side tables (ast nodes are unhashable-by-value; id()
        # keys index the per-function facts)
        self._imports: Dict[str, Dict[str, Tuple]] = {}
        self._classes: Dict[str, Dict[str, ast.ClassDef]] = {}
        self._instances: Dict[str, Dict[str, Tuple[ModuleAnalysis, ast.ClassDef]]] = {}
        # class-aware side tables: `self.<attr> = ProjectClass(...)` keyed
        # by the OWNING class (so two classes with a same-named attr never
        # collide), and `self.<attr> = jax.jit(...)` bindings per class.
        self._attr_instances: Dict[
            Tuple[int, str], Tuple[ModuleAnalysis, ast.ClassDef]
        ] = {}
        self._class_attr_bindings: Dict[Tuple[int, str], JitBinding] = {}
        self._callees: Dict[int, List[Tuple[ModuleAnalysis, ast.AST]]] = {}
        self._factory_seeds: List[Tuple[ModuleAnalysis, ast.AST]] = []
        self._returns_device: Set[int] = set()
        self._returns_jit: Set[int] = set()
        # id(fn) -> parameter names that receive device-tainted arguments
        # at some resolvable call site (GL005's cross-function taint).
        self._device_params: Dict[int, Set[str]] = {}
        self._donates_params: Dict[int, Set[int]] = {}
        self._collective: Set[int] = set()
        # Lazy (policy-parameterized): the divergence policy lives in
        # rules.py, which imports this module, so the summary is computed
        # on first query with the policy class passed in — None until then.
        # The lock serializes the lazy build under `lint.py --jobs`.
        self._returns_divergent: Optional[Set[int]] = None
        # RLock: the divergence policy's classify_call re-enters
        # call_returns_divergent while the summary is mid-build.
        self._divergent_lock = threading.RLock()

        self._build_imports()
        self._index_classes()
        self._index_instances()
        self._index_class_attr_bindings()
        self._build_callgraph()
        self._infer_traced_project()
        self._inject_jit_bindings()
        self._compute_returns_jit()
        self._compute_returns_device()
        self._compute_donations()
        self._compute_collectives()
        # concurrency facts (GL011-GL014) ride on the call graph above
        from tools.graftlint.concurrency import ConcurrencyAnalysis  # local: avoids cycle

        self.concurrency = ConcurrencyAnalysis(self)

    # -- imports -----------------------------------------------------------
    def _build_imports(self) -> None:
        for a in self.analyses:
            table: Dict[str, Tuple] = {}
            mod_parts = (a.module_name or "").split(".")
            is_pkg = os.path.basename(a.path) == "__init__.py"
            pkg_parts = mod_parts if is_pkg else mod_parts[:-1]
            for node in ast.walk(a.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if alias.asname:
                            table[alias.asname] = ("module", alias.name)
                        else:
                            # `import a.b.c` binds `a`; dotted call targets
                            # (`a.b.c.f`) resolve through by_module directly.
                            head = alias.name.split(".")[0]
                            table.setdefault(head, ("module", head))
                elif isinstance(node, ast.ImportFrom):
                    if node.level:
                        anchor = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                        base = ".".join(
                            anchor + (node.module.split(".") if node.module else [])
                        )
                    else:
                        base = node.module or ""
                    for alias in node.names:
                        if alias.name == "*":
                            continue
                        bound = alias.asname or alias.name
                        full = f"{base}.{alias.name}" if base else alias.name
                        if full in self.by_module:
                            table[bound] = ("module", full)
                        else:
                            table[bound] = ("symbol", base, alias.name)
            self._imports[a.path] = table

    def resolve_name(self, analysis: ModuleAnalysis, name: str):
        """("module", ModuleAnalysis) | ("symbol", ModuleAnalysis, sym) |
        None for a bare name bound by an import in `analysis`."""
        entry = self._imports.get(analysis.path, {}).get(name)
        if entry is None:
            return None
        if entry[0] == "module":
            mod = self.by_module.get(entry[1])
            return ("module", mod) if mod is not None else None
        mod = self.by_module.get(entry[1])
        return ("symbol", mod, entry[2]) if mod is not None else None

    # -- classes / instances ----------------------------------------------
    def _index_classes(self) -> None:
        for a in self.analyses:
            self._classes[a.path] = {
                n.name: n
                for n in ast.walk(a.tree)
                if isinstance(n, ast.ClassDef)
            }

    def _resolve_class(
        self, analysis: ModuleAnalysis, expr: ast.expr
    ) -> Optional[Tuple[ModuleAnalysis, ast.ClassDef]]:
        if isinstance(expr, ast.Name):
            cls = self._classes[analysis.path].get(expr.id)
            if cls is not None:
                return analysis, cls
            r = self.resolve_name(analysis, expr.id)
            if r and r[0] == "symbol":
                cls = self._classes.get(r[1].path, {}).get(r[2])
                if cls is not None:
                    return r[1], cls
        elif isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
            r = self.resolve_name(analysis, expr.value.id)
            if r and r[0] == "module":
                cls = self._classes.get(r[1].path, {}).get(expr.attr)
                if cls is not None:
                    return r[1], cls
        return None

    def _index_instances(self) -> None:
        """`v = ClassName(...)` / `self.x = ClassName(...)` where ClassName
        is a project class: remember v -> class so `v.method()` resolves.
        Flat per module — scoping collisions are acceptable noise."""
        for a in self.analyses:
            table: Dict[str, Tuple[ModuleAnalysis, ast.ClassDef]] = {}
            for node in ast.walk(a.tree):
                if not isinstance(node, ast.Assign) or not isinstance(
                    node.value, ast.Call
                ):
                    continue
                resolved = self._resolve_class(a, node.value.func)
                if resolved is None:
                    continue
                for tgt in node.targets:
                    key = None
                    if isinstance(tgt, ast.Name):
                        key = tgt.id
                    elif isinstance(tgt, ast.Attribute):
                        key = dotted_name(tgt)
                        # class-aware: `self.x = Cls()` is keyed by the
                        # OWNING class too, so `self.x.m()` resolves to
                        # the right class even when another class binds a
                        # same-named attr to a different type.
                        if (
                            isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"
                        ):
                            cls = self._enclosing_class(node)
                            if cls is not None:
                                self._attr_instances[(id(cls), tgt.attr)] = (
                                    resolved
                                )
                    if key is not None:
                        table[key] = resolved
            self._instances[a.path] = table

    def _index_class_attr_bindings(self) -> None:
        """`self.<attr> = jax.jit(...)` (or an alias of a registered jit
        name) keyed by the owning class, plus jit-DECORATED methods — the
        class-aware upgrade over the first-wins flat attr union that
        `_inject_jit_bindings` still provides for unknown receivers."""
        for a in self.analyses:
            for cls in self._classes[a.path].values():
                for stmt in cls.body:
                    if isinstance(stmt, _FN_NODES) and stmt.name in a.jit_bindings:
                        b = a.jit_bindings[stmt.name]
                        if not b.is_attr and b.line == stmt.lineno:
                            self._class_attr_bindings[(id(cls), stmt.name)] = b
            for node in ast.walk(a.tree):
                if not isinstance(node, ast.Assign):
                    continue
                call = a._jit_call(node.value)  # noqa: SLF001
                alias_of: Optional[JitBinding] = None
                if call is None and isinstance(node.value, ast.Name):
                    alias_of = a.jit_bindings.get(node.value.id)
                if call is None and alias_of is None:
                    continue
                for tgt in node.targets:
                    if not (
                        isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"
                    ):
                        continue
                    cls = self._enclosing_class(node)
                    if cls is None:
                        continue
                    self._class_attr_bindings[(id(cls), tgt.attr)] = JitBinding(
                        name=tgt.attr,
                        is_attr=True,
                        call=call if call is not None else alias_of.call,
                        line=node.lineno,
                        owner=a,
                    )

    def resolve_self_attr_binding(
        self, analysis: ModuleAnalysis, func: ast.Attribute
    ) -> Optional[JitBinding]:
        """Class-aware jit-binding lookup for `self.<attr>(...)`: when the
        enclosing class is known, its own binding (assignment or decorated
        method) wins over the project-wide flat attr union."""
        cls = self._enclosing_class(func)
        if cls is None:
            return None
        return self._class_attr_bindings.get((id(cls), func.attr))

    def _method(
        self, owner: Tuple[ModuleAnalysis, ast.ClassDef], name: str
    ) -> Optional[Tuple[ModuleAnalysis, ast.AST]]:
        analysis, cls = owner
        for stmt in cls.body:
            if isinstance(stmt, _FN_NODES) and stmt.name == name:
                return analysis, stmt
        return None

    def _enclosing_class(
        self, node: Optional[ast.AST]
    ) -> Optional[ast.ClassDef]:
        cur = getattr(node, "_graftlint_parent", None) if node is not None else None
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = getattr(cur, "_graftlint_parent", None)
        return None

    # -- call resolution ---------------------------------------------------
    def resolve_function(
        self,
        analysis: ModuleAnalysis,
        func: ast.expr,
        enclosing: Optional[ast.AST] = None,
    ) -> Optional[Tuple[ModuleAnalysis, ast.AST]]:
        """Resolve a call target to (analysis, function node) when it names
        a project function; None for externals / dynamic values."""
        if isinstance(func, ast.Name):
            local = analysis._local_defs.get(func.id)  # noqa: SLF001
            if local is not None:
                return analysis, local
            r = self.resolve_name(analysis, func.id)
            if r and r[0] == "symbol":
                target = r[1]._local_defs.get(r[2])  # noqa: SLF001
                if target is not None:
                    return r[1], target
            inst = self._instances[analysis.path].get(func.id)
            if inst is not None:
                return self._method(inst, "__call__")
            return None
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name):
                if base.id == "self":
                    cls = self._enclosing_class(enclosing)
                    if cls is not None:
                        return self._method((analysis, cls), func.attr)
                    return None
                r = self.resolve_name(analysis, base.id)
                if r and r[0] == "module":
                    target = r[1]._local_defs.get(func.attr)  # noqa: SLF001
                    if target is not None:
                        return r[1], target
                inst = self._instances[analysis.path].get(base.id)
                if inst is not None:
                    return self._method(inst, func.attr)
                return None
            # attribute-of-attribute receiver: `self.metrics.record(...)` /
            # `coord.metrics.record(...)` — walk the chain class-aware
            # through the per-class attr-instance table.
            chained = self._resolve_chained_receiver(analysis, base, enclosing)
            if chained is not None:
                return self._method(chained, func.attr)
            # fully dotted module path: a.b.c.f
            dn = dotted_name(func)
            if dn and "." in dn:
                mod_path, _, attr = dn.rpartition(".")
                mod = self.by_module.get(mod_path)
                if mod is not None:
                    target = mod._local_defs.get(attr)  # noqa: SLF001
                    if target is not None:
                        return mod, target
        return None

    def _resolve_chained_receiver(
        self,
        analysis: ModuleAnalysis,
        base: ast.expr,
        enclosing: Optional[ast.AST],
    ) -> Optional[Tuple[ModuleAnalysis, ast.ClassDef]]:
        """Resolve a dotted receiver (`self.metrics`, `coord.metrics.sub`)
        to the project class of its final attribute, walking the chain
        through per-class `self.<attr> = Cls()` assignments. Class-aware:
        each hop looks up the attr under the CURRENT hop's class."""
        dn = dotted_name(base)
        if dn is None or "." not in dn:
            return None
        parts = dn.split(".")
        cur: Optional[Tuple[ModuleAnalysis, ast.ClassDef]]
        if parts[0] == "self":
            cls = self._enclosing_class(enclosing if enclosing is not None else base)
            if cls is None:
                return None
            cur = (analysis, cls)
        else:
            cur = self._instances[analysis.path].get(parts[0])
            if cur is None:
                return None
        for attr in parts[1:]:
            cur = self._attr_instances.get((id(cur[1]), attr))
            if cur is None:
                return None
        return cur

    def _build_callgraph(self) -> None:
        for a in self.analyses:
            for fn in a.functions:
                edges: List[Tuple[ModuleAnalysis, ast.AST]] = []
                for node in a.own_body_nodes(fn):
                    if isinstance(node, ast.Call):
                        target = self.resolve_function(a, node.func, enclosing=fn)
                        if target is not None:
                            edges.append(target)
                self._callees[id(fn)] = edges

    # -- traced-ness across modules ---------------------------------------
    def _returned_functions(
        self, analysis: ModuleAnalysis, fn: ast.AST
    ) -> List[Tuple[ModuleAnalysis, ast.AST]]:
        out: List[Tuple[ModuleAnalysis, ast.AST]] = []
        for node in analysis.own_body_nodes(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            values = (
                node.value.elts
                if isinstance(node.value, (ast.Tuple, ast.List))
                else [node.value]
            )
            for v in values:
                if isinstance(v, ast.Lambda):
                    out.append((analysis, v))
                elif isinstance(v, ast.Name):
                    target = analysis._local_defs.get(v.id)  # noqa: SLF001
                    if target is not None:
                        out.append((analysis, target))
        return out

    def _infer_traced_project(self) -> None:
        # (a) tracing entry points fed a cross-module symbol, or a FACTORY
        # CALL whose returned function(s) are what actually get traced:
        # `self.train_step = jax.jit(make_train_step(...), ...)` marks
        # step_fn traced — no pragma required.
        for a in self.analyses:
            for call in ast.walk(a.tree):
                if not isinstance(call, ast.Call):
                    continue
                is_pallas = callee_matches(call.func, PALLAS_CALLEES)
                is_tracing = is_pallas or callee_matches(call.func, TRACING_CALLEES)
                is_defgrad = isinstance(call.func, ast.Attribute) and call.func.attr in (
                    "defvjp",
                    "defjvp",
                )
                if not (is_tracing or is_defgrad):
                    continue
                enclosing = a.enclosing_function(call)
                for arg in call.args:
                    inner = arg
                    if _is_partial_call(inner) and inner.args:
                        inner = inner.args[0]
                    if isinstance(inner, ast.Name) and inner.id not in a._local_defs:  # noqa: SLF001
                        r = self.resolve_name(a, inner.id)
                        if r and r[0] == "symbol":
                            target = r[1]._local_defs.get(r[2])  # noqa: SLF001
                            if target is not None:
                                self._factory_seeds.append((r[1], target))
                                r[1]._mark_traced(target, kernel=is_pallas)  # noqa: SLF001
                    elif isinstance(inner, ast.Call):
                        factory = self.resolve_function(a, inner.func, enclosing)
                        if factory is None:
                            continue
                        for fa, fnode in self._returned_functions(*factory):
                            self._factory_seeds.append((fa, fnode))
                            fa._mark_traced(fnode, kernel=is_pallas)  # noqa: SLF001
        # (b) a traced function's resolvable callees run under the same
        # trace — propagate to a fixed point (cycles converge: marking is
        # monotone).
        changed = True
        while changed:
            changed = False
            for a in self.analyses:
                for fn in list(a.traced):
                    kernel = fn in a.kernels
                    for ca, cfn in self._callees.get(id(fn), ()):
                        if cfn not in ca.traced or (kernel and cfn not in ca.kernels):
                            ca._mark_traced(cfn, kernel=kernel)  # noqa: SLF001
                            changed = True

    def _nonpragma_closure(self) -> Set[int]:
        """id()s of every function traced WITHOUT any `# graftlint: traced`
        pragma: the closure over decorator/entry-point/factory seeds plus
        nested defs plus callees. A pragma'd function inside this closure is
        redundant — the interprocedural inference sees it on its own."""
        seen: Set[int] = set()
        stack: List[Tuple[ModuleAnalysis, ast.AST]] = []

        def push(a: ModuleAnalysis, fn: ast.AST) -> None:
            if id(fn) in seen:
                return
            seen.add(id(fn))
            stack.append((a, fn))
            for child in ast.walk(fn):
                if child is not fn and isinstance(child, _ANY_FN):
                    if id(child) not in seen:
                        seen.add(id(child))
                        stack.append((a, child))

        for a in self.analyses:
            for fn in a.nonpragma_seed_fns:
                push(a, fn)
        for a, fn in self._factory_seeds:
            push(a, fn)
        while stack:
            a, fn = stack.pop()
            for ca, cfn in self._callees.get(id(fn), ()):
                push(ca, cfn)
        return seen

    def stale_traced_pragmas(self) -> List[Tuple[str, int, str]]:
        """(path, line, detail) for `# graftlint: traced` pragmas that are
        redundant (the function is inferable without them) or that mark no
        function at all."""
        closure = self._nonpragma_closure()
        out: List[Tuple[str, int, str]] = []
        for a in self.analyses:
            claimed: Set[int] = set()
            for fn in a.pragma_traced_fns:
                lines = {fn.lineno} | {d.lineno for d in fn.decorator_list}
                lines &= a.traced_pragma_lines
                claimed.update(lines)
                if id(fn) in closure:
                    for line in sorted(lines):
                        out.append(
                            (
                                a.path,
                                line,
                                f"traced pragma on `{fn.name}` is redundant — "
                                "the cross-module inference already sees it",
                            )
                        )
            for line in sorted(a.traced_pragma_lines - claimed):
                out.append((a.path, line, "traced pragma marks no function"))
        return sorted(out)

    # -- cross-module jit registry ----------------------------------------
    def _inject_jit_bindings(self) -> None:
        attr_union: Dict[str, JitBinding] = {}
        for a in self.analyses:
            for name, b in a.jit_bindings.items():
                if b.is_attr and name not in attr_union:
                    attr_union[name] = b
        for a in self.analyses:
            for name, b in attr_union.items():
                if name not in a.jit_bindings:
                    a.external_attr_bindings[name] = b
            for name, entry in self._imports[a.path].items():
                if entry[0] != "symbol":
                    continue
                mod = self.by_module.get(entry[1])
                if mod is None:
                    continue
                b = mod.jit_bindings.get(entry[2])
                if b is not None and not b.is_attr:
                    a.external_name_bindings[name] = b

    def resolve_module_attr_binding(
        self, analysis: ModuleAnalysis, func: ast.Attribute
    ) -> Optional[JitBinding]:
        """`modalias.f(...)` where `modalias` imports a project module that
        bound `f` to a jit result."""
        mod: Optional[ModuleAnalysis] = None
        if isinstance(func.value, ast.Name):
            r = self.resolve_name(analysis, func.value.id)
            if r and r[0] == "module":
                mod = r[1]
        else:
            dn = dotted_name(func)
            if dn and "." in dn:
                mod = self.by_module.get(dn.rpartition(".")[0])
        if mod is not None:
            b = mod.jit_bindings.get(func.attr)
            if b is not None and not b.is_attr:
                return b
        return None

    # -- function summaries -------------------------------------------------
    def _compute_returns_jit(self) -> None:
        """Factories whose return value IS a compiled callable: a jit call,
        or a local name bound to one (`return jax.jit(lambda ...)`,
        `@jax.jit def chained: ...; return chained`)."""
        for a in self.analyses:
            for fn in a.functions:
                if isinstance(fn, ast.Lambda):
                    continue
                for node in a.own_body_nodes(fn):
                    if not isinstance(node, ast.Return) or node.value is None:
                        continue
                    v = node.value
                    if a._jit_call(v) is not None:  # noqa: SLF001
                        self._returns_jit.add(id(fn))
                    elif isinstance(v, ast.Name) and v.id in a.jit_bindings:
                        self._returns_jit.add(id(fn))

    def call_returns_device(self, analysis: ModuleAnalysis, call: ast.Call) -> bool:
        """Does this call yield a device value by PROJECT knowledge — a
        project function summarized returns-device, or the product of a
        jit-factory applied immediately (`F(cfg)(rng, x)`)?"""
        func = call.func
        if isinstance(func, ast.Call):
            factory = self.resolve_function(
                analysis, func.func, analysis.enclosing_function(call)
            )
            return factory is not None and id(factory[1]) in self._returns_jit
        target = self.resolve_function(
            analysis, func, analysis.enclosing_function(call)
        )
        return target is not None and id(target[1]) in self._returns_device

    @staticmethod
    def _param_names(fn: ast.AST) -> List[str]:
        if isinstance(fn, ast.Lambda):
            return []
        return [
            arg.arg
            for arg in list(fn.args.posonlyargs) + list(fn.args.args)
        ]

    def device_param_taint(self, fn: ast.AST) -> Set[str]:
        """Parameter names of `fn` that receive device-tainted arguments at
        some resolvable call site — GL005's cross-function taint: the
        summaries carry the taint INTO helpers, not just out of them."""
        return self._device_params.get(id(fn), set())

    def _compute_returns_device(self) -> None:
        """Two interleaved fixed points over one loop: (a) functions whose
        RETURN value carries device taint (a helper returning
        `train_step(...)`'s result makes ITS callers' results device
        values too), and (b) parameters that RECEIVE device-tainted
        arguments at a resolvable call site (`log_loss(metrics)` after
        `metrics = train_step(...)` makes `log_loss`'s parameter a device
        value inside the helper). Each pass re-seeds TaintScope with the
        current param taint, so the two propagate through each other."""
        for _ in range(16):
            changed = False
            for a in self.analyses:
                for fn in a.functions:
                    if fn in a.traced:
                        continue
                    scope = TaintScope(
                        a, fn, initial=self._device_params.get(id(fn), ())
                    )
                    if isinstance(fn, ast.Lambda):
                        if id(fn) not in self._returns_device and (
                            scope.expr_tainted(fn.body)
                        ):
                            self._returns_device.add(id(fn))
                            changed = True
                        continue
                    for node in a.own_body_nodes(fn):
                        if (
                            id(fn) not in self._returns_device
                            and isinstance(node, ast.Return)
                            and node.value is not None
                            and scope.expr_tainted(node.value)
                        ):
                            self._returns_device.add(id(fn))
                            changed = True
                        if not isinstance(node, ast.Call):
                            continue
                        target = self.resolve_function(a, node.func, enclosing=fn)
                        if target is None:
                            continue
                        ta, tfn = target
                        if tfn in ta.traced or isinstance(tfn, ast.Lambda):
                            continue
                        params = self._param_names(tfn)
                        if not params:
                            continue
                        # bound method call: position 0 maps to params[1]
                        offset = (
                            1
                            if isinstance(node.func, ast.Attribute)
                            and self._fn_is_method(tfn)
                            else 0
                        )
                        sink = self._device_params.setdefault(id(tfn), set())
                        for i, arg in enumerate(node.args):
                            idx = i + offset
                            if idx >= len(params):
                                break
                            if params[idx] not in sink and scope.expr_tainted(arg):
                                sink.add(params[idx])
                                changed = True
                        for kw in node.keywords:
                            if (
                                kw.arg in params
                                and kw.arg not in sink
                                and kw.value is not None
                                and scope.expr_tainted(kw.value)
                            ):
                                sink.add(kw.arg)
                                changed = True
            if not changed:
                break

    # -- donation summaries (GL010) ---------------------------------------
    def donated_positions_of_binding(self, binding: JitBinding) -> Set[int]:
        """Positional indices a jit binding donates (donate_argnums, plus
        donate_argnames mapped through the wrapped local def's signature)."""
        if binding.call is None:
            return set()
        positions: Set[int] = set()
        num = binding.keyword("donate_argnums")
        if isinstance(num, ast.Constant) and isinstance(num.value, int):
            positions.add(num.value)
        elif isinstance(num, (ast.Tuple, ast.List)):
            positions.update(
                e.value
                for e in num.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            )
        names_kw = binding.keyword("donate_argnames")
        names: Set[str] = set()
        if isinstance(names_kw, ast.Constant) and isinstance(names_kw.value, str):
            names = {names_kw.value}
        elif isinstance(names_kw, (ast.Tuple, ast.List)):
            names = {
                e.value
                for e in names_kw.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            }
        if names and binding.call.args and binding.owner is not None:
            inner = binding.call.args[0]
            if isinstance(inner, ast.Name):
                fn_def = binding.owner._local_defs.get(inner.id)  # noqa: SLF001
                if fn_def is not None:
                    for i, arg in enumerate(fn_def.args.args):
                        if arg.arg in names:
                            positions.add(i)
        return positions

    def call_donated_positions(
        self, analysis: ModuleAnalysis, call: ast.Call
    ) -> Set[int]:
        """Argument positions this call site donates — directly (a jit
        binding with donate_argnums) or through a helper whose summary says
        it forwards that parameter into a donated position."""
        binding = analysis.is_jitted_callee(call.func)
        if binding is not None:
            return self.donated_positions_of_binding(binding)
        target = self.resolve_function(
            analysis, call.func, analysis.enclosing_function(call)
        )
        if target is not None:
            return self._donates_params.get(id(target[1]), set())
        return set()

    def _fn_is_method(self, fn: ast.AST) -> bool:
        """A def whose direct parent is a ClassDef and whose first parameter
        is self/cls: call sites reach it BOUND, so its donation summary must
        be in bound-argument positions (the `self` slot dropped)."""
        if isinstance(fn, ast.Lambda) or not fn.args.args and not fn.args.posonlyargs:
            return False
        parent = getattr(fn, "_graftlint_parent", None)
        if not isinstance(parent, ast.ClassDef):
            return False
        first = (list(fn.args.posonlyargs) + list(fn.args.args))[0].arg
        return first in ("self", "cls")

    def _compute_donations(self) -> None:
        changed = True
        while changed:
            changed = False
            for a in self.analyses:
                for fn in a.functions:
                    if isinstance(fn, ast.Lambda):
                        continue
                    params = [
                        arg.arg
                        for arg in list(fn.args.posonlyargs) + list(fn.args.args)
                    ]
                    is_method = self._fn_is_method(fn)
                    current = self._donates_params.get(id(fn), set())
                    new = set(current)
                    for node in a.own_body_nodes(fn):
                        if not isinstance(node, ast.Call):
                            continue
                        for i in self.call_donated_positions(a, node):
                            if i < len(node.args) and isinstance(
                                node.args[i], ast.Name
                            ):
                                name = node.args[i].id
                                if name in params:
                                    pos = params.index(name)
                                    if is_method:
                                        if pos == 0:
                                            continue  # `self` itself
                                        pos -= 1  # bound-call position
                                    new.add(pos)
                    if new != current:
                        self._donates_params[id(fn)] = new
                        changed = True

    # -- collective summaries (GL008) --------------------------------------
    def _compute_collectives(self) -> None:
        for a in self.analyses:
            for fn in a.functions:
                for node in a.own_body_nodes(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    if callee_matches(
                        node.func, MULTIHOST_COLLECTIVE_CALLEES
                    ) or a.is_jitted_callee(node.func) is not None:
                        self._collective.add(id(fn))
                        break
        changed = True
        while changed:
            changed = False
            for a in self.analyses:
                for fn in a.functions:
                    if id(fn) in self._collective:
                        continue
                    for ca, cfn in self._callees.get(id(fn), ()):
                        if id(cfn) in self._collective:
                            self._collective.add(id(fn))
                            changed = True
                            break

    def call_reaches_collective(
        self, analysis: ModuleAnalysis, call: ast.Call
    ) -> bool:
        """Does this call enter a pod-wide program (compiled callable or
        multihost collective), directly or through project helpers?"""
        if callee_matches(call.func, MULTIHOST_COLLECTIVE_CALLEES):
            return True
        if analysis.is_jitted_callee(call.func) is not None:
            return True
        target = self.resolve_function(
            analysis, call.func, analysis.enclosing_function(call)
        )
        return target is not None and id(target[1]) in self._collective

    # -- divergent-return summaries (GL008, interprocedural) ----------------
    def _compute_returns_divergent(self, policy_cls) -> None:
        """Functions whose RETURN value carries host-divergent taint under
        `policy_cls` — fixed point, so `_probe()` returning
        `os.path.exists(p)` makes `_probe_twice()`'s (and ITS callers')
        verdicts divergent too. The policy's classify_call queries
        `call_returns_divergent` re-entrantly; initializing the set BEFORE
        iterating makes those mid-computation queries read the partial
        (monotonically growing) set, which is exactly the fixed-point
        semantics — a function promoted late in a pass re-taints its
        callers on the next pass."""
        with self._divergent_lock:
            self._compute_returns_divergent_locked(policy_cls)

    def _compute_returns_divergent_locked(self, policy_cls) -> None:
        if self._returns_divergent is not None:
            return
        self._returns_divergent = set()
        for _ in range(16):
            changed = False
            for a in self.analyses:
                for fn in a.functions:
                    if id(fn) in self._returns_divergent or fn in a.traced:
                        continue
                    scope = TaintScope(a, fn, policy=policy_cls())
                    if isinstance(fn, ast.Lambda):
                        if scope.expr_tainted(fn.body):
                            self._returns_divergent.add(id(fn))
                            changed = True
                        continue
                    for node in a.own_body_nodes(fn):
                        if isinstance(node, ast.Return) and node.value is not None:
                            if scope.expr_tainted(node.value):
                                self._returns_divergent.add(id(fn))
                                changed = True
                                break
            if not changed:
                break

    def call_returns_divergent(
        self, analysis: ModuleAnalysis, call: ast.Call, policy_cls
    ) -> bool:
        """Does this call return a value that can differ between hosts —
        a project function whose returned verdict is divergence-tainted
        under `policy_cls`? This is what tracks `if _has_checkpoint(p):`
        into the caller: the intraprocedural pass sees an opaque call, the
        summary sees the `os.path.exists` inside."""
        self._compute_returns_divergent(policy_cls)
        target = self.resolve_function(
            analysis, call.func, analysis.enclosing_function(call)
        )
        return target is not None and id(target[1]) in self._returns_divergent
