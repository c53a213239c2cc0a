"""graftlint engine: JAX-aware AST analysis shared by every rule.

Generic Python linters cannot see the hazards that matter on this codebase —
whether a function body runs under `jax.jit` tracing changes what is legal in
it (host numpy becomes a silent device sync, `if` on a value becomes a
ConcretizationTypeError or worse a per-step recompile), and none of that is
visible to pyflakes/ruff. This engine computes the JAX facts once per module
and hands them to the rules (rules.py):

- **traced functions**: functions whose body executes under a JAX trace.
  Inferred from decorators (`@jax.jit`, `@functools.partial(jax.jit, ...)`,
  `@jax.custom_vjp`, ...), from being passed to a tracing entry point
  (`jax.jit(f)`, `jax.lax.scan(f, ...)`, `pl.pallas_call(f, ...)`,
  `defvjp(fwd, bwd)`, ...), and transitively for defs nested inside traced
  functions. Where inference cannot see a trace boundary (a factory returns
  a function that a DIFFERENT module jits), the function can be declared
  with a `# graftlint: traced` pragma on its `def` line.
- **kernel functions**: the subset of traced functions passed to
  `pallas_call` (directly or through `functools.partial(kernel, ...)`) —
  GL007's scope.
- **jitted callables registry**: local names and `self.<attr>` targets bound
  to a `jax.jit(...)` result (or decorated with it), with the jit call's
  keywords. GL004 reads the keywords (donation), GL005 uses the registry to
  find step-loop functions, GL006 to match static-arg call sites.
- **device taint** (per function, on demand): names/attribute targets whose
  value flows from a jitted call's result. `jax.device_get` launders taint
  (it IS the sanctioned explicit fetch); shape/dtype/ndim/size accessors are
  static metadata and stay clean. The same flow-sensitive `TaintScope` pass
  is parameterized by a `TaintPolicy` (seed/launder sets), so GL002's
  tracer taint, GL005's device taint, and GL008's host-divergence taint all
  share one analysis instead of three hand-rolled walks.

Whole-program analysis (tools/graftlint/callgraph.py `Project`) augments the
per-module facts: traced-ness propagates across module boundaries (a factory
whose return value is jitted in ANOTHER module marks the returned function
traced, and callees of traced functions are traced transitively), jitted
bindings are visible to importing modules, and per-function summaries
(returns-device-value, donates-parameter, reaches-collective) feed the
interprocedural rules GL005/GL008/GL010. `lint_sources` lints a file set as
one project; `lint_source` remains the single-module wrapper.

Suppression: `# graftlint: disable=GL001[,GL002|all]` on the finding's line
suppresses it there; `# graftlint: disable-file=GL001[,...]` anywhere in the
file suppresses the rule(s) for the whole file. Each suppression records
whether it actually fired, so the runner can flag stale pragmas
(`scripts/lint.py --report-unused-suppressions`).

The engine is stdlib-only (ast + re): it runs in tier-1 with no JAX device,
no imports of the linted code, and no third-party deps.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# Call targets whose function-valued arguments are traced. Matched against
# the trailing dotted components of the callee (so `jax.jit`, `jit`, and
# `jax.experimental.pjit.pjit` all resolve). Bare names cover the common
# `from jax import jit` import style.
TRACING_CALLEES = {
    "jax.jit", "jit", "pjit",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.jacfwd", "jacfwd", "jax.jacrev", "jacrev",
    "jax.checkpoint", "jax.remat", "checkpoint", "remat",
    "jax.lax.scan", "lax.scan", "scan",
    "jax.lax.while_loop", "lax.while_loop", "while_loop",
    "jax.lax.cond", "lax.cond", "cond",
    "jax.lax.fori_loop", "lax.fori_loop", "fori_loop",
    "jax.lax.map", "lax.map",
    "shard_map", "jax.experimental.shard_map.shard_map",
    "pl.pallas_call", "pallas_call",
}

# Decorators that make the decorated function's body run under a trace.
TRACING_DECORATORS = {
    "jax.jit", "jit", "pjit",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.checkpoint", "jax.remat", "checkpoint", "remat",
    "jax.custom_vjp", "custom_vjp", "jax.custom_jvp", "custom_jvp",
}

# jit-like callees whose result is a compiled callable (the registry).
JIT_CALLEES = {"jax.jit", "jit", "pjit"}

PALLAS_CALLEES = {"pl.pallas_call", "pallas_call"}

PARTIAL_CALLEES = {"functools.partial", "partial"}

# Attribute accesses that read static metadata off a traced/device value —
# branching or host math on these is legal and must stay clean.
STATIC_ACCESSORS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*(disable-file|disable|traced)\s*(?:=\s*([A-Za-z0-9_,\s]+))?"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def dotted_name(node: ast.AST) -> Optional[str]:
    """`jax.lax.scan` -> "jax.lax.scan"; returns None for non-name chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def callee_matches(node: ast.AST, names: Set[str]) -> bool:
    """True when the call target's dotted name (or any dotted suffix of it)
    is in `names` — `jax.experimental.pjit.pjit` matches "pjit"."""
    dn = dotted_name(node)
    if dn is None:
        return False
    if dn in names:
        return True
    parts = dn.split(".")
    return any(".".join(parts[i:]) in names for i in range(1, len(parts)))


def _is_partial_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and callee_matches(node.func, PARTIAL_CALLEES)


@dataclasses.dataclass
class JitBinding:
    """A local binding of a compiled callable: `f = jax.jit(g, ...)`,
    `self.step = jax.jit(...)`, or a jit-decorated def."""

    name: str            # bare name or attr name ("train_step" for self.train_step)
    is_attr: bool        # bound via self.<attr>
    call: Optional[ast.Call]  # the jax.jit(...) call node (None for decorators)
    line: int
    owner: Optional[object] = None  # the ModuleAnalysis that registered it

    def keyword(self, *names: str) -> Optional[ast.expr]:
        if self.call is None:
            return None
        for kw in self.call.keywords:
            if kw.arg in names:
                return kw.value
        return None


class ModuleAnalysis:
    """All per-module facts the rules consume. Built once per file."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        self._attach_parents()
        self.line_suppressions: Dict[int, Set[str]] = {}
        self.file_suppressions: Set[str] = set()
        self.traced_pragma_lines: Set[int] = set()
        # Suppressions that actually fired — the complement is what
        # `--report-unused-suppressions` flags as stale.
        self.used_line_suppressions: Dict[int, Set[str]] = {}
        self.used_file_suppressions: Set[str] = set()
        self._scan_pragmas()
        self.functions = [
            n
            for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
        ]
        self.traced: Set[ast.AST] = set()
        self.kernels: Set[ast.AST] = set()
        # Traced-ness seeded ONLY by a "graftlint: traced" pragma — kept
        # separate so the project pass can tell which pragmas the
        # interprocedural inference has made redundant. (Spelled without
        # the leading hash here: a literal pragma in a comment token would
        # activate.)
        self.pragma_traced_fns: Set[ast.AST] = set()
        # ...and its complement: functions the per-module inference marks
        # WITHOUT a pragma (decorators, tracing entry points). The project
        # pass re-runs its closure from these seeds alone to decide which
        # `traced` pragmas are now redundant.
        self.nonpragma_seed_fns: Set[ast.AST] = set()
        self.jit_bindings: Dict[str, JitBinding] = {}
        # Cross-module facts injected by callgraph.Project (None when the
        # module is linted standalone): bare imported names bound to a jit
        # result elsewhere, and the project backref for call resolution.
        self.external_name_bindings: Dict[str, JitBinding] = {}
        self.external_attr_bindings: Dict[str, JitBinding] = {}
        self.project = None  # callgraph.Project | None
        self.module_name: Optional[str] = None
        self._local_defs = {
            n.name: n
            for n in self.functions
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        self._infer_traced()
        self._build_registry()

    # -- construction -----------------------------------------------------
    def _attach_parents(self) -> None:
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                child._graftlint_parent = parent  # noqa: SLF001

    def _iter_comment_tokens(self) -> Iterable[Tuple[int, str]]:
        """(lineno, text) for real COMMENT tokens only — a pragma quoted in a
        docstring or string literal (e.g. documentation of the suppression
        syntax itself) must NOT activate a suppression."""
        try:
            for tok in tokenize.generate_tokens(io.StringIO(self.source).readline):
                if tok.type == tokenize.COMMENT:
                    yield tok.start[0], tok.string
        except (tokenize.TokenError, IndentationError):  # pragma: no cover
            # ast.parse already accepted this source; tokenize failures here
            # would be pathological — degrade to no pragmas, never crash.
            return

    def _scan_pragmas(self) -> None:
        for i, comment in self._iter_comment_tokens():
            m = _PRAGMA_RE.search(comment)
            if not m:
                continue
            kind, arg = m.group(1), m.group(2)
            rules = {r.strip() for r in (arg or "all").split(",") if r.strip()}
            if kind == "disable":
                self.line_suppressions.setdefault(i, set()).update(rules)
            elif kind == "disable-file":
                self.file_suppressions.update(rules)
            elif kind == "traced":
                self.traced_pragma_lines.add(i)

    def _mark_traced(self, fn: ast.AST, kernel: bool = False) -> None:
        if fn in self.traced and (not kernel or fn in self.kernels):
            return
        self.traced.add(fn)
        if kernel:
            self.kernels.add(fn)
        # Defs nested inside a traced function execute under the same trace.
        for child in ast.walk(fn):
            if child is not fn and isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                self.traced.add(child)
                if kernel:
                    self.kernels.add(child)

    def _fn_from_arg(self, arg: ast.expr) -> Tuple[Optional[ast.AST], bool]:
        """Resolve a call argument to a local function node. Returns
        (fn, via_partial). Handles Name, Lambda, functools.partial(Name, ...)."""
        if isinstance(arg, ast.Lambda):
            return arg, False
        if isinstance(arg, ast.Name) and arg.id in self._local_defs:
            return self._local_defs[arg.id], False
        if _is_partial_call(arg) and arg.args:
            inner = arg.args[0]
            if isinstance(inner, ast.Name) and inner.id in self._local_defs:
                return self._local_defs[inner.id], True
            if isinstance(inner, ast.Lambda):
                return inner, True
        return None, False

    def _infer_traced(self) -> None:
        # 1. pragma-declared
        for fn in self.functions:
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if fn.lineno in self.traced_pragma_lines or (
                    fn.decorator_list
                    and any(
                        d.lineno in self.traced_pragma_lines for d in fn.decorator_list
                    )
                ):
                    self.pragma_traced_fns.add(fn)
                    self._mark_traced(fn)
        # 2. decorators
        for fn in self.functions:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if callee_matches(target, TRACING_DECORATORS):
                    self.nonpragma_seed_fns.add(fn)
                    self._mark_traced(fn)
                elif isinstance(dec, ast.Call) and _is_partial_call(dec) and dec.args:
                    if callee_matches(dec.args[0], TRACING_DECORATORS):
                        self.nonpragma_seed_fns.add(fn)
                        self._mark_traced(fn)
        # 3. passed to a tracing entry point
        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            is_pallas = callee_matches(call.func, PALLAS_CALLEES)
            is_tracing = is_pallas or callee_matches(call.func, TRACING_CALLEES)
            # *.defvjp(fwd, bwd) / *.defjvp(...) trace their arguments too.
            is_defgrad = isinstance(call.func, ast.Attribute) and call.func.attr in (
                "defvjp",
                "defjvp",
            )
            if not (is_tracing or is_defgrad):
                continue
            for arg in call.args:
                fn, _ = self._fn_from_arg(arg)
                if fn is not None:
                    self.nonpragma_seed_fns.add(fn)
                    self._mark_traced(fn, kernel=is_pallas)

    def _jit_call(self, node: ast.expr) -> Optional[ast.Call]:
        """node is `jax.jit(...)` or `functools.partial(jax.jit, ...)` ->
        the jit-carrying Call; else None."""
        if isinstance(node, ast.Call):
            if callee_matches(node.func, JIT_CALLEES):
                return node
            if _is_partial_call(node) and node.args and callee_matches(
                node.args[0], JIT_CALLEES
            ):
                return node
        return None

    def _build_registry(self) -> None:
        # decorated defs are compiled callables under their own name
        for fn in self.functions:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if callee_matches(target, JIT_CALLEES):
                    self.jit_bindings[fn.name] = JitBinding(
                        name=fn.name,
                        is_attr=False,
                        call=dec if isinstance(dec, ast.Call) else None,
                        line=fn.lineno,
                        owner=self,
                    )
        # assignments: x = jax.jit(...) / self.x = jax.jit(...) / chains where
        # a plain local alias is re-bound to a registered jitted name
        # (`self._fwd = fwd` after `@jax.jit def fwd`).
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Assign):
                continue
            call = self._jit_call(node.value)
            alias_of: Optional[JitBinding] = None
            if call is None and isinstance(node.value, ast.Name):
                alias_of = self.jit_bindings.get(node.value.id)
            if call is None and alias_of is None:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    name, is_attr = tgt.id, False
                elif isinstance(tgt, ast.Attribute):
                    name, is_attr = tgt.attr, True
                else:
                    continue
                self.jit_bindings[name] = JitBinding(
                    name=name,
                    is_attr=is_attr,
                    call=call if call is not None else alias_of.call,
                    line=node.lineno,
                    owner=self,
                )

    # -- queries ----------------------------------------------------------
    def is_traced(self, fn: ast.AST) -> bool:
        return fn in self.traced

    def is_kernel(self, fn: ast.AST) -> bool:
        return fn in self.kernels

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = getattr(node, "_graftlint_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return cur
            cur = getattr(cur, "_graftlint_parent", None)
        return None

    def own_body_nodes(self, fn: ast.AST) -> Iterable[ast.AST]:
        """Walk fn's body EXCLUDING nested function bodies (each function is
        analyzed in its own scope)."""
        body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
        stack: List[ast.AST] = list(body) if isinstance(body, list) else [body]
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue  # separate scope
            stack.extend(ast.iter_child_nodes(node))

    def is_jitted_callee(self, func: ast.expr) -> Optional[JitBinding]:
        """Call target resolves to a registered compiled callable? Accepts
        `name(...)`, `self.name(...)`, and `obj.name(...)`. With a project
        attached, bindings travel across module boundaries: a name imported
        from a module that bound it to a jit result, and `self.<attr>`
        bindings made by any project class (`trainer.train_step` is
        recognized in chip_smoke.py, not just in trainer.py)."""
        if isinstance(func, ast.Name):
            b = self.jit_bindings.get(func.id)
            if b is not None and not b.is_attr:
                return b
            return self.external_name_bindings.get(func.id)
        if isinstance(func, ast.Attribute):
            if (
                self.project is not None
                and isinstance(func.value, ast.Name)
                and func.value.id == "self"
            ):
                # Class-aware: inside a known class, that class's OWN
                # binding (assignment or jit-decorated method) decides —
                # the flat attr union below only serves receivers whose
                # class the analysis cannot see.
                b = self.project.resolve_self_attr_binding(self, func)
                if b is not None:
                    return b
            b = self.jit_bindings.get(func.attr)
            if b is not None and b.is_attr:
                return b
            ext = self.external_attr_bindings.get(func.attr)
            if ext is not None:
                return ext
            if self.project is not None:
                return self.project.resolve_module_attr_binding(self, func)
        return None

    def is_suppressed(self, finding: Finding) -> bool:
        file_hit = {"all", finding.rule} & self.file_suppressions
        if file_hit:
            self.used_file_suppressions.update(file_hit)
            return True
        rules = self.line_suppressions.get(finding.line, set())
        line_hit = {"all", finding.rule} & rules
        if line_hit:
            self.used_line_suppressions.setdefault(finding.line, set()).update(
                line_hit
            )
            return True
        return False

    def unused_suppressions(self) -> List[Tuple[int, str]]:
        """(line, detail) for pragmas that suppressed nothing in the last
        lint run over this module. Only meaningful after ALL rules ran
        (a --select subset would false-flag the unselected rules')."""
        stale: List[Tuple[int, str]] = []
        for line, rules in sorted(self.line_suppressions.items()):
            used = self.used_line_suppressions.get(line, set())
            for rule in sorted(rules - used):
                stale.append((line, f"disable={rule}"))
        for rule in sorted(self.file_suppressions - self.used_file_suppressions):
            stale.append((1, f"disable-file={rule}"))
        return stale


# -- flow-sensitive taint analysis (shared by GL002 / GL005 / GL008) ------

LAUNDERING_CALLEES = {"jax.device_get", "device_get"}


class TaintPolicy:
    """What a TaintScope pass means: which expressions SEED taint, which
    LAUNDER it, and which attribute reads stay clean. One flow-sensitive
    engine (TaintScope) serves every rule by swapping the policy:

    - DeviceTaintPolicy (GL005): seeds = jitted-call results (incl. project
      functions that return one); launder = jax.device_get; clean attrs =
      shape/dtype/... static metadata.
    - TracerTaintPolicy (GL002): seeds = function params + jnp/lax math;
      launder = len()/.shape; jnp./jax. dotted chains are module attrs,
      never data.
    - DivergencePolicy (GL008): seeds = process_index / host RNG /
      filesystem predicates / preemption flags; launder = process_count
      (host-uniform by definition).
    """

    launder_attrs: Set[str] = STATIC_ACCESSORS
    # taint-regardless attribute names (e.g. ".stop_requested" for GL008)
    tainted_attrs: Set[str] = frozenset()
    # dotted-prefix module roots whose attribute chains are never data
    clean_attr_prefixes: Tuple[str, ...] = ()
    # `x is None` / `x is not None` launder: identity tests yield host
    # bools with no device op (tracers are never None), so they are clean
    # for the tracer and device policies — but NOT for divergence taint: a
    # host-divergent value compared `is None` is still a host-divergent
    # branch condition (the checkpoint-resume `if step is None:` pattern
    # GL008 exists for), so DivergencePolicy opts out.
    identity_comparison_is_clean: bool = True

    def classify_call(self, scope: "TaintScope", node: ast.Call):
        """True: result tainted regardless of operands. False: result clean
        (laundering). None: propagate taint from the operands."""
        raise NotImplementedError


class DeviceTaintPolicy(TaintPolicy):
    """GL005: values flowed from a compiled callable's result."""

    # Their CALL on a device value is the implicit sync GL005 flags — but
    # the RESULT is a plain host scalar, so taint must not propagate
    # through it (an f-string on `loss = float(m)` is host math, not a
    # second sync).
    _HOST_SCALAR_CASTS = {"float", "int", "bool", "str"}

    def classify_call(self, scope: "TaintScope", node: ast.Call):
        if callee_matches(node.func, LAUNDERING_CALLEES):
            return False  # explicit fetch: result is host data
        dn = dotted_name(node.func)
        if dn in self._HOST_SCALAR_CASTS:
            return False  # the cast itself is flagged; its result is host
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            return False  # same: .item() syncs, but yields a host scalar
        if scope.analysis.is_jitted_callee(node.func) is not None:
            return True
        project = scope.analysis.project
        if project is not None and project.call_returns_device(
            scope.analysis, node
        ):
            return True
        return None


class TracerTaintPolicy(TaintPolicy):
    """GL002: values that are (potential) tracers inside a traced body."""

    clean_attr_prefixes = ("jnp.", "jax.", "lax.", "np.", "numpy.")

    def classify_call(self, scope: "TaintScope", node: ast.Call):
        dn = dotted_name(node.func)
        if dn == "len" or (dn and dn.split(".")[-1] == "shape"):
            return False
        if dn and (
            dn.startswith("jnp.")
            or dn.startswith("jax.numpy.")
            or dn.startswith("jax.lax.")
            or dn.startswith("lax.")
        ):
            return True  # jnp math produces tracers under trace
        return None


class TaintScope:
    """Per-function forward taint pass: which names/`self.attr` targets hold
    tainted values under the given policy (default: device values flowed
    from a compiled callable's result). One linear source-order pass,
    queried FLOW-SENSITIVELY: `expr_tainted(node)` uses the taint state as
    of `node`'s line, so a name rebound from a jitted call AFTER a host use
    doesn't retro-flag it, and a later `jax.device_get` laundering doesn't
    excuse an earlier implicit sync. Queries inside a loop conservatively
    use the state at the END of the loop body (an assignment later in the
    body taints earlier uses on the next iteration). `initial` pre-taints
    names at function entry (GL002 seeds the parameters this way)."""

    def __init__(
        self,
        analysis: ModuleAnalysis,
        fn: ast.AST,
        policy: Optional[TaintPolicy] = None,
        initial: Iterable[str] = (),
    ):
        self.analysis = analysis
        self.fn = fn
        self.policy = policy if policy is not None else DeviceTaintPolicy()
        self._initial = frozenset(initial)
        self.tainted: Set[str] = set(self._initial)
        # (lineno, state AFTER the assignments on/through that line) in
        # source order; _state_at() replays to a query line.
        self._snapshots: List[Tuple[int, frozenset]] = []
        self._run()

    def _state_at(self, lineno: int) -> frozenset:
        """Taint state just before `lineno` (assignments on earlier lines
        applied, later ones not)."""
        state: frozenset = self._initial
        for alineno, snap in self._snapshots:
            if alineno < lineno:
                state = snap
            else:
                break
        return state

    def _query_line(self, node: ast.expr) -> int:
        """Effective line for a taint query: inside a loop, the loop body's
        end (may-taint across iterations); otherwise the node's own line."""
        cur = getattr(node, "_graftlint_parent", None)
        end = node.lineno
        while cur is not None and cur is not self.fn:
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                end = max(end, (cur.end_lineno or cur.lineno) + 1)
            cur = getattr(cur, "_graftlint_parent", None)
        return end

    def _target_key(self, node: ast.expr) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            dn = dotted_name(node)
            return dn  # "self.state" etc.
        return None

    def expr_tainted(self, node: ast.expr) -> bool:
        """Does evaluating `node` yield a tainted value (or contain one)?"""
        if isinstance(node, ast.Call):
            verdict = self.policy.classify_call(self, node)
            if verdict is not None:
                return verdict
            # conservative: a call on tainted operands stays tainted
            return any(self.expr_tainted(a) for a in node.args) or any(
                kw.value is not None and self.expr_tainted(kw.value)
                for kw in node.keywords
            )
        if isinstance(node, ast.Attribute):
            if node.attr in self.policy.tainted_attrs:
                return True  # e.g. `.stop_requested`: host-local by contract
            if node.attr in self.policy.launder_attrs:
                return False  # shape/dtype/... is host metadata
            dn = dotted_name(node)
            if dn is not None:
                if dn in self._state_at(self._query_line(node)):
                    return True
                if self.policy.clean_attr_prefixes and dn.startswith(
                    self.policy.clean_attr_prefixes
                ):
                    return False  # module attr chain (jnp.float32), not data
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Name):
            return node.id in self._state_at(self._query_line(node))
        if isinstance(node, ast.Subscript):
            return self.expr_tainted(node.value)
        if isinstance(node, (ast.BinOp,)):
            return self.expr_tainted(node.left) or self.expr_tainted(node.right)
        if isinstance(node, ast.Compare):
            if self.policy.identity_comparison_is_clean and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
            ):
                # Identity tests are host-static regardless of operand
                # taint: a tracer is never None (`x is None` dispatches to
                # no device op and yields a Python bool), and `is` between
                # arrays compares object identity, not values. Lets traced
                # code branch on `Optional[Array]` arguments — the fused
                # kernel wrappers' optional-operand pattern. Policy-gated:
                # divergence taint (GL008) must keep flowing through
                # identity tests (see TaintPolicy).
                return False
            return self.expr_tainted(node.left) or any(
                self.expr_tainted(c) for c in node.comparators
            )
        if isinstance(node, ast.BoolOp):
            return any(self.expr_tainted(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.expr_tainted(node.operand)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr_tainted(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.expr_tainted(node.body) or self.expr_tainted(node.orelse)
        return False

    def _assign(self, targets: Sequence[ast.expr], value: ast.expr) -> None:
        tainted = self.expr_tainted(value)
        for tgt in targets:
            if isinstance(tgt, (ast.Tuple, ast.List)):
                # tuple unpack of a tainted producer taints every element
                for el in tgt.elts:
                    key = self._target_key(el)
                    if key is not None:
                        (self.tainted.add if tainted else self.tainted.discard)(key)
                continue
            key = self._target_key(tgt)
            if key is not None:
                (self.tainted.add if tainted else self.tainted.discard)(key)

    def _run(self) -> None:
        nodes = sorted(
            (
                n
                for n in self.analysis.own_body_nodes(self.fn)
                if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))
            ),
            key=lambda n: (n.lineno, n.col_offset),
        )
        for node in nodes:
            if isinstance(node, ast.Assign):
                self._assign(node.targets, node.value)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                self._assign([node.target], node.value)
            elif isinstance(node, ast.AugAssign):
                if self.expr_tainted(node.value):
                    key = self._target_key(node.target)
                    if key is not None:
                        self.tainted.add(key)
            self._snapshots.append((node.lineno, frozenset(self.tainted)))


# -- driver ---------------------------------------------------------------


def lint_sources(
    sources: Sequence[Tuple[str, str]],
    rules: Sequence,
    select: Optional[Set[str]] = None,
    root: str = ".",
    jobs: int = 1,
    stats: Optional[Dict[str, float]] = None,
):
    """Run `rules` over a file set AS ONE PROJECT: cross-module call-graph,
    traced-ness, and taint are resolved before any rule fires. Returns
    (findings, suppressed_count, project).

    `jobs` > 1 fans the PER-MODULE rule passes out over a thread pool (the
    project build stays serial — every summary is a shared fixed point).
    One task runs ALL rules for one module, so suppression-usage accounting
    (`analysis._used_*`, mutated by is_suppressed) never crosses threads.
    `stats`, when given a dict, accumulates per-rule wall-clock seconds
    into it (rule name -> total) for `scripts/lint.py --stats`."""
    import time as _time

    from tools.graftlint.callgraph import Project  # local: avoids cycle

    analyses = [ModuleAnalysis(path, source) for path, source in sources]
    project = Project(analyses, root=root)

    def run_module(analysis):
        mod_findings: List[Finding] = []
        mod_suppressed = 0
        mod_stats: Dict[str, float] = {}
        for rule in rules:
            if select is not None and rule.name not in select:
                continue
            t0 = _time.perf_counter() if stats is not None else 0.0
            for f in rule.check(analysis):
                if analysis.is_suppressed(f):
                    mod_suppressed += 1
                else:
                    mod_findings.append(f)
            if stats is not None:
                mod_stats[rule.name] = (
                    mod_stats.get(rule.name, 0.0) + _time.perf_counter() - t0
                )
        return mod_findings, mod_suppressed, mod_stats

    findings: List[Finding] = []
    suppressed = 0
    if jobs > 1 and len(analyses) > 1:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=jobs) as pool:
            results = list(pool.map(run_module, analyses))
    else:
        results = [run_module(a) for a in analyses]
    for mod_findings, mod_suppressed, mod_stats in results:
        findings.extend(mod_findings)
        suppressed += mod_suppressed
        if stats is not None:
            for name, dt in mod_stats.items():
                stats[name] = stats.get(name, 0.0) + dt
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, suppressed, project


def lint_source(
    path: str, source: str, rules: Sequence, select: Optional[Set[str]] = None
) -> Tuple[List[Finding], int]:
    """Run `rules` over one module (single-module project). Returns
    (findings, suppressed_count)."""
    findings, suppressed, _ = lint_sources([(path, source)], rules, select)
    return findings, suppressed
