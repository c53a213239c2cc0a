"""graftlint rules GL001-GL010: the JAX hazards that kill TPU throughput
silently (no test fails — the step loop just gets slower, the host blocks on
hidden device syncs, or a pod wedges at a collective half the processes
never enter).

Each rule documents WHAT it flags, WHY it is a hazard on the RAFT-Stereo hot
path (a long ConvGRU refinement loop under jit — ROADMAP north star), and the
sanctioned fix. False positives are silenced in place with
`# graftlint: disable=GLxxx` so every suppression is a reviewed, visible
decision — or, for whole false-positive CLASSES, become launder-set entries
in the shared taint policies (engine.TaintPolicy subclasses) with a fixture
proving the exemption.

GL008-GL010 are interprocedural: they read the whole-program summaries the
callgraph.Project pass computes (reaches-collective, donates-parameter,
returns-device) and are impossible per-function.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from tools.graftlint.callgraph import MULTIHOST_COLLECTIVE_CALLEES
from tools.graftlint.concurrency import iter_findings as iter_concurrency_findings
from tools.graftlint.engine import (
    PARTIAL_CALLEES,
    Finding,
    ModuleAnalysis,
    TaintPolicy,
    TaintScope,
    TracerTaintPolicy,
    callee_matches,
    dotted_name,
)

# numpy aliases flagged inside traced code. jnp/jax.numpy are the device
# library and always legal under trace.
_HOST_NUMPY_ROOTS = {"np", "numpy"}

# stdlib roots whose calls are side effects under trace: they run ONCE at
# trace time (not per step), so timing/randomness/printing under jit is
# either dead code or a trace-time leak, never the per-step behavior the
# author expected.
_IMPURE_ROOTS = {"time", "random", "os"}

# host sync constructors: applying these to a jax.Array blocks the host on
# the device stream (device->host transfer) — the classic silent
# steps-per-second killer in a step loop.
_SYNC_BUILTINS = {"float", "int", "bool"}
_SYNC_NUMPY = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


class Rule:
    name: str = ""
    summary: str = ""

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(
        self, analysis: ModuleAnalysis, node: ast.AST, message: str
    ) -> Finding:
        return Finding(
            rule=self.name,
            path=analysis.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0) + 1,
            message=message,
        )


class GL001HostNumpyUnderTrace(Rule):
    """Host `numpy` call inside a jitted/scanned function.

    Under trace, `np.*` on a tracer either raises (TracerArrayConversionError)
    or — worse — silently constant-folds a trace-time value into the compiled
    program, freezing the first batch's data into every future step. The fix
    is `jnp.*` (device math) or hoisting genuinely-static numpy work out of
    the traced function.
    """

    name = "GL001"
    summary = "host numpy call on traced values inside a jitted function"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for fn in analysis.traced:
            for node in analysis.own_body_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                dn = dotted_name(node.func)
                if dn is None:
                    continue
                root = dn.split(".", 1)[0]
                if root in _HOST_NUMPY_ROOTS:
                    yield self.finding(
                        analysis,
                        node,
                        f"host numpy call `{dn}` inside a traced function — "
                        "use jnp.* (device math) or hoist static work out of "
                        "the trace",
                    )


def _static_scalar_annotation(ann) -> bool:
    """True for parameter annotations that declare an untraceable static
    type: `str`, as a name or a string literal (the
    `from __future__ import annotations` form). Deliberately NOT `bool` or
    `int` — annotations are unenforced, and both genuinely arrive as
    tracers (`flip=jnp.any(mask)`, loop carries/indices); only strings can
    never be device values."""
    if isinstance(ann, ast.Name):
        return ann.id == "str"
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return ann.value.strip() == "str"
    return False


class GL002TracerControlFlow(Rule):
    """Python `if`/`while` branching on a tracer-derived value.

    Inside jit, Python control flow runs at TRACE time: branching on a traced
    value raises a ConcretizationTypeError at best; branching on a value that
    jit re-traces per shape/dtype (weak types, captured scalars) silently
    forks the compile cache — the steady-state recompile hazard. Branch on
    static config/shapes, or use `jnp.where` / `jax.lax.cond`.

    Scope: conditions that reference the traced function's own parameters or
    locals assigned from them / from jnp math. Branching on `.shape`,
    `.ndim`, `.dtype`, `len(...)` is static and stays clean. An `if` whose
    body is ONLY `raise` is exempt: it is a trace-time validation guard —
    a real tracer in its condition would have raised a
    ConcretizationTypeError at the first trace, so surviving code proves
    the condition static (helpers reached through the cross-module traced
    closure routinely validate static config this way).
    """

    name = "GL002"
    summary = "Python if/while on a tracer inside a jitted function"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for fn in analysis.traced:
            if isinstance(fn, ast.Lambda):
                continue  # lambdas cannot contain if/while statements
            # One shared flow-sensitive pass (engine.TaintScope) with the
            # tracer policy: params seed the taint, jnp/lax math taints,
            # len()/.shape/... launders. Per-line state with loop-end
            # may-taint — the same semantics GL005/GL008 get.
            args = fn.args
            params = [
                a.arg
                for a in (
                    list(args.posonlyargs)
                    + list(args.args)
                    + list(args.kwonlyargs)
                    + ([args.vararg] if args.vararg else [])
                    + ([args.kwarg] if args.kwarg else [])
                )
                # Launder-set entry: a parameter annotated `str` is
                # static config by declaration — strings never become
                # tracers, so the annotation cannot lie. Lets kernel
                # wrappers dispatch on mode strings (`affine_form: str`)
                # without per-line waivers. `bool`/`int` get no exemption:
                # annotations are unenforced and both arrive as tracers.
                if not _static_scalar_annotation(a.annotation)
            ]
            scope = TaintScope(
                analysis, fn, policy=TracerTaintPolicy(), initial=params
            )
            for node in analysis.own_body_nodes(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if isinstance(node, ast.If) and all(
                    isinstance(s, ast.Raise) for s in node.body
                ) and not node.orelse:
                    continue  # raise-only validation guard: static by construction
                if scope.expr_tainted(node.test):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield self.finding(
                        analysis,
                        node,
                        f"Python `{kind}` branches on a tracer-derived value "
                        "inside a traced function — use jnp.where / "
                        "jax.lax.cond, or branch on static config/shapes",
                    )


class GL003ImpureUnderTrace(Rule):
    """Impure call (`time.*`, `random.*`, `os.*`, `print`) or global mutation
    under jit.

    These execute ONCE at trace time, not per step: a `time.time()` inside a
    jitted step measures tracing, `random.random()` freezes one sample into
    the compiled program, `print` fires only on (re)trace, and `global`
    writes leak trace-time state. Use jax.random / jax.debug.print / host
    callbacks, or hoist the side effect out of the trace.
    """

    name = "GL003"
    summary = "impure call (time/random/print/os, global mutation) under jit"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for fn in analysis.traced:
            for node in analysis.own_body_nodes(fn):
                if isinstance(node, ast.Global):
                    yield self.finding(
                        analysis,
                        node,
                        "`global` mutation inside a traced function runs at "
                        "trace time only — hoist host state out of the trace",
                    )
                    continue
                if not isinstance(node, ast.Call):
                    continue
                dn = dotted_name(node.func)
                if dn is None:
                    continue
                if dn == "print":
                    yield self.finding(
                        analysis,
                        node,
                        "`print` under jit fires only at trace time — use "
                        "jax.debug.print for per-step output",
                    )
                    continue
                root = dn.split(".", 1)[0]
                if root in _IMPURE_ROOTS and "." in dn:
                    yield self.finding(
                        analysis,
                        node,
                        f"impure call `{dn}` inside a traced function runs "
                        "once at trace time, not per step — hoist it out of "
                        "the trace (use jax.random for randomness)",
                    )


class GL004MissingDonation(Rule):
    """Train-step-shaped `jax.jit` without buffer donation.

    A step function that threads a state pytree (params + optimizer) through
    itself doubles its HBM footprint without `donate_argnums`: XLA keeps the
    input buffers alive across the call instead of updating in place. On the
    reference training recipe that is the difference between fitting the
    batch and OOM. Any jit whose wrapped callable looks like a step
    (name contains "step", or a local def whose first parameter is a state)
    must donate its state argument.
    """

    name = "GL004"
    summary = "train-step-shaped jax.jit without donate_argnums"

    def _step_shaped(self, analysis: ModuleAnalysis, wrapped: ast.expr) -> Optional[str]:
        # Unwrap functools.partial(f, ...) chains to f — a partial-wrapped
        # step is still a step (the engine's jit registry unwraps the same
        # way).
        while (
            isinstance(wrapped, ast.Call)
            and callee_matches(wrapped.func, PARTIAL_CALLEES)
            and wrapped.args
        ):
            wrapped = wrapped.args[0]
        dn = dotted_name(wrapped)
        if dn is None and isinstance(wrapped, ast.Call):
            dn = dotted_name(wrapped.func)
        if dn is None:
            return None
        base = dn.split(".")[-1]
        if "step" in base.lower():
            return base
        local = analysis._local_defs.get(base)  # noqa: SLF001
        if local is not None and local.args.args:
            first = local.args.args[0].arg
            if first in ("state", "train_state", "opt_state"):
                return base
        return None

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for node in ast.walk(analysis.tree):
            if not isinstance(node, ast.Call):
                continue
            if not callee_matches(node.func, {"jax.jit", "jit", "pjit"}):
                continue
            if not node.args:
                continue
            shaped = self._step_shaped(analysis, node.args[0])
            if shaped is None:
                continue
            kwargs = {kw.arg for kw in node.keywords}
            if not ({"donate_argnums", "donate_argnames"} & kwargs):
                yield self.finding(
                    analysis,
                    node,
                    f"jit of step-shaped `{shaped}` without donate_argnums/"
                    "donate_argnames — the un-donated state pytree doubles "
                    "HBM across the step call",
                )


class GL005ImplicitHostSync(Rule):
    """Implicit device->host sync on a compiled callable's results.

    `float(x)`, `int(x)`, `bool(x)`, `x.item()`, `np.asarray(x)`, and
    f-string interpolation of a `jax.Array` all block the host until the
    device stream drains — one hidden host stall per occurrence, and the
    end of async dispatch in a step loop. The
    sanctioned fetch is an EXPLICIT, batched `jax.device_get` at a
    whitelisted point (utils/jit_hygiene.py); everything else in a function
    that drives a jitted callable must stay on device.
    """

    name = "GL005"
    summary = "implicit host sync (float/int/bool/.item/np.asarray/f-string) on jit results"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        project = analysis.project
        for fn in analysis.functions:
            if fn in analysis.traced:
                continue  # host-side rule; traced bodies are GL001-003 land
            # scope: functions that actually drive a compiled callable —
            # directly, or through a project function that returns a device
            # value (cross-module taint: a helper returning a jit result
            # taints its callers everywhere).
            # cross-function taint: the project's combined fixed point marks
            # parameters that receive device values from SOME call site
            # (device_param_taint), so a sync inside a helper that never
            # creates the device value itself is still flagged.
            initial: Set[str] = (
                set(project.device_param_taint(fn)) if project is not None else set()
            )
            drives = bool(initial) or any(
                isinstance(n, ast.Call)
                and (
                    analysis.is_jitted_callee(n.func) is not None
                    or (
                        project is not None
                        and project.call_returns_device(analysis, n)
                    )
                )
                for n in analysis.own_body_nodes(fn)
            )
            if not drives:
                continue
            taint = TaintScope(analysis, fn, initial=initial)
            for node in analysis.own_body_nodes(fn):
                if isinstance(node, ast.Call):
                    dn = dotted_name(node.func)
                    if dn in _SYNC_BUILTINS and node.args:
                        if taint.expr_tainted(node.args[0]):
                            yield self.finding(
                                analysis,
                                node,
                                f"`{dn}(...)` on a device value blocks the "
                                "host on the device stream — fetch explicitly "
                                "with jax.device_get at a whitelisted point",
                            )
                    elif dn in _SYNC_NUMPY and node.args:
                        if taint.expr_tainted(node.args[0]):
                            yield self.finding(
                                analysis,
                                node,
                                f"`{dn}(...)` on a device value is an "
                                "implicit device->host transfer — use "
                                "jax.device_get (explicit, strict-mode safe)",
                            )
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"
                        and taint.expr_tainted(node.func.value)
                    ):
                        yield self.finding(
                            analysis,
                            node,
                            "`.item()` on a device value is a per-call host "
                            "sync — batch the fetch with jax.device_get",
                        )
                elif isinstance(node, ast.FormattedValue) and taint.expr_tainted(
                    node.value
                ):
                    yield self.finding(
                        analysis,
                        node,
                        "f-string interpolation of a device value syncs the "
                        "host — jax.device_get first (or log outside the "
                        "step loop)",
                    )


class GL006UnhashableStaticArgs(Rule):
    """Unhashable static args and mutable default arguments.

    jit static arguments are cache keys: a list/dict/set passed at a static
    position raises `TypeError: unhashable` at best, and a mutable default
    on a traced function is shared trace-time state at worst. Use tuples /
    frozen dataclasses for static config, `None` + in-body default for
    mutables.
    """

    name = "GL006"
    summary = "unhashable/list static args; mutable default arguments"

    _MUTABLE_CALLS = {"list", "dict", "set"}

    def _is_mutable_literal(self, node: ast.expr) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func)
            return dn in self._MUTABLE_CALLS
        return False

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        # (a) mutable defaults on any def (hazard is worst on traced fns,
        # where the default is captured into the trace).
        for fn in analysis.functions:
            if isinstance(fn, ast.Lambda):
                continue
            for default in list(fn.args.defaults) + [
                d for d in fn.args.kw_defaults if d is not None
            ]:
                if self._is_mutable_literal(default):
                    where = (
                        "a traced function"
                        if fn in analysis.traced
                        else f"`{fn.name}`"
                    )
                    yield self.finding(
                        analysis,
                        default,
                        f"mutable default argument on {where} — shared "
                        "between calls (and baked into the trace under jit); "
                        "default to None and build inside the body",
                    )
        # (b) mutable literal passed at a position a jit declared static.
        for node in ast.walk(analysis.tree):
            if not isinstance(node, ast.Call):
                continue
            binding = analysis.is_jitted_callee(node.func)
            if binding is None or binding.call is None:
                continue
            static = binding.keyword("static_argnums")
            static_names = binding.keyword("static_argnames")
            if static is None and static_names is None:
                continue
            positions = set()
            if isinstance(static, ast.Constant) and isinstance(static.value, int):
                positions = {static.value}
            elif isinstance(static, (ast.Tuple, ast.List)):
                positions = {
                    e.value
                    for e in static.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                }
            names = set()
            if isinstance(static_names, ast.Constant) and isinstance(
                static_names.value, str
            ):
                names = {static_names.value}
            elif isinstance(static_names, (ast.Tuple, ast.List)):
                names = {
                    e.value
                    for e in static_names.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                }
            # static_argnames also binds positionally: when the jitted target
            # is a local def, map the declared names onto its signature.
            if names and binding.call is not None and binding.call.args:
                inner = binding.call.args[0]
                if isinstance(inner, ast.Name):
                    fn_def = analysis._local_defs.get(inner.id)  # noqa: SLF001
                    if fn_def is not None:
                        for i, a in enumerate(fn_def.args.args):
                            if a.arg in names:
                                positions.add(i)
            for i, arg in enumerate(node.args):
                if i in positions and self._is_mutable_literal(arg):
                    yield self.finding(
                        analysis,
                        arg,
                        f"mutable (unhashable) argument at static position "
                        f"{i} of jitted `{binding.name}` — static args are "
                        "cache keys; pass a tuple/frozen value",
                    )
            for kw in node.keywords:
                if kw.arg in names and self._is_mutable_literal(kw.value):
                    yield self.finding(
                        analysis,
                        kw.value,
                        f"mutable (unhashable) value for static arg "
                        f"`{kw.arg}` of jitted `{binding.name}` — static "
                        "args are cache keys; pass a tuple/frozen value",
                    )


class GL007PallasDtypePitfalls(Rule):
    """`jnp` dtype-widening pitfalls inside Pallas kernels.

    Mosaic tiles are dtype-sized: a store that lets jnp's promotion pick the
    dtype silently widens bf16 accumulators to f32 (doubling VMEM and write
    traffic) or narrows f32 math to the ref dtype one op too early. Every
    `ref[...] = value` store must round explicitly via `.astype(ref.dtype)`
    (or store a bare ref-to-ref copy), and every dtype-defaulting
    constructor (`jnp.zeros`, `jnp.arange`, `jnp.full`, iota) must pin its
    dtype.
    """

    name = "GL007"
    summary = "dtype-widening pitfalls in Pallas kernels (unpinned stores/constructors)"

    _CONSTRUCTORS = {
        "jnp.zeros", "jnp.ones", "jnp.full", "jnp.arange", "jnp.empty",
        "jnp.zeros_like", "jnp.ones_like", "jnp.full_like",
    }
    # *_like default to the model array's dtype — acceptable; only flag when
    # the plain constructors omit dtype.
    _NEED_DTYPE = {"jnp.zeros", "jnp.ones", "jnp.full", "jnp.arange", "jnp.empty"}

    def _has_dtype(self, call: ast.Call, min_positional: int) -> bool:
        if any(kw.arg == "dtype" for kw in call.keywords):
            return True
        # positional dtype: jnp.zeros(shape, jnp.float32)
        return len(call.args) > min_positional

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for fn in analysis.kernels:
            if isinstance(fn, ast.Lambda):
                continue
            params = {a.arg for a in fn.args.args}
            ref_params = {p for p in params if p.endswith("_ref") or p.endswith("_refs")}
            for node in analysis.own_body_nodes(fn):
                if isinstance(node, ast.Assign):
                    for tgt in node.targets:
                        if not isinstance(tgt, ast.Subscript):
                            continue
                        base = tgt.value
                        base_name = base.id if isinstance(base, ast.Name) else None
                        if base_name is None or not (
                            base_name in ref_params or base_name.endswith("_ref")
                        ):
                            continue
                        value = node.value
                        # sanctioned forms: `.astype(...)` rounding, or a
                        # bare ref-to-ref copy `a_ref[...] = b_ref[...]`.
                        if (
                            isinstance(value, ast.Call)
                            and isinstance(value.func, ast.Attribute)
                            and value.func.attr == "astype"
                        ):
                            continue
                        if isinstance(value, ast.Subscript) and isinstance(
                            value.value, ast.Name
                        ) and value.value.id.endswith("_ref"):
                            continue
                        yield self.finding(
                            analysis,
                            node,
                            f"store into `{base_name}` without an explicit "
                            "`.astype(...)` — jnp promotion picks the dtype "
                            "silently (bf16 math widens to f32, doubling "
                            "VMEM/write traffic); round explicitly",
                        )
                elif isinstance(node, ast.AugAssign):
                    # `o_ref[...] += value` is a read-modify-write store:
                    # the add itself promotes (a bf16 ref accumulating an
                    # unpinned f32 intermediate runs — and stores — wide),
                    # so the accumulated value needs the same explicit
                    # rounding as a plain store. Same sanctioned forms.
                    tgt = node.target
                    if not isinstance(tgt, ast.Subscript):
                        continue
                    base = tgt.value
                    base_name = base.id if isinstance(base, ast.Name) else None
                    if base_name is None or not (
                        base_name in ref_params or base_name.endswith("_ref")
                    ):
                        continue
                    value = node.value
                    if (
                        isinstance(value, ast.Call)
                        and isinstance(value.func, ast.Attribute)
                        and value.func.attr == "astype"
                    ):
                        continue
                    if isinstance(value, ast.Subscript) and isinstance(
                        value.value, ast.Name
                    ) and value.value.id.endswith("_ref"):
                        continue
                    yield self.finding(
                        analysis,
                        node,
                        f"augmented store into `{base_name}` without an "
                        "explicit `.astype(...)` — the in-place add promotes "
                        "through jnp rules (a bf16 ref accumulating f32 math "
                        "widens silently); round the accumulated value",
                    )
                elif isinstance(node, ast.Call):
                    dn = dotted_name(node.func)
                    if dn in self._NEED_DTYPE:
                        min_pos = 0 if dn == "jnp.arange" else 1
                        if dn == "jnp.full":
                            min_pos = 2
                        if dn == "jnp.arange":
                            # arange(start[, stop[, step]], dtype=...) —
                            # positional dtype is ambiguous; require keyword.
                            if not any(kw.arg == "dtype" for kw in node.keywords):
                                yield self.finding(
                                    analysis,
                                    node,
                                    "`jnp.arange` without dtype= in a Pallas "
                                    "kernel — the int32/float32 default "
                                    "drifts with inputs; pin it",
                                )
                            continue
                        if not self._has_dtype(node, min_pos):
                            yield self.finding(
                                analysis,
                                node,
                                f"`{dn}` without an explicit dtype in a "
                                "Pallas kernel — the float32 default widens "
                                "bf16 pipelines silently; pin the dtype",
                            )


# -- interprocedural rules (GL008-GL010) -----------------------------------


def _name_bound_in(scope_node: ast.AST, name: str) -> bool:
    """Is `name` (a bare name or dotted attr key) rebound anywhere inside
    `scope_node` (excluding nested function bodies)? Used by the loop checks:
    a donation/key-consumption inside a loop is only safe when the loop body
    rebinds the name before the next iteration."""
    stack = list(ast.iter_child_nodes(scope_node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        targets: List[ast.expr] = []
        if isinstance(n, ast.Assign):
            targets = list(n.targets)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            targets = [n.target]
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            targets = [n.target]
        for tgt in targets:
            elts = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) else [tgt]
            for el in elts:
                if isinstance(el, ast.Name) and el.id == name:
                    return True
                if isinstance(el, ast.Attribute) and dotted_name(el) == name:
                    return True
        stack.extend(ast.iter_child_nodes(n))
    return False


def _enclosing_loop(node: ast.AST, fn: ast.AST) -> Optional[ast.AST]:
    cur = getattr(node, "_graftlint_parent", None)
    while cur is not None and cur is not fn:
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return cur
        cur = getattr(cur, "_graftlint_parent", None)
    return None


def _branch_arms(node: ast.AST, fn: ast.AST) -> dict:
    """{id(if_node): "body"|"orelse"} for every enclosing If arm of `node`.
    Lets the linear event walks respect mutual exclusion: two events in
    OPPOSITE arms of the same If can never both execute."""
    arms: dict = {}
    prev, cur = node, getattr(node, "_graftlint_parent", None)
    while cur is not None and cur is not fn:
        if isinstance(cur, ast.If):
            if any(prev is s for s in cur.body):
                arms[id(cur)] = "body"
            elif any(prev is s for s in cur.orelse):
                arms[id(cur)] = "orelse"
            # (prev is the test expr otherwise: guards both arms, no label)
        prev, cur = cur, getattr(cur, "_graftlint_parent", None)
    return arms


def _mutually_exclusive(arms_a: dict, arms_b: dict) -> bool:
    """True when the two events sit in opposite arms of a shared If —
    only one of them can execute in any run."""
    return any(
        if_id in arms_b and arms_b[if_id] != arm
        for if_id, arm in arms_a.items()
    )


class DivergencePolicy(TaintPolicy):
    """GL008 seeds: values that can DIFFER between the hosts of one pod.

    - `jax.process_index()` (and `process_topology()`'s first element) is
      divergent by definition; `process_count()` is pod-uniform and
      launders.
    - Host-local RNG: `np.random.*` / `random.*` CONSUMERS depend on hidden
      per-process state. Explicitly seeded constructors
      (`np.random.default_rng(0)`) are deterministic and stay clean —
      that's a launder-set entry, not a waiver (fixture: gl008_good).
    - Filesystem predicates (`os.path.exists`, `os.listdir`, `glob.glob`,
      ...): local disks answer differently per host.
    - `.stop_requested` attributes: a preemption signal lands on ONE
      process (utils/resilience.PreemptionGuard's contract).
    - Project helpers whose RETURN value is divergence-tainted (the
      callgraph returns-divergent summary): `if _has_checkpoint(p):` is as
      divergent as the `os.path.exists` inside the helper. Multihost
      collective RESULTS launder — allgather/broadcast values are
      pod-uniform by definition (fixture: gl008_returns_good).

    Identity comparisons stay TAINTED here (unlike the tracer/device
    policies): `if step is None:` on a host-divergent checkpoint probe is
    exactly the divergent-branch-into-collective pattern this rule exists
    for.
    """

    tainted_attrs = frozenset({"stop_requested"})
    identity_comparison_is_clean = False

    _FS_PREDICATES = {
        "exists", "isdir", "isfile", "islink", "listdir", "scandir",
        "glob", "iglob", "stat", "getmtime", "getsize",
    }
    _RNG_ROOTS = ("np.random.", "numpy.random.", "random.")
    _SEEDED_CONSTRUCTORS = {"default_rng", "Random", "RandomState", "seed"}

    def classify_call(self, scope: TaintScope, node: ast.Call):
        if callee_matches(node.func, {"process_index", "process_topology"}):
            return True
        if callee_matches(node.func, {"process_count", "device_count",
                                      "local_device_count"}):
            return False
        dn = dotted_name(node.func) or ""
        if dn.startswith(self._RNG_ROOTS):
            base = dn.split(".")[-1]
            if base in self._SEEDED_CONSTRUCTORS and node.args and all(
                isinstance(a, ast.Constant) for a in node.args
            ):
                return False  # deterministic, host-uniform by construction
            return True
        if callee_matches(node.func, self._FS_PREDICATES):
            return True
        if callee_matches(node.func, MULTIHOST_COLLECTIVE_CALLEES):
            # A collective's RESULT is pod-uniform by definition — every
            # host receives the same allgather/broadcast value, so branching
            # on it is the sanctioned reduce-then-decide pattern.
            return False
        project = scope.analysis.project
        if project is not None and project.call_returns_divergent(
            scope.analysis, node, type(self)
        ):
            # Interprocedural: a project helper whose RETURNED verdict is
            # divergence-tainted (`return os.path.exists(p)`) taints the
            # caller's condition — the returns-divergent summary closes the
            # "verdict hidden behind a helper" gap the intraprocedural
            # seeds cannot see.
            return True
        return None


def _single_host_conjunct(test: ast.expr) -> bool:
    """True when a divergent condition is conjoined with a single-host
    guard (`... and not coord.active`, `... and process_count() == 1`):
    the branch only executes where no peer exists, so divergence is moot.
    A reviewed launder-set entry (fixture: gl008_good), not a waiver."""
    if not (isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And)):
        return False
    for v in test.values:
        if (
            isinstance(v, ast.UnaryOp)
            and isinstance(v.op, ast.Not)
            and isinstance(v.operand, ast.Attribute)
            and v.operand.attr == "active"
        ):
            return True
        if isinstance(v, ast.Compare) and len(v.ops) == 1 and isinstance(
            v.ops[0], ast.Eq
        ):
            sides = (v.left, v.comparators[0])
            for a, b in (sides, sides[::-1]):
                if (
                    isinstance(a, ast.Call)
                    and callee_matches(a.func, {"process_count"})
                    and isinstance(b, ast.Constant)
                    and b.value == 1
                ):
                    return True
    return False


class GL008MultiHostDivergence(Rule):
    """Host-divergent branch reaching a collective.

    Under SPMD every compiled program and every multihost collective must be
    entered by ALL processes at the same point — a branch that only some
    hosts take (guarded by `jax.process_index()`, host-local RNG, filesystem
    state, or a per-host preemption flag) wedges the pod at the first
    collective inside it: the peers wait forever at a rendezvous half the
    processes never reach. This is the static twin of the runtime
    coordination layer (parallel/coordination.py exists because this bug
    class is the deadliest in multi-host training). Host-local work (file
    I/O, logging) under such a guard is fine; collectives are not — hoist
    them out of the branch, or reduce the divergent signal into a pod-wide
    decision first (HostCoordinator.sync).
    """

    name = "GL008"
    summary = "host-divergent branch (process_index/RNG/filesystem) reaching a collective"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        project = analysis.project
        if project is None:
            return
        for fn in analysis.functions:
            if fn in analysis.traced or isinstance(fn, ast.Lambda):
                continue
            scope = TaintScope(analysis, fn, policy=DivergencePolicy())
            flagged: Set[int] = set()
            for node in analysis.own_body_nodes(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if _single_host_conjunct(node.test):
                    continue
                if not scope.expr_tainted(node.test):
                    continue
                stack: List[ast.AST] = list(node.body) + list(node.orelse)
                while stack:
                    sub = stack.pop()
                    if isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                    ):
                        continue
                    if isinstance(sub, ast.Call) and id(sub) not in flagged:
                        if project.call_reaches_collective(analysis, sub):
                            flagged.add(id(sub))
                            callee = dotted_name(sub.func) or "<call>"
                            yield self.finding(
                                analysis,
                                sub,
                                f"`{callee}` enters a collective program but "
                                "is guarded by a host-divergent condition "
                                f"(line {node.lineno}) — hosts that skip the "
                                "branch hang the pod at the rendezvous; hoist "
                                "the collective out of the branch or reduce "
                                "the signal pod-wide first "
                                "(HostCoordinator.sync)",
                            )
                    stack.extend(ast.iter_child_nodes(sub))


class GL009RngHygiene(Rule):
    """PRNG key misuse: reuse without split/fold_in, and key construction
    under trace.

    jax PRNG keys are VALUES, not stateful generators: feeding one key to
    two consumers yields correlated (often identical) streams — silently
    degraded augmentation/dropout, the kind of bug that shows up as a
    half-point of EPE months later. And `jax.random.PRNGKey(seed)` inside a
    jitted function constant-folds: every step re-derives the SAME key, so
    "fresh randomness per step" is actually one frozen sample. Split or
    fold_in before each consumer; construct keys on the host and pass them
    in.
    """

    name = "GL009"
    summary = "PRNGKey reused without split/fold_in, or constructed under trace"

    _CONSTRUCTORS = {"PRNGKey", "key"}
    # fold_in(key, i) DERIVES a fresh key per distinct i — the sanctioned
    # per-iteration pattern — so it neither consumes nor needs a rebind.
    # (A fold_in with the same data twice is missed; that trade keeps the
    # loop idiom clean.) Key metadata accessors are inert too.
    _NONCONSUMING = {"fold_in", "key_data", "wrap_key_data", "key_impl"}

    def _jax_random_fn(self, dn: Optional[str]) -> Optional[str]:
        """'jax.random.normal' -> 'normal'; None for anything that is not a
        jax.random call (stdlib random and np.random are stateful by design
        and belong to GL003/GL008)."""
        if not dn:
            return None
        if dn.startswith("jax.random."):
            return dn.split(".")[-1]
        parts = dn.split(".")
        if len(parts) == 2 and parts[0] in ("jrandom", "jr"):
            return parts[1]
        return None

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        for fn in analysis.functions:
            traced = fn in analysis.traced
            events: List[Tuple[Tuple[int, int, int], str, ast.AST]] = []
            for node in analysis.own_body_nodes(fn):
                if isinstance(node, ast.Call):
                    events.append(
                        (
                            (node.end_lineno or node.lineno,
                             node.end_col_offset or 0, 1),
                            "call",
                            node,
                        )
                    )
                elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    events.append(
                        (
                            (node.end_lineno or node.lineno,
                             node.end_col_offset or 0, 2),
                            "bind",
                            node,
                        )
                    )
            consumed: dict = {}
            for _, kind, node in sorted(events, key=lambda e: e[0]):
                if kind == "call":
                    fname = self._jax_random_fn(dotted_name(node.func))
                    if fname is None or fname in self._NONCONSUMING:
                        continue
                    if fname in self._CONSTRUCTORS:
                        if traced:
                            yield self.finding(
                                analysis,
                                node,
                                f"`jax.random.{fname}` under trace constant-"
                                "folds to ONE key — every step reuses the "
                                "same stream; construct keys on the host and "
                                "pass them in (fold_in(step) for per-step "
                                "streams)",
                            )
                        continue
                    key_arg: Optional[ast.expr] = None
                    if node.args:
                        key_arg = node.args[0]
                    else:
                        for kw in node.keywords:
                            if kw.arg == "key":
                                key_arg = kw.value
                    if not isinstance(key_arg, ast.Name):
                        continue
                    name = key_arg.id
                    arms = _branch_arms(node, fn)
                    # Consumers in OPPOSITE arms of one If are mutually
                    # exclusive — a train/eval split over one key is one
                    # consumer per run, not two (launder-class, not waiver).
                    prior = [
                        rec
                        for rec in consumed.get(name, [])
                        if not _mutually_exclusive(rec[2], arms)
                    ]
                    if prior:
                        callee, line, _ = prior[0]
                        yield self.finding(
                            analysis,
                            node,
                            f"key `{name}` already consumed by "
                            f"`{callee}` (line {line}) and reused here "
                            "without split/fold_in — two consumers of one "
                            "key share a stream",
                        )
                    else:
                        loop = _enclosing_loop(node, fn)
                        if loop is not None and not _name_bound_in(loop, name):
                            yield self.finding(
                                analysis,
                                node,
                                f"key `{name}` consumed inside a loop that "
                                "never rebinds it — every iteration replays "
                                "the same stream; split/fold_in per "
                                "iteration",
                            )
                    consumed.setdefault(name, []).append(
                        (f"jax.random.{fname}", node.lineno, arms)
                    )
                else:
                    targets: List[ast.expr] = []
                    if isinstance(node, ast.Assign):
                        targets = list(node.targets)
                    else:
                        targets = [node.target]
                    for tgt in targets:
                        elts = (
                            tgt.elts
                            if isinstance(tgt, (ast.Tuple, ast.List))
                            else [tgt]
                        )
                        for el in elts:
                            if isinstance(el, ast.Name):
                                consumed.pop(el.id, None)


class GL010UseAfterDonate(Rule):
    """Reading an argument after it was donated to a jit.

    `donate_argnums` hands the argument's buffers to XLA: after the call the
    old arrays are DELETED, and touching them raises
    "Array has been deleted" — but only at runtime, possibly steps later on
    a path tests never walk (the classic case: logging `state.x` after
    `state = train_step(state, ...)` forgot to rebind). The helper-call form
    is nastier: a function that forwards its parameter into a donated
    position donates its CALLER's argument, invisibly per-function. Thread
    the returned value instead; rebind donated names in loops.

    Alias tracking: plain name-to-name binds (`snapshot = state`) put both
    names in one alias group, and donating ANY member poisons the whole
    group — so `snapshot = state; state = step(state, ...); snapshot.x`
    flags even though the donated NAME was rebound. Rebinding a name to
    anything else removes it from its group. Only bare names alias;
    attributes don't. `self.<attr>(...)` receivers resolve class-aware
    (the enclosing class's own binding wins); the flat per-module attr
    union remains the documented fallback for receivers whose class the
    project cannot see.
    """

    name = "GL010"
    summary = "argument read after being donated to a jit (donate_argnums)"

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        project = analysis.project
        if project is None:
            return
        for fn in analysis.functions:
            if fn in analysis.traced or isinstance(fn, ast.Lambda):
                continue
            events: List[Tuple[Tuple[int, int, int], str, ast.AST]] = []
            for node in analysis.own_body_nodes(fn):
                if isinstance(node, ast.Call):
                    events.append(
                        (
                            (node.end_lineno or node.lineno,
                             node.end_col_offset or 0, 1),
                            "call",
                            node,
                        )
                    )
                elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    events.append(
                        (
                            (node.end_lineno or node.lineno,
                             node.end_col_offset or 0, 2),
                            "bind",
                            node,
                        )
                    )
                elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load
                ):
                    events.append(
                        (((node.lineno, node.col_offset, 0)), "read", node)
                    )
                elif isinstance(node, ast.Attribute) and isinstance(
                    node.ctx, ast.Load
                ):
                    if dotted_name(node) is not None:
                        events.append(
                            (((node.lineno, node.col_offset, 0)), "aread", node)
                        )
            donated: dict = {}
            # name -> SHARED set of names bound to the same buffers via
            # plain `y = x` assigns; donation poisons the whole group.
            groups: dict = {}

            def _group_of(name: str) -> set:
                g = groups.get(name)
                if g is None:
                    g = {name}
                    groups[name] = g
                return g

            def _unalias(name: str) -> None:
                g = groups.get(name)
                if g is not None:
                    g.discard(name)
                groups[name] = {name}

            for _, kind, node in sorted(events, key=lambda e: e[0]):
                if kind == "call":
                    positions = project.call_donated_positions(analysis, node)
                    if not positions:
                        continue
                    callee = dotted_name(node.func) or "<call>"
                    for i in sorted(positions):
                        if i >= len(node.args):
                            continue
                        arg = node.args[i]
                        key = None
                        if isinstance(arg, ast.Name):
                            key = arg.id
                        elif isinstance(arg, ast.Attribute):
                            key = dotted_name(arg)
                        if key is None:
                            continue
                        record = (callee, node.lineno, _branch_arms(node, fn))
                        donated[key] = record
                        # Donation poisons every alias of the name: the
                        # buffers are shared, so `snapshot` dies with
                        # `state` no matter which name was passed.
                        for alias in groups.get(key, ()):
                            if alias != key:
                                donated[alias] = record
                        loop = _enclosing_loop(node, fn)
                        if loop is not None and not _name_bound_in(loop, key):
                            donated.pop(key, None)
                            yield self.finding(
                                analysis,
                                node,
                                f"`{key}` is donated to `{callee}` inside a "
                                "loop that never rebinds it — iteration 2 "
                                "passes an already-deleted buffer; rebind "
                                "the donated name from the call's result",
                            )
                elif kind == "bind":
                    targets = (
                        list(node.targets)
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for tgt in targets:
                        elts = (
                            tgt.elts
                            if isinstance(tgt, (ast.Tuple, ast.List))
                            else [tgt]
                        )
                        for el in elts:
                            if isinstance(el, ast.Name):
                                donated.pop(el.id, None)
                                _unalias(el.id)
                            elif isinstance(el, ast.Attribute):
                                dn = dotted_name(el)
                                if dn is not None:
                                    donated.pop(dn, None)
                    if (
                        isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and isinstance(node.value, ast.Name)
                    ):
                        # `y = x`: same buffers under two names from here on.
                        g = _group_of(node.value.id)
                        g.add(node.targets[0].id)
                        groups[node.targets[0].id] = g
                else:
                    read_key = (
                        node.id if kind == "read" else dotted_name(node)
                    )
                    if read_key is None:
                        continue
                    hit = None
                    if read_key in donated:
                        hit = read_key
                    else:
                        for key in donated:
                            if read_key.startswith(key + "."):
                                hit = key
                                break
                    if hit is not None and _mutually_exclusive(
                        donated[hit][2], _branch_arms(node, fn)
                    ):
                        continue  # donation and read sit in opposite If arms
                    if hit is not None:
                        callee, line, _ = donated.pop(hit)
                        yield self.finding(
                            analysis,
                            node,
                            f"`{hit}` was donated to `{callee}` at line "
                            f"{line} and read here — donated buffers are "
                            "deleted after the call; use the returned "
                            "value instead",
                        )


class _ConcurrencyRule(Rule):
    """Base for GL011-GL014: the findings are computed once per project by
    callgraph.ConcurrencyAnalysis (lock indexing, with-scope nesting, thread
    reachability, entry-held/acquires/may-block fixed points) and bucketed by
    path; each rule just replays its bucket for the module under check so
    suppression/baseline handling stays in the ordinary per-rule pipeline.
    """

    bucket_name: str = ""

    def check(self, analysis: ModuleAnalysis) -> Iterator[Finding]:
        project = analysis.project
        if project is None or getattr(project, "concurrency", None) is None:
            return
        bucket = getattr(project.concurrency, self.bucket_name)
        for node, message in iter_concurrency_findings(bucket, analysis.path):
            yield self.finding(analysis, node, message)


class GL011GuardedBy(_ConcurrencyRule):
    """Guarded-by inference: attribute touched outside its inferred lock.

    Per class, every `with self._lock:` scope votes on which lock guards
    which instance attributes (an attribute accessed under the same lock in
    >= 2 distinct scopes, and more often locked than not, is GUARDED by it).
    A read/write of a guarded attribute with no lock held — lexically or on
    entry via every call site (interprocedural entry-held intersection) — in
    a thread-reachable method is exactly the watchdog-armed-outside-the-lock
    bug class: the attribute's invariant is maintained everywhere except the
    one racy path. Fix by taking the lock (or an already-held caller lock);
    waive single-writer init/close paths with `# graftlint: disable=GL011`.
    Only mutable attributes count (assigned somewhere outside `__init__`);
    config-frozen attributes never flag.
    """

    name = "GL011"
    summary = "attribute guarded by an inferred lock is accessed without it"
    bucket_name = "guard_findings"


class GL012LockOrderCycle(_ConcurrencyRule):
    """Lock-order cycle: two code paths acquire the same locks in opposite
    orders, so two threads can each hold one lock and block forever on the
    other.

    Edges come from lexically nested `with`-lock scopes AND from calls made
    while a lock is held into functions whose `acquires-locks` summary is
    non-empty (interprocedural, propagated through the callgraph to a fixed
    point). RLock self-edges are ignored (re-entrancy is legal); any other
    strongly connected component in the acquisition-order graph is a
    deadlock waiting for traffic. Fix by picking one global order (document
    it) and re-ordering the minority path; there is no sanctioned waiver —
    a cycle is always a bug or a missing lock-free redesign.
    """

    name = "GL012"
    summary = "lock acquisition-order cycle (deadlock potential)"
    bucket_name = "cycle_findings"


class GL013ThreadLifecycle(_ConcurrencyRule):
    """Thread lifecycle: started threads must be join-able.

    `Thread(...).start()` with the handle discarded (chained call) or bound
    to a local that is never joined, stored, returned, or handed off leaks
    an unjoinable thread: shutdown can't wait for it, exceptions in it
    vanish, and under churn they pile up (the PR-16 batcher fix introduced
    the `_spawn`-tracked shape — append the handle to a tracked list and
    join on close — which is the sanctioned pattern). Daemon threads
    spawned from close/shutdown paths are exempt (best-effort teardown
    helpers); everything else needs an owner.
    """

    name = "GL013"
    summary = "Thread started but never joined/tracked (untracked lifecycle)"
    bucket_name = "lifecycle_findings"


class GL014BlockingUnderLock(_ConcurrencyRule):
    """Blocking call while holding a lock.

    `block_until_ready`/`jax.device_get` (device-stream drain),
    `queue.get`/`future.result` (unbounded wait), `time.sleep`, HTTP/
    subprocess calls — executed while a lock is held, directly or via any
    callee whose may-block summary is set (interprocedural) — serialize
    every thread contending for that lock behind the slow operation. This
    is the staging-queue and watchdog-arming hazard class: the lock was
    meant to protect microseconds of state, and now it gates a ~100 ms
    device sync. Fix by moving the blocking call outside the `with` (snap
    state under the lock, block after); `Condition.wait` on the lock's own
    condition is exempt (that is what conditions are for) unless OTHER
    locks are also held across the wait.
    """

    name = "GL014"
    summary = "blocking call (sync/queue/sleep/HTTP) while holding a lock"
    bucket_name = "blocking_findings"


ALL_RULES = [
    GL001HostNumpyUnderTrace(),
    GL002TracerControlFlow(),
    GL003ImpureUnderTrace(),
    GL004MissingDonation(),
    GL005ImplicitHostSync(),
    GL006UnhashableStaticArgs(),
    GL007PallasDtypePitfalls(),
    GL008MultiHostDivergence(),
    GL009RngHygiene(),
    GL010UseAfterDonate(),
    GL011GuardedBy(),
    GL012LockOrderCycle(),
    GL013ThreadLifecycle(),
    GL014BlockingUnderLock(),
]

RULE_TABLE = {r.name: r.summary for r in ALL_RULES}
