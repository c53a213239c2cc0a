"""Rule-driven sharding engine: declarative PartitionSpec rules over pytrees.

Replaces the hand-wired `batch_sharding` call sites (trainer step, test-mode
forward, serving warm) with a declarative rule table in the fmengine style
(SNIPPETS.md [2]): a list of ``(regex, PartitionSpec)`` pairs is matched
against the '/'-joined path of every pytree leaf, first match wins, scalars
are never partitioned, and an unmatched leaf is a hard error — a missing
rule should fail loudly at placement time, not silently replicate a tensor
that was meant to shard.

Four named presets cover this model family on the (data, spatial) mesh:

- ``dp``          — pure data parallelism. Params/state replicated, batch
                    over the data axis. On a ``(n, 1)`` mesh this emits the
                    exact specs the legacy hand-wired path used, so step
                    outputs are bit-identical by construction.
- ``spatial``     — image-row (H) sharding on a ``(1, n)`` mesh. The corr
                    volume/pyramid/lookup chain is per-row independent
                    (1-D epipolar matching), so the activation constraints
                    this preset turns on shard the O(H·W²) volume and the
                    GRU hidden state over H with zero collectives in that
                    chain; only the conv encoders need halo exchange, which
                    XLA SPMD inserts (and which the audit below expects).
- ``dp+spatial``  — both axes: batch over data, rows over spatial.
- ``fsdp``        — DP batch layout plus conv kernels (and their adam
                    moments) sharded over the data axis — the FSDP-ish
                    one-line rule-table change the param table was designed
                    for. XLA all-gathers params at use sites and
                    reduce-scatters grads; multi-host placement goes
                    per-process through ``make_array_from_callback``.

Activation constraints (`with_sharding_constraint` on the corr pyramid and
GRU hidden state) are emitted by the model itself, gated by
``RAFTStereoConfig.spatial_constraints``. Because that flag lives on the
model config it is part of every jit cache key — two engines with different
presets can never share a traced graph. The constraint needs a concrete
Mesh at *trace* time, which tracing-time code cannot receive as an
argument, so the engine exposes :func:`activation_mesh` (a scope holding
the current mesh) and :meth:`ShardingEngine.wrap` (enters the scope around
every call of a jitted function, so whenever tracing happens the mesh is
in place). ``constrain_spatial`` raises if the flag is set but no mesh is
in scope — a silent no-op there would cache an unconstrained graph.

HLO audit: ``collective_counts`` / ``assert_no_collectives`` grep compiled
HLO for the four collective families. For the spatial presets the corr
chain must audit clean (zero collectives — the epipolar-independence
claim); the *full* forward legitimately carries halo collective-permutes
and instance-norm all-reduces, which is what the per-preset
``collectives_expected`` flag of the multichip dry run's record says.
"""

from __future__ import annotations

import math
import re
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_stereo_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS, replicate_pytree

Rule = Tuple[str, P]

# The four collective families XLA SPMD inserts; shared with the HLO audits
# in tests/test_spatial.py and tests/test_sharding.py. The parser itself
# lives in tools/graftaudit/hlo.py — the tree's single HLO-text parser —
# and this module re-exports its helpers so existing call sites keep their
# import path.
from tools.graftaudit.hlo import (  # noqa: E402  (after package imports by design)
    COLLECTIVE_OPS,
    collective_counts,
    corr_collective_lines,
    unexpected_collectives,
)


# ---------------------------------------------------------------------------
# Rule matching
# ---------------------------------------------------------------------------


def _leaf_name(path) -> str:
    """'/'-join a jax key path into the flat name the rules match against."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - future key types
            parts.append(str(k))
    return "/".join(parts)


def _leaf_shape(leaf) -> Tuple[int, ...]:
    """Shape of an array-ish leaf; python scalars count as shape ()."""
    return tuple(getattr(leaf, "shape", ()))


def _is_scalar(leaf) -> bool:
    shape = _leaf_shape(leaf)
    return len(shape) == 0 or math.prod(shape) == 1


def validate_rules(rules: Sequence[Rule]) -> Tuple[Rule, ...]:
    """Compile-check a rule table: patterns must be valid regexes and the
    LAST rule must be the literal catch-all ``.*`` — every table is total by
    construction, so "unmatched leaf" can only happen with ad-hoc rule lists
    passed straight to :func:`match_partition_rules`."""
    rules = tuple(rules)
    if not rules:
        raise ValueError("empty sharding rule table")
    for pattern, spec in rules:
        re.compile(pattern)
        if not isinstance(spec, P):
            raise ValueError(f"rule {pattern!r}: spec must be a PartitionSpec, got {type(spec)}")
    if rules[-1][0] != ".*":
        raise ValueError(
            f"rule table must end with the catch-all ('.*', ...); last rule is {rules[-1][0]!r}"
        )
    return rules


def _match_leaf(rules: Sequence[Rule], name: str, leaf) -> Tuple[Optional[str], P]:
    """(winning pattern, spec) for one leaf. Scalars are never partitioned
    regardless of what any rule says — a PartitionSpec on a 0-d/1-element
    tensor is at best a no-op and at worst a shape error."""
    if _is_scalar(leaf):
        return None, P()
    for pattern, spec in rules:
        if re.search(pattern, name):
            ndim = len(_leaf_shape(leaf))
            if len(spec) > ndim:
                raise ValueError(
                    f"sharding rule {pattern!r} -> {spec} has rank {len(spec)} but leaf "
                    f"{name!r} has rank {ndim}"
                )
            return pattern, spec
    raise ValueError(
        f"no sharding rule matched leaf {name!r} (shape {_leaf_shape(leaf)}); "
        "add an explicit rule or a trailing ('.*', P()) catch-all"
    )


def match_partition_rules(rules: Sequence[Rule], tree) -> Any:
    """Map a rule table over a pytree: returns a tree of PartitionSpecs with
    the same structure. First match wins (``re.search`` over the '/'-joined
    leaf path); scalar leaves always get ``P()``; an unmatched leaf raises."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _match_leaf(rules, _leaf_name(path), leaf)[1], tree
    )


def explain_sharding(rules: Sequence[Rule], tree, label: str = "tree") -> str:
    """Human-readable dump of every leaf -> spec decision (the
    ``--explain_sharding`` payload): path, shape, the rule that won (or the
    scalar exemption), and the resulting PartitionSpec."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    lines = [f"# sharding decisions for {label} ({len(leaves)} leaves)"]
    for path, leaf in leaves:
        name = _leaf_name(path)
        pattern, spec = _match_leaf(rules, name, leaf)
        why = "scalar (never partitioned)" if pattern is None else f"rule {pattern!r}"
        lines.append(f"{name:<60s} shape={_leaf_shape(leaf)!s:<20s} {why:<32s} -> {spec}")
    return "\n".join(lines)


def make_shard_and_gather_fns(mesh: Mesh, spec_tree):
    """fmengine-style helper: from a tree of PartitionSpecs build matching
    trees of ``shard_fn(host_array) -> sharded jax.Array`` and
    ``gather_fn(jax.Array) -> host np.ndarray`` (gather replicates first, so
    it is checkpoint-safe for arbitrarily sharded leaves)."""

    def _shard_fn(spec):
        sharding = NamedSharding(mesh, spec)
        return lambda x: jax.device_put(x, sharding)

    def _gather_fn(spec):
        rep = NamedSharding(mesh, P())
        return lambda x: np.asarray(jax.device_get(jax.device_put(x, rep)))

    is_spec = lambda s: isinstance(s, P)
    shard_fns = jax.tree.map(_shard_fn, spec_tree, is_leaf=is_spec)
    gather_fns = jax.tree.map(_gather_fn, spec_tree, is_leaf=is_spec)
    return shard_fns, gather_fns


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------

# Batch pytree rules, shared by every preset: the (data, spatial) placement
# is the same everywhere — presets differ in mesh shape and activation
# constraints, not in how the input batch is laid out. On a (n, 1) mesh the
# spatial entry is inert and this is pure DP (the legacy layout, verbatim).
BATCH_RULES: Tuple[Rule, ...] = (
    (r"^(image1|image2|flow)$", P(DATA_AXIS, SPATIAL_AXIS, None, None)),
    (r"^valid$", P(DATA_AXIS, SPATIAL_AXIS, None)),
    # The token family's batch (train/families.py): rows over the data axis,
    # a row's positions (or its blocks' noise levels) whole.
    (r"^(tokens|masked|noise_t)$", P(DATA_AXIS, None)),
    (r".*", P()),
)

# Param/state rules: conv kernels in this model top out at ~1.3 MB, far below
# any useful tensor-parallel threshold, so the default presets replicate
# state; the table exists so an FSDP-ish placement is a one-line rule change
# — which `fsdp` below IS — and so the scalar exemption + catch-all machinery
# is exercised on the real tree.
REPLICATE_ALL: Tuple[Rule, ...] = ((r".*", P()),)

# FSDP-ish parameter placement: every conv kernel (HWIO, the only rank-4
# params in this family — and, via the mirrored adam mu/nu trees, the bulk of
# optimizer state) splits its output channels over the data axis; rank-1
# biases/scales and scalars fall through to the replicated catch-all. Kernels
# whose C_out does not divide the data axis (the disparity-native C_out=1
# flow head, the 126-channel motion-encoder conv on 4+-way meshes) are
# demoted to replicated by `ShardingEngine.state_specs` — same
# divide-evenly-or-leave-alone policy `constrain_spatial` applies to ragged
# pyramid levels.
# The `sdar-moe` tree (models/sdar_moe.py) has no leaf called `kernel`: its
# matrices are `w_*` with every layer stacked on a leading axis, and the
# same placement splits each one's LAST axis (a product's output features)
# over the data axis: the experts' (layers, experts, in, out), the attention
# and router matrices' (layers, in, out), the embedding's and the head's
# (rows, features) / (features, rows). Spreading the EXPERTS over an axis,
# with the all-to-all that needs, is not among these presets.
FSDP_RULES: Tuple[Rule, ...] = (
    (r"kernel$", P(None, None, None, DATA_AXIS)),
    (r"experts/w_(gate|up|down)$", P(None, None, None, DATA_AXIS)),
    (r"(attention/w_[qkvo]|router/w_router)$", P(None, None, DATA_AXIS)),
    (r"(embed/embedding|lm_head/w_head)$", P(None, DATA_AXIS)),
    (r".*", P()),
)

# The stereo family's train-batch template (name -> rank); mirrors what the
# data pipeline emits and what the legacy batch_sharding_tree hard-wired. A
# trainer of another family passes its own (train/families.py).
BATCH_TEMPLATE: Dict[str, int] = {"image1": 4, "image2": 4, "flow": 4, "valid": 3}


@dataclass(frozen=True)
class ShardingPreset:
    name: str
    param_rules: Tuple[Rule, ...]
    batch_rules: Tuple[Rule, ...]
    # Emit with_sharding_constraint on the corr pyramid + GRU hidden state
    # (H rows over SPATIAL_AXIS). Off for dp => graphs bit-identical to the
    # legacy hand-wired path.
    constrain_activations: bool
    # Whether the FULL forward is expected to carry collectives under this
    # preset (conv halo exchange, instance-norm partial reductions). The
    # corr chain itself must be collective-free whenever constraints are on.
    collectives_expected: bool
    description: str


PRESETS: Dict[str, ShardingPreset] = {
    "dp": ShardingPreset(
        name="dp",
        param_rules=validate_rules(REPLICATE_ALL),
        batch_rules=validate_rules(BATCH_RULES),
        constrain_activations=False,
        collectives_expected=False,
        description="pure data parallelism; legacy layout, bit-identical",
    ),
    "spatial": ShardingPreset(
        name="spatial",
        param_rules=validate_rules(REPLICATE_ALL),
        batch_rules=validate_rules(BATCH_RULES),
        constrain_activations=True,
        collectives_expected=True,
        description="H-row sharding; corr volume + GRU state split over chips",
    ),
    "dp+spatial": ShardingPreset(
        name="dp+spatial",
        param_rules=validate_rules(REPLICATE_ALL),
        batch_rules=validate_rules(BATCH_RULES),
        constrain_activations=True,
        collectives_expected=True,
        description="batch over data axis AND rows over spatial axis",
    ),
    "fsdp": ShardingPreset(
        name="fsdp",
        param_rules=validate_rules(FSDP_RULES),
        batch_rules=validate_rules(BATCH_RULES),
        constrain_activations=False,
        # Sharded params mean XLA all-gathers them at use sites (and
        # reduce-scatters grads) — collectives are the point, not a bug.
        collectives_expected=True,
        description="FSDP-ish: conv kernels + adam moments sharded over the "
        "data axis, batch over data (one-line rule-table change, as "
        "advertised)",
    ),
}


def resolve_mesh_shape(preset: str, n_devices: int, batch: int) -> Tuple[int, int]:
    """Default (data, spatial) mesh shape for a preset at a given device
    count and global batch. DP — and fsdp, whose batch layout is DP's —
    can only use as many chips as divide the batch (gcd keeps it even); the
    spatial presets always light up all chips, splitting leftover devices
    onto the spatial axis."""
    if preset not in PRESETS:
        raise ValueError(f"unknown sharding preset {preset!r}; have {sorted(PRESETS)}")
    d = math.gcd(max(batch, 1), n_devices)
    if preset in ("dp", "fsdp"):
        return (d, 1)
    if preset == "spatial":
        return (1, n_devices)
    return (d, n_devices // d)


# ---------------------------------------------------------------------------
# Activation constraints (trace-time mesh scope)
# ---------------------------------------------------------------------------

_ACTIVATION_MESH: Optional[Mesh] = None


@contextmanager
def activation_mesh(mesh: Optional[Mesh]) -> Iterator[None]:
    """Scope providing the mesh that `constrain_spatial` binds its
    NamedShardings to. Must be active whenever a graph with
    ``spatial_constraints=True`` is *traced*; `ShardingEngine.wrap` keeps it
    active around every call so lazy jit tracing always lands inside."""
    global _ACTIVATION_MESH
    prev = _ACTIVATION_MESH
    _ACTIVATION_MESH = mesh
    try:
        yield
    finally:
        _ACTIVATION_MESH = prev


def constrain_spatial(x, enabled: bool):
    """H-shard an activation (axis 1 = image rows) over SPATIAL_AXIS via
    with_sharding_constraint. Identity when disabled — the dp preset and all
    single-device paths trace the exact legacy graph. Model code calls this
    gated by ``cfg.spatial_constraints`` so the choice is jit-cache-keyed."""
    if not enabled:
        return x
    if getattr(x, "ndim", 0) < 2:
        return x
    mesh = _ACTIVATION_MESH
    if mesh is None:
        raise RuntimeError(
            "spatial_constraints=True but no activation mesh is in scope; trace/call "
            "through ShardingEngine.wrap(...) or inside sharding.activation_mesh(mesh)"
        )
    # Only constrain levels whose row count splits evenly over the axis:
    # pinning a coarse pyramid level with fewer/ragged rows (e.g. the 1/16-res
    # GRU state on small inputs) forces the partitioner to pad-and-gather
    # around every op touching it — exactly the spec-fighting the HLO audit
    # exists to catch. Uneven levels are left to SPMD propagation instead.
    if x.shape[1] % mesh.shape[SPATIAL_AXIS] != 0:
        return x
    spec = P(*([None, SPATIAL_AXIS] + [None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_spatial_tree(tree, enabled: bool):
    """`constrain_spatial` over every array leaf of a pytree (corr pyramids
    are tuples of per-level volumes)."""
    if not enabled:
        return tree
    return jax.tree.map(lambda t: constrain_spatial(t, True), tree)


@contextmanager
def _trace_scope(mesh: Mesh) -> Iterator[None]:
    """Both scopes a traced graph may need the mesh from: `activation_mesh`
    for the spatial constraints, and jax's own `jax.set_mesh` for the Pallas
    kernels, which `shard_map` themselves over it (ops/corr_pallas.py). jax
    keys its trace cache on the latter, so a graph traced outside the scope
    is never reused inside it."""
    with activation_mesh(mesh), jax.set_mesh(mesh):
        yield


class _ScopedFn:
    """Callable wrapper that enters `_trace_scope` around every call (and
    `.lower`), so tracing — whenever jit decides to do it — sees the mesh.
    Negligible per-call cost."""

    def __init__(self, fn, mesh: Mesh):
        self._fn = fn
        self._mesh = mesh

    def __call__(self, *args, **kwargs):
        with _trace_scope(self._mesh):
            return self._fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with _trace_scope(self._mesh):
            return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


# ---------------------------------------------------------------------------
# HLO collective audit
# ---------------------------------------------------------------------------
#
# `collective_counts`, `unexpected_collectives` and `corr_collective_lines`
# are re-exported verbatim from tools/graftaudit/hlo.py (imported at the top
# of this module) — ONE HLO parser in the tree; tests/test_graftaudit.py
# pins the delegation bit-for-bit against the legacy regexes.


def assert_no_collectives(hlo: str, context: str) -> None:
    """Raise if any collective family appears — the zero-communication claim
    for the H-sharded corr chain (and for pure-DP inference forwards)."""
    counts = {k: v for k, v in collective_counts(hlo).items() if v}
    if counts:
        raise AssertionError(f"unexpected collectives in {context}: {counts}")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class ShardingEngine:
    """Binds a preset's rule tables to a concrete mesh and hands out the
    NamedShardings / placement fns / trace scopes the rest of the system
    consumes. One engine per Trainer / serving engine / harness program."""

    def __init__(self, mesh: Mesh, rules: str = "dp"):
        if rules not in PRESETS:
            raise ValueError(f"unknown sharding preset {rules!r}; have {sorted(PRESETS)}")
        self.mesh = mesh
        self.preset = PRESETS[rules]

    # -- spec/shardings -----------------------------------------------------

    def _fit_spec(self, spec: P, shape: Tuple[int, ...]) -> P:
        """Demote sharded dims that don't split evenly over their mesh axis
        to replicated. The rule table names the INTENT (e.g. fsdp's "shard
        every kernel's C_out over data"); a leaf whose dim isn't divisible
        (the C_out=1 flow head) replicates instead of erroring at placement
        — the same divide-evenly-or-leave-alone policy `constrain_spatial`
        applies to ragged pyramid levels. No-op for fully replicated specs,
        so dp/spatial placements are byte-identical to before."""
        if all(a is None for a in spec):
            return spec
        axes = []
        changed = False
        for dim, axis in zip(shape, spec):
            if axis is None:
                axes.append(None)
                continue
            names = (axis,) if isinstance(axis, str) else tuple(axis)
            size = math.prod(self.mesh.shape[n] for n in names)
            if dim % size == 0:
                axes.append(axis)
            else:
                axes.append(None)
                changed = True
        return P(*axes) if changed else spec

    def state_specs(self, state_tree):
        def resolve(path, leaf):
            _, spec = _match_leaf(self.preset.param_rules, _leaf_name(path), leaf)
            return self._fit_spec(spec, _leaf_shape(leaf))

        return jax.tree_util.tree_map_with_path(resolve, state_tree)

    def state_shardings(self, state_tree):
        """Full NamedSharding tree for the train state (jit in/out_shardings)."""
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.state_specs(state_tree),
            is_leaf=lambda s: isinstance(s, P),
        )

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_shardings(self, template: Optional[Dict[str, int]] = None):
        """NamedSharding tree for the train batch, driven by the preset's
        batch rules over the canonical template (name -> rank)."""
        template = BATCH_TEMPLATE if template is None else template
        out = {}
        for name, ndim in template.items():
            probe = jax.ShapeDtypeStruct((2,) * ndim, np.float32)
            _, spec = _match_leaf(self.preset.batch_rules, name, probe)
            out[name] = NamedSharding(self.mesh, spec)
        return out

    def input_sharding(self, ndim: int = 4, name: Optional[str] = None) -> NamedSharding:
        """Sharding for a single input placed as the batch leaf `name` is.
        Without a name the input is image-like (the test-mode forward and
        serving staging path): `image1` at rank 4, `valid` below."""
        if name is None:
            name = "image1" if ndim == 4 else "valid"
        probe = jax.ShapeDtypeStruct((2,) * ndim, np.float32)
        _, spec = _match_leaf(self.preset.batch_rules, name, probe)
        return NamedSharding(self.mesh, spec)

    # -- placement ----------------------------------------------------------

    def place_state(self, state_tree):
        """Put the host-side train state on the mesh per the param rules.
        All-replicated trees take the multi-host-safe `replicate_pytree`
        path (no cross-process equality broadcast). Sharded rule tables
        (fsdp) place leaves per-process via `make_array_from_callback`:
        every host holds the SAME state by construction (same seeded init,
        same restored checkpoint — the replicate_pytree argument), so each
        process serves its addressable shards from its local copy and no
        collective runs. The gather side (`make_shard_and_gather_fns`) is
        checkpoint-safe for these arrays, and orbax saves/restores sharded
        leaves shard-wise."""
        specs = self.state_specs(state_tree)
        is_spec = lambda s: isinstance(s, P)
        flat_specs = jax.tree.leaves(specs, is_leaf=is_spec)
        if all(s == P() for s in flat_specs):
            return replicate_pytree(self.mesh, state_tree)
        if jax.process_count() > 1:

            def place(spec, x):
                sharding = NamedSharding(self.mesh, spec)
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    # Already a committed global array (orbax restores
                    # sharded leaves shard-wise straight onto the mesh):
                    # its bytes span other processes, so verify the layout
                    # instead of fetching it.
                    assert x.sharding.is_equivalent_to(sharding, x.ndim), (
                        x.sharding, sharding
                    )
                    return x
                host = np.asarray(x)
                return jax.make_array_from_callback(
                    host.shape, sharding, lambda idx: host[idx]
                )

            return jax.tree.map(place, specs, state_tree, is_leaf=is_spec)
        shard_fns, _ = make_shard_and_gather_fns(self.mesh, specs)
        return jax.tree.map(lambda fn, x: fn(x), shard_fns, state_tree)

    def place_batch(self, batch):
        """Place a host-side batch pytree per the batch rules (multi-host:
        per-process shards via make_array_from_process_local_data, same
        contract as the legacy mesh.shard_batch)."""
        multiprocess = jax.process_count() > 1

        def place(path, x):
            x = np.asarray(x)
            _, spec = _match_leaf(self.preset.batch_rules, _leaf_name(path), x)
            sharding = NamedSharding(self.mesh, spec)
            if multiprocess:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map_with_path(place, batch)

    # -- activation constraints / tracing scope -----------------------------

    @property
    def constrain_activations(self) -> bool:
        return self.preset.constrain_activations and self.mesh.shape[SPATIAL_AXIS] > 1

    def wrap(self, fn):
        """Wrap a jitted callable so tracing happens inside the mesh scope
        (`_ScopedFn`): the spatial presets' constraints bind to it, and on
        any multi-device mesh the Pallas kernels shard_map themselves over
        it. Identity on a one-device mesh — the single-chip path keeps the
        raw jit object."""
        if self.mesh.size == 1:
            return fn
        return _ScopedFn(fn, self.mesh)

    def scope(self):
        """Explicit activation-mesh context manager (harness/test use)."""
        return activation_mesh(self.mesh if self.constrain_activations else None)

    # -- introspection ------------------------------------------------------

    def explain(self, state_tree=None, batch_template: Optional[Dict[str, int]] = None) -> str:
        """The --explain_sharding dump: every leaf -> spec decision for the
        state tree and the batch template, plus the mesh and preset header."""
        d, s = self.mesh.shape[DATA_AXIS], self.mesh.shape[SPATIAL_AXIS]
        lines = [
            f"sharding preset: {self.preset.name} ({self.preset.description})",
            f"mesh: {d}x{s} (data x spatial) over {d * s} device(s)",
            f"activation constraints: "
            f"{'corr pyramid + GRU hidden over SPATIAL_AXIS' if self.constrain_activations else 'off'}",
        ]
        if state_tree is not None:
            lines.append(explain_sharding(self.preset.param_rules, state_tree, label="train state"))
        template = BATCH_TEMPLATE if batch_template is None else batch_template
        probe_tree = {
            name: jax.ShapeDtypeStruct((2,) * ndim, np.float32) for name, ndim in template.items()
        }
        lines.append(explain_sharding(self.preset.batch_rules, probe_tree, label="batch"))
        return "\n".join(lines)
