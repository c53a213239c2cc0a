"""Rule-driven sharding engine (parallel/sharding.py): rule-matching units,
preset placements on the 8-device virtual mesh, dp bit-identity vs the
unsharded step math, the spatial corr-chain collective audit, and the merged
coordination flag fetch.

The engine is the single source of every PartitionSpec in the system
(trainer step in/out shardings, batch placement, serving staging, activation
constraints), so these tests pin both the rule semantics and the end-to-end
numerics each preset promises: `dp` must reproduce the legacy hand-wired
layout bit-identically, `spatial` must H-shard the corr chain with zero
collectives inside it (the per-row epipolar-independence claim).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import TEST_H, TEST_W
from raft_stereo_tpu.config import SHARDING_PRESETS, RAFTStereoConfig, TrainConfig
from raft_stereo_tpu.ops.corr import corr_lookup, corr_pyramid, corr_volume
from raft_stereo_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS, make_mesh
from raft_stereo_tpu.parallel.sharding import (
    BATCH_RULES,
    PRESETS,
    ShardingEngine,
    corr_collective_lines,
    explain_sharding,
    make_shard_and_gather_fns,
    match_partition_rules,
    resolve_mesh_shape,
    unexpected_collectives,
    validate_rules,
)
from test_spatial import _assert_no_collectives

pytestmark = pytest.mark.sharding


# ---------------------------------------------------------------------------
# Rule matching units
# ---------------------------------------------------------------------------


def _arr(*shape):
    return np.zeros(shape, np.float32)


def test_first_match_wins_and_scalars_are_exempt():
    rules = (
        (r"kernel", P(DATA_AXIS, None)),
        (r"encoder/.*", P(None, SPATIAL_AXIS)),
        (r".*", P()),
    )
    tree = {
        "encoder": {"kernel": _arr(4, 4), "bias": _arr(4, 4)},
        "head": {"kernel": _arr(4, 4)},
        "step": np.float32(3.0),  # scalar: never partitioned, rules ignored
        "one": _arr(1),  # 1-element: also scalar-exempt
    }
    specs = match_partition_rules(rules, tree)
    # 'encoder/kernel' matches BOTH the kernel rule and the encoder rule;
    # first match wins.
    assert specs["encoder"]["kernel"] == P(DATA_AXIS, None)
    assert specs["encoder"]["bias"] == P(None, SPATIAL_AXIS)
    assert specs["head"]["kernel"] == P(DATA_AXIS, None)
    assert specs["step"] == P()
    assert specs["one"] == P()


def test_unmatched_leaf_is_a_hard_error():
    with pytest.raises(ValueError, match="no sharding rule matched"):
        match_partition_rules(((r"^kernel$", P()),), {"weird_leaf": _arr(2, 2)})


def test_rank_overflow_is_a_hard_error():
    with pytest.raises(ValueError, match="rank"):
        match_partition_rules(((r".*", P(None, None, SPATIAL_AXIS)),), {"x": _arr(4, 4)})


def test_validate_rules_requires_trailing_catch_all():
    with pytest.raises(ValueError, match="catch-all"):
        validate_rules(((r"^kernel$", P()),))
    with pytest.raises(ValueError, match="empty"):
        validate_rules(())
    with pytest.raises(ValueError, match="PartitionSpec"):
        validate_rules(((r".*", ("data",)),))


def test_explain_lists_every_leaf_with_winning_rule():
    tree = {"image1": _arr(2, 8, 8, 3), "step": np.float32(0)}
    text = explain_sharding(BATCH_RULES, tree, label="demo")
    assert "demo (2 leaves)" in text
    assert "image1" in text and "^(image1|image2|flow)$" in text
    assert "scalar (never partitioned)" in text


def test_presets_match_config_registry():
    # config.py validates TrainConfig.sharding_rules against SHARDING_PRESETS;
    # the engine resolves from PRESETS. Drift between them would make a
    # config validate and then fail inside the Trainer.
    assert set(SHARDING_PRESETS) == set(PRESETS)
    assert PRESETS["dp"].constrain_activations is False
    assert PRESETS["dp"].collectives_expected is False
    for name in ("spatial", "dp+spatial"):
        assert PRESETS[name].constrain_activations is True
        assert PRESETS[name].collectives_expected is True
    # fsdp keeps dp's activation story (no constraints) but EXPECTS
    # collectives: sharded params are all-gathered at use sites by design.
    assert PRESETS["fsdp"].constrain_activations is False
    assert PRESETS["fsdp"].collectives_expected is True


def test_resolve_mesh_shape():
    assert resolve_mesh_shape("dp", 8, 4) == (4, 1)
    assert resolve_mesh_shape("dp", 8, 8) == (8, 1)
    assert resolve_mesh_shape("dp", 8, 3) == (1, 1)  # gcd(3, 8) = 1
    assert resolve_mesh_shape("spatial", 8, 4) == (1, 8)
    assert resolve_mesh_shape("dp+spatial", 8, 4) == (4, 2)
    assert resolve_mesh_shape("dp+spatial", 8, 1) == (1, 8)
    # fsdp's batch layout IS dp's, so its mesh resolution matches dp.
    assert resolve_mesh_shape("fsdp", 8, 4) == (4, 1)
    assert resolve_mesh_shape("fsdp", 8, 8) == (8, 1)
    with pytest.raises(ValueError, match="unknown sharding preset"):
        resolve_mesh_shape("tensor_parallel", 8, 4)


def test_shard_and_gather_round_trip():
    mesh = make_mesh((2, 4))
    rules = ((r"big", P(DATA_AXIS, SPATIAL_AXIS)), (r".*", P()))
    tree = {"big": np.arange(64, dtype=np.float32).reshape(8, 8), "bias": _arr(3)}
    specs = match_partition_rules(rules, tree)
    shard_fns, gather_fns = make_shard_and_gather_fns(mesh, specs)
    placed = jax.tree.map(lambda fn, x: fn(x), shard_fns, tree)
    assert placed["big"].sharding.is_equivalent_to(
        NamedSharding(mesh, P(DATA_AXIS, SPATIAL_AXIS)), 2
    )
    assert {s.data.shape for s in placed["big"].addressable_shards} == {(4, 2)}
    back = jax.tree.map(lambda fn, x: fn(x), gather_fns, placed)
    np.testing.assert_array_equal(back["big"], tree["big"])
    np.testing.assert_array_equal(back["bias"], tree["bias"])


# ---------------------------------------------------------------------------
# Engine placements on the real model
# ---------------------------------------------------------------------------


def test_param_tree_specs_on_real_model(default_model_bundle):
    """The replicate-all presets replicate the real RAFTStereo param tree
    (rules are exercised over every leaf; conv kernels are too small to
    usefully shard by default), and every preset — fsdp included — keeps the
    (data, spatial) batch layout on the image dims."""
    _, _, variables = default_model_bundle
    for name in PRESETS:
        engine = ShardingEngine(make_mesh((2, 4)), name)
        if name != "fsdp":  # fsdp's param placement is pinned by the snapshot test
            specs = engine.state_specs(variables)
            flat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
            assert len(flat) > 50  # the whole real tree was matched
            assert all(s == P() for s in flat)
        batch = engine.batch_shardings()
        assert batch["image1"].spec == P(DATA_AXIS, SPATIAL_AXIS, None, None)
        assert batch["valid"].spec == P(DATA_AXIS, SPATIAL_AXIS, None)
        assert engine.input_sharding(4).spec == P(DATA_AXIS, SPATIAL_AXIS, None, None)


@pytest.mark.io_spine
def test_fsdp_param_tree_spec_snapshot(default_model_bundle):
    """Acceptance spec snapshot: under `fsdp` on a (2, 4) mesh, every conv
    kernel whose C_out divides the data axis carries
    P(None, None, None, 'data'); indivisible kernels (the C_out=1 flow head)
    demote to replicated via the divide-evenly-or-leave-alone fit policy,
    and every bias/scale/scalar falls through to the replicated catch-all."""
    _, _, variables = default_model_bundle
    engine = ShardingEngine(make_mesh((2, 4)), "fsdp")
    specs = engine.state_specs(variables)

    sharded = P(None, None, None, DATA_AXIS)
    param_leaves = jax.tree_util.tree_flatten_with_path(variables)[0]
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(param_leaves) == len(spec_leaves) > 50
    n_sharded = n_demoted = 0
    for (path, leaf), spec in zip(param_leaves, spec_leaves):
        last = path[-1]
        name = last.key if hasattr(last, "key") else str(last)
        shape = np.shape(leaf)
        if name == "kernel":
            assert len(shape) == 4, (path, shape)  # all kernels are HWIO conv
            if shape[-1] % 2 == 0:
                assert spec == sharded, (path, shape, spec)
                n_sharded += 1
            else:
                # Demotion rewrites the sharded axis to None positionally.
                assert all(a is None for a in spec), (path, shape, spec)
                n_demoted += 1
        else:
            assert spec == P(), (path, shape, spec)
    assert n_sharded > 20  # the bulk of the tree genuinely shards
    assert n_demoted >= 1  # the C_out=1 flow head exercises the demotion


def _synthetic_batch(rng, b, h, w, disparity=4.0):
    base = rng.uniform(0, 255, (b, h, w + 16, 3)).astype(np.float32)
    d = int(disparity)
    return {
        "image1": base[:, :, d : w + d].copy(),
        "image2": base[:, :, :w].copy(),
        "flow": np.full((b, h, w, 1), -disparity, np.float32),
        "valid": np.ones((b, h, w), np.float32),
    }


def test_dp_step_bit_identical_to_legacy_layout(tmp_path):
    """Acceptance: the dp preset reproduces the legacy hand-wired layout
    bit-identically. Reference = the exact pre-engine wiring (replicated
    state NamedSharding + the hard-wired batch tree + shard_batch placement)
    on the same (4, 1) mesh; the engine-wired step must match it array for
    array with zero tolerance. (An UNSHARDED single-device step is NOT the
    right oracle: the data-axis loss reduction reassociates at ~1e-7 rel.)"""
    from raft_stereo_tpu.parallel.mesh import replicate_pytree, replicated, shard_batch
    from raft_stereo_tpu.train.trainer import Trainer, make_train_step

    # Slim model: bit-identity is a claim about the WIRING (placements,
    # shardings, donation), not the architecture — the full-width train-step
    # backward is by far the most expensive compile in tier-1.
    h, w = 32, 48
    cfg = TrainConfig(
        model=dataclasses.replace(RAFTStereoConfig(), hidden_dims=(32, 32, 32), corr_levels=2),
        batch_size=4,
        num_steps=1,
        train_iters=2,
        mesh_shape=(4, 1),
        checkpoint_every=10**9,
        checkpoint_dir=str(tmp_path),
    )
    trainer = Trainer(cfg, sample_shape=(h, w, 3))
    assert trainer.sharding.preset.name == "dp"
    assert not trainer.sharding.constrain_activations
    # Param placement: fully replicated, one copy per device.
    for leaf in jax.tree.leaves(trainer.state.params)[:3]:
        assert leaf.sharding.is_equivalent_to(trainer.sharding.replicated(), leaf.ndim)

    batch = _synthetic_batch(np.random.default_rng(7), 4, h, w)
    host_state = jax.device_get(trainer.state)

    new_state, metrics = trainer.train_step(trainer.state, trainer.sharding.place_batch(batch))

    # The legacy wiring, verbatim (trainer.py through PR 7): one replicated
    # NamedSharding broadcast over the state tree, the hand-built batch
    # sharding dict, shard_batch placement.
    mesh = trainer.mesh
    rep = replicated(mesh)
    s4 = NamedSharding(mesh, P(DATA_AXIS, SPATIAL_AXIS, None, None))
    s3 = NamedSharding(mesh, P(DATA_AXIS, SPATIAL_AXIS, None))
    legacy_batch_sh = {"image1": s4, "image2": s4, "flow": s4, "valid": s3}
    ref_step = jax.jit(
        make_train_step(trainer.config, trainer.tx, trainer.schedule),
        in_shardings=(rep, legacy_batch_sh),
        out_shardings=(rep, rep),
    )
    ref_state, ref_metrics = ref_step(
        replicate_pytree(mesh, host_state), shard_batch(mesh, batch)
    )

    np.testing.assert_array_equal(
        np.asarray(jax.device_get(metrics["live_loss"])),
        np.asarray(jax.device_get(ref_metrics["live_loss"])),
    )
    got_params = jax.device_get(new_state.params)
    want_params = jax.device_get(ref_state.params)
    jax.tree.map(np.testing.assert_array_equal, got_params, want_params)


# ---------------------------------------------------------------------------
# Spatial preset: corr-chain collective audit + forward parity
# ---------------------------------------------------------------------------


def test_engine_spatial_corr_chain_audits_clean():
    """The corr volume/pyramid/lookup chain, jitted with ENGINE-derived
    shardings and the engine's activation-constraint scope, compiles with
    zero collectives and matches the unsharded chain bit-exactly."""
    from raft_stereo_tpu.parallel.sharding import constrain_spatial_tree

    engine = ShardingEngine(make_mesh((1, 8)), "spatial")
    assert engine.constrain_activations
    b, h, w, d = 2, 64, 24, 64
    rng = np.random.default_rng(0)
    f1 = jnp.asarray(rng.normal(size=(b, h, w, d)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(b, h, w, d)).astype(np.float32))
    coords = jnp.tile(jnp.arange(w, dtype=jnp.float32)[None, None, :], (b, h, 1))

    def chain(f1, f2, coords, constrain):
        pyr = corr_pyramid(corr_volume(f1, f2), num_levels=4)
        pyr = constrain_spatial_tree(pyr, constrain)
        return pyr[0], corr_lookup(pyr, coords, radius=4)

    sh4, sh3 = engine.input_sharding(4), engine.input_sharding(3)
    jitted = engine.wrap(
        jax.jit(
            lambda a, b_, c: chain(a, b_, c, True),
            in_shardings=(sh4, sh4, sh3),
            out_shardings=(sh4, sh4),
        )
    )
    hlo = jitted.lower(f1, f2, coords).compile().as_text()
    _assert_no_collectives(hlo, "engine-sharded corr chain")

    vol, taps = jitted(f1, f2, coords)
    assert {s.data.shape for s in vol.addressable_shards} == {(b, h // 8, w, w)}
    vol_ref, taps_ref = jax.jit(lambda a, b_, c: chain(a, b_, c, False))(f1, f2, coords)
    np.testing.assert_array_equal(np.asarray(vol), np.asarray(vol_ref))
    np.testing.assert_array_equal(np.asarray(taps), np.asarray(taps_ref))


def test_engine_spatial_forward_matches_unsharded(default_model_bundle):
    """Full-model forward under the spatial preset (H-sharded inputs +
    activation constraints on corr pyramid / GRU state) matches the
    unsharded forward. The constraint flag changes no params, so the
    session bundle's variables drive both sides. The compiled module also
    passes the no-unexpected-collectives audit: halo permutes, norm
    reductions, and coarse-level gathers only — nothing inside the corr
    chain, no all-to-all anywhere."""
    cfg, model, variables = default_model_bundle
    engine = ShardingEngine(make_mesh((1, 8)), "spatial")
    smodel = type(model)(dataclasses.replace(cfg, spatial_constraints=True))

    rng = np.random.default_rng(5)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)).astype(np.float32))

    sh = engine.input_sharding(4)
    sharded = engine.wrap(
        jax.jit(
            lambda v, a, b: smodel.apply(v, a, b, iters=2, test_mode=True)[1],
            in_shardings=(engine.replicated(), sh, sh),
            out_shardings=sh,
        )
    )
    hlo = sharded.lower(variables, i1, i2).compile().as_text()
    assert not unexpected_collectives(hlo, ("collective-permute", "all-reduce", "all-gather"))
    assert not corr_collective_lines(hlo)

    got = sharded(variables, i1, i2)
    assert {s.data.shape for s in got.addressable_shards} == {(1, TEST_H // 8, TEST_W, 1)}
    want = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=2, test_mode=True)[1])(
        variables, i1, i2
    )
    # Cross-H reductions (instance norm) reassociate under sharding; same
    # tolerance as tests/test_spatial.py.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


def test_engine_spatial_fullres_batched_forward_runs(default_model_bundle):
    """ISSUE acceptance: full-res (Middlebury-F height 1984, narrow-W CPU
    proxy) BATCHED forward runs under the spatial preset with every
    sharding coming from the engine. Numeric parity at this shape is pinned
    by tests/test_spatial.py; here the engine-driven program must execute
    batched and keep the promised H/8-row per-device layout."""
    cfg, model, variables = default_model_bundle
    engine = ShardingEngine(make_mesh((1, 8)), "spatial")
    smodel = type(model)(dataclasses.replace(cfg, spatial_constraints=True))
    fullres_h, narrow_w, b = 1984, 96, 2

    rng = np.random.default_rng(9)
    i1 = jnp.asarray(rng.uniform(0, 255, (b, fullres_h, narrow_w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (b, fullres_h, narrow_w, 3)).astype(np.float32))

    sh = engine.input_sharding(4)
    fwd = engine.wrap(
        jax.jit(
            lambda v, a, c: smodel.apply(v, a, c, iters=2, test_mode=True)[1],
            in_shardings=(engine.replicated(), sh, sh),
            out_shardings=sh,
        )
    )
    flow = fwd(variables, jax.device_put(i1, sh), jax.device_put(i2, sh))
    assert {s.data.shape for s in flow.addressable_shards} == {(b, fullres_h // 8, narrow_w, 1)}
    assert np.isfinite(np.asarray(flow)).all()


def test_constraints_require_mesh_scope():
    """Tracing a constrained graph OUTSIDE the engine scope is a hard error,
    not a silent unconstrained cache entry."""
    from raft_stereo_tpu.parallel.sharding import constrain_spatial

    with pytest.raises(RuntimeError, match="no activation mesh"):
        jax.jit(lambda x: constrain_spatial(x, True))(jnp.zeros((2, 8, 4)))
    # A one-device engine hands back the raw callable: no scope wrapper, no
    # overhead. On several devices every preset scopes — the Pallas kernels
    # find the mesh they must shard_map over there (dp included).
    fn = jax.jit(lambda x: x)
    assert ShardingEngine(make_mesh((1, 1)), "dp").wrap(fn) is fn
    assert ShardingEngine(make_mesh((8, 1)), "dp").wrap(fn) is not fn


# ---------------------------------------------------------------------------
# Merged coordination fetch (satellite: parallel/coordination.py)
# ---------------------------------------------------------------------------


def test_merged_coordination_fetch_adds_no_syncs_or_executables(monkeypatch):
    """The pod-flag all-reduce result rides the SAME jax.device_get as the
    step's pending nonfinite-flag window (one-window-lag fold, the PR-2 cost
    question). Regression, via RecompileMonitor + a counted jax.device_get:
    after the first sync compiles the flag-reduce program once, N further
    sync boundaries add ZERO extra executables and ZERO device->host syncs
    beyond the one bulk fetch the nan-flag drain performs anyway — submit()
    dispatches async and complete() is pure host math."""
    from raft_stereo_tpu.parallel import coordination
    from raft_stereo_tpu.utils.jit_hygiene import RecompileMonitor

    # Fake a 2-process pod: process_topology drives coord.active; with one
    # real process the flag reduce runs as a single-program reduction.
    monkeypatch.setattr(coordination, "process_topology", lambda: (0, 2))
    coord = coordination.HostCoordinator()
    assert coord.active

    fetches = [0]
    real_get = jax.device_get

    def counted_get(x):
        fetches[0] += 1
        return real_get(x)

    # A pending nonfinite-flag window like the trainer accumulates: one
    # device scalar per step since the last drain.
    def window():
        return [jnp.float32(0.0) for _ in range(4)]

    with RecompileMonitor(hard_fail=False, label="coord_first") as warm:
        handle = coord.submit(stop=False)
        decision = coord.complete(counted_get(window() + [handle])[-1])
    assert not decision.stop
    assert warm.compiles_total >= 1  # the reduce program, compiled ONCE
    assert fetches[0] == 1

    fetches[0] = 0
    monkeypatch.setattr(jax, "device_get", counted_get)
    with RecompileMonitor(hard_fail=False, label="coord_steady") as mon:
        for step in range(3):
            before = fetches[0]
            handle = coord.submit(stop=False, dropped=step)
            assert fetches[0] == before  # submit never round-trips to the host
            fetched = counted_get(window() + [handle])  # the drain's own fetch
            decision = coord.complete(fetched[-1])
            assert fetches[0] == before + 1  # complete is pure host math
            assert not decision.nonfinite
    monkeypatch.setattr(jax, "device_get", real_get)
    # Steady state: one merged fetch per boundary (the window fetch that the
    # nan drain performs regardless), zero new executables.
    assert fetches[0] == 3
    assert mon.compiles_total == 0, mon.compiles_total

    # Single-host fast path: submit is a host tuple — no device work at all.
    monkeypatch.setattr(coordination, "process_topology", lambda: (0, 1))
    local = coordination.HostCoordinator()
    assert not local.active
    with RecompileMonitor(hard_fail=False, label="coord_local") as lmon:
        h = local.submit(stop=True)
        d = local.complete(jax.device_get(h))
    assert d.stop and lmon.compiles_total == 0
