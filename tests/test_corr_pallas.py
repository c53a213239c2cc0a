"""Fused Pallas lookup parity vs the pure-jnp "reg" path.

On the CPU test mesh the kernel runs in Pallas interpreter mode; the math is
identical to the compiled Mosaic path (same kernel body), so these tests pin
the semantics the TPU build must reproduce. The gradient contract is the
reference CUDA sampler's: d(volume) only, no coords grad (core/corr.py:24-29).
"""

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.ops import corr_lookup, corr_pyramid, corr_volume, make_corr_fn
from raft_stereo_tpu.ops.corr_pallas import (
    make_pallas_corr_fn,
    pad_pyramid,
    pallas_corr_lookup,
    pallas_corr_lookup_padded,
    pallas_corr_state,
)

B, H, W, D = 2, 4, 24, 16
LEVELS, RADIUS = 4, 4


def make_inputs(rng, w=W):
    f1 = rng.standard_normal((B, H, w, D)).astype(np.float32)
    f2 = rng.standard_normal((B, H, w, D)).astype(np.float32)
    coords = rng.uniform(-6, w + 6, size=(B, H, w)).astype(np.float32)
    return jnp.asarray(f1), jnp.asarray(f2), jnp.asarray(coords)


def test_pallas_matches_reg(rng):
    f1, f2, coords = make_inputs(rng)
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)
    want = corr_lookup(pyr, coords, RADIUS)
    got = pallas_corr_lookup(pyr, coords, RADIUS)
    assert got.shape == (B, H, W, LEVELS * (2 * RADIUS + 1))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_pallas_matches_reg_wide_multi_tile(rng):
    """W2 > 128 forces the multi-tile masked-gather path."""
    f1, f2, coords = make_inputs(rng, w=300)
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)
    want = corr_lookup(pyr, coords, RADIUS)
    got = jax.jit(lambda p, c: pallas_corr_lookup(p, c, RADIUS))(pyr, coords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_pallas_bf16_pyramid(rng):
    f1, f2, coords = make_inputs(rng)
    state16 = pallas_corr_state(f1, f2, LEVELS, corr_dtype=jnp.bfloat16)
    assert state16[0].dtype == jnp.bfloat16
    got16 = pallas_corr_lookup_padded(state16, coords, RADIUS)
    assert got16.dtype == jnp.float32
    pyr16 = corr_pyramid(corr_volume(f1, f2, out_dtype=jnp.bfloat16), LEVELS)
    want16 = corr_lookup(pyr16, coords, RADIUS)
    np.testing.assert_allclose(np.asarray(got16), np.asarray(want16), rtol=1e-6, atol=1e-6)


def test_padded_state_matches_unpadded_wrapper(rng):
    """pallas_corr_state pre-pads to the kernel layout (pads hoisted out of
    the iteration loop); results must be bit-identical to padding per call."""
    f1, f2, coords = make_inputs(rng, w=300)
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)
    padded = pad_pyramid(pyr, coords.shape)
    got = pallas_corr_lookup_padded(padded, coords, RADIUS)
    want = pallas_corr_lookup(pyr, coords, RADIUS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


def test_pallas_volume_grads_match_reg_and_coords_grad_zero(rng):
    f1, f2, coords = make_inputs(rng)
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)

    def loss_pallas(p, c):
        return pallas_corr_lookup(p, c, RADIUS).sum()

    def loss_reg(p, c):
        return corr_lookup(p, c, RADIUS).sum()

    gp, gc = jax.grad(loss_pallas, argnums=(0, 1))(pyr, coords)
    rp, _ = jax.grad(loss_reg, argnums=(0, 1))(pyr, coords)
    for a, b in zip(gp, rp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gc), 0.0)


def test_model_forward_pallas_matches_reg(rng, default_model_bundle):
    """End-to-end: the corr implementation is a pure compute-strategy switch —
    identical params, identical outputs (reference analogue: the four
    interchangeable corr blocks, core/raft_stereo.py:90-100)."""
    import dataclasses

    from raft_stereo_tpu.models import RAFTStereo

    cfg, model, variables = default_model_bundle
    h, w = 48, 64
    img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, cfg.in_channels)).astype(np.float32))
    img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, cfg.in_channels)).astype(np.float32))

    pallas_model = RAFTStereo(dataclasses.replace(cfg, corr_implementation="pallas"))

    def fwd(m):
        return jax.jit(
            lambda v, a, b: m.apply(v, a, b, iters=3, test_mode=True)[1]
        )(variables, img1, img2)

    want = fwd(model)
    got = fwd(pallas_model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_make_corr_fn_pallas_strategy(rng):
    f1, f2, coords = make_inputs(rng)
    reg = make_corr_fn("reg", f1, f2, LEVELS, RADIUS)(coords)
    pal = make_corr_fn("pallas", f1, f2, LEVELS, RADIUS)(coords)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(reg), rtol=1e-6, atol=1e-6)
    direct = make_pallas_corr_fn(f1, f2, LEVELS, RADIUS)(coords)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(pal), rtol=0, atol=0)


def test_pallas_wide_w1_block_split(rng):
    """w1 just above one block (800 > 768) must split into minimal blocks,
    not round up to 2x768 — and stay exact."""
    B2, H2, W2, D2 = 1, 2, 800, 8
    f1 = jnp.asarray(rng.standard_normal((B2, H2, W2, D2)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((B2, H2, W2, D2)).astype(np.float32))
    coords = jnp.asarray(rng.uniform(-6, W2 + 6, (B2, H2, W2)).astype(np.float32))
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)
    want = corr_lookup(pyr, coords, RADIUS)
    got = pallas_corr_lookup(pyr, coords, RADIUS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_padded_lookup_rejects_unpadded_state(rng):
    """A state not built by pad_pyramid must raise, not silently drop taps
    (the tile loops truncate at the last full 128-lane tile)."""
    import pytest

    f1, f2, coords = make_inputs(rng, w=200)
    pyr = corr_pyramid(corr_volume(f1, f2), LEVELS)
    bad = (pyr[0].reshape(B * H, 200, 200),)  # lane dim 200: not a 128 multiple
    with pytest.raises(ValueError):
        pallas_corr_lookup_padded(bad, coords, RADIUS)


# --- several devices: the kernels shard_map themselves over the data axis ---


def _value_and_grad(state, coords):
    def loss(state):
        taps = pallas_corr_lookup_padded(state, coords, RADIUS)
        return jnp.sum(jnp.square(taps)), taps

    (_, taps), grad = jax.value_and_grad(loss, has_aux=True)(state)
    return taps, grad


def test_lookup_and_scatter_on_a_data_mesh_match_one_device(rng):
    """Under a data mesh's trace scope the lookup and its scatter backward
    run per device on that device's rows (a Mosaic kernel cannot be
    partitioned by XLA), with the same bits as the unsharded call and the
    batch sharding carried through."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_stereo_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from raft_stereo_tpu.parallel.sharding import ShardingEngine

    f1, f2, coords = make_inputs(rng)
    state = pallas_corr_state(f1, f2, LEVELS)
    want_taps, want_grad = jax.jit(_value_and_grad)(state, coords)

    mesh = make_mesh((2, 1), devices=jax.devices()[:2])
    rows = NamedSharding(mesh, P(DATA_AXIS))
    sharded = ShardingEngine(mesh, "dp").wrap(jax.jit(_value_and_grad))
    taps, grad = sharded(jax.device_put(state, rows), jax.device_put(coords, rows))
    np.testing.assert_array_equal(np.asarray(taps), np.asarray(want_taps))
    for got, want in zip(grad, want_grad):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.sharding.is_equivalent_to(rows, got.ndim)
    # The one-device trace above is not reused: jax keys traces on the mesh.
    with jax.set_mesh(mesh):
        assert "shard_map" in str(jax.make_jaxpr(_value_and_grad)(state, coords))


def test_lookup_on_a_spatial_mesh_is_refused(rng):
    """The kernels are wired for the data axis only; an H-sharded mesh is an
    error at trace time, not a silently different program."""
    import pytest

    from raft_stereo_tpu.parallel.mesh import make_mesh
    from raft_stereo_tpu.parallel.sharding import ShardingEngine

    f1, f2, coords = make_inputs(rng)
    state = pallas_corr_state(f1, f2, LEVELS)
    engine = ShardingEngine(make_mesh((1, 2), devices=jax.devices()[:2]), "spatial")
    lookup = engine.wrap(jax.jit(lambda s, c: pallas_corr_lookup_padded(s, c, RADIUS)))
    with pytest.raises(NotImplementedError, match="data mesh axis only"):
        lookup(state, coords)
