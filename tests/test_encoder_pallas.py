"""Fused encoder-kernel parity vs the XLA blocks (ops/encoder_pallas.py,
ops/corr_pallas.fused_pyramid_state).

On the CPU test mesh the kernels run in Pallas interpreter mode — the same
kernel bodies the TPU build compiles, so these tests pin the semantics the
Mosaic path must reproduce: implicit-GEMM conv parity, in-register
norm/relu/join epilogues, grid-accumulated InstanceNorm statistics, the
manual-DMA row ring, and dtype-pinned stores (the bf16 cases fail loudly if
any store silently widens — the GL007 contract).

Marked `kernels` (tier-1, CPU-safe, small shapes): select with -m kernels.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.models.layers import (
    _conv_s2d,
    dense_w_kernel,
    s2d_instance_norm,
)
from raft_stereo_tpu.ops.corr_pallas import fused_pyramid_state, pallas_corr_state
from raft_stereo_tpu.ops.encoder_pallas import (
    bn_affine,
    fused_conv_s2d,
    fused_join_s2d,
    fused_layer1_s2d,
    instance_affine_from_stats,
)

pytestmark = pytest.mark.kernels

B, H, W2, C = 2, 6, 8, 64
C2 = 2 * C


def _conv_weights(rng, n=1, c=C):
    out = []
    for _ in range(n):
        k = jnp.asarray(rng.standard_normal((3, 3, c, c)).astype(np.float32) * 0.1)
        b = jnp.asarray(rng.standard_normal((c,)).astype(np.float32) * 0.1)
        out.append((dense_w_kernel(k), jnp.tile(b, 2)))
    return out if n > 1 else out[0]


def _xla_block_in(y, parts):
    """ResidualBlockS2D math under instance norm, raw arrays."""
    (w1, b1), (w2, b2) = parts
    z = _conv_s2d(y, w1, b1, (1, 1), ((1, 1), (1, 1)))
    z = nn.relu(s2d_instance_norm(z))
    z = _conv_s2d(z, w2, b2, (1, 1), ((1, 1), (1, 1)))
    z = nn.relu(s2d_instance_norm(z))
    return nn.relu(y + z)


def test_fused_conv_matches_xla_s2d_conv(rng):
    x = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    w, b = _conv_weights(rng)
    want = _conv_s2d(x, w, b, (1, 1), ((1, 1), (1, 1)))
    got, stats = jax.jit(
        lambda x, w, b: fused_conv_s2d(x, w, b, None, "none", emit_stats=True)
    )(x, w, b)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # Grid-accumulated stats must equal full-tensor reductions of the
    # STORED output (what s2d_instance_norm computes from).
    ws = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(stats[:, 0]), ws.sum(axis=(1, 2)), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(stats[:, 1]), (ws.astype(np.float64) ** 2).sum(axis=(1, 2)),
        rtol=1e-4, atol=1e-4,
    )


def test_fused_conv_single_row_and_tall(rng):
    """H=1 (stencil fully masked) and H > ring depth exercise the DMA ring's
    prologue/epilogue edges."""
    w, b = _conv_weights(rng)
    for hh in (1, 2, 9):
        x = jnp.asarray(rng.standard_normal((1, hh, W2, C2)).astype(np.float32))
        want = _conv_s2d(x, w, b, (1, 1), ((1, 1), (1, 1)))
        got, _ = jax.jit(
            lambda x, w, b: fused_conv_s2d(x, w, b, None, "none")
        )(x, w, b)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5, err_msg=f"H={hh}"
        )


def test_fused_conv_instance_affine_input_stage(rng):
    """relu((x - mean) * inv) folded into the conv operand read must match
    the XLA normalize-then-conv chain, including the 'same' zero padding of
    the NORMALIZED operand at the H edges."""
    x = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    w, b = _conv_weights(rng)
    y1, stats = jax.jit(
        lambda x, w, b: fused_conv_s2d(x, w, b, None, "none", emit_stats=True)
    )(x, w, b)
    aff = instance_affine_from_stats(stats, H * W2 * 2)
    got, _ = jax.jit(
        lambda y, w, b, a: fused_conv_s2d(y, w, b, a, "in")
    )(y1, w, b, aff)
    z = nn.relu(s2d_instance_norm(_conv_s2d(x, w, b, (1, 1), ((1, 1), (1, 1)))))
    want = _conv_s2d(z, w, b, (1, 1), ((1, 1), (1, 1)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_fused_join_matches_xla_tail(rng):
    x = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    s = jnp.sum(y, axis=(1, 2), dtype=jnp.float32)
    sq = jnp.sum(jnp.square(y), axis=(1, 2), dtype=jnp.float32)
    aff = instance_affine_from_stats(jnp.stack([s, sq], axis=1), H * W2 * 2)
    got = jax.jit(lambda s, y, a: fused_join_s2d(s, y, a, "in"))(x, y, aff)
    want = nn.relu(x + nn.relu(s2d_instance_norm(y)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_layer1_chain_instance(rng):
    x = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    p0, p1 = _conv_weights(rng, 2), _conv_weights(rng, 2)
    x_in = nn.relu(s2d_instance_norm(x))
    want = _xla_block_in(_xla_block_in(x_in, p0), p1)

    s = jnp.sum(x, axis=(1, 2), dtype=jnp.float32)
    sq = jnp.sum(jnp.square(x), axis=(1, 2), dtype=jnp.float32)
    aff0 = instance_affine_from_stats(jnp.stack([s, sq], axis=1), H * W2 * 2)
    blocks = [p[0] + p[1] + (None, None) for p in (p0, p1)]
    got = jax.jit(lambda x, a: fused_layer1_s2d(x, a, blocks, "instance"))(x, aff0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)


def test_fused_layer1_chain_batch(rng):
    x = jnp.asarray(rng.standard_normal((B, H, W2, C2)).astype(np.float32))
    p0, p1 = _conv_weights(rng, 2), _conv_weights(rng, 2)

    def bn():
        inv = jnp.tile(jnp.asarray(rng.uniform(0.5, 2.0, (C,)).astype(np.float32)), 2)
        sh = jnp.tile(jnp.asarray(rng.standard_normal((C,)).astype(np.float32) * 0.1), 2)
        return inv, sh

    a0, a1, a2, a3, a4 = bn(), bn(), bn(), bn(), bn()

    def block(y, parts, aa, ab):
        (w1, b1), (w2, b2) = parts
        z = _conv_s2d(y, w1, b1, (1, 1), ((1, 1), (1, 1)))
        z = nn.relu(z * aa[0] + aa[1])
        z = _conv_s2d(z, w2, b2, (1, 1), ((1, 1), (1, 1)))
        z = nn.relu(z * ab[0] + ab[1])
        return nn.relu(y + z)

    x_in = nn.relu(x * a0[0] + a0[1])
    want = block(block(x_in, p0, a1, a2), p1, a3, a4)

    blocks = [
        p0[0] + p0[1] + (bn_affine(*a1, B), bn_affine(*a2, B)),
        p1[0] + p1[1] + (bn_affine(*a3, B), bn_affine(*a4, B)),
    ]
    got = jax.jit(
        lambda x, a: fused_layer1_s2d(x, a, blocks, "batch")
    )(x, bn_affine(*a0, B))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)


def test_fused_conv_bf16_store_dtype_pinned(rng):
    """bf16 operands must produce bf16 stores (fp32 accumulation happens on
    the MXU, the STORE is rounded) — the GL007 dtype-pinning contract, and
    the mixed-precision path the bench runs."""
    x = jnp.asarray(
        rng.standard_normal((1, H, W2, C2)).astype(np.float32)
    ).astype(jnp.bfloat16)
    w, b = _conv_weights(rng)
    got, stats = jax.jit(
        lambda x, w, b: fused_conv_s2d(x, w.astype(jnp.bfloat16), b, None, "none", emit_stats=True)
    )(x, w, b)
    assert got.dtype == jnp.bfloat16
    assert stats.dtype == jnp.float32  # stats stay fp32 like the XLA reductions
    want = _conv_s2d(x, w.astype(jnp.bfloat16), b, (1, 1), ((1, 1), (1, 1)))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0.1, atol=0.1
    )
    aff = instance_affine_from_stats(stats, H * W2 * 2)
    joined = jax.jit(lambda s, y, a: fused_join_s2d(s, y, a, "in"))(x, got, aff)
    assert joined.dtype == jnp.bfloat16


def test_fused_layer1_rejects_bad_norm():
    x = jnp.zeros((1, 2, 4, C2))
    with pytest.raises(ValueError):
        fused_layer1_s2d(x, jnp.zeros((1, 2, C2)), [], "group")
    with pytest.raises(ValueError):
        fused_conv_s2d(x, jnp.zeros((3, 3, C2, C2)), jnp.zeros((C2,)), None, "in")


# --- fused corr volume+pyramid+pad kernel ---------------------------------


# float32 tolerance of the fused pyramid against `pallas_corr_state` on the
# CPU. The kernel contracts each row against fmap2 zero-padded to a 128-lane
# tile, the reference against the true W2, and XLA:CPU picks its dot tiling —
# and with it the order in which the D products are summed — from that N
# dimension. Traced under jax 0.9.0: a plain jitted (24x16)·(128x16)ᵀ dot
# reproduces the kernel's level-0 bits exactly and the (24x16)·(24x16)ᵀ one
# the reference's; they differ by <= 6e-7 at |v| <= 4.5 (a few float32 ulp,
# both within 7e-7 of the float64 product). Codegen, not the kernel's math.
_F32_SUM_ORDER_TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("corr_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_pyramid_matches_pallas_corr_state(rng, corr_dtype):
    f1 = jnp.asarray(rng.standard_normal((2, 4, 24, 16)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((2, 4, 24, 16)).astype(np.float32))
    want = pallas_corr_state(f1, f2, 4, corr_dtype=corr_dtype)
    got = jax.jit(lambda a, b: fused_pyramid_state(a, b, 4, corr_dtype=corr_dtype))(f1, f2)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        if corr_dtype == jnp.bfloat16:
            # bf16 operands multiply exactly in float32 and the store rounds
            # to bf16: identical rounding points, bit-parity.
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), **_F32_SUM_ORDER_TOL
            )


def test_fused_pyramid_odd_width_floor_semantics(rng):
    """Odd level widths must trim the last sample (avg_pool floor
    semantics) and keep the padded lanes exactly zero — the lookup kernel
    treats stored pad values as real taps."""
    f1 = jnp.asarray(rng.standard_normal((1, 2, 37, 16)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((1, 2, 37, 16)).astype(np.float32))
    want = pallas_corr_state(f1, f2, 3)
    got = jax.jit(lambda a, b: fused_pyramid_state(a, b, 3))(f1, f2)
    widths = [37, 18, 9]
    for g, w, tw in zip(got, want, widths):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **_F32_SUM_ORDER_TOL)
        assert not np.any(np.asarray(g)[:, :, tw:])  # pads exactly zero


def test_fused_pyramid_wide_multi_block(rng):
    """W1 > one block exercises the (rows, w1_blocks) grid split."""
    f1 = jnp.asarray(rng.standard_normal((1, 2, 800, 8)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((1, 2, 800, 8)).astype(np.float32))
    want = pallas_corr_state(f1, f2, 4)
    got = jax.jit(lambda a, b: fused_pyramid_state(a, b, 4))(f1, f2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **_F32_SUM_ORDER_TOL)


def test_fused_pyramid_feeds_lookup(rng):
    """The fused state must be consumable by pallas_corr_lookup_padded
    unchanged (no layout boundary faces the iteration loop)."""
    from raft_stereo_tpu.ops.corr_pallas import pallas_corr_lookup_padded

    f1 = jnp.asarray(rng.standard_normal((1, 3, 24, 16)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((1, 3, 24, 16)).astype(np.float32))
    coords = jnp.asarray(rng.uniform(-4, 28, (1, 3, 24)).astype(np.float32))
    want = pallas_corr_lookup_padded(pallas_corr_state(f1, f2, 4), coords, 4)
    got = pallas_corr_lookup_padded(
        jax.jit(lambda a, b: fused_pyramid_state(a, b, 4))(f1, f2), coords, 4
    )
    # The taps interpolate pyramid values that agree to _F32_SUM_ORDER_TOL.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_F32_SUM_ORDER_TOL)


# --- model-level integration ----------------------------------------------


def test_model_forward_fused_matches_xla(rng, default_model_bundle):
    """fused_encoder is a pure compute-strategy switch: identical params,
    same outputs up to fp32 reassociation (the recurrent refinement
    amplifies the encoder's ~1e-5 conv reassociation noise, hence the
    looser tolerance than the corr-strategy parity test)."""
    from raft_stereo_tpu.models import RAFTStereo

    cfg, model, variables = default_model_bundle
    h, w = 48, 64
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, cfg.in_channels)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, cfg.in_channels)).astype(np.float32))
    fused_model = RAFTStereo(
        dataclasses.replace(cfg, fused_encoder=True, corr_implementation="pallas")
    )
    pallas_model = RAFTStereo(dataclasses.replace(cfg, corr_implementation="pallas"))

    def fwd(m):
        return jax.jit(
            lambda v, a, b: m.apply(v, a, b, iters=2, test_mode=True)[1]
        )(variables, i1, i2)

    want = fwd(pallas_model)
    got = fwd(fused_model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_fused_init_param_tree_identical(rng):
    """Initializing with the fused path traced must produce the exact
    parameter/variable tree (names, shapes, dtypes) of the XLA path — the
    checkpoint-interchangeability contract. eval_shape: the tree structure
    is a trace-time property, no compile needed (value equality is covered
    by test_model_forward_fused_matches_xla, which drives the fused path
    with XLA-initialized variables)."""
    import dataclasses as dc

    import jax.tree_util as jtu

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(corr_implementation="pallas")
    img = jnp.zeros((1, 32, 48, 3))  # smallest non-degenerate pyramid shape
    va = jax.eval_shape(
        lambda r: RAFTStereo(cfg).init(r, img, img, iters=1),
        jax.random.PRNGKey(0),
    )
    vb = jax.eval_shape(
        lambda r: RAFTStereo(dc.replace(cfg, fused_encoder=True)).init(
            r, img, img, iters=1, test_mode=True
        ),
        jax.random.PRNGKey(0),
    )
    ka = [(jtu.keystr(k), v.shape, v.dtype) for k, v in jtu.tree_flatten_with_path(va)[0]]
    kb = [(jtu.keystr(k), v.shape, v.dtype) for k, v in jtu.tree_flatten_with_path(vb)[0]]
    assert ka == kb


def test_training_path_unaffected_by_fused_flag(rng):
    """test_mode=False must never trace the fused kernels (they define no
    VJP): the GRADIENT COMPUTATION with the flag on must be the identical
    program. Asserted at the jaxpr level — structural identity is stronger
    than comparing compiled outputs, and costs a trace instead of two full
    XLA compiles. (A fused kernel leaking into the trace would also fail
    loudly here: pallas_call carries no AD rule.)"""
    import dataclasses as dc

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig()  # reg corr: grads flow through the volume
    model = RAFTStereo(cfg)
    fused_model = RAFTStereo(dc.replace(cfg, fused_encoder=True))
    img = jnp.zeros((1, 32, 48, 3))
    variables = jax.eval_shape(
        lambda r: model.init(r, img, img, iters=1), jax.random.PRNGKey(0)
    )
    i1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 48, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 48, 3)).astype(np.float32))

    def grad_jaxpr(m):
        import re

        def f(v):
            flows = m.apply(v, i1, i2, iters=1, test_mode=False)
            return jnp.sum(jnp.square(flows))

        text = str(jax.make_jaxpr(jax.grad(f))(variables))
        # The jaxpr embeds thunk reprs (`<function ... at 0x...>`) whose
        # addresses differ per trace; everything semantic stays.
        return re.sub(r"0x[0-9a-f]+", "0x-", text)

    assert grad_jaxpr(model) == grad_jaxpr(fused_model)
