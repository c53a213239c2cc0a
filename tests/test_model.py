"""Model-layer tests: shapes across config variants, parameter-count parity,
gradient flow, and full-forward numerical parity against the torch reference
(used strictly as an oracle, imported from /root/reference when present).

All forwards are jitted — see conftest docstring for why.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_H, TEST_W, jit_init
from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models import RAFTStereo
from raft_stereo_tpu.utils.geometry import unblock_predictions

REFERENCE = "/root/reference"

# Reference torch model has 11,116,176 params (SURVEY.md §6, ~11.1M). Ours
# drops exactly the always-zero flow-y weights: 3,136 (motion encoder convf1
# y-input slice, 64*7*7) + 2,305 (flow head conv2 y-output row, 256*9+1).
TORCH_PARAM_COUNT = 11_116_176
EXPECTED_PARAMS = TORCH_PARAM_COUNT - 3_136 - 2_305


def count_params(variables):
    return sum(x.size for x in jax.tree.leaves(variables["params"]))


def test_param_count_matches_reference(default_model_bundle):
    _, _, variables = default_model_bundle
    assert count_params(variables) == EXPECTED_PARAMS


def test_forward_shapes_and_grads(default_model_bundle):
    """Train-mode shapes, test-mode shapes, flow_init, and full gradient
    coverage — one test so the compiled forwards are reused."""
    cfg, model, variables = default_model_bundle
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)), jnp.float32)
    i2 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)), jnp.float32)

    # train mode: per-iteration upsampled flows (blocked layout; the
    # unblock helper restores the reference's (iters, B, H, W, 1) stack)
    f0 = cfg.downsample_factor
    train_fwd = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=2))
    flows = train_fwd(variables, i1, i2)
    assert flows.shape == (2, 1, TEST_H // f0, f0, TEST_W // f0, f0)
    flows = unblock_predictions(flows)
    assert flows.shape == (2, 1, TEST_H, TEST_W, 1)
    assert np.isfinite(np.asarray(flows)).all()

    # test mode: (low-res flow, upsampled final flow)
    f = cfg.downsample_factor
    test_fwd = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=2, test_mode=True))
    lo, up = test_fwd(variables, i1, i2)
    assert lo.shape == (1, TEST_H // f, TEST_W // f)
    assert up.shape == (1, TEST_H, TEST_W, 1)

    # flow_init shifts the starting coords (reference core/raft_stereo.py:104-105)
    init_fwd = jax.jit(
        lambda v, a, b, fi: model.apply(v, a, b, iters=1, flow_init=fi, test_mode=True)
    )
    lo0, _ = init_fwd(variables, i1, i2, jnp.zeros_like(lo))
    lo1, _ = init_fwd(variables, i1, i2, jnp.full_like(lo, -2.0))
    assert float(jnp.abs(lo1 - lo0).mean()) > 0.1

    # gradients reach every parameter
    def loss_fn(params):
        out = model.apply({**variables, "params": params}, i1, i2, iters=2)
        return jnp.abs(out).mean()

    grads = jax.jit(jax.grad(loss_fn))(variables["params"])
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for path, g in flat:
        assert np.isfinite(np.asarray(g)).all(), f"non-finite grad at {path}"
    nonzero = sum(bool(jnp.any(g != 0)) for _, g in flat)
    assert nonzero == len(flat), f"only {nonzero}/{len(flat)} params got gradient"


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(n_gru_layers=2, slow_fast_gru=True),
        dict(shared_backbone=True, n_downsample=3, n_gru_layers=2, slow_fast_gru=True),  # realtime config
        dict(corr_implementation="alt", data_modality="All Gated"),
        dict(mixed_precision=True, n_gru_layers=1),
    ],
)
def test_config_variants_forward(kwargs):
    cfg = RAFTStereoConfig(**kwargs)
    model, variables = jit_init(cfg)
    fwd = jax.jit(lambda v, a, b: unblock_predictions(model.apply(v, a, b, iters=2)))
    img = jnp.zeros((1, TEST_H, TEST_W, cfg.in_channels))
    flows = fwd(variables, img, img)
    assert flows.shape == (2, 1, TEST_H, TEST_W, 1)
    assert np.isfinite(np.asarray(flows, np.float32)).all()


@pytest.mark.skipif(not os.path.isdir(REFERENCE), reason="reference repo not mounted")
def test_torch_reference_parity():
    """End-to-end numerical parity: run the torch reference model (as an
    oracle) and this framework's model from the converted checkpoint on the
    same input; per-iteration training flows must agree."""
    import argparse

    import torch

    if REFERENCE not in sys.path:
        sys.path.insert(0, REFERENCE)
    from core.raft_stereo import RAFTStereo as TorchRAFTStereo

    from raft_stereo_tpu.utils.checkpoints import convert_state_dict

    cfg = RAFTStereoConfig(encoder_s2d=False)  # exact-parity path vs the torch oracle
    args = argparse.Namespace(
        hidden_dims=list(cfg.hidden_dims),
        corr_implementation="reg",
        corr_levels=cfg.corr_levels,
        corr_radius=cfg.corr_radius,
        n_downsample=cfg.n_downsample,
        n_gru_layers=cfg.n_gru_layers,
        slow_fast_gru=cfg.slow_fast_gru,
        shared_backbone=cfg.shared_backbone,
        mixed_precision=False,
    )
    torch.manual_seed(7)
    tmodel = TorchRAFTStereo(args, "RGB").eval()

    # W/4 must be >= 16: the torch oracle builds a 5-entry pyramid
    # (core/corr.py:122-125) and pools the last axis down 4 times.
    rng = np.random.default_rng(3)
    i1 = rng.uniform(0, 255, (1, 3, 32, 64)).astype(np.float32)
    i2 = rng.uniform(0, 255, (1, 3, 32, 64)).astype(np.float32)
    with torch.no_grad():
        tflows = tmodel(torch.from_numpy(i1), torch.from_numpy(i2), iters=3)
    want = np.stack([f.numpy() for f in tflows])  # (iters, B, 1, H, W)

    sd = {k: v.numpy() for k, v in tmodel.state_dict().items()}
    variables = jax.tree.map(jnp.asarray, convert_state_dict(sd, cfg))

    model = RAFTStereo(cfg)
    # Default conv precision is reduced (TPU MXU passes); parity against the
    # fp32 torch oracle needs full-precision convolutions.
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda v, a, b: unblock_predictions(model.apply(v, a, b, iters=3)))
        got = fwd(
            variables,
            jnp.asarray(i1.transpose(0, 2, 3, 1)),
            jnp.asarray(i2.transpose(0, 2, 3, 1)),
        )
    got = np.asarray(got).transpose(0, 1, 4, 2, 3)  # → (iters, B, 1, H, W)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_torch_pth_loader_decodes_all_float_dtypes(tmp_path):
    """The zip-.pth reader must decode fp32/fp16/bf16 storages to real float
    arrays (bf16 goes through ml_dtypes, not raw uint16 bits)."""
    import torch

    from raft_stereo_tpu.utils.checkpoints import load_torch_state_dict

    want = {
        "module.a": torch.arange(6, dtype=torch.float32).reshape(2, 3) / 7,
        "module.b": (torch.arange(4, dtype=torch.float32) / 3).to(torch.bfloat16),
        "module.c": (torch.arange(4, dtype=torch.float32) / 3).to(torch.float16),
    }
    path = tmp_path / "ckpt.pth"
    torch.save(want, path)
    got = load_torch_state_dict(str(path))
    assert set(got) == {"a", "b", "c"}
    for key in "abc":
        t = want[f"module.{key}"].to(torch.float32).numpy()
        np.testing.assert_allclose(np.asarray(got[key], np.float32), t, rtol=0, atol=0)


def test_s2d_kernel_embeddings_match_direct_conv(rng):
    """The W-space-to-depth kernel embeddings (dense stride-1, stride-2
    entry, 1x1 skip) must reproduce the direct conv exactly up to f32
    rounding — the unit-level guard for the encoder_s2d path (round 4;
    derivation in layers.py)."""
    from raft_stereo_tpu.models.layers import (
        dense_w_kernel,
        entry_w_kernel,
        skip_w_kernel,
        w_s2d,
    )

    def conv(x, k, strides=(1, 1), padding=((1, 1), (1, 1))):
        return jax.lax.conv_general_dilated(
            x, k, strides, padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    x = jnp.asarray(rng.standard_normal((2, 10, 16, 8)).astype(np.float32))
    xs = w_s2d(x)
    k3 = jnp.asarray(rng.standard_normal((3, 3, 8, 8)).astype(np.float32))
    want = conv(x, k3)
    got = conv(xs, dense_w_kernel(k3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(w_s2d(want)), rtol=1e-5, atol=1e-5)

    k_entry = jnp.asarray(rng.standard_normal((3, 3, 8, 12)).astype(np.float32))
    want = conv(x, k_entry, strides=(2, 2))
    got = conv(xs, entry_w_kernel(k_entry), strides=(2, 1), padding=((1, 1), (1, 0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    k_skip = jnp.asarray(rng.standard_normal((1, 1, 8, 12)).astype(np.float32))
    want = conv(x, k_skip, strides=(2, 2), padding=((0, 0), (0, 0)))
    got = conv(xs, skip_w_kernel(k_skip), strides=(2, 1), padding=((0, 0), (0, 0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_encoder_s2d_consistency(rng):
    """encoder_s2d (the default TPU fast path) must produce the same flows
    as the direct-conv path from the SAME variables — parameter trees are
    interchangeable by construction, outputs agree within the f32
    accumulation-noise band (the formulation is f64-exact; the band covers
    conv-order drift amplified by instance-norm rsqrt and GRU iteration)."""
    cfg_off = RAFTStereoConfig(encoder_s2d=False)
    cfg_on = RAFTStereoConfig(encoder_s2d=True)
    model_off, variables = jit_init(cfg_off)
    model_on, variables_on = jit_init(cfg_on)
    assert jax.tree.structure(variables) == jax.tree.structure(variables_on)

    i1 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, TEST_H, TEST_W, 3)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        fa = jax.jit(lambda v, a, b: model_off.apply(v, a, b, iters=3))(variables, i1, i2)
        fb = jax.jit(lambda v, a, b: model_on.apply(v, a, b, iters=3))(variables, i1, i2)
    d = float(jnp.max(jnp.abs(fa - fb)))
    assert d < 2e-2, f"s2d vs direct flow drift {d} px exceeds the noise band"


def test_instance_norm_matches_torch(rng):
    """Direct parity of the one-pass (E[x²]−mean²) InstanceNorm against
    torch `nn.InstanceNorm2d` (reference fnet norm, core/extractor.py:134-135)
    — the round-3 restructuring changed the variance formulation, so this
    guards it at the layer level, not just via the full-forward goldens.
    Channel 0 is near-constant (var ≪ mean²) to exercise the cancellation /
    clamp path the advisor flagged: both implementations are one-pass, so
    they must degrade the same way."""
    import torch

    from raft_stereo_tpu.models.layers import InstanceNorm

    b, h, w, c = 2, 9, 13, 8
    x = rng.standard_normal((b, c, h, w)).astype(np.float32)
    # near-constant channel: large mean, tiny spread (var/mean² ≈ 1e-14)
    x[:, 0] = 100.0 + 1e-5 * rng.standard_normal((b, h, w)).astype(np.float32)
    # exactly-constant channel: variance underflows to 0 in BOTH
    # implementations; output must be finite (rsqrt(eps)-scaled), not NaN
    x[:, 1] = 42.0

    with torch.no_grad():
        want = torch.nn.InstanceNorm2d(c, eps=1e-5)(torch.from_numpy(x)).numpy()

    m = InstanceNorm(c)
    got = jax.jit(m.apply)({}, jnp.asarray(x.transpose(0, 2, 3, 1)))
    got = np.asarray(got).transpose(0, 3, 1, 2)
    assert np.isfinite(got).all()
    # normal channels: tight agreement
    np.testing.assert_allclose(got[:, 2:], want[:, 2:], rtol=1e-5, atol=1e-5)
    # degenerate channels: same zero-centering, amplitude within the slack
    # the differing cancellation orders allow (both forms are one-pass;
    # outputs are O((x-mean)/sqrt(eps)) ≈ O(1e-3) here)
    np.testing.assert_allclose(got[:, :2], want[:, :2], atol=5e-2)


def test_convgru_segmented_matches_concat_formulation(rng):
    """ConvGRU applies each gate kernel segment-wise (no hx/rx concat
    materialization); the math must equal the concat formulation exactly
    in fp32 (conv distributes over input-channel concat)."""
    from raft_stereo_tpu.models.update import ConvGRU

    hdim, cin_x = 8, 16
    m = ConvGRU(hdim)
    h = jnp.asarray(rng.standard_normal((1, 6, 10, hdim)).astype(np.float32))
    cz, cr, cq = (
        jnp.asarray(rng.standard_normal((1, 6, 10, hdim)).astype(np.float32))
        for _ in range(3)
    )
    x = jnp.asarray(rng.standard_normal((1, 6, 10, cin_x)).astype(np.float32))
    variables = m.init(jax.random.PRNGKey(0), h, cz, cr, cq, x)
    got = m.apply(variables, h, cz, cr, cq, x)

    def conv(inp, k, b):
        return (
            jax.lax.conv_general_dilated(
                inp, k, (1, 1), [(1, 1), (1, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            + b
        )

    p = variables["params"]
    hx = jnp.concatenate([h, x], -1)
    z = jax.nn.sigmoid(conv(hx, p["convz"]["Conv_0"]["kernel"], p["convz"]["Conv_0"]["bias"]) + cz)
    r = jax.nn.sigmoid(conv(hx, p["convr"]["Conv_0"]["kernel"], p["convr"]["Conv_0"]["bias"]) + cr)
    q = jnp.tanh(
        conv(jnp.concatenate([r * h, x], -1), p["convq"]["Conv_0"]["kernel"], p["convq"]["Conv_0"]["bias"])
        + cq
    )
    want = (1.0 - z) * h + z * q
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sequential_batch_forward_matches_single_pairs(rng):
    """B=2 inference via sequential_batch_forward must equal two
    independent B=1 forwards exactly (the scan body IS the single-pair
    program) — the round-4 batching answer: per-map parity, flat memory."""
    from raft_stereo_tpu.models import sequential_batch_forward

    cfg = RAFTStereoConfig()
    model, variables = jit_init(cfg)
    i1 = jnp.asarray(rng.uniform(0, 255, (2, TEST_H, TEST_W, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (2, TEST_H, TEST_W, 3)).astype(np.float32))

    lo_b, up_b = jax.jit(
        lambda v, a, b: sequential_batch_forward(model, v, a, b, iters=3)
    )(variables, i1, i2)
    single = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=3, test_mode=True))
    for k in range(2):
        lo_s, up_s = single(variables, i1[k : k + 1], i2[k : k + 1])
        np.testing.assert_array_equal(np.asarray(lo_b[k]), np.asarray(lo_s[0]))
        np.testing.assert_array_equal(np.asarray(up_b[k]), np.asarray(up_s[0]))


@pytest.mark.parametrize("b", [1, 2])
def test_sequential_encoder_matches_batched(rng, b):
    """sequential_encoder processes the feature encoder one image at a time
    (structural memory guarantee for full-res single-chip inference —
    round-2 verdict item 5): the B=1 anchor form and the B>=2 scan form
    must both match the batched path exactly, math and PARAMETER TREE
    (same variables run through both configs)."""

    cfg = RAFTStereoConfig()
    cfg_seq = RAFTStereoConfig(sequential_encoder=True)
    model, variables = jit_init(cfg, b=b)
    model_seq, variables_seq = jit_init(cfg_seq, b=b)

    # identical param trees (checkpoints are interchangeable)
    assert jax.tree.structure(variables) == jax.tree.structure(variables_seq)

    i1 = jnp.asarray(rng.uniform(0, 255, (b, TEST_H, TEST_W, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (b, TEST_H, TEST_W, 3)).astype(np.float32))
    lo_b, up_b = jax.jit(
        lambda v, a, b: model.apply(v, a, b, iters=3, test_mode=True)
    )(variables, i1, i2)
    lo_s, up_s = jax.jit(
        lambda v, a, b: model_seq.apply(v, a, b, iters=3, test_mode=True)
    )(variables, i1, i2)
    np.testing.assert_allclose(np.asarray(up_s), np.asarray(up_b), rtol=2e-5, atol=2e-5)


# The test-mode forward and the serving chunk as the parent of PR 32 lowered
# them (sha256 of the StableHLO text at a tiny shape, recorded on that parent
# with these very functions): a refactor of models/ that means to change no
# operation keeps them; one that means to records them anew and says so.
FORWARD_SHA256 = {
    "reg": "77a68bfb6d41011fe7352e8d08a3ab9949604cd95e71aacaeeba0b636b5e9078",
    "pallas": "4309df9f5959ac9522af899d07ce5ccee7a52699cf0ac1e103e19ff28a6cc7b7",
}
CHUNK_SHA256 = {
    "reg": "79faf3ab67f7d665bb1cbdbf0a0a92b6b33fcf6249553f8496d3ea4a022d60a8",
    "pallas": "9b19d32af9f9014e7b487a407afca57a1f1bd03b79d9c597d4fb2579645829d4",
}


def _tiny_abstract(corr_implementation):
    """A tiny configuration, an abstract image and the model's abstract variables."""
    cfg = RAFTStereoConfig(hidden_dims=(16, 16, 16), n_gru_layers=2, corr_levels=2, corr_radius=2,
                           corr_implementation=corr_implementation)
    image = jax.ShapeDtypeStruct((1, 32, 48, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda r, a, b: RAFTStereo(cfg).init(r, a, b, iters=1), jax.random.PRNGKey(0), image, image)
    return cfg, image, variables


def stereo_forward_text(corr_implementation):
    cfg, image, variables = _tiny_abstract(corr_implementation)
    forward = jax.jit(lambda v, a, b: RAFTStereo(cfg).apply(v, a, b, iters=3, test_mode=True))
    return forward.lower(variables, image, image).as_text()


def anytime_chunk_text(corr_implementation):
    from raft_stereo_tpu.models.anytime import AnytimeChunk, AnytimePrelude

    cfg, image, variables = _tiny_abstract(corr_implementation)
    state = jax.eval_shape(AnytimePrelude(cfg).apply, variables, image, image)
    return jax.jit(AnytimeChunk(cfg, chunk_iters=2).apply).lower(variables, state).as_text()


def _sha256(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("corr_implementation", sorted(FORWARD_SHA256))
def test_the_stereo_forward_lowers_to_the_parents_text(corr_implementation):
    assert _sha256(stereo_forward_text(corr_implementation)) == FORWARD_SHA256[corr_implementation]


@pytest.mark.parametrize("corr_implementation", sorted(CHUNK_SHA256))
def test_the_anytime_chunk_lowers_to_the_parents_text(corr_implementation):
    assert _sha256(anytime_chunk_text(corr_implementation)) == CHUNK_SHA256[corr_implementation]
