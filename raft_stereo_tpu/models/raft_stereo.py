"""RAFT-Stereo top-level model, TPU-native.

Re-design of /root/reference/core/raft_stereo.py:22-141 for XLA:

- The reference's Python `for itr in range(iters)` loop (:108) is a
  `flax.linen.scan` over a single iteration body — traced once, compiled
  once, with per-iteration `stop_gradient` standing in for `.detach()` (:109).
- Disparity-native: the flow field is a single x-channel (the reference
  zeroes flow-y every iteration, :120, and slices it away, :134 — see
  models/update.py for why this is exact).
- Mixed precision is a dtype policy (params fp32, compute bf16) replacing
  torch AMP (:77,:112). Correlation lookup ARITHMETIC stays fp32 in every
  strategy (evaluate_stereo.py:227-230 rationale); under mixed precision
  the Pallas strategy stores the resulting taps in bf16 (the consumer
  casts them to bf16 anyway — see _corr_sample).
- Both images ride one 2B batch through the feature encoder (:83 passes a
  list) — one big MXU matmul instead of two.

The latent reference bug `context_zqr_convs[i]` using `context_dims[i]`
against a GRU expecting `hidden_dims[2-i]` biases (core/raft_stereo.py:32,
benign because all dims are 128) is fixed here: conv widths follow the scale
they feed.
"""

from __future__ import annotations

from typing import Optional

from flax import linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp

from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models.extractor import (
    BasicEncoder,
    EncoderTrunk,
    MultiBasicEncoder,
)
from raft_stereo_tpu.models.layers import Conv, ResidualBlock
from raft_stereo_tpu.models.update import GATE_SUM, BasicMultiUpdateBlock, UpsampleMaskHead
from raft_stereo_tpu.ops.corr import (
    corr_pyramid,
    corr_volume,
    corr_lookup,
    corr_lookup_alt,
    pool_fmap_levels,
)
from raft_stereo_tpu.parallel.sharding import constrain_spatial_tree
from raft_stereo_tpu.utils.geometry import (
    convex_upsample,
    convex_upsample_blocked,
    coords_grid_x,
)

Array = jax.Array

# What the remat of the iteration body keeps across the backward when
# `config.remat_save_corr` is on: the lookup's taps, and the pre-activation
# sums of every GRU gate (named in models/update.py), so the recompute pass
# re-runs no GRU convolution. Still recomputed, by choice (each buys under
# 8 ms of a step per GB kept at the recipe's geometry, PERF.md section 6,
# PR 28): the motion encoder, the flow head, the cross-scale pool /
# interpolation.
CORR_TAPS = "corr_taps"
REMAT_SAVED_NAMES = (CORR_TAPS, GATE_SUM)


def _corr_state(cfg: RAFTStereoConfig, fmap1: Array, fmap2: Array, fused: bool = False):
    """Precompute the loop-invariant correlation state for the chosen
    implementation; returned as a pytree so it can broadcast through scan.

    `fused` (the test-mode `fused_encoder` strategy) swaps the "pallas"
    state build for the single-kernel volume+pyramid+pad fusion
    (ops/corr_pallas.fused_pyramid_state) — same output pytree, so the
    iteration loop's lookup is untouched."""
    with jax.named_scope("corr_build"):
        f1 = fmap1.astype(jnp.float32)
        f2 = fmap2.astype(jnp.float32)
    if cfg.corr_implementation == "reg":
        vol = corr_volume(f1, f2, out_dtype=jnp.dtype(cfg.corr_dtype))
        return tuple(corr_pyramid(vol, cfg.corr_levels))
    if cfg.corr_implementation == "alt":
        return (f1, tuple(pool_fmap_levels(f2, cfg.corr_levels)))
    if cfg.corr_implementation == "pallas":
        from raft_stereo_tpu.ops.corr_pallas import (
            fused_pyramid_state,
            pallas_corr_state,
        )

        if fused:
            return fused_pyramid_state(
                f1, f2, cfg.corr_levels, corr_dtype=jnp.dtype(cfg.corr_dtype)
            )
        return pallas_corr_state(f1, f2, cfg.corr_levels, corr_dtype=jnp.dtype(cfg.corr_dtype))
    raise ValueError(cfg.corr_implementation)


def _corr_sample(
    cfg: RAFTStereoConfig,
    state,
    coords: Array,
    out_dtype=jnp.float32,
    prefetch: bool = False,
) -> Array:
    """Correlation taps at `coords`. `out_dtype` is the STORAGE dtype of the
    result; the Pallas kernel honors it directly (fp32 interpolation, store
    rounded — saves a full-tensor convert per iteration under mixed
    precision), while the XLA strategies return fp32 and let the caller's
    cast fuse. `prefetch` (the test-mode `prefetch_lookup` strategy) swaps
    the dense Pallas lookup for the scalar-prefetch windowed kernel — no VJP,
    so callers must gate it out of gradient traces; ignored by the XLA
    strategies."""
    if cfg.corr_implementation == "reg":
        return corr_lookup(state, coords, cfg.corr_radius)
    if cfg.corr_implementation == "alt":
        f1, levels = state
        return corr_lookup_alt(f1, levels, coords, cfg.corr_radius)
    if cfg.corr_implementation == "pallas":
        from raft_stereo_tpu.ops.corr_pallas import (
            pallas_corr_lookup_padded,
            prefetch_corr_lookup_padded,
        )

        if prefetch:
            return prefetch_corr_lookup_padded(state, coords, cfg.corr_radius, out_dtype)
        return pallas_corr_lookup_padded(state, coords, cfg.corr_radius, out_dtype)
    raise ValueError(cfg.corr_implementation)


class _SequentialEncoderStep(nn.Module):
    """One image through the feature encoder — the body of the sequential-
    encoder batch scan. Mirrors BasicEncoder's module layout exactly
    (reference core/extractor.py:122-201) so the parameter tree under the
    scanned module named "fnet" is byte-identical to the batched path's."""

    output_dim: int
    norm_fn: str
    downsample: int
    s2d_layer1: bool = False
    fused_layer1: bool = False

    @nn.compact
    def __call__(self, carry, image: Array):
        x = EncoderTrunk(
            self.norm_fn, self.downsample, self.s2d_layer1, self.fused_layer1,
            name="trunk",
        )(image[None])
        x = Conv(self.output_dim, (1, 1), padding=0, name="conv2")(x)
        return carry, x[0]


class _IterationBody(nn.Module):
    """One GRU refinement step — the scanned body (reference loop body,
    core/raft_stereo.py:108-136)."""

    config: RAFTStereoConfig
    test_mode: bool

    @nn.compact
    def __call__(self, carry, context, corr_state, coords0):
        cfg = self.config
        net, coords1 = carry
        compute_dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32

        coords1 = jax.lax.stop_gradient(coords1)
        corr = _corr_sample(
            cfg,
            corr_state,
            coords1,
            out_dtype=compute_dtype,
            # Windowed scalar-prefetch lookup: no VJP, so test_mode gates it
            # out of every gradient trace (same discipline as fused_encoder).
            prefetch=cfg.prefetch_lookup and self.test_mode,
        )
        # Named so the remat policy can keep the taps across backward
        # (config.remat_save_corr) instead of re-running the gather kernel.
        corr = checkpoint_name(corr, CORR_TAPS)
        flow = (coords1 - coords0)[..., None]  # (B,H,W,1)

        update_block = BasicMultiUpdateBlock(
            hidden_dims=tuple(cfg.hidden_dims),
            corr_channels=cfg.corr_channels,
            n_gru_layers=cfg.n_gru_layers,
            n_downsample=cfg.n_downsample,
            # Fused gate tail + motion concat (ops/gru_tail_pallas.py): no
            # VJP, so test_mode keeps it out of every gradient trace.
            fused_tail=cfg.fused_gru_tail and self.test_mode,
            name="update_block",
        )

        # slow_fast_gru: advance coarse GRUs extra times without running the
        # heads (reference core/raft_stereo.py:113-116).
        if cfg.slow_fast_gru and cfg.n_gru_layers == 3:
            net = update_block(net, context, iter32=True, iter16=False, iter08=False, update=False)
        if cfg.slow_fast_gru and cfg.n_gru_layers >= 2:
            net = update_block(
                net, context, iter32=cfg.n_gru_layers == 3, iter16=True, iter08=False, update=False
            )
        net, delta_flow = update_block(
            net,
            context,
            corr.astype(compute_dtype),
            flow.astype(compute_dtype),
            iter32=cfg.n_gru_layers == 3,
            iter16=cfg.n_gru_layers >= 2,
        )

        # Epipolar projection is structural: delta is a single x channel.
        coords1 = coords1 + delta_flow[..., 0].astype(jnp.float32)
        # Keep the recurrent carry H-sharded across iterations under the
        # spatial presets (identity otherwise): without the pin, the
        # partitioner is free to gather the hidden state between scan steps.
        net = constrain_spatial_tree(net, cfg.spatial_constraints)

        if self.test_mode:
            # Mask + upsample happen after the scan, on the final state only
            # (reference skips intermediate upsamples in test_mode,
            # core/raft_stereo.py:126-127; the mask head feeds no recurrence).
            y = ()
        else:
            # Emit the per-iteration low-res flow and hidden state; the model
            # applies the mask head + convex upsample batched over iterations
            # after the scan (same math as the reference's per-iteration
            # upsample_flow, core/raft_stereo.py:126-136).
            y = (coords1 - coords0, net[0])
        return (net, coords1), y


def sequential_batch_forward(model, variables, image1, image2, iters: int = 32):
    """Test-mode inference over a batch as a `lax.scan` of single-pair
    forwards — the TPU-native answer to round-3's "batching loses" verdict.

    Nothing in this model is shared across batch elements (correlation
    state, context, heads are all per-pair), so single-chip B>1 can at best
    match B=1 per-map throughput; the round-3 scan-form encoder paid a
    ~5.6% shell penalty ON TOP (1.011 vs 1.071 maps/s at B=2), and a fully
    batched full-res encoder OOMs outright (37 GB: XLA pads the batched
    C=64 trunk's lane dim 64->128, 2x on every buffer — round-4 measure).
    Scanning the WHOLE forward per pair makes per-map cost identical to
    B=1 by construction and keeps peak memory flat at the B=1 footprint
    for any batch size. Real batch scaling is data parallelism across
    chips (parallel/mesh.py), exactly as the reference scales with
    nn.DataParallel (/root/reference/train_stereo.py:137).

    Returns (low_res_flow (B,h,w), flow_up (B,H,W,1))."""
    import jax as _jax

    def body(carry, pair):
        i1, i2 = pair
        lo, up = model.apply(
            variables, i1[None], i2[None], iters=iters, test_mode=True
        )
        return carry, (lo[0], up[0])

    _, (lo, up) = _jax.lax.scan(body, jnp.float32(0), (image1, image2))
    return lo, up


def encode_features(cfg: RAFTStereoConfig, image1: Array, image2: Array, test_mode: bool):
    """The loop-invariant forward prelude: normalization, context + feature
    encoders, per-scale GRU context biases, correlation state, and the
    coordinate grids. Everything before the first GRU iteration.

    MUST be called inside an `nn.compact` module body — the submodules
    constructed here attach to the CALLER's scope under the exact names the
    checkpoint tree uses ("cnet", "fnet", "context_zqr_conv{i}",
    "conv2_res"/"conv2_out" for the shared backbone) — so RAFTStereo.__call__
    and the serving tier's AnytimePrelude (models/anytime.py) share ONE
    parameter tree: the same `variables` drive the monolithic forward and the
    chunked anytime engine, byte-identical.

    Returns (net, context, corr_state, coords0, coords1) with
    coords1 == coords0 (callers apply flow_init/warm starts themselves).
    """
    compute_dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32

    image1 = (2.0 * (image1 / 255.0) - 1.0).astype(compute_dtype)
    image2 = (2.0 * (image2 / 255.0) - 1.0).astype(compute_dtype)

    # s2d encoder domain: a large TRAINING win (0.513 -> 0.462 s/step at
    # the b4 recipe, -3.2 GB HBM — the C=128 dw convs avoid the kx-minor
    # stacked-layout pathology) but an inference REGRESSION (the
    # test-mode graph pays ~100 ms of layout copies around the s2d convs
    # and loses the conv+IN-sum multi-output fusion; round-4 trace).
    # Gate on test_mode so each graph keeps its faster path.
    s2d = cfg.encoder_s2d and not test_mode
    # Fused Pallas encoder kernels (ops/encoder_pallas.py): test-mode
    # only — the kernels define no VJP, so the training path keeps the
    # XLA formulation untouched.
    fused = cfg.fused_encoder and test_mode

    output_dims = (tuple(cfg.hidden_dims), tuple(cfg.context_dims))
    cnet = MultiBasicEncoder(
        output_dims=output_dims, norm_fn="batch", downsample=cfg.n_downsample,
        s2d_layer1=s2d, fused_layer1=fused, name="cnet"
    )
    if cfg.shared_backbone:
        scales, trunk = cnet(
            jnp.concatenate([image1, image2], axis=0),
            dual_inp=True,
            num_layers=cfg.n_gru_layers,
        )
        fmaps = nn.Sequential(
            [
                ResidualBlock(128, "instance", stride=1, name="conv2_res"),
                Conv(256, (3, 3), name="conv2_out"),
            ]
        )(trunk)
        fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
    else:
        scales = cnet(image1, num_layers=cfg.n_gru_layers)
        if cfg.sequential_encoder and image1.shape[0] > 1:
            # One image per scan step: the scan body compiles once and
            # its full-res trunk buffers are structurally reused across
            # steps, so peak memory is ONE image's trunk regardless of
            # batch — the single-chip enabler for full-res inference at
            # B >= 2 (round-2 verdict item 5). Param tree is identical
            # to BasicEncoder's ("fnet/trunk/..", "fnet/conv2") so
            # checkpoints are unaffected.
            scanned = nn.scan(
                _SequentialEncoderStep,
                variable_broadcast="params",
                split_rngs={"params": False},
                in_axes=0,
                out_axes=0,
            )(
                output_dim=256,
                norm_fn="instance",
                downsample=cfg.n_downsample,
                s2d_layer1=s2d,
                fused_layer1=fused,
                name="fnet",
            )
            imgs = jnp.concatenate([image1, image2], axis=0)
            _, fmaps = scanned((), imgs)
            fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        elif cfg.sequential_encoder:
            # B=1: the anchor data-dependency form measures ~1.5% faster
            # than the 2-step scan at Middlebury-F (no while-loop shell
            # around the two passes); same math, same params. The scalar
            # anchor forces image1's trunk to be freed before image2's
            # is built (see config docstring).
            fnet = BasicEncoder(
                output_dim=256, norm_fn="instance", downsample=cfg.n_downsample,
                s2d_layer1=s2d, fused_layer1=fused, name="fnet"
            )
            fmap1 = fnet(image1)
            anchor = (fmap1.reshape(-1)[0] * 1e-30).astype(image2.dtype)
            fmap2 = fnet(image2 + anchor)
        else:
            fnet = BasicEncoder(
                output_dim=256, norm_fn="instance", downsample=cfg.n_downsample,
                s2d_layer1=s2d, fused_layer1=fused, name="fnet"
            )
            fmaps = fnet(jnp.concatenate([image1, image2], axis=0))
            fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)

    net = tuple(jnp.tanh(s[0]) for s in scales)
    inp = [nn.relu(s[1]) for s in scales]

    # Precompute GRU context biases once (reference core/raft_stereo.py:88).
    # Width follows the scale each conv feeds: scale i (finest-first) has
    # hidden width hidden_dims[2-i].
    context = []
    for i, x in enumerate(inp):
        width = cfg.hidden_dims[2 - i]
        czqr = Conv(width * 3, (3, 3), name=f"context_zqr_conv{i}")(x)
        context.append(tuple(jnp.split(czqr, 3, axis=-1)))
    context = tuple(context)

    corr_state = _corr_state(cfg, fmap1, fmap2, fused=fused)
    # Spatial presets pin the O(H·W²) corr state and the GRU hidden state to
    # H-row shards here, so the partitioner never materializes either
    # replicated — the full-res memory wall splits linearly across chips.
    # Identity unless cfg.spatial_constraints (see config docstring).
    corr_state = constrain_spatial_tree(corr_state, cfg.spatial_constraints)
    net = constrain_spatial_tree(net, cfg.spatial_constraints)

    b, h, w, _ = net[0].shape
    coords0 = coords_grid_x(b, h, w)
    return net, context, corr_state, coords0, coords0


class RAFTStereo(nn.Module):
    """Full model. Call signature mirrors the reference forward
    (core/raft_stereo.py:70-141) with NHWC images in [0, 255].

    Returns:
      test_mode=False → (iters, B, H/f, f, W/f, f) per-iteration upsampled
        disparity flows in the convex-upsample BLOCKED layout (f = the
        downsample factor; element [it,b,h,i,w,j] is full-res pixel
        (h*f+i, w*f+j)). sequence_loss consumes this directly;
        utils.geometry.unblock_predictions reshapes to the reference's
        (iters, B, H, W, 1) stack for free.
      test_mode=True → (low_res_flow (B,h,w), flow_up (B,H,W,1)).
    """

    config: RAFTStereoConfig

    @nn.compact
    def __call__(
        self,
        image1: Array,
        image2: Array,
        iters: int = 12,
        flow_init: Optional[Array] = None,
        test_mode: bool = False,
    ):
        cfg = self.config

        # Encoder prelude shared verbatim with the serving tier's chunked
        # anytime engine (models/anytime.py) — see encode_features.
        net, context, corr_state, coords0, coords1 = encode_features(
            cfg, image1, image2, test_mode
        )
        _, h, w, _ = net[0].shape
        if flow_init is not None:
            flow_init = jnp.asarray(flow_init)
            if flow_init.ndim == 4:
                flow_init = flow_init[..., 0]
            coords1 = coords1 + flow_init

        factor = cfg.downsample_factor

        # remat: recompute the iteration's internals during backward instead
        # of saving 22+ iterations of GRU/corr activations (config docstring),
        # but for REMAT_SAVED_NAMES, which the policy keeps.
        # prevent_cse=False: under scan the per-iteration CSE barrier is
        # unnecessary (jax.checkpoint docs) and costs fusion opportunities.
        # Never remat in test_mode: with no backward it buys nothing, and its
        # barriers make XLA re-copy the (loop-invariant) correlation state
        # every iteration at full-res scale.
        remat_policy = (
            jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED_NAMES)
            if cfg.remat_save_corr
            else None
        )
        body_cls = (
            nn.remat(_IterationBody, prevent_cse=False, policy=remat_policy)
            if (cfg.remat_iterations and not test_mode)
            else _IterationBody
        )
        body = nn.scan(
            body_cls,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            out_axes=0,
            length=iters,
        )(config=cfg, test_mode=test_mode, name="iteration")

        (net, coords1), ys = body((net, coords1), context, corr_state, coords0)

        mask_head = UpsampleMaskHead(cfg.n_downsample, name="mask_head")

        if test_mode:
            flow_lowres = coords1 - coords0
            mask = mask_head(net[0]).astype(jnp.float32)
            flow_up = convex_upsample(flow_lowres[..., None], mask, factor)
            return flow_lowres, flow_up

        # Batched mask + upsample over all iterations (one big conv instead
        # of `iters` small ones; exact per-iteration reference semantics).
        # Memory note: the scan stacks net[0] per iteration — 128ch at 1/4
        # res (bf16 under mixed precision), ~8x the upsampled-flow stack the
        # per-iteration upsample would emit. At training crops this is tens
        # of MB per device sample; at full-res inference test_mode avoids it
        # entirely (nothing is emitted).
        flows_low, net0s = ys  # (iters, B, h, w), (iters, B, h, w, C)
        it, bb = net0s.shape[0], net0s.shape[1]
        mask = mask_head(net0s.reshape(it * bb, *net0s.shape[2:])).astype(jnp.float32)
        # Blocked form: reshaping the 22-prediction stack to row-major
        # full-res made XLA materialize ~19 ms/step of layout transposes
        # between the upsample einsum and the loss (round-5 train trace);
        # sequence_loss consumes this layout natively. Full-res view:
        # utils.geometry.unblock_predictions (a free reshape).
        flows = convex_upsample_blocked(
            flows_low.reshape(it * bb, h, w)[..., None], mask, factor
        )
        return flows.reshape(it, bb, h, factor, w, factor)
