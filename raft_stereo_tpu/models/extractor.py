"""Feature and context encoders.

TPU-native re-design of the reference encoders
(/root/reference/core/extractor.py:122-308). Differences from the reference
are layout (NHWC) and norm semantics (FrozenBatchNorm, see layers.py), not
architecture: channel progression 64→64→96→128, stride placement
`1 + (downsample > k)` (core/extractor.py:144,149,150), kernel-7 stem,
per-scale (hidden, context) output heads in `MultiBasicEncoder`
(core/extractor.py:235-258).

The reference's `BottleneckBlock` is dead code (never instantiated) and is
intentionally not reproduced (SURVEY.md §2 item 2).
"""

from __future__ import annotations

from typing import Tuple

from flax import linen as nn
import jax
import jax.numpy as jnp

from raft_stereo_tpu.models.layers import (
    Conv,
    ConvParams,
    FrozenBatchNorm,
    ResidualBlock,
    ResidualBlockFromS2D,
    ResidualBlockS2D,
    dense_w_kernel,
    im2col_conv,
    make_norm,
    w_s2d,
)

Array = jax.Array


class _FusedBlockParams(nn.Module):
    """Declares exactly the parameter/variable tree of a stride-1
    `ResidualBlock`/`ResidualBlockS2D` (conv1, conv2, FrozenBatchNorm_{0,1}
    under batch norm) without computing anything — the fused Pallas path
    (ops/encoder_pallas.py) consumes the raw arrays, checkpoints are
    interchangeable with the XLA blocks."""

    features: int
    norm_fn: str

    @nn.compact
    def __call__(self):
        c = self.features
        k1, b1 = ConvParams(c, c, (3, 3), name="conv1")()
        k2, b2 = ConvParams(c, c, (3, 3), name="conv2")()
        if self.norm_fn == "batch":
            # Unnamed, declared in conv order like ResidualBlockS2D's norm
            # calls, so auto-numbering (FrozenBatchNorm_0/1) matches.
            a1 = FrozenBatchNorm(c, phases=2)(None)
            a2 = FrozenBatchNorm(c, phases=2)(None)
        else:
            a1 = a2 = None
        return k1, b1, k2, b2, a1, a2


def _stride(downsample: int, threshold: int) -> int:
    """Reference stride rule `1 + (downsample > k)` (core/extractor.py:144-150)."""
    return 1 + int(downsample > threshold)


class EncoderTrunk(nn.Module):
    """Shared stem + layer1-3 trunk: input → 128ch at 1/2**downsample res.

    `s2d_layer1` evaluates layer1 (and the layer2_0 entry convs) in the
    W-space-to-depth domain: the C=64 convs half-starve the MXU's
    contraction lanes (~28 TF/s); the 128-channel s2d embedding runs ~1.7x
    faster despite 2x structural-zero FLOPs (measured round 4; math proven
    exact in f64, tests/test_model.py). Entry is
    a pure reshape, exit rides the stride-2 layer2 kernels — no transpose
    anywhere. Param tree is unchanged. Applies when layer1 runs at stem
    resolution with even W and an s2d-capable norm."""

    norm_fn: str
    downsample: int
    s2d_layer1: bool = False
    # Fused-Pallas layer1 (ops/encoder_pallas.py): the stem norm, both
    # layer1 blocks and their InstanceNorm/FrozenBN epilogues run as
    # implicit-GEMM kernels in the W-s2d domain — inference-only (the
    # kernels define no VJP; gated on test_mode by the model). Same
    # applicability conditions as s2d_layer1; parameter tree unchanged.
    fused_layer1: bool = False

    @nn.compact
    def __call__(self, x: Array) -> Array:
        s0 = _stride(self.downsample, 2)
        # The stride-1 stem (n_downsample<=2) as a direct conv is MXU-starved
        # at C_in=3 (3 of 128 contraction lanes): measured 19.2 ms/image at
        # 5.6 TF/s on Middlebury-F. Restructured as column im2col (7 shifted
        # slices -> 21 channels) + a 7x1 conv — 6.5 ms vs 17.1 measured in
        # isolation (layers.im2col_conv). (Rejected along the way: a 4x4
        # space-to-depth stem — fast in isolation, 40 ms slower in context —
        # and full 7x7/147-channel im2col, whose patch tensor pays an 18 ms
        # layout copy.) The stride-2 stem keeps the direct conv: its im2col
        # would need stride-2 slices, which XLA:TPU lowers as row gathers
        # (see utils/geometry.avg_pool2x).
        if s0 == 1:
            kernel, bias = ConvParams(64, x.shape[-1], kernel_size=(7, 7), name="conv1")()
            # checkpoint: the patch tensor (7x the input) is cheap to
            # rebuild but costly to keep alive for the kernel gradient —
            # without remat the training step at the reference recipe
            # overflowed HBM (24.6 GB vs 15.75 on v5e with the earlier 49x
            # variant; the 7x form still saves ~1.6 GB of saved activations).
            x = jax.checkpoint(im2col_conv)(kernel, bias, x)
        else:
            x = Conv(64, (7, 7), strides=(s0, s0), padding=3, name="conv1")(x)

        s1 = _stride(self.downsample, 1)
        use_fused = (
            self.fused_layer1
            and x.shape[2] % 2 == 0
            and self.norm_fn in ("instance", "batch")
        )
        if use_fused:
            # x is the RAW stem output here: the stem norm + relu are folded
            # into the first fused conv's input stage (one fewer full-res
            # elementwise pass), so the XLA norm apply below must not run.
            x = self._fused_layer1(x, s1)
        else:
            x = make_norm(self.norm_fn, 64)(x)
            x = nn.relu(x)

            use_s2d = (
                self.s2d_layer1
                and x.shape[2] % 2 == 0
                and self.norm_fn in ("instance", "batch")
            )
            if use_s2d:
                b, h, w, c = x.shape
                x = w_s2d(x)  # pure reshape: (B,H,W/2,128)
                x = ResidualBlockS2D(64, self.norm_fn, name="layer1_0")(x)
                x = ResidualBlockS2D(64, self.norm_fn, name="layer1_1")(x)
                if s1 == 2:
                    x = ResidualBlockFromS2D(96, self.norm_fn, in_features=64, name="layer2_0")(x)
                else:
                    x = x.reshape(b, h, w, c)  # leave the domain (pure reshape)
                    x = ResidualBlock(96, self.norm_fn, stride=1, name="layer2_0")(x)
            else:
                x = ResidualBlock(64, self.norm_fn, stride=1, name="layer1_0")(x)
                x = ResidualBlock(64, self.norm_fn, stride=1, name="layer1_1")(x)
                x = ResidualBlock(96, self.norm_fn, stride=s1, name="layer2_0")(x)
        x = ResidualBlock(96, self.norm_fn, stride=1, name="layer2_1")(x)
        s2 = _stride(self.downsample, 0)
        x = ResidualBlock(128, self.norm_fn, stride=s2, name="layer3_0")(x)
        x = ResidualBlock(128, self.norm_fn, stride=1, name="layer3_1")(x)
        return x

    def _fused_layer1(self, stem_y: Array, s1: int) -> Array:
        """Stem-norm + layer1 + layer2_0 entry, fused-kernel form: the raw
        stem output enters the W-s2d domain (pure reshape), the fused chain
        (ops/encoder_pallas.py) runs stem-norm/relu + both blocks with
        norms and joins in-register, and the stride-2 layer2_0 entry
        consumes the s2d layout through the existing phase-structured XLA
        kernels — no layout boundary anywhere on the path."""
        from raft_stereo_tpu.ops.encoder_pallas import (
            bn_affine,
            fused_layer1_s2d,
            instance_affine_from_stats,
        )

        b, h, w, c = stem_y.shape
        dtype = stem_y.dtype
        y = w_s2d(stem_y)

        if self.norm_fn == "batch":
            # Declared unnamed like the non-fused `make_norm` call so the
            # trunk-scope auto-number (FrozenBatchNorm_0) matches.
            inv, shift = FrozenBatchNorm(c)(None)
            aff0 = bn_affine(jnp.tile(inv, 2), jnp.tile(shift, 2), b)
        else:
            # Stem InstanceNorm statistics; XLA multi-output-fuses these
            # reductions into the stem conv (see layers.InstanceNorm), so
            # no extra full-res pass happens here.
            s = jnp.sum(y, axis=(1, 2), dtype=jnp.float32)
            sq = jnp.sum(
                jnp.square(y.astype(jnp.float32)), axis=(1, 2), dtype=jnp.float32
            )
            aff0 = instance_affine_from_stats(jnp.stack([s, sq], axis=1), h * w)

        blocks = []
        for name in ("layer1_0", "layer1_1"):
            k1, b1, k2, b2, a1, a2 = _FusedBlockParams(c, self.norm_fn, name=name)()
            blocks.append(
                (
                    dense_w_kernel(k1).astype(dtype),
                    jnp.tile(b1, 2),
                    dense_w_kernel(k2).astype(dtype),
                    jnp.tile(b2, 2),
                    bn_affine(a1[0], a1[1], b) if a1 is not None else None,
                    bn_affine(a2[0], a2[1], b) if a2 is not None else None,
                )
            )

        y = fused_layer1_s2d(y, aff0, blocks, self.norm_fn)

        if s1 == 2:
            return ResidualBlockFromS2D(96, self.norm_fn, in_features=c, name="layer2_0")(y)
        y = y.reshape(b, h, w, c)
        return ResidualBlock(96, self.norm_fn, stride=1, name="layer2_0")(y)


class BasicEncoder(nn.Module):
    """Correlation-feature encoder: trunk + 1x1 projection to `output_dim`
    (reference core/extractor.py:122-201; instance norm, output_dim=256).

    The reference batches [image1, image2] into one 2B forward
    (core/extractor.py:180-183); callers here do the same concat/split so both
    images ride one MXU-friendly batch.
    """

    output_dim: int = 256
    norm_fn: str = "instance"
    downsample: int = 3
    s2d_layer1: bool = False
    fused_layer1: bool = False

    @nn.compact
    def __call__(self, x: Array) -> Array:
        x = EncoderTrunk(
            self.norm_fn, self.downsample, self.s2d_layer1, self.fused_layer1,
            name="trunk",
        )(x)
        return Conv(self.output_dim, (1, 1), padding=0, name="conv2")(x)


class MultiBasicEncoder(nn.Module):
    """Context encoder: trunk + stride-2 layer4/layer5 + per-scale output heads
    (reference core/extractor.py:203-308).

    Returns `num_layers` scales, finest first: each scale is a tuple of
    `len(output_dims)` tensors (hidden, context) produced by that scale's
    heads. `output_dims` follows the reference indexing: `output_dims[j][2]`
    is the 1/8-scale (finest) width, `[j][1]` the 1/16, `[j][0]` the 1/32
    (core/extractor.py:235-258).

    When `dual_inp` is True the trunk runs on a 2B batch and the trunk features
    are also returned for the shared-backbone corr head
    (core/extractor.py:291-293, core/raft_stereo.py:78-80).
    """

    output_dims: Tuple[Tuple[int, ...], ...] = ((128, 128, 128), (128, 128, 128))
    norm_fn: str = "batch"
    downsample: int = 3
    s2d_layer1: bool = False
    fused_layer1: bool = False

    @nn.compact
    def __call__(self, x: Array, dual_inp: bool = False, num_layers: int = 3):
        x = EncoderTrunk(
            self.norm_fn, self.downsample, self.s2d_layer1, self.fused_layer1,
            name="trunk",
        )(x)

        trunk_out = None
        if dual_inp:
            trunk_out = x
            x = x[: x.shape[0] // 2]

        outputs08 = tuple(
            nn.Sequential(
                [
                    ResidualBlock(128, self.norm_fn, stride=1, name=f"res08_{j}"),
                    Conv(dims[2], (3, 3), name=f"out08_{j}"),
                ]
            )(x)
            for j, dims in enumerate(self.output_dims)
        )
        scales = [outputs08]

        if num_layers >= 2:
            y = ResidualBlock(128, self.norm_fn, stride=2, name="layer4_0")(x)
            y = ResidualBlock(128, self.norm_fn, stride=1, name="layer4_1")(y)
            outputs16 = tuple(
                nn.Sequential(
                    [
                        ResidualBlock(128, self.norm_fn, stride=1, name=f"res16_{j}"),
                        Conv(dims[1], (3, 3), name=f"out16_{j}"),
                    ]
                )(y)
                for j, dims in enumerate(self.output_dims)
            )
            scales.append(outputs16)

        if num_layers >= 3:
            z = ResidualBlock(128, self.norm_fn, stride=2, name="layer5_0")(y)
            z = ResidualBlock(128, self.norm_fn, stride=1, name="layer5_1")(z)
            outputs32 = tuple(
                Conv(dims[0], (3, 3), name=f"out32_{j}")(z)
                for j, dims in enumerate(self.output_dims)
            )
            scales.append(outputs32)

        if dual_inp:
            return tuple(scales), trunk_out
        return tuple(scales)
