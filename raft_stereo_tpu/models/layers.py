"""Neural building blocks shared by the encoders and update block.

TPU-native notes:
- Everything is NHWC with HWIO conv kernels — the layouts XLA:TPU tiles onto
  the MXU without transposes.
- Normalization layers follow the reference's *effective* semantics
  (/root/reference/core/extractor.py): `FrozenBatchNorm` always normalizes
  with stored running statistics because the reference freezes every
  BatchNorm before the first step (train_stereo.py:170 →
  core/raft_stereo.py:41-44), so batch statistics are never used in training
  or eval. That removes any cross-device stat sync — frozen BN is a pure
  per-channel affine, which XLA fuses into the neighbouring conv.
- `compute_dtype` implements the reference's AMP autocast boundary
  (core/raft_stereo.py:77,112): params live in fp32, compute may be bf16.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from flax import linen as nn
import jax
import jax.numpy as jnp

Array = jax.Array
Dtype = jnp.dtype


class FrozenBatchNorm(nn.Module):
    """BatchNorm that always uses stored running statistics.

    Matches the reference's frozen-BN training regime (core/raft_stereo.py:41-44):
    `m.eval()` on every BatchNorm2d before training, so normalization always
    reads `running_mean`/`running_var`. Stats are non-trainable variables in
    the `batch_stats` collection so checkpoint converters can populate them
    from torch `running_mean`/`running_var`.

    `phases > 1` applies the affine in a space-to-depth domain where the
    input carries `phases * features` channels ([phase0 | phase1 | ...],
    each block the original channels): the per-channel affine simply tiles
    across phase blocks. Parameter shapes are unchanged.

    Calling with `x=None` declares the identical parameters/variables but
    returns the folded fp32 `(inv, shift)` affine instead of applying it —
    for consumers that apply the affine inside a fused kernel
    (ops/encoder_pallas.py) while keeping this exact parameter tree.
    """

    features: int
    epsilon: float = 1e-5
    dtype: Optional[Dtype] = None
    phases: int = 1

    @nn.compact
    def __call__(self, x: Optional[Array] = None):
        mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        ).value
        var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        ).value
        scale = self.param("scale", nn.initializers.ones, (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        # Fold stats into a single per-channel affine in fp32, then cast once.
        inv = jax.lax.rsqrt(var + self.epsilon) * scale
        shift = bias - mean * inv
        if self.phases > 1:
            inv = jnp.tile(inv, self.phases)
            shift = jnp.tile(shift, self.phases)
        if x is None:
            return inv, shift
        dtype = self.dtype or x.dtype
        return x * inv.astype(dtype) + shift.astype(dtype)


class InstanceNorm(nn.Module):
    """Per-sample, per-channel normalization over (H, W).

    torch `nn.InstanceNorm2d` defaults: affine=False, no running stats
    (reference fnet, core/extractor.py:134-135) — so this layer has no
    parameters at all. Statistics are computed in fp32 for bf16 inputs.
    """

    features: int  # kept for interface symmetry; no params
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: Array) -> Array:
        # ONE-pass statistics (E[x²] − mean²), both reductions in fp32: the
        # round-3 trace showed XLA multi-output-fuses reductions of a conv's
        # output INTO the conv fusion (convert_reduce_fusion) — with sum and
        # sumsq both derived directly from x, the producer conv emits both
        # and the separate full-tensor variance pass disappears (was
        # ~1.9 ms/IN at Middlebury-F full res, ~19 ms/forward). Accumulation
        # is fp32 (`dtype=float32` reduces; the bf16→fp32 convert and the
        # square fuse into the reduce, nothing full-res materializes).
        # Cancellation note: E[x²] − mean² loses precision only when
        # var ≪ mean² (near-constant channels); conv pre-activations are
        # zero-mean-ish, and torch's own var computation is one-pass too —
        # parity-tested against torch InstanceNorm2d in test_model.py.
        b, h, w, c = x.shape
        n = h * w
        x32sum = jnp.sum(x, axis=(1, 2), dtype=jnp.float32)
        sq = jnp.sum(
            jnp.square(x.astype(jnp.float32)), axis=(1, 2), dtype=jnp.float32
        )
        mean = x32sum / n
        var = jnp.maximum(sq / n - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + self.epsilon)
        return (x - mean.astype(x.dtype)[:, None, None, :]) * inv.astype(x.dtype)[
            :, None, None, :
        ]


class GroupNorm(nn.Module):
    """GroupNorm with torch's num_groups = features // 8 convention
    (reference ResidualBlock, core/extractor.py:14-20)."""

    features: int
    num_groups: int
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: Array) -> Array:
        scale = self.param("scale", nn.initializers.ones, (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        b, h, w, c = x.shape
        g = self.num_groups
        x32 = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
        mean = x32.mean(axis=(1, 2, 4), keepdims=True)
        var = x32.var(axis=(1, 2, 4), keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y.reshape(b, h, w, c) * scale + bias
        return y.astype(x.dtype)


def make_norm(norm_fn: str, features: int) -> Callable[[Array], Array]:
    """Norm factory mirroring the reference's `norm_fn` switch
    (core/extractor.py:16-38)."""
    if norm_fn == "batch":
        return FrozenBatchNorm(features)
    if norm_fn == "instance":
        return InstanceNorm(features)
    if norm_fn == "group":
        return GroupNorm(features, num_groups=features // 8)
    if norm_fn == "none":
        return lambda x: x
    raise ValueError(f"unknown norm_fn {norm_fn!r}")


def kaiming_out() -> nn.initializers.Initializer:
    """torch `kaiming_normal_(mode='fan_out', nonlinearity='relu')`
    (reference core/extractor.py:161) — variance 2/fan_out."""
    return nn.initializers.variance_scaling(2.0, "fan_out", "truncated_normal")


class Conv(nn.Module):
    """3x3/1x1/NxN conv with torch-style symmetric padding and fp32 params.

    Compute dtype follows the input; params are stored fp32 and cast at use —
    the standard TPU mixed-precision pattern replacing torch AMP.
    """

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: Optional[int] = None  # default: kernel//2 ("same" for odd kernels)
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: Array) -> Array:
        kh, kw = self.kernel_size
        pad = self.padding if self.padding is not None else kh // 2
        y = nn.Conv(
            features=self.features,
            kernel_size=self.kernel_size,
            strides=self.strides,
            padding=[(pad, pad), (pad, pad)] if isinstance(pad, int) else pad,
            use_bias=self.use_bias,
            dtype=x.dtype,
            param_dtype=jnp.float32,
            kernel_init=kaiming_out(),
        )(x)
        return y


class RawConvParams(nn.Module):
    """Declares exactly the parameters flax `nn.Conv` would (names `kernel`/
    `bias`, same shapes and init) without computing anything — for modules
    that restructure a conv's math but must keep its parameter tree."""

    features: int
    in_features: int
    kernel_size: Tuple[int, int] = (3, 3)

    @nn.compact
    def __call__(self):
        kh, kw = self.kernel_size
        kernel = self.param(
            "kernel", kaiming_out(), (kh, kw, self.in_features, self.features), jnp.float32
        )
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        return kernel, bias


class ConvParams(nn.Module):
    """Conv-compatible parameter holder: nests `RawConvParams` under
    "Conv_0" so the param tree is byte-identical to the `Conv` wrapper's
    (<name>/Conv_0/kernel) — converted checkpoints are unaffected."""

    features: int
    in_features: int
    kernel_size: Tuple[int, int] = (3, 3)

    @nn.compact
    def __call__(self):
        return RawConvParams(
            self.features, self.in_features, self.kernel_size, name="Conv_0"
        )()


def im2col_conv(kernel: Array, bias: Array, x: Array) -> Array:
    """Stride-1 "same" KxK conv for tiny C_in, as column im2col + a Kx1 conv.

    A direct conv starves the MXU's contraction lanes at small C_in (the
    Middlebury-F stem ran at 5.6 TF/s with C_in=3). Packing the K column
    taps into channels (one loop fusion of unit-stride shifted slices)
    gives the conv K*C_in input channels; the kernel-height dimension stays
    spatial, which the conv lowering handles with unit-stride row access.
    Measured on v5e at the full-res stem: 6.5 ms vs 17.1 direct — and vs
    25.5 for full KxK im2col + 1x1 conv, whose (B, H, W, K*K*C_in) patch
    tensor pays an 18 ms layout copy (device trace, round 2).

    Patch channel t = kx*C_in + c_in matches reshaping the (K, K, C_in,
    C_out) kernel to (K, 1, K*C_in, C_out), so the math is the conv's
    exactly."""
    kh, kw, cin, cout = kernel.shape
    assert kh == kw and kh % 2 == 1, "square odd kernels only"
    dtype = x.dtype
    b, h, w, c = x.shape
    assert c == cin, (c, cin)
    p = kh // 2
    xp = jnp.pad(x, ((0, 0), (0, 0), (p, p), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, :, kx : kx + w, :] for kx in range(kw)], axis=-1
    )
    wk = kernel.reshape(kh, kw * cin, cout).astype(dtype)[:, None, :, :]
    return jax.lax.conv_general_dilated(
        patches, wk, (1, 1), [(p, p), (0, 0)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=dtype,
    ) + bias.astype(dtype)


# --- W-space-to-depth (s2d) conv domain -------------------------------------
#
# XLA:TPU's conv emitter runs the full-res C=64 encoder convs at ~28 TF/s
# (the 64-channel contraction fills half the MXU's 128 lanes); the same
# kernel embedded in a 128-channel space-to-depth domain runs at ~48 TF/s
# useful despite carrying 50% structural zeros (measured round 4, a layer
# alone: direct 14.9 ms vs s2d 8.8 ms per layer1 conv at Middlebury-F; the
# layer1 chain 81.3 -> 65.0 ms).
#
# The W dimension is chosen because (B,H,W,C) -> (B,H,W/2,2C) is a PURE
# RESHAPE in row-major (W and C are adjacent), so entering the domain is
# free; leaving it never happens — the stride-2 layer2 entry consumes the
# s2d layout directly through phase-structured kernels. Channel layout of
# the domain: [even-col channels | odd-col channels].
#
# Replaces the role of the reference's layer1 convs
# (/root/reference/core/extractor.py:6-60,144-148) with identical math
# (formulation proven exact in f64, tests/test_model.py
# test_s2d_kernel_embeddings_match_direct_conv).


def w_s2d(x: Array) -> Array:
    """(B,H,W,C) -> (B,H,W/2,2C); W must be even."""
    b, h, w, c = x.shape
    return x.reshape(b, h, w // 2, 2 * c)


def dense_w_kernel(k: Array) -> Array:
    """Embed a 3x3xCxC stride-1 'same' kernel into the W-s2d domain:
    (3,3,2C,2C), 50% structural zeros. Output cols of phase E (even) read
    col taps {2j-1,2j,2j+1} = blocks {j-1:O, j:E, j:O}; phase O reads
    blocks {j:E, j:O, j+1:E}; a kw=3 window over block cols {j-1,j,j+1}
    covers both phases."""
    kh, kw, c, co = k.shape
    K = jnp.zeros((kh, 3, 2 * c, 2 * co), k.dtype)
    # E outputs (first co block)
    K = K.at[:, 0, c:, :co].set(k[:, 0])   # block j-1, O part, tap dw=-1
    K = K.at[:, 1, :c, :co].set(k[:, 1])   # block j,   E part, tap dw=0
    K = K.at[:, 1, c:, :co].set(k[:, 2])   # block j,   O part, tap dw=+1
    # O outputs (second co block)
    K = K.at[:, 1, :c, co:].set(k[:, 0])   # block j,   E part, tap dw=-1
    K = K.at[:, 1, c:, co:].set(k[:, 1])   # block j,   O part, tap dw=0
    K = K.at[:, 2, :c, co:].set(k[:, 2])   # block j+1, E part, tap dw=+1
    return K


def entry_w_kernel(k: Array) -> Array:
    """Embed a 3x3xCxCo stride-(2,2) 'same' kernel as (3,2,2C,Co) with
    stride (2,1) consuming the W-s2d domain (the layer2_0 entry): output
    col 2j reads col taps {2j-1,2j,2j+1} = blocks {j-1:O, j:E, j:O}, so the
    kw=2 window is {j-1, j} with W padding (1,0)."""
    kh, kw, c, co = k.shape
    K = jnp.zeros((kh, 2, 2 * c, co), k.dtype)
    K = K.at[:, 0, c:, :].set(k[:, 0])
    K = K.at[:, 1, :c, :].set(k[:, 1])
    K = K.at[:, 1, c:, :].set(k[:, 2])
    return K


def skip_w_kernel(k: Array) -> Array:
    """Embed a 1x1xCxCo stride-(2,2) kernel as (1,1,2C,Co) stride (2,1):
    output col 2j is exactly the even phase."""
    kh, kw, c, co = k.shape
    K = jnp.zeros((1, 1, 2 * c, co), k.dtype)
    K = K.at[0, 0, :c, :].set(k[0, 0])
    return K


def _conv_s2d(x: Array, kernel: Array, bias: Array, strides, padding) -> Array:
    dtype = x.dtype
    y = jax.lax.conv_general_dilated(
        x, kernel.astype(dtype), strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=dtype,
    )
    return y + bias.astype(dtype)


def s2d_instance_norm(y: Array, phases: int = 2, epsilon: float = 1e-5) -> Array:
    """InstanceNorm in the s2d domain: the (H,W) statistics of original
    channel c pool phase blocks c and c+C; the affine tiles them back. Same
    one-pass E[x^2]-mean^2 form as `InstanceNorm` (both reductions
    multi-output-fuse into the producer conv)."""
    b, h, w2, pc = y.shape
    c = pc // phases
    n = h * w2 * phases
    s = jnp.sum(y, axis=(1, 2), dtype=jnp.float32).reshape(b, phases, c).sum(axis=1)
    sq = (
        jnp.sum(jnp.square(y.astype(jnp.float32)), axis=(1, 2), dtype=jnp.float32)
        .reshape(b, phases, c)
        .sum(axis=1)
    )
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + epsilon)
    mean_t = jnp.tile(mean, (1, phases)).astype(y.dtype)[:, None, None, :]
    inv_t = jnp.tile(inv, (1, phases)).astype(y.dtype)[:, None, None, :]
    return (y - mean_t) * inv_t


class ResidualBlockS2D(nn.Module):
    """`ResidualBlock` (stride 1, in_features == features) evaluated in the
    W-s2d domain. Parameter tree is byte-identical to `ResidualBlock`'s
    (conv1/Conv_0, conv2/Conv_0, FrozenBatchNorm_{0,1}) — checkpoints are
    interchangeable; only the compute layout differs."""

    features: int
    norm_fn: str = "instance"

    def _norm(self, y: Array) -> Array:
        if self.norm_fn == "instance":
            return s2d_instance_norm(y)
        # "batch": FrozenBatchNorm with the affine tiled across phases.
        # Unnamed like ResidualBlock's make_norm call so auto-numbering
        # (FrozenBatchNorm_0/1) matches.
        return FrozenBatchNorm(self.features, phases=2)(y)

    @nn.compact
    def __call__(self, y: Array) -> Array:
        c = self.features
        k1, b1 = ConvParams(c, c, (3, 3), name="conv1")()
        z = _conv_s2d(y, dense_w_kernel(k1), jnp.tile(b1, 2), (1, 1), ((1, 1), (1, 1)))
        z = nn.relu(self._norm(z))
        k2, b2 = ConvParams(c, c, (3, 3), name="conv2")()
        z = _conv_s2d(z, dense_w_kernel(k2), jnp.tile(b2, 2), (1, 1), ((1, 1), (1, 1)))
        z = nn.relu(self._norm(z))
        return nn.relu(y + z)


class ResidualBlockFromS2D(nn.Module):
    """The stride-2 `ResidualBlock` (layer2_0) with conv1 and the 1x1
    downsample consuming W-s2d input through phase-structured kernels; the
    rest of the block (and its output) live in the normal domain. Parameter
    tree identical to `ResidualBlock`'s stride-2 form."""

    features: int
    norm_fn: str
    in_features: int

    @nn.compact
    def __call__(self, y: Array) -> Array:
        c_in, c = self.in_features, self.features
        k1, b1 = ConvParams(c, c_in, (3, 3), name="conv1")()
        z = _conv_s2d(y, entry_w_kernel(k1), b1, (2, 1), ((1, 1), (1, 0)))
        z = make_norm(self.norm_fn, c)(z)
        z = nn.relu(z)
        z = Conv(c, (3, 3), name="conv2")(z)
        z = make_norm(self.norm_fn, c)(z)
        z = nn.relu(z)
        kd, bd = ConvParams(c, c_in, (1, 1), name="downsample")()
        x = _conv_s2d(y, skip_w_kernel(kd), bd, (2, 1), ((0, 0), (0, 0)))
        x = make_norm(self.norm_fn, c)(x)
        return nn.relu(x + z)


class ResidualBlock(nn.Module):
    """Two 3x3 convs + skip, pre-activation ordering of the reference
    (core/extractor.py:6-60): conv→norm→relu twice, optional strided 1x1
    downsample on the skip, relu(x + y) at the join."""

    features: int
    norm_fn: str = "group"
    stride: int = 1
    in_features: Optional[int] = None  # needed only to decide the skip path

    @nn.compact
    def __call__(self, x: Array) -> Array:
        in_features = self.in_features if self.in_features is not None else x.shape[-1]
        y = Conv(self.features, (3, 3), strides=(self.stride, self.stride), name="conv1")(x)
        y = make_norm(self.norm_fn, self.features)(y)
        y = nn.relu(y)
        y = Conv(self.features, (3, 3), name="conv2")(y)
        y = make_norm(self.norm_fn, self.features)(y)
        y = nn.relu(y)

        if not (self.stride == 1 and in_features == self.features):
            x = Conv(
                self.features,
                (1, 1),
                strides=(self.stride, self.stride),
                padding=0,
                name="downsample",
            )(x)
            x = make_norm(self.norm_fn, self.features)(x)
        return nn.relu(x + y)
