"""Iterative-refinement update block: motion encoder, coupled ConvGRUs,
flow + upsample-mask heads.

TPU-native re-design of /root/reference/core/update.py:6-138. Architectural
deltas, all mathematically exact w.r.t. the reference:

- **Disparity-native (1-channel) flow.** The reference carries a 2-channel
  flow whose y component is identically zero (zeroed every iteration,
  core/raft_stereo.py:120) and sliced away at the end (:134). We carry 1
  channel: the motion encoder's 7x7 flow conv drops its y-input slice
  (exact, since those weights always multiply 0) and the flow head emits 1
  channel (exact, since channel y was overwritten with 0). The checkpoint
  converter slices torch weights accordingly.
- The GRU context biases (cz, cr, cq) are precomputed once outside the
  iteration loop by the model (reference optimization, core/raft_stereo.py:88)
  and passed in per scale.
- Cross-scale exchange uses avg-pool 3x3/s2 downward and align-corners
  bilinear upward, as in the reference (core/update.py:87-95).

The reference's `SepConvGRU` is dead code and not reproduced (SURVEY.md §2).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from flax import linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp

from raft_stereo_tpu.models.layers import Conv, ConvParams, im2col_conv
from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.utils.geometry import avg_pool2x, resize_bilinear_align_corners

Array = jax.Array

# The name the iteration body's checkpoint policy keeps across the backward
# (raft_stereo.REMAT_SAVED_NAMES). It sits on the nonlinearity's INPUT: the
# derivative rules of `logistic` and `tanh` read their own output variable, so
# a name on z, r or q saves a copy nobody asks for while the convolution is
# still re-run to rebuild the variable the rule wants. From the saved sum the
# backward re-runs only the elementwise nonlinearity.
GATE_SUM = "gru_gate_sum"


class FlowHead(nn.Module):
    """conv3x3 → relu → conv3x3 (reference core/update.py:6-14), emitting a
    single disparity channel.

    The output conv is MXU-starved as a convolution (C_out=1 uses 1 of 128
    output lanes; measured 1.1 ms of each iteration at Middlebury-F), so for
    output_dim=1 it is computed as the same math restructured MXU-first:
    one K=256 matmul onto 9 tap columns (per-pixel dot with each kernel
    tap's 256-vector), then a 9-way shifted sum — a cheap loop fusion.
    Parameters are identical to the conv form (converted checkpoints are
    unaffected)."""

    hidden_dim: int = 256
    output_dim: int = 1

    @nn.compact
    def __call__(self, x: Array) -> Array:
        y = nn.relu(Conv(self.hidden_dim, (3, 3), name="conv1")(x))
        if self.output_dim != 1:
            return Conv(self.output_dim, (3, 3), name="conv2")(y)
        kernel, bias = ConvParams(1, self.hidden_dim, name="conv2")()
        dtype = y.dtype
        # kernel (3, 3, C, 1) → a 1x1 conv onto 9 tap channels (channel
        # t = ky*3+kx holds per-pixel dot with tap K[ky, kx, :]). A 1x1 conv
        # (not a reshaped matmul) so it consumes conv1's output in conv
        # layout — the matmul form triggered a layout copy + depad slice that
        # cost as much as the starved conv it replaced.
        w9 = kernel[..., 0].reshape(1, 1, 9, self.hidden_dim)
        w9 = jnp.swapaxes(w9, 2, 3).astype(dtype)  # (1, 1, C, 9) HWIO
        p = jax.lax.conv_general_dilated(
            y, w9, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=dtype,
        )  # (B, H+2, W+2, 9) — the pad doubles as the 3x3 halo
        h, w = y.shape[1], y.shape[2]
        out = None
        for ky in range(3):
            for kx in range(3):
                tap = p[:, ky : ky + h, kx : kx + w, ky * 3 + kx]
                out = tap if out is None else out + tap
        return out[..., None] + bias.astype(dtype)


def _segmented_conv3x3(kernel: Array, bias: Array, segments: Sequence[Array]) -> Array:
    """conv(concat(segments)) as a sum of per-segment convs with the kernel
    sliced on the input-channel axis — convolution distributes over
    input-channel concat, so the math is the concat conv's, but the
    concatenated tensor is never materialized. Inside the GRU scan the hx/rx
    concats cost ~2 ms of each 36 ms iteration at Middlebury-F scale
    (device-trace measurement).

    Numerics note: each per-segment partial is rounded to the compute dtype
    before the cross-segment add — a different accumulation association
    than the fused conv, so results agree only to rounding error (last-ulp
    diffs in fp32; under mixed precision 1-2 extra bf16 roundings per gate,
    ~0.4% relative noise on gate pre-activations). Keeping partials fp32
    measures 1.8% slower end-to-end and was deliberately not chosen."""
    dtype = segments[0].dtype
    assert all(s.dtype == dtype for s in segments), (
        "segments must share one dtype; the concat conv this replaces would "
        f"have promoted implicitly ({[str(s.dtype) for s in segments]})"
    )
    off = 0
    out = None
    for seg in segments:
        c = seg.shape[-1]
        k = jax.lax.slice_in_dim(kernel, off, off + c, axis=2).astype(dtype)
        y = jax.lax.conv_general_dilated(
            seg,
            k,
            (1, 1),
            [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=dtype,
        )
        out = y if out is None else out + y
        off += c
    assert off == kernel.shape[2]
    return out + bias.astype(dtype)


class ConvGRU(nn.Module):
    """Conv GRU cell with external context biases (reference core/update.py:16-32).

    `h` is the hidden state; `cz, cr, cq` are the precomputed per-scale context
    contributions; `inputs` join `h` (or `r*h` for the candidate gate) on the
    channel axis — applied segment-wise, see _segmented_conv3x3. z and r stay
    separate convs on purpose: XLA:TPU co-schedules the two same-input convs
    at ~166 TF/s combined, measurably faster than one fused double-width conv
    (110 TF/s) on v5e.
    """

    hidden_dim: int
    # Single-call fused gate tail (config.fused_gru_tail): z/tanh/blend in one
    # Pallas pass at the carry boundary; r stays in the conv epilogue. No VJP
    # — RAFTStereo sets this only under test_mode. See ops/gru_tail_pallas.py.
    fused_tail: bool = False

    @nn.compact
    def __call__(self, h: Array, cz: Array, cr: Array, cq: Array, *inputs: Array) -> Array:
        cin = h.shape[-1] + sum(i.shape[-1] for i in inputs)
        kz, bz = ConvParams(self.hidden_dim, cin, name="convz")()
        kr, br = ConvParams(self.hidden_dim, cin, name="convr")()
        kq, bq = ConvParams(self.hidden_dim, cin, name="convq")()
        if self.fused_tail:
            from raft_stereo_tpu.ops import gru_tail_pallas

            zx = _segmented_conv3x3(kz, bz, (h, *inputs))
            r = jax.nn.sigmoid(_segmented_conv3x3(kr, br, (h, *inputs)) + cr)
            qx = _segmented_conv3x3(kq, bq, (r * h, *inputs))
            return gru_tail_pallas.fused_gru_tail(zx, cz, qx, cq, h)
        z = jax.nn.sigmoid(checkpoint_name(_segmented_conv3x3(kz, bz, (h, *inputs)) + cz, GATE_SUM))
        r = jax.nn.sigmoid(checkpoint_name(_segmented_conv3x3(kr, br, (h, *inputs)) + cr, GATE_SUM))
        q = jnp.tanh(checkpoint_name(_segmented_conv3x3(kq, bq, (r * h, *inputs)) + cq, GATE_SUM))
        return (1.0 - z) * h + z * q


class BasicMotionEncoder(nn.Module):
    """Fuse correlation taps and current flow into 128 motion features
    (reference core/update.py:64-85). `flow` is 1-channel disparity; output is
    cat([conv features (126ch), flow (1ch), zeros (1ch)]) — the zero plane
    stands in for the reference's always-zero flow-y channel so downstream
    channel counts (and converted checkpoints) line up exactly."""

    corr_channels: int
    # Fuse the final relu + [features, flow, zeros] concat into one Pallas
    # write (config.fused_gru_tail; no VJP — test-mode only, set by
    # RAFTStereo). See ops/gru_tail_pallas.fused_motion_tail.
    fused_tail: bool = False

    @nn.compact
    def __call__(self, flow: Array, corr: Array) -> Array:
        cor = nn.relu(Conv(64, (1, 1), padding=0, name="convc1")(corr))
        cor = nn.relu(Conv(64, (3, 3), name="convc2")(cor))
        # The 7x7 conv on the 1-channel flow is MXU-starved as a convolution
        # (C_in=1 fills 1 of 128 contraction lanes; 0.63 ms/iteration at
        # Middlebury-F) — restructured as column im2col (7 channels) + a
        # 7x1 conv (layers.im2col_conv). Parameters identical to the conv
        # form.
        kf, bf = ConvParams(64, 1, kernel_size=(7, 7), name="convf1")()
        flo = nn.relu(im2col_conv(kf, bf, flow))
        flo = nn.relu(Conv(64, (3, 3), name="convf2")(flo))
        # conv(cat(cor, flo)) applied segment-wise (conv distributes over
        # input-channel concat, _segmented_conv3x3): the (cor, flo) concat
        # materialization was ~0.3 ms of each iteration at Middlebury-F.
        kc, bc = ConvParams(126, 128, name="conv")()
        if self.fused_tail:
            from raft_stereo_tpu.ops import gru_tail_pallas

            pre = _segmented_conv3x3(kc, bc, (cor, flo))
            return gru_tail_pallas.fused_motion_tail(pre, flow)
        out = nn.relu(_segmented_conv3x3(kc, bc, (cor, flo)))
        zero = jnp.zeros_like(flow)
        return jnp.concatenate([out, flow, zero], axis=-1)


# The cross-scale exchange is plain functions, so flax gives it no scope;
# `interp_pool` is the component obs/scopes.py reads.
@scoped("interp_pool")
def _interp_to(x: Array, like: Array) -> Array:
    return resize_bilinear_align_corners(x, like.shape[1], like.shape[2])


_pool2x = scoped("interp_pool")(avg_pool2x)


class BasicMultiUpdateBlock(nn.Module):
    """1–3 coupled ConvGRUs across scales + heads (reference core/update.py:97-138).

    `net` is the hidden-state tuple, finest scale first (net[0] at 1/2**K res);
    `context` holds per-scale (cz, cr, cq) triples. `hidden_dims` follows the
    reference indexing: hidden_dims[2] is the finest scale's width.

    The `iter08/iter16/iter32` flags reproduce the slow_fast_gru schedule
    (core/raft_stereo.py:113-116); with `update=False` only hidden states are
    advanced and no heads run.
    """

    hidden_dims: Tuple[int, int, int]
    corr_channels: int
    n_gru_layers: int
    n_downsample: int
    fused_tail: bool = False  # config.fused_gru_tail, see ops/gru_tail_pallas.py

    @nn.compact
    def __call__(
        self,
        net: Tuple[Array, ...],
        context: Sequence[Tuple[Array, Array, Array]],
        corr: Optional[Array] = None,
        flow: Optional[Array] = None,
        iter08: bool = True,
        iter16: bool = True,
        iter32: bool = True,
        update: bool = True,
    ):
        net = list(net)
        n = self.n_gru_layers

        # Instantiate cells unconditionally so params are stable across the
        # slow_fast_gru call variants (flax setup-by-first-use otherwise
        # depends on call order).
        ft = self.fused_tail
        gru08 = ConvGRU(self.hidden_dims[2], fused_tail=ft, name="gru08")
        gru16 = ConvGRU(self.hidden_dims[1], fused_tail=ft, name="gru16") if n >= 2 else None
        gru32 = ConvGRU(self.hidden_dims[0], fused_tail=ft, name="gru32") if n == 3 else None

        if iter32 and n == 3:
            net[2] = gru32(net[2], *context[2], _pool2x(net[1]))
        if iter16 and n >= 2:
            if n > 2:
                net[1] = gru16(net[1], *context[1], _pool2x(net[0]), _interp_to(net[2], net[1]))
            else:
                net[1] = gru16(net[1], *context[1], _pool2x(net[0]))
        if iter08:
            motion = BasicMotionEncoder(
                self.corr_channels, fused_tail=ft, name="encoder"
            )(flow, corr)
            if n > 1:
                net[0] = gru08(net[0], *context[0], motion, _interp_to(net[1], net[0]))
            else:
                net[0] = gru08(net[0], *context[0], motion)

        if not update:
            return tuple(net)

        delta_flow = FlowHead(256, output_dim=1, name="flow_head")(net[0])
        return tuple(net), delta_flow


class UpsampleMaskHead(nn.Module):
    """Convex-upsampling mask head (reference core/update.py:108-113,137).

    Hoisted out of the iteration block: the mask depends only on the
    post-update hidden state and feeds no recurrence, so the model applies it
    outside the scan — once on the final state in test mode (instead of
    every iteration like the reference's loop, ~13% of per-iteration conv
    FLOPs at default config), and batched over all iterations' states in
    train mode (one big MXU matmul instead of `iters` small ones)."""

    n_downsample: int

    @nn.compact
    def __call__(self, net0: Array) -> Array:
        factor = 2**self.n_downsample
        mask = nn.Sequential(
            [
                Conv(256, (3, 3), name="mask_conv1"),
                nn.relu,
                Conv(factor * factor * 9, (1, 1), padding=0, name="mask_conv2"),
            ]
        )(net0)
        # 0.25 scaling "to balance gradients" (reference core/update.py:137).
        return 0.25 * mask
