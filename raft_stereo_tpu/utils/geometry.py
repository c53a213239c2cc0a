"""L1 tensor utilities, NHWC throughout.

TPU-native counterparts of the reference's torch helpers
(/root/reference/core/utils/utils.py). Everything here is shape-static and
jit/vmap/scan friendly: no data-dependent Python control flow, gathers are
expressed with `take_along_axis` so XLA lowers them to TPU-friendly dynamic
slices, and interpolation is separable so it fuses into neighbouring ops.
"""

from __future__ import annotations

import jax
from jax import lax
import jax.numpy as jnp

from raft_stereo_tpu.obs.scopes import scoped


def coords_grid_x(batch: int, height: int, width: int, dtype=jnp.float32) -> jax.Array:
    """Base x-coordinate grid, shape (B, H, W).

    The stereo problem is 1D: matching happens along the epipolar (x) axis and
    the y component of the flow field is identically zero (the reference zeroes
    it every iteration, core/raft_stereo.py:120). We therefore carry only the x
    grid — half the memory traffic of the reference's 2-channel `coords_grid`
    (core/utils/utils.py:77-80).
    """
    xs = jnp.arange(width, dtype=dtype)
    return jnp.broadcast_to(xs[None, None, :], (batch, height, width))


def linear_sample_1d(values: jax.Array, x: jax.Array) -> jax.Array:
    """Linearly interpolate `values` (..., W) at positions `x` (..., K).

    Matches `F.grid_sample(..., align_corners=True, padding_mode='zeros')` on a
    height-1 image (the semantics of the reference's corr lookup,
    core/utils/utils.py:59-74): each of the two gather taps contributes zero
    when it falls outside [0, W-1].

    Leading dims of `values` and `x` must agree; the last dims are independent
    (W sample points for K query positions).
    """
    w = values.shape[-1]
    x0f = jnp.floor(x)
    frac = x - x0f
    x0 = x0f.astype(jnp.int32)
    x1 = x0 + 1

    def tap(idx, weight):
        valid = (idx >= 0) & (idx <= w - 1)
        gathered = jnp.take_along_axis(values, jnp.clip(idx, 0, w - 1), axis=-1)
        # Keep the lerp weights fp32: gathers from a reduced-precision source
        # (bf16 corr volumes) promote to fp32 here, so only the memory/gather
        # side is low-precision — the interpolation arithmetic never is.
        return gathered * (weight * valid.astype(jnp.float32))

    return tap(x0, 1.0 - frac) + tap(x1, frac)


def resize_bilinear_align_corners(x: jax.Array, out_h: int, out_w: int) -> jax.Array:
    """Bilinear resize with align_corners=True, NHWC.

    `jax.image.resize` uses half-pixel centers, but the reference's cross-scale
    GRU exchange uses align-corners interpolation (core/update.py:93-95).
    Implemented as separable matmuls with 2-banded interpolation matrices:
    constant-index row/column gathers lower poorly on TPU (the same family
    of problem as avg_pool2x's strided slices — see its docstring), while
    the banded matmul rides the MXU. Each output has exactly the same two
    products and one add as the gather-lerp form: exact in fp32 (the
    HIGHEST-precision einsum computes fp32 products and rounds once);
    under bf16 inputs results differ from the old bf16 gather-lerp within
    one rounding (the matmul path is the more accurate of the two).
    Output (B, out_h, out_w, C).
    """
    b, in_h, in_w, c = x.shape

    def interp_matrix(n_in, n_out, dtype):
        """(n_out, n_in) with S[o, i0] = 1-frac, S[o, i0+1] = frac."""
        if n_out == 1 or n_in == 1:
            return jnp.zeros((n_out, n_in), dtype).at[:, 0].set(1.0)
        pos = jnp.linspace(0.0, n_in - 1.0, n_out).astype(jnp.float32)
        i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_in - 2)
        frac = pos - i0.astype(jnp.float32)
        o = jnp.arange(n_out)
        s = jnp.zeros((n_out, n_in), jnp.float32)
        s = s.at[o, i0].add(1.0 - frac).at[o, i0 + 1].add(frac)
        return s.astype(dtype)

    if in_h != out_h:
        sh = interp_matrix(in_h, out_h, x.dtype)
        x = jnp.einsum("oh,bhwc->bowc", sh, x, precision=lax.Precision.HIGHEST)
    if in_w != out_w:
        sw = interp_matrix(in_w, out_w, x.dtype)
        x = jnp.einsum("ow,bhwc->bhoc", sw, x, precision=lax.Precision.HIGHEST)
    return x


def avg_pool2x(x: jax.Array) -> jax.Array:
    """3x3 stride-2 average pool with zero padding 1, NHWC.

    Matches `F.avg_pool2d(x, 3, stride=2, padding=1)` with its default
    count_include_pad=True — the divisor is always 9, padded zeros included
    (reference core/update.py:87-88).

    Not `lax.reduce_window`: the window primitive has no linearization rule
    inside `lax.scan` bodies (grad blows up with "Linearization failed").
    Not 9 strided slices either: XLA:TPU lowers stride-2 slices on the
    row/column axes as row-index GATHERS — measured 9 x 0.64 ms per GRU
    iteration at Middlebury-F, ~22% of the whole iteration
    (device trace, round 2). Instead, stride-2 sampling is expressed as
    reshape-to-pairs + unit-stride slices, which compile to plain loop
    fusions at full bandwidth:

        even[i] = P[2i], odd[i] = P[2i+1]  via reshape(n, 2)
        3-tap stride-2 sum = even[:n] + odd[:n] + even[1:n+1]

    applied along W then H.
    """
    b, h, w, c = x.shape
    oh, ow = (h + 1) // 2, (w + 1) // 2
    # Pad so both pair-reshapes are exact: W side needs 2*ow+2 columns
    # (ow pairs plus the shifted-even tap), H side 2*oh+2 rows.
    padded = jnp.pad(x, ((0, 0), (1, 2 * oh + 1 - h), (1, 2 * ow + 1 - w), (0, 0)))

    pw = padded.reshape(b, 2 * oh + 2, ow + 1, 2, c)
    we, wo = pw[:, :, :, 0, :], pw[:, :, :, 1, :]
    h3 = we[:, :, :ow] + wo[:, :, :ow] + we[:, :, 1 : ow + 1]  # (b, 2*oh+2, ow, c)

    ph = h3.reshape(b, oh + 1, 2, ow, c)
    he, ho = ph[:, :, 0], ph[:, :, 1]
    total = he[:, :oh] + ho[:, :oh] + he[:, 1 : oh + 1]
    return total / jnp.asarray(9, x.dtype)


def extract_3x3_patches(x: jax.Array) -> jax.Array:
    """Zero-padded 3x3 neighbourhoods: (B, H, W, C) -> (B, H, W, 9, C).

    Tap order is (ky, kx) row-major, matching torch `F.unfold`'s kernel
    ordering so upsample masks convert 1:1 from reference checkpoints.
    """
    b, h, w, c = x.shape
    padded = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = [
        padded[:, ky : ky + h, kx : kx + w, :]
        for ky in range(3)
        for kx in range(3)
    ]
    return jnp.stack(taps, axis=3)


@scoped("upsample")
def convex_upsample_blocked(field: jax.Array, mask: jax.Array, factor: int) -> jax.Array:
    """`convex_upsample` stopping at the einsum's native blocked form.

    Returns (B, H, factor, W, factor, C) with
    out[b, h, i, w, j, c] == upsampled[b, h*factor+i, w*factor+j, c]; the
    row-major reshape to (B, H*factor, W*factor, C) is free. Training
    consumes THIS form: reshaping the 22-prediction stack to row-major
    full-res forced XLA:TPU to materialize ~81 MB layout transposes on both
    sides of the loss (~19 ms/step of pure copies in the round-5 train
    trace, loss.py:55/67 + this einsum's transpose); keeping the loss in
    the blocked domain reshapes the ground truth instead (a (B,H,W) ->
    (B,H/f,f,W/f,f) free reshape of a 4x-smaller tensor)."""
    b, h, w, c = field.shape
    logits = mask.reshape(b, h, w, 9, factor, factor)
    weights = jax.nn.softmax(logits, axis=3)
    patches = extract_3x3_patches(field * factor)  # (B, H, W, 9, C)
    # out[b, h*f+i, w*f+j, c] = sum_k weights[b,h,w,k,i,j] * patches[b,h,w,k,c]
    return jnp.einsum("bhwkij,bhwkc->bhiwjc", weights, patches)


def convex_upsample(field: jax.Array, mask: jax.Array, factor: int) -> jax.Array:
    """Convex-combination upsampling of a flow/disparity field, NHWC.

    field: (B, H, W, C) low-res field; mask: (B, H, W, 9*factor*factor) raw
    logits from the mask head. Each fine pixel is a softmax-weighted convex
    combination of the 3x3 coarse neighbourhood, and the field magnitude is
    scaled by `factor` (reference core/raft_stereo.py:55-67). Returns
    (B, H*factor, W*factor, C).

    The mask channel layout is (9, factor, factor) fastest-last — identical to
    the reference's `mask.view(N, 1, 9, factor, factor, H, W)` — so converted
    checkpoints need no channel permutation.
    """
    b, h, w, c = field.shape
    up = convex_upsample_blocked(field, mask, factor)
    return up.reshape(b, h * factor, w * factor, c)


def unblock_predictions(flows: jax.Array) -> jax.Array:
    """(iters, B, H/f, f, W/f, f) blocked prediction stack (the train-mode
    model output) -> (iters, B, H, W, 1) row-major full-res. Pure reshape;
    use at API edges (tests, visualization) — the loss consumes the blocked
    form directly."""
    it, b, hb, f1, wb, f2 = flows.shape
    return flows.reshape(it, b, hb * f1, wb * f2, 1)


def upsample_bilinear_scaled(field: jax.Array, factor: int) -> jax.Array:
    """Bilinear `factor`-x upsample that also scales values by `factor`.

    Generalizes the reference's `upflow8` fallback (core/utils/utils.py:83-85)
    to any downsample factor — fixing the reference quirk where the fallback
    hardcodes 8x regardless of `n_downsample` (SURVEY.md appendix).
    """
    b, h, w, c = field.shape
    return factor * resize_bilinear_align_corners(field, h * factor, w * factor)
