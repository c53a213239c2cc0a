"""1D (epipolar) all-pairs correlation: volume, pyramid, and radius lookup.

TPU-native re-design of the reference's correlation stack
(/root/reference/core/corr.py plus the CUDA sampler in
/root/reference/sampler/):

- The volume build is a batched matmul over the feature dim — it runs on the
  MXU. With an fp32 volume the inputs stay fp32 (the reference keeps lookups
  fp32 to avoid half-precision rounding in the interpolation weights,
  evaluate_stereo.py:227-230); with a bf16 volume the matmul also reads bf16
  inputs (fp32 accumulation) — see `corr_volume` for the precision contract.
- The lookup is a gather + linear interpolation expressed with
  `take_along_axis`; XLA autodiff yields the scatter-add backward that the
  reference hand-writes in CUDA (sampler_kernel.cu:63-105) — and on TPU the
  scatter is deterministic, unlike the reference's racy `+=`.
- Two interchangeable strategies, as in the reference:
  * "reg": precompute the pooled pyramid of the full (B, H, W1, W2) volume
    (CorrBlock1D, core/corr.py:110-156). O(H*W^2) memory, fastest lookups.
  * "alt": keep only pooled copies of fmap2 and form the 9 correlation taps
    on the fly each iteration (PytorchAlternateCorrBlock1D,
    core/corr.py:64-107). O(H*W*D) memory — the high-resolution path.
- A third "pallas" strategy (ops/corr_pallas.py) fuses the pyramid lookup into
  a single kernel — the role the reference's `corr_sampler` CUDA extension
  plays.

Everything is NHWC / (B, H, W, D); per-row independence of the 1D problem is
what makes spatial (H) sharding communication-free here.

Scopes (trace-time metadata only, read by obs/scopes.py): the volume, the
pyramid and the pooled feature levels are `corr_build`, every lookup is
`corr_lookup`.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
from jax import lax
import jax.numpy as jnp

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.utils.geometry import linear_sample_1d

Array = jax.Array

# Accuracy budget for the bf16 correlation volume: max end-point-error shift
# (px) a bf16-stored pyramid may introduce vs the fp32 pyramid on the
# synthetic eval, held by the tier-1 test (tests/test_fast_path.py) and
# read by chip_smoke.py. The eval regime is 2 refinement iterations with fp32
# compute: at RANDOM init the GRU is not contractive, so pyramid rounding
# amplifies chaotically with iteration count (measured: 0.012 px at 2 iters
# vs 6.1 px at 16 on the same weights) — the 2-iter delta is the bounded,
# lever-isolated quantity a budget can govern; re-anchor at 32 iters when a
# trained checkpoint lands (ROADMAP R7).
#
# One draw of untrained weights is noise around this number, so the tier-1
# test holds the MEDIAN of five draws to it (CPU, 128x192: 0.010-0.088 px
# per draw under jax 0.9.0, median 0.030; the spread, not the arithmetic, is
# what moved with the toolchain — jax_threefry_partitionable's default
# flipped in jax 0.5, so PRNGKey(0) draws other weights than it did).
# Measured on the chip (v5e, chip_smoke.py --seed 0, PR 22): 0.063 px on one
# draw at 384x512 with the Pallas lookup — inside that spread, over the
# budget, and one more reason the budget wants R7's trained checkpoint.
BF16_CORR_EPE_BUDGET_PX = 0.05


@scoped("corr_build")
def corr_volume(fmap1: Array, fmap2: Array, out_dtype=jnp.float32) -> Array:
    """All-pairs 1D correlation volume.

    fmap1: (B, H, W1, D), fmap2: (B, H, W2, D) -> (B, H, W1, W2), normalized
    by sqrt(D) (reference core/corr.py:148-156). The einsum accumulates in
    fp32 on the MXU; `out_dtype=bfloat16` stores the volume half-size — the
    TPU counterpart of the reference's fp16 reg_cuda volume
    (core/corr.py:31-61), with more exponent range and fp32 lookup math.
    """
    dim = fmap1.shape[-1]
    if jnp.dtype(out_dtype) == jnp.bfloat16:
        # bf16-stored volume: feed the MXU bf16 inputs with fp32 accumulation
        # (preferred_element_type) — ~8x the fp32-HIGHEST matmul rate on v5e.
        # Input rounding is within the storage precision already accepted by
        # choosing a bf16 volume (the TPU analogue of the reference's fp16
        # reg_cuda volume, core/corr.py:31-61).
        vol = jnp.einsum(
            "bhwd,bhvd->bhwv",
            fmap1.astype(jnp.bfloat16),
            fmap2.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    else:
        f1 = fmap1.astype(jnp.float32)
        f2 = fmap2.astype(jnp.float32)
        vol = jnp.einsum("bhwd,bhvd->bhwv", f1, f2, precision=lax.Precision.HIGHEST)
    return (vol / jnp.sqrt(jnp.asarray(dim, jnp.float32))).astype(out_dtype)


def _avg_pool_last(x: Array) -> Array:
    """Average-pool the last axis by 2 (window 2, stride 2, floor semantics —
    matches `F.avg_pool2d(x, [1, 2], stride=[1, 2])`).

    Computed as a matmul with a 0.5-entry pair-averaging matrix: the last
    axis is the TPU lane axis, where the reshape-to-pairs + mean form costs
    lane shuffles (measured 9.7 ms for the Middlebury-F pyramid vs ~1 ms as
    MXU matmuls). Exact: 0.5 is a power of two, so each product is exact
    and the fp32 accumulation matches the fp32 mean bit-for-bit."""
    w = x.shape[-1]
    w2 = w // 2
    trimmed = x[..., : w2 * 2]
    pool = jnp.repeat(jnp.eye(w2, dtype=x.dtype), 2, axis=0) * jnp.asarray(0.5, x.dtype)
    out = lax.dot_general(
        trimmed,
        pool,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    return out.astype(x.dtype)


@scoped("corr_build")
def corr_pyramid(volume: Array, num_levels: int) -> List[Array]:
    """Pyramid over the W2 axis: level i has W2 // 2**i samples.

    The reference builds num_levels+1 entries but only ever reads the first
    num_levels (core/corr.py:122-125 vs :133); we build exactly what is read.
    """
    pyramid = [volume]
    for _ in range(num_levels - 1):
        pyramid.append(_avg_pool_last(pyramid[-1]))
    return pyramid


@scoped("corr_lookup")
def corr_lookup(pyramid: Sequence[Array], coords: Array, radius: int) -> Array:
    """Sample a (2r+1)-tap window around `coords` at every pyramid level.

    coords: (B, H, W1) absolute x positions at level-0 resolution. Returns
    (B, H, W1, num_levels * (2r+1)), level-major tap order like the
    reference's channel concat (core/corr.py:127-146). Out-of-range taps are
    zero (grid_sample zero-padding semantics).
    """
    offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    out = []
    for i, vol in enumerate(pyramid):
        x = coords.astype(jnp.float32)[..., None] / (2**i) + offsets
        out.append(linear_sample_1d(vol, x))
    return jnp.concatenate(out, axis=-1)


@scoped("corr_build")
def pool_fmap_levels(fmap2: Array, num_levels: int) -> List[Array]:
    """Pooled right-image features for the on-the-fly ("alt") strategy.

    fmap2: (B, H, W2, D); level i is pooled 2**i along W (reference
    core/corr.py:104 pools after each level's correlation).
    """
    levels = [fmap2.astype(jnp.float32)]
    for _ in range(num_levels - 1):
        prev = levels[-1]
        w2 = prev.shape[2] // 2
        trimmed = prev[:, :, : w2 * 2, :]
        levels.append(trimmed.reshape(prev.shape[0], prev.shape[1], w2, 2, prev.shape[3]).mean(axis=3))
    return levels


@scoped("corr_lookup")
def corr_lookup_alt(
    fmap1: Array, fmap2_levels: Sequence[Array], coords: Array, radius: int
) -> Array:
    """On-the-fly correlation taps: sample fmap2 at the tap positions and dot
    with fmap1, never materializing the W1 x W2 volume.

    Memory per step is O(B*H*W1*(2r+1)*D) instead of O(B*H*W1*W2) persistent —
    the reference's "alt" trade-off for full-resolution Middlebury
    (README.md:134). Returns (B, H, W1, num_levels * (2r+1)).
    """
    f1 = fmap1.astype(jnp.float32)
    dim = f1.shape[-1]
    scale = jnp.sqrt(jnp.asarray(dim, jnp.float32))
    offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    taps = 2 * radius + 1
    out = []
    for i, f2 in enumerate(fmap2_levels):
        x = coords.astype(jnp.float32)[..., None] / (2**i) + offsets  # (B,H,W1,K)
        # Sample each feature channel at the tap positions: gather along W.
        # values (B,H,D,W2), positions broadcast over D.
        vals = jnp.moveaxis(f2, -1, 2)  # (B, H, D, W2)
        xb = jnp.broadcast_to(x[:, :, None, :, :].reshape(x.shape[0], x.shape[1], 1, -1),
                              (x.shape[0], x.shape[1], vals.shape[2], x.shape[2] * taps))
        sampled = linear_sample_1d(vals, xb)  # (B, H, D, W1*K)
        sampled = sampled.reshape(vals.shape[0], vals.shape[1], vals.shape[2], x.shape[2], taps)
        corr = jnp.einsum("bhdwk,bhwd->bhwk", sampled, f1, precision=lax.Precision.HIGHEST)
        out.append(corr / scale)
    return jnp.concatenate(out, axis=-1)


def make_corr_fn(
    implementation: str,
    fmap1: Array,
    fmap2: Array,
    num_levels: int,
    radius: int,
    corr_dtype=jnp.float32,
    prefetch: bool = False,
) -> Callable[[Array], Array]:
    """Build a `coords -> corr taps` closure for the chosen strategy.

    The closure is used inside the jitted scan body; all captured arrays are
    traced values of the enclosing jit, so strategy selection is static and
    free at runtime (reference: class dispatch in core/raft_stereo.py:90-100).
    `corr_dtype` selects the "reg"/"pallas" pyramid storage dtype (see
    corr_volume); `prefetch` selects the scalar-prefetch windowed lookup for
    the "pallas" strategy only (no VJP — inference closures; ignored by the
    XLA strategies).
    """
    if implementation == "reg":
        pyramid = corr_pyramid(corr_volume(fmap1, fmap2, out_dtype=corr_dtype), num_levels)
        return lambda coords: corr_lookup(pyramid, coords, radius)
    if implementation == "alt":
        f1 = fmap1.astype(jnp.float32)
        levels = pool_fmap_levels(fmap2, num_levels)
        return lambda coords: corr_lookup_alt(f1, levels, coords, radius)
    if implementation == "pallas":
        from raft_stereo_tpu.ops.corr_pallas import make_pallas_corr_fn

        return make_pallas_corr_fn(
            fmap1, fmap2, num_levels, radius, corr_dtype=corr_dtype, prefetch=prefetch
        )
    raise ValueError(f"unknown corr implementation {implementation!r}")
