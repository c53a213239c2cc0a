"""Fused Pallas TPU kernel for the correlation-pyramid lookup.

This plays the role of the reference's `corr_sampler` CUDA extension
(/root/reference/sampler/sampler_kernel.cu:19-60 forward, :63-105 backward,
bound in /root/reference/sampler/sampler.cpp:48-51 and driven from
/root/reference/core/corr.py:17-61): sample a (2r+1)-tap linearly
interpolated window around per-pixel coordinates from every level of the 1D
correlation pyramid, in one fused pass.

TPU-native design (not a translation of the CUDA thread-block layout):

- Grid over (B*H rows, W1 query blocks). Queries live on the sublane axis
  and pyramid samples on the lane axis, so the inner gather is Mosaic's
  native `dynamic_gather` (a lane shuffle), not a scalar loop like the CUDA
  kernel's per-thread `volume[...]` reads.
- The TPU vector unit can only gather within a single 128-lane tile, so each
  level's row is processed as ceil(W2/128) tiles with masked accumulation:
  every tap index lands in exactly one tile, all others contribute zero.
  Both lerp taps (floor and floor+1) for all 2r+1 window positions are
  packed into one 128-lane index vector, so each tile costs one gather.
- All `num_levels` levels are fused into a single kernel launch writing one
  (B, H, W1, num_levels*(2r+1)) output — the reference launches one CUDA
  kernel per level (core/corr.py:40-45) and concatenates on the host side.
- The pyramid may be stored bfloat16 (the TPU analogue of the fp16 reg_cuda
  volume, sampler_kernel.cu:126); tiles are upcast in VMEM so the
  interpolation arithmetic is always fp32.

Backward: gradient w.r.t. the pyramid only, matching the CUDA sampler
(`coords` gets a None grad, core/corr.py:29). It is a second fused Pallas
kernel (_scatter_kernel): each query's 2*(2r+1) lerp contributions collapse
onto 2r+2 contiguous positions of the query's OWN volume row, built per
128-lane tile as a one-hot accumulation — deterministic and collision-free
by construction, unlike the reference's racy unsynchronized `+=`
(sampler_kernel.cu:102), and ~2.3x faster end-to-end in training than
XLA's scatter lowering of the equivalent vjp.

On the CPU backend (the test mesh) the kernels run in interpreter mode, so
parity tests cover identical kernel bodies; on a TPU backend they are
compiled, and any other backend is an error (ops/pallas_mode.py). What only
the chip's compiler can refuse is held by tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.corr import corr_pyramid, corr_volume
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array

_LANES = 128

# Queries (W1) per kernel program. Bigger blocks amortize per-program
# overhead against VMEM pressure (each program holds a (W1_BLOCK, sum W2p)
# slice of all pyramid levels). Tuned on v5e at Middlebury-F scale:
# 768 > 256 > 128 (11.1 / 12.6 / 14.3 ms per 32-iter lookup).
_W1_BLOCK = 768


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _w1_blocks(w1: int) -> Tuple[int, int]:
    """Smallest count of <= _W1_BLOCK-sized, 8-aligned blocks covering W1
    (avoids the padding cliff of rounding W1 itself up to a _W1_BLOCK
    multiple — e.g. w1=800 gets 2x400 blocks, not 2x768) → (w1_blk, w1_pad)."""
    n_blocks = -(-w1 // _W1_BLOCK)
    w1_blk = _round_up(-(-w1 // n_blocks), 8)
    return w1_blk, w1_blk * n_blocks


def _query_layout(coords: Array):
    """Shared forward/backward query tiling: coords flattened to
    (B*H, W1_pad, 1) with queries on the sublane axis."""
    b, h, w1 = coords.shape
    rows = b * h
    w1_blk, w1_pad = _w1_blocks(w1)
    coords_flat = jnp.pad(
        coords.reshape(rows, w1, 1).astype(jnp.float32),
        ((0, 0), (0, w1_pad - w1), (0, 0)),
    )
    return rows, w1_blk, w1_pad, coords_flat


def _lookup_kernel(coords_ref, *rest, radius: int, w2_padded: Tuple[int, ...]):
    """One (row, W1-block): fused all-level gather-lerp.

    coords_ref: (1, W1_BLK, 1); rest = per-level volume refs (1, W1_BLK, W2p_i)
    followed by the output ref (1, W1_BLK, L*K).
    """
    vol_refs, out_ref = rest[:-1], rest[-1]
    k = 2 * radius + 1
    w1_blk = coords_ref.shape[1]

    x = coords_ref[0].astype(jnp.float32)  # (W1_BLK, 1), queries on sublanes
    offsets = (
        jax.lax.broadcasted_iota(jnp.int32, (w1_blk, k), 1).astype(jnp.float32)
        - radius
    )  # (W1_BLK, K); tpu.iota only produces integers

    for level, vol_ref in enumerate(vol_refs):
        t = x / (2.0**level) + offsets  # (W1_BLK, K) tap positions
        x0f = jnp.floor(t)
        frac = t - x0f  # fp32 lerp weights (geometry.linear_sample_1d parity)
        x0 = x0f.astype(jnp.int32)

        # Pack both lerp taps into one 128-lane index vector; -1 padding is
        # out of range for every tile, so padded lanes accumulate zero.
        idx = jnp.pad(
            jnp.concatenate([x0, x0 + 1], axis=1),
            ((0, 0), (0, _LANES - 2 * k)),
            constant_values=-1,
        )  # (W1_BLK, 128) int32

        # Tile-loop-invariant decomposition (hoisted: the loop body below is
        # the VPU-bound part of the kernel): lane-within-tile is idx & 127
        # (always a valid gather index), owning tile is idx >> 7 (negative /
        # past-the-end indices never match any tile, so boundary handling
        # stays free). Each tile then costs one gather + one compare + one
        # select-accumulate instead of the previous ~7 vector passes.
        low = jnp.bitwise_and(idx, _LANES - 1)
        tile_id = jnp.right_shift(idx, _LANES.bit_length() - 1)

        acc = jnp.zeros((w1_blk, _LANES), jnp.float32)
        for tile in range(w2_padded[level] // _LANES):
            # Upcast-then-gather: Mosaic's dynamic gather requires the index
            # bitwidth to match the data's, and int16 indices don't satisfy
            # it either (tried; "different bitwidths" both ways), so bf16
            # tiles pay one upcast pass before the 32-bit gather.
            vol_tile = vol_ref[0, :, tile * _LANES : (tile + 1) * _LANES].astype(
                jnp.float32
            )
            gathered = jnp.take_along_axis(vol_tile, low, axis=-1)
            # Each index belongs to EXACTLY one tile (tile_id = idx >> 7;
            # -1 padding matches none), so select-into-acc replaces the
            # round-3 masked add — one full-vector VPU pass fewer per tile.
            # Measured effect is marginal (3.59-3.85 vs 3.89-3.91 ms/iter in
            # a 32-call chain at Middlebury-F, round 4) but never slower.
            acc = jnp.where(tile_id == tile, gathered, acc)

        tap0 = acc[:, :k]
        tap1 = acc[:, k : 2 * k]
        out_ref[0, :, level * k : (level + 1) * k] = (
            tap0 * (1.0 - frac) + tap1 * frac
        ).astype(out_ref.dtype)


def _scatter_kernel(
    coords_ref, grad_ref, *dvol_refs, radius: int, w2_padded: Tuple[int, ...]
):
    """Backward: scatter-add weighted cotangents into d(volume) — the role
    of the reference's CUDA backward (sampler_kernel.cu:63-105), but
    deterministic and collision-free by construction: query w1 only ever
    writes its own (w1, :) volume row.

    Two structural simplifications over a generic scatter:
    - All 2r+1 taps of one query share the same fractional part (tap
      positions differ by exact integers), so the 2*(2r+1) lerp
      contributions collapse onto 2r+2 CONTIGUOUS positions x0+m with
      combined weights cw[m] = g[m]*(1-f) + g[m-1]*f.
    - TPUs have no vector scatter; each 128-lane tile is built as a one-hot
      accumulation over those 2r+2 window offsets (compare-select-add on
      the VPU). Out-of-range positions land in lane padding or match no
      tile, so boundary handling is free (mirrors the forward's
      zero-padding semantics).
    """
    k = 2 * radius + 1
    w1_blk = coords_ref.shape[1]
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (w1_blk, _LANES), 1)

    for level, dvol_ref in enumerate(dvol_refs):
        x = coords_ref[0].astype(jnp.float32) / (2.0**level)  # (W1_BLK, 1)
        x0f = jnp.floor(x)
        frac = x - x0f  # shared by every tap of the window
        base = x0f.astype(jnp.int32) - radius  # first tap's floor index

        g = grad_ref[0, :, level * k : (level + 1) * k].astype(jnp.float32)
        # cw[m] = g[m]*(1-f) + g[m-1]*f for m in 0..2r+1 (g[-1]=g[2r+1]=0)
        zero = jnp.zeros((w1_blk, 1), jnp.float32)
        g_lo = jnp.concatenate([g, zero], axis=1)  # g[m]
        g_hi = jnp.concatenate([zero, g], axis=1)  # g[m-1]
        cw = g_lo * (1.0 - frac) + g_hi * frac  # (W1_BLK, K+1)
        # Zero-pad cw to a full lane vector once per level: the per-tile
        # one-hot build then becomes ONE dynamic gather by window position
        # (+ range mask) instead of the round-3 K+1 compare-select-add
        # passes — ~6 vector ops per tile vs ~30. The `& 127` wraps any
        # out-of-window position into [0,128); wrapped aliases that land
        # back in [0,k] are killed by the explicit range mask.
        cw_pad = jnp.pad(cw, ((0, 0), (0, _LANES - (k + 1))))

        for tile in range(w2_padded[level] // _LANES):
            pos = lane_ids - (base - tile * _LANES)  # window offset per lane
            vals = jnp.take_along_axis(
                cw_pad, jnp.bitwise_and(pos, _LANES - 1), axis=-1
            )
            acc = jnp.where((pos >= 0) & (pos <= k), vals, 0.0)
            dvol_ref[0, :, tile * _LANES : (tile + 1) * _LANES] = acc.astype(
                dvol_ref.dtype
            )


@scoped("corr_lookup")
def _scatter_pallas_padded(
    padded_shapes: Sequence[Tuple[int, ...]],
    padded_dtypes: Sequence,
    coords: Array,
    grad: Array,
    radius: int,
):
    """d(padded pyramid) from the lookup cotangent. padded_shapes[i]:
    (rows, w1_pad, w2p_i); grad: (B, H, W1, L*(2r+1)) fp32."""
    k = 2 * radius + 1
    num_levels = len(padded_shapes)
    w1 = coords.shape[-1]
    rows, w1_blk, w1_pad, coords_flat = _query_layout(coords)
    w2_padded = [s[-1] for s in padded_shapes]
    grad_flat = jnp.pad(
        grad.reshape(rows, w1, num_levels * k).astype(jnp.float32),
        ((0, 0), (0, w1_pad - w1), (0, 0)),
    )

    in_specs = [
        pl.BlockSpec((1, w1_blk, 1), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(
            (1, w1_blk, num_levels * k), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM
        ),
    ]
    out_specs = [
        pl.BlockSpec((1, w1_blk, w2p), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM)
        for w2p in w2_padded
    ]

    def call(coords_flat, grad_flat):
        rows = coords_flat.shape[0]
        return pl.pallas_call(
            functools.partial(_scatter_kernel, radius=radius, w2_padded=tuple(w2_padded)),
            grid=(rows, w1_pad // w1_blk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=[
                jax.ShapeDtypeStruct((rows, w1_pad, w2p), dtype)
                for w2p, dtype in zip(w2_padded, padded_dtypes)
            ],
            interpret=pallas_interpret(),
            name="corr_scatter",
        )(coords_flat, grad_flat)

    return over_data_axis(call, (coords_flat, grad_flat))


@scoped("corr_build")
def pad_pyramid(pyramid: Sequence[Array], coords_shape: Tuple[int, int, int]):
    """Flatten + zero-pad each (B, H, W1, W2_i) level to the kernel's
    (rows, w1_pad, w2p_i) layout. Zero lane padding reproduces grid_sample
    zero-padding: taps at or past the true W2 read zeros, exactly a zero
    contribution. Done ONCE at correlation-state build: inside the GRU scan
    XLA does not hoist loop-invariant pads, and at Middlebury-F scale they
    cost more than the lookup kernel itself (~3.5 ms/iteration, measured)."""
    b, h, w1 = coords_shape
    rows = b * h
    _, w1_pad = _w1_blocks(w1)
    padded = []
    for vol in pyramid:
        flat = vol.reshape(rows, w1, vol.shape[-1])
        w2p = _round_up(flat.shape[-1], _LANES)
        padded.append(
            jnp.pad(flat, ((0, 0), (0, w1_pad - w1), (0, w2p - flat.shape[-1])))
        )
    return tuple(padded)


@scoped("corr_lookup")
def _lookup_pallas_padded(padded, coords: Array, radius: int, out_dtype=jnp.float32) -> Array:
    """Raw fused lookup (no vjp) over a pre-padded pyramid (see pad_pyramid).
    coords: (B, H, W1) level-0 x positions → (B, H, W1, L*(2r+1)) in
    `out_dtype`. Interpolation arithmetic is always fp32; out_dtype=bfloat16
    only rounds the STORE — the right choice under mixed precision, where
    the consumer casts the taps to bf16 anyway (skipping a full-tensor
    convert per iteration and halving the output write traffic)."""
    k = 2 * radius + 1
    num_levels = len(padded)
    if 2 * k > _LANES:
        raise ValueError(f"radius {radius} too large for the fused kernel")
    b, h, w1 = coords.shape
    rows, w1_blk, w1_pad, coords_flat = _query_layout(coords)
    if any(p.shape[:2] != (rows, w1_pad) for p in padded):
        raise ValueError(
            f"padded pyramid layout {[p.shape[:2] for p in padded]} does not "
            f"match the query layout {(rows, w1_pad)}; build it with pad_pyramid"
        )
    w2_padded = [p.shape[-1] for p in padded]
    if any(w2p % _LANES for w2p in w2_padded):
        # The tile loops truncate at the last full lane tile, so an unpadded
        # W2 would silently drop taps (and leave backward output unwritten).
        raise ValueError(
            f"padded pyramid W2 dims {w2_padded} must be multiples of "
            f"{_LANES}; build the state with pad_pyramid"
        )

    in_specs = [
        pl.BlockSpec((1, w1_blk, 1), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM)
    ]
    for w2p in w2_padded:
        in_specs.append(
            pl.BlockSpec(
                (1, w1_blk, w2p), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM
            )
        )

    def call(coords_flat, *padded):
        rows = coords_flat.shape[0]
        return pl.pallas_call(
            functools.partial(
                _lookup_kernel, radius=radius, w2_padded=tuple(w2_padded)
            ),
            grid=(rows, w1_pad // w1_blk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, w1_blk, num_levels * k),
                lambda r, w: (r, w, 0),
                memory_space=pltpu.VMEM,
            ),
            out_shape=jax.ShapeDtypeStruct((rows, w1_pad, num_levels * k), out_dtype),
            interpret=pallas_interpret(),
            name="corr_lookup",
        )(coords_flat, *padded)

    out = over_data_axis(call, (coords_flat, *padded))
    return out[:, :w1, :].reshape(b, h, w1, num_levels * k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def pallas_corr_lookup_padded(
    padded, coords: Array, radius: int, out_dtype=jnp.float32
) -> Array:
    """Fused pyramid lookup over a pre-padded state, with the CUDA sampler's
    gradient contract: d(volume) via deterministic scatter-add, no gradient
    to `coords` (core/corr.py:24-29 — the model detaches coords each
    iteration anyway, core/raft_stereo.py:109)."""
    return _lookup_pallas_padded(tuple(padded), coords, radius, out_dtype)


def _lookup_padded_fwd(padded, coords, radius, out_dtype):
    # Keep the caller's container (list or tuple): the bwd cotangent must
    # mirror the primal pytree structure exactly.
    return _lookup_pallas_padded(tuple(padded), coords, radius, out_dtype), (
        padded,
        coords,
    )


def _lookup_padded_bwd(radius, out_dtype, residuals, g):
    padded, coords = residuals
    leaves = list(padded)
    d_leaves = _scatter_pallas_padded(
        [p.shape for p in leaves], [p.dtype for p in leaves], coords, g, radius
    )
    d_padded = type(padded)(d_leaves)
    return d_padded, jnp.zeros_like(coords)


pallas_corr_lookup_padded.defvjp(_lookup_padded_fwd, _lookup_padded_bwd)


def pallas_corr_lookup(pyramid, coords: Array, radius: int) -> Array:
    """Unpadded-pyramid convenience wrapper: pads per call, then runs the
    fused lookup. Gradient reaches the pyramid through the pad's slice-vjp —
    same d(volume) scatter contract, still no gradient to coords. Inside an
    iteration loop prefer pad_pyramid + pallas_corr_lookup_padded so the pads
    stay loop-invariant."""
    padded = pad_pyramid(tuple(pyramid), coords.shape)
    return pallas_corr_lookup_padded(padded, coords, radius)


# --- Scalar-prefetch windowed lookup (config.prefetch_lookup) ---------------
#
# Same gather-lerp math as _lookup_kernel, different data movement: instead of
# DMAing every level's FULL padded row into VMEM per program, integer window
# START tiles (derived from the lookup coordinates on the host side of the
# call) arrive as a scalar-prefetch operand (pltpu.PrefetchScalarGridSpec), and
# the BlockSpec index_maps use them to DMA only a fixed per-level window of
# 128-lane tiles around where the taps actually land — data-dependent DMA
# issued ahead of compute. The inner tile loop then runs over `win` tiles
# instead of W2p/128, so both DMA volume and VPU gather passes shrink when the
# window undercuts the row.
#
# Exactness contract: a tap contributes zero unless its owning tile is in the
# window (tile match is by ABSOLUTE tile id, start + j), and out-of-range taps
# are zero by the pad_pyramid contract — so the windowed kernel is bit-exact
# iff every tap in [0, W2p) lands inside its block's window. That predicate is
# computed by _pf_plan alongside the starts; prefetch_corr_lookup_padded
# checks it and falls back to the dense kernel via lax.cond for coordinate
# fields too rough to window (guaranteeing exactness on ANY input). Smooth
# disparity fields — the actual model regime, where coords track the pixel
# grid minus a locally-bounded disparity — fit essentially always.
#
# Test-mode only (no VJP; training keeps pallas_corr_lookup_padded). The
# window only undercuts the full row when the W1 block is small relative to
# W2, so this path uses its own <= _PF_W1_BLOCK query blocks: more programs,
# each lighter on VMEM (the dense kernel's (768, sum W2p) resident slice
# shrinks ~6x), the hypothesis being that deeper DMA/compute overlap beats
# the per-program overhead the _W1_BLOCK tuning note documents. Measured as
# the `prefetch_lookup` key of a configuration's `program` group: PERF.md
# section 6, "Levers".

_PF_W1_BLOCK = 256


def _pf_w1_block(w1_pad: int) -> int:
    """Largest 8-aligned divisor of w1_pad that is <= _PF_W1_BLOCK (the
    prefetch grid must tile the SAME w1_pad the state was padded to)."""
    best = 8
    for d in range(8, min(_PF_W1_BLOCK, w1_pad) + 1, 8):
        if w1_pad % d == 0:
            best = d
    return best


def _pf_window_tiles(w1_blk: int, radius: int, level: int, n_tiles: int) -> int:
    """Window capacity in 128-lane tiles for one level: the lane span of a
    monotone query block ((w1_blk-1)/2^level) plus the 2r+2 tap footprint,
    plus one tile for floor-boundary straddle; capped at the full row."""
    span = (w1_blk - 1) / (2.0**level) + 2 * radius + 2
    return min(int(-(-span // _LANES)) + 1, n_tiles)


def _pf_plan(coords_flat: Array, w1: int, w1_blk: int, radius: int,
             w2_padded: Sequence[int], win_tiles: Sequence[int]):
    """Window start tiles + the exactness predicate for the windowed kernel.

    coords_flat: (rows, w1_pad, 1) from _query_layout. Returns
    (starts (L, rows, n_blk) int32, fits scalar bool): fits is True iff every
    tap with a tile in [0, W2p) is covered by its block's window at every
    level — the condition under which the windowed kernel is bit-exact.
    Queries past the true W1 (layout padding, coords zero-filled) are masked
    out so they never drag a far block's window toward tile 0."""
    rows, w1_pad, _ = coords_flat.shape
    n_blk = w1_pad // w1_blk
    x = coords_flat[..., 0].reshape(rows, n_blk, w1_blk)
    qvalid = (
        jax.lax.broadcasted_iota(jnp.int32, (n_blk, w1_blk), 0) * w1_blk
        + jax.lax.broadcasted_iota(jnp.int32, (n_blk, w1_blk), 1)
        < w1
    )[None]
    starts = []
    fits = jnp.bool_(True)
    for level, (w2p, win) in enumerate(zip(w2_padded, win_tiles)):
        n_tiles = w2p // _LANES
        x0 = jnp.floor(x / (2.0**level)).astype(jnp.int32)
        lo_tap = x0 - radius  # first tap; last lerp tap is x0 + radius + 1
        hi_tap = x0 + radius + 1
        valid = qvalid & (hi_tap >= 0) & (lo_tap <= w2p - 1)
        lo_t = jnp.clip(lo_tap, 0, w2p - 1) // _LANES
        hi_t = jnp.clip(hi_tap, 0, w2p - 1) // _LANES
        lo_min = jnp.min(jnp.where(valid, lo_t, n_tiles), axis=-1)
        hi_max = jnp.max(jnp.where(valid, hi_t, -1), axis=-1)
        any_valid = jnp.any(valid, axis=-1)
        lo_min = jnp.where(any_valid, lo_min, 0)
        hi_max = jnp.where(any_valid, hi_max, 0)
        fits = fits & jnp.all(hi_max - lo_min + 1 <= win)
        starts.append(jnp.clip(lo_min, 0, n_tiles - win))
    return jnp.stack(starts).astype(jnp.int32), fits


def _pf_lookup_kernel(starts_ref, coords_ref, *rest, radius: int,
                      win_tiles: Tuple[int, ...]):
    """Windowed variant of _lookup_kernel. starts_ref is the scalar-prefetch
    operand (L, rows, n_blk); rest holds win_tiles[l] single-tile volume refs
    (1, W1_BLK, 128) per level (window tile j of level l was DMA'd from
    absolute tile starts[l, r, w] + j by the BlockSpec index_map), then the
    output ref. Tile matching is by absolute tile id, so taps outside the
    window accumulate zero — exactly the dense kernel's out-of-range
    semantics under the _pf_plan fits predicate."""
    vol_refs, out_ref = rest[:-1], rest[-1]
    k = 2 * radius + 1
    w1_blk = coords_ref.shape[1]
    r = pl.program_id(0)
    w = pl.program_id(1)

    x = coords_ref[0].astype(jnp.float32)
    offsets = (
        jax.lax.broadcasted_iota(jnp.int32, (w1_blk, k), 1).astype(jnp.float32)
        - radius
    )

    off = 0
    for level, win in enumerate(win_tiles):
        start = starts_ref[level, r, w]
        t = x / (2.0**level) + offsets
        x0f = jnp.floor(t)
        frac = t - x0f
        x0 = x0f.astype(jnp.int32)
        idx = jnp.pad(
            jnp.concatenate([x0, x0 + 1], axis=1),
            ((0, 0), (0, _LANES - 2 * k)),
            constant_values=-1,
        )
        low = jnp.bitwise_and(idx, _LANES - 1)
        tile_id = jnp.right_shift(idx, _LANES.bit_length() - 1)

        acc = jnp.zeros((w1_blk, _LANES), jnp.float32)
        for j in range(win):
            vol_tile = vol_refs[off + j][0].astype(jnp.float32)
            gathered = jnp.take_along_axis(vol_tile, low, axis=-1)
            acc = jnp.where(tile_id == start + j, gathered, acc)
        off += win

        tap0 = acc[:, :k]
        tap1 = acc[:, k : 2 * k]
        out_ref[0, :, level * k : (level + 1) * k] = (
            tap0 * (1.0 - frac) + tap1 * frac
        ).astype(out_ref.dtype)


def _lookup_pallas_prefetch_windowed(
    padded, coords: Array, radius: int, out_dtype, starts: Array, w1_blk: int,
    win_tiles: Tuple[int, ...],
) -> Array:
    """Raw windowed call (no fits fallback — callers must hold the _pf_plan
    predicate, see prefetch_corr_lookup_padded)."""
    k = 2 * radius + 1
    num_levels = len(padded)
    b, h, w1 = coords.shape
    rows, _, w1_pad, coords_flat = _query_layout(coords)

    in_specs = [
        pl.BlockSpec(
            (1, w1_blk, 1), lambda r, w, s: (r, w, 0), memory_space=pltpu.VMEM
        )
    ]
    vols = []
    for level, (vol, win) in enumerate(zip(padded, win_tiles)):
        for j in range(win):
            in_specs.append(
                pl.BlockSpec(
                    (1, w1_blk, _LANES),
                    # Data-dependent DMA: window tile j of this level starts
                    # at the scalar-prefetched tile index (block units ==
                    # lane tiles because the block is exactly one tile wide).
                    lambda r, w, s, level=level, j=j: (r, w, s[level, r, w] + j),
                    memory_space=pltpu.VMEM,
                )
            )
            vols.append(vol)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, w1_pad // w1_blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, w1_blk, num_levels * k),
            lambda r, w, s: (r, w, 0),
            memory_space=pltpu.VMEM,
        ),
    )
    out = pl.pallas_call(
        functools.partial(_pf_lookup_kernel, radius=radius, win_tiles=win_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, w1_pad, num_levels * k), out_dtype),
        interpret=pallas_interpret(),
        name="corr_lookup_prefetch",
    )(starts, coords_flat, *vols)
    return out[:, :w1, :].reshape(b, h, w1, num_levels * k)


@scoped("corr_lookup")
def prefetch_corr_lookup_padded(
    padded, coords: Array, radius: int, out_dtype=jnp.float32
) -> Array:
    """Scalar-prefetch windowed lookup with the dense kernel as an exactness
    fallback: computes the window plan from `coords`, runs the windowed
    kernel when every tap fits its window, and lax.cond-falls back to
    _lookup_pallas_padded otherwise — bit-identical output to the dense
    kernel on EVERY input, windowed DMA on the smooth inputs the model
    produces. No VJP (test-mode only; training uses
    pallas_corr_lookup_padded)."""
    padded = tuple(padded)
    k = 2 * radius + 1
    if 2 * k > _LANES:
        raise ValueError(f"radius {radius} too large for the fused kernel")
    rows, _, w1_pad, coords_flat = _query_layout(coords)
    if any(p.shape[:2] != (rows, w1_pad) for p in padded):
        raise ValueError(
            f"padded pyramid layout {[p.shape[:2] for p in padded]} does not "
            f"match the query layout {(rows, w1_pad)}; build it with pad_pyramid"
        )
    w2_padded = [p.shape[-1] for p in padded]
    if any(w2p % _LANES for w2p in w2_padded):
        raise ValueError(
            f"padded pyramid W2 dims {w2_padded} must be multiples of "
            f"{_LANES}; build the state with pad_pyramid"
        )
    w1 = coords.shape[-1]
    w1_blk = _pf_w1_block(w1_pad)
    win_tiles = tuple(
        _pf_window_tiles(w1_blk, radius, level, w2p // _LANES)
        for level, w2p in enumerate(w2_padded)
    )
    starts, fits = _pf_plan(coords_flat, w1, w1_blk, radius, w2_padded, win_tiles)
    return jax.lax.cond(
        fits,
        lambda: _lookup_pallas_prefetch_windowed(
            padded, coords, radius, out_dtype, starts, w1_blk, win_tiles
        ),
        lambda: _lookup_pallas_padded(padded, coords, radius, out_dtype),
    )


def pallas_corr_state(
    fmap1: Array, fmap2: Array, num_levels: int, corr_dtype=jnp.float32
):
    """Loop-invariant state: the pooled pyramid of the MXU-built volume,
    pre-padded to the lookup kernel's layout (pad once here, not per
    iteration — see pad_pyramid)."""
    vol = corr_volume(fmap1, fmap2, out_dtype=corr_dtype)
    pyramid = corr_pyramid(vol, num_levels)
    b, h, w1 = vol.shape[:3]
    return pad_pyramid(pyramid, (b, h, w1))


def _pyramid_kernel(f1_ref, f2_ref, *out_refs, widths: Tuple[int, ...], dim: int):
    """One (row, W1-block): fused volume matmul + pooled-pyramid build,
    written directly in the lookup kernel's padded layout.

    f1_ref: (1, w1_blk, D); f2_ref: (1, w2p0, D) zero-padded past the true
    W2 (so the volume's padded lanes are exactly the zeros pad_pyramid
    writes). Each level is pooled from the previous level's STORED values
    (post corr_dtype rounding) with a 0.5-entry pair matrix on the MXU —
    bit-matching the `_avg_pool_last` chain: 0.5 is exact in every float
    dtype, accumulation is fp32, floor semantics come from the row mask.
    """
    a = f1_ref[0]
    vol = jax.lax.dot_general(
        a, f2_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    vol = (vol / jnp.sqrt(jnp.asarray(dim, jnp.float32))).astype(out_refs[0].dtype)
    out_refs[0][0] = vol
    lvl = vol
    for i in range(1, len(out_refs)):
        wprev = widths[i - 1]
        wp_prev, wp = lvl.shape[-1], out_refs[i].shape[-1]
        r = jax.lax.broadcasted_iota(jnp.int32, (wp_prev, wp), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (wp_prev, wp), 1)
        # Row r feeds output pair r >> 1; floor semantics trim the last odd
        # sample (r < 2*(wprev//2)), and padded input rows never reach a
        # TRUE output column, so padded columns stay exactly zero (the
        # lookup kernel's zero-tap contract).
        mask = ((r >> 1) == c) & (r < 2 * (wprev // 2))
        # Select in float32, then narrow: the mask comes from int32 iotas, and
        # Mosaic has no relayout of an i1 vector from the 32-bit tiling to
        # the packed 16-bit one a bf16 select needs ("Invalid relayout ...
        # (8,128) -> (16,128)" at Middlebury-F width). 0.5 and 0 are exact in
        # every float dtype, so the pool matrix is bit-identical.
        pool = jnp.where(mask, 0.5, 0.0).astype(lvl.dtype)
        nxt = jax.lax.dot_general(
            lvl, pool, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(out_refs[i].dtype)
        out_refs[i][0] = nxt
        lvl = nxt


@scoped("corr_build")
def fused_pyramid_state(
    fmap1: Array, fmap2: Array, num_levels: int, corr_dtype=jnp.float32
):
    """Fused replacement for `pallas_corr_state`: the volume matmul, the
    avg-pool pyramid and the pad-to-lookup-layout copies in ONE kernel —
    the volume and intermediate levels never round-trip HBM unpadded, and
    the separate pad pass disappears. Output pytree (shapes, dtypes,
    values) matches `pallas_corr_state` so `pallas_corr_lookup_padded`
    consumes it unchanged — no layout boundary faces the iteration loop.

    Part of the `fused_encoder` strategy (ops/encoder_pallas.py docstring
    carries the A/B verdict discipline)."""
    b, h, w1, dim = fmap1.shape
    w2 = fmap2.shape[2]
    rows = b * h
    w1_blk, w1_pad = _w1_blocks(w1)
    # Mirror corr_volume's precision contract: bf16 storage reads bf16
    # operands (fp32 accumulation); fp32 storage keeps fp32 operands.
    op_dtype = (
        jnp.bfloat16 if jnp.dtype(corr_dtype) == jnp.bfloat16 else jnp.float32
    )
    f1 = jnp.pad(
        fmap1.astype(op_dtype).reshape(rows, w1, dim),
        ((0, 0), (0, w1_pad - w1), (0, 0)),
    )
    w2p0 = _round_up(w2, _LANES)
    f2 = jnp.pad(
        fmap2.astype(op_dtype).reshape(rows, w2, dim),
        ((0, 0), (0, w2p0 - w2), (0, 0)),
    )

    widths = [w2]
    for _ in range(num_levels - 1):
        widths.append(widths[-1] // 2)
    padded_w = [_round_up(w, _LANES) for w in widths]

    out_shapes = [
        jax.ShapeDtypeStruct((rows, w1_pad, wp), jnp.dtype(corr_dtype))
        for wp in padded_w
    ]
    out_specs = [
        pl.BlockSpec((1, w1_blk, wp), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM)
        for wp in padded_w
    ]
    out = pl.pallas_call(
        functools.partial(_pyramid_kernel, widths=tuple(widths), dim=dim),
        grid=(rows, w1_pad // w1_blk),
        in_specs=[
            pl.BlockSpec(
                (1, w1_blk, dim), lambda r, w: (r, w, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, w2p0, dim), lambda r, w: (r, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=pallas_interpret(),
        name="corr_pyramid",
    )(f1, f2)
    return tuple(out)


def make_pallas_corr_fn(
    fmap1: Array,
    fmap2: Array,
    num_levels: int,
    radius: int,
    corr_dtype=jnp.float32,
    prefetch: bool = False,
):
    """`coords -> taps` closure, the "pallas" strategy for ops.corr.make_corr_fn.
    `prefetch` swaps in the scalar-prefetch windowed lookup (no VJP —
    inference closures only, see prefetch_corr_lookup_padded)."""
    state = pallas_corr_state(fmap1, fmap2, num_levels, corr_dtype=corr_dtype)
    if prefetch:
        return lambda coords: prefetch_corr_lookup_padded(state, coords, radius)
    return lambda coords: pallas_corr_lookup_padded(state, coords, radius)
