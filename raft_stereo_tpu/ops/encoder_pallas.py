"""Fused Pallas TPU kernels for the encoder ResidualBlock chain.

Targets the loop-invariant forward prefix (feature + context encoders +
corr-pyramid build: 230 ms of a 939 ms Middlebury-F map, PERF.md section 5,
and most of a low-iteration inference).
The XLA inference graph pays, per full-res residual block, two conv fusions
PLUS separate full-resolution elementwise passes for every
InstanceNorm/FrozenBN apply and residual join — each pass is a ~1.5 GB
HBM round-trip at Middlebury-F scale. This module fuses each block into
implicit-GEMM Pallas kernels where those epilogues never leave VMEM:

- `fused_conv_s2d`: one 3x3 stride-1 conv evaluated in the W-space-to-depth
  domain (the round-4 measured MXU win: the C=64 layer1 convs half-starve
  the 128 contraction lanes; the dual-phase s2d embedding fills both the
  contraction AND output lanes at the cost of 50% structural-zero FLOPs —
  the same trade XLA's s2d path makes, here without its inference-graph
  layout-copy tax because Mosaic consumes the arrays' native tiled layout).
  The previous layer's norm (InstanceNorm stats affine or frozen-BN affine)
  and relu are applied IN-REGISTER to the operand rows as they are read, so
  the separate normalize pass — and its full-res HBM round-trip —
  disappears. Per-channel sum/sumsq of the conv output are accumulated
  across the grid into a (2, 2C) stats output (the next norm's input),
  replacing the full-tensor reduction pass.
- `fused_join_s2d`: the block tail out = relu(x + relu(norm(y2))) as a
  single elementwise pass (one read of each operand, one write), with the
  skip's own pending norm applied in-register when the skip is the raw stem
  output.
- `fused_layer1_s2d`: the whole stem-norm -> layer1_0 -> layer1_1 chain
  (2 convs + 1 join per block; 6 kernel launches per image) on top of the
  two kernels. Math is `ResidualBlockS2D`'s exactly; parameter trees are
  untouched (the flax glue in models/extractor.py declares the identical
  `ConvParams`/`FrozenBatchNorm` trees and passes raw arrays here).

Memory discipline (fuse at BLOCK granularity so no layout boundary lands
inside a hot loop; PERF.md section 6, "Levers", gate fusion): conv operands are read through a
manual HBM->VMEM DMA ring (4 row slots, one-row lookahead), so every input
row is fetched exactly ONCE per conv despite the 3-row stencil — a
BlockSpec halo would re-fetch each row three times and erase the win. All
arrays stay in their native (B, H, W2, 2C) tiling: entering the s2d domain
is a pure reshape, leaving it rides the existing stride-2 layer2 entry
kernels (`ResidualBlockFromS2D`), exactly like the training-mode s2d path.

Activation: `RAFTStereoConfig.fused_encoder` (test-mode forwards only — the
kernels define no VJP; the training path is untouched). On the CPU backend
the kernels run in the Pallas interpreter (ops/pallas_mode.py), which the
tier-1 `-m kernels` parity tests rely on; full-resolution interpret
execution is pathologically slow.

Measured as the `fused_encoder` key of a configuration's `program` group
(PERF.md section 6, "Levers"; ROADMAP D1 holds what follows from it). From
the chip's compiler (PR 22, tests/test_chip_compile.py): `fused_conv_s2d` and
`fused_pyramid_state` compile for v5e at Middlebury-F width, the latter with
bf16 storage only since its pooling mask is selected in float32 (the bf16
select was refused: "Invalid relayout ... (8,128) -> (16,128)").
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array

# DMA ring depth for the 3-row conv stencil: rows h-1, h, h+1 in use while
# row h+2 streams in — four distinct slots, proven in the interpret-mode
# ring tests (a 3-slot ring overwrites row h-1 mid-step).
_NSLOTS = 4

# Affine input-stage forms (static kernel parameters, not traced):
#   "none": operand used as-is (already normalized + activated).
#   "in":   relu((x - mean) * inv)  — InstanceNorm apply, stats-derived.
#   "bn":   relu(x * inv + shift)   — FrozenBatchNorm's folded affine.
# Both mirror the XLA formulations bit-for-bit in compute dtype
# (layers.s2d_instance_norm / layers.FrozenBatchNorm).
_AFFINE_FORMS = ("none", "in", "bn")


def _apply_affine(x: Array, aff: Optional[Array], form: str) -> Array:
    """Input-stage affine+relu in x.dtype (aff rows are f32, cast at use —
    the same cast placement as the XLA norm layers). Keepdims (1, 2C)
    slices: 1-D lane vectors are a known Mosaic lowering hazard."""
    if form == "none":
        return x
    a = aff[0:1].astype(x.dtype)
    b = aff[1:2].astype(x.dtype)
    if form == "in":
        y = (x - a) * b
    else:  # "bn"
        y = x * a + b
    return jnp.maximum(y, jnp.zeros((), x.dtype))


def _shift_w(z: Array, delta: int) -> Array:
    """Sublane shift along the s2d block-column axis with zero fill —
    the 'same' padding of the embedded kw=3 window."""
    if delta == 0:
        return z
    zero = jnp.zeros((1, z.shape[1]), z.dtype)
    if delta < 0:
        return jnp.concatenate([zero, z[:-1]], axis=0)
    return jnp.concatenate([z[1:], zero], axis=0)


def _conv_s2d_kernel(
    w_ref,
    bias_ref,
    aff_ref,
    x_hbm,
    y_ref,
    stats_ref,
    xrows,
    sems,
    *,
    nrows: int,
    affine_form: str,
    emit_stats: bool,
):
    """One output row of the dual-phase s2d 3x3 conv.

    Grid (B, H). The operand lives in ANY/HBM; a 4-slot VMEM ring holds the
    3-row stencil with a one-row DMA lookahead, so each input row is
    fetched exactly once per conv. The 9 tap matmuls contract the full
    2C-lane dimension (dense_w-embedded weights); accumulation is fp32 on
    the MXU, stats (when emitted) are fp32 over the STORED output values —
    both matching the XLA path's precision contract.
    """
    b = pl.program_id(0)
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _prologue():
        # Rows 0 and 1 synchronously; row 2 is started by the h=0 lookahead
        # below (exactly one start per sems[2] signal, waited at h=1).
        cp = pltpu.make_async_copy(x_hbm.at[b, 0], xrows.at[0], sems.at[0])
        cp.start()
        cp.wait()
        if nrows > 1:
            cp = pltpu.make_async_copy(x_hbm.at[b, 1], xrows.at[1], sems.at[1])
            cp.start()
            cp.wait()

    @pl.when((h > 0) & (h + 1 < nrows))
    def _wait_lookahead():
        # Row h+1's copy was started one step ago; settle it before use.
        slot = jax.lax.rem(h + 1, _NSLOTS)
        pltpu.make_async_copy(
            x_hbm.at[b, jnp.minimum(h + 1, nrows - 1)], xrows.at[slot], sems.at[slot]
        ).wait()

    @pl.when(h + 2 < nrows)
    def _start_lookahead():
        slot = jax.lax.rem(h + 2, _NSLOTS)
        pltpu.make_async_copy(x_hbm.at[b, h + 2], xrows.at[slot], sems.at[slot]).start()

    w2, c2 = xrows.shape[1], xrows.shape[2]
    aff = aff_ref[0] if affine_form != "none" else None
    acc = jnp.zeros((w2, c2), jnp.float32)
    for dh in range(3):
        idx = jnp.clip(h + dh - 1, 0, nrows - 1)
        row = xrows[jax.lax.rem(idx, _NSLOTS)]
        z = _apply_affine(row, aff, affine_form)
        # 'same' zero padding pads the NORMALIZED operand: mask AFTER the
        # affine (relu((0 - mean) * inv) is not zero).
        valid = (h + dh - 1 >= 0) & (h + dh - 1 < nrows)
        z = jnp.where(valid, z, jnp.zeros((), z.dtype))
        for dw in range(3):
            acc = acc + jax.lax.dot_general(
                _shift_w(z, dw - 1),
                w_ref[dh, dw],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    y = acc.astype(y_ref.dtype) + bias_ref[0:1].astype(y_ref.dtype)
    y_ref[0, 0] = y

    if emit_stats:
        # Stats of the STORED values (post-rounding), like the XLA path's
        # reductions over the materialized conv output. Keepdims shapes
        # throughout (Mosaic 1-D hazard, as above).
        y32 = y.astype(jnp.float32)

        @pl.when(h == 0)
        def _init():
            stats_ref[0] = jnp.zeros((2, c2), jnp.float32)

        stats_ref[0, 0:1, :] = stats_ref[0, 0:1, :] + jnp.sum(
            y32, axis=0, keepdims=True
        )
        stats_ref[0, 1:2, :] = stats_ref[0, 1:2, :] + jnp.sum(
            jnp.square(y32), axis=0, keepdims=True
        )


def fused_conv_s2d(
    x: Array,
    w_dense: Array,
    bias_tiled: Array,
    aff: Optional[Array],
    affine_form: str = "none",
    emit_stats: bool = False,
) -> Tuple[Array, Optional[Array]]:
    """Dual-phase s2d 3x3 'same' conv with fused input affine+relu and
    per-channel output stats.

    x: (B, H, W2, 2C) s2d-domain operand (any float dtype; compute follows).
    w_dense: (3, 3, 2C, 2C) dense_w_kernel-embedded weights (compute dtype).
    bias_tiled: (2C,) phase-tiled conv bias.
    aff: (B, 2, 2C) fp32 affine rows for the input stage (see _AFFINE_FORMS),
      or None with affine_form="none".
    Returns (y, stats): y (B, H, W2, 2C) in x.dtype; stats (B, 2, 2C) fp32
    [sum, sumsq] over (H, W2) per s2d channel, or None.
    """
    if affine_form not in _AFFINE_FORMS:
        raise ValueError(f"affine_form {affine_form!r} not in {_AFFINE_FORMS}")
    if (aff is None) != (affine_form == "none"):
        raise ValueError("aff must be provided iff affine_form != 'none'")
    b, hh, w2, c2 = x.shape
    if w_dense.shape != (3, 3, c2, c2):
        raise ValueError(f"w_dense shape {w_dense.shape} != (3, 3, {c2}, {c2})")
    if aff is None:
        # Constant placeholder so the kernel signature is static; never read.
        aff = jnp.zeros((b, 2, c2), jnp.float32)

    kernel = functools.partial(
        _conv_s2d_kernel,
        nrows=hh,
        affine_form=affine_form,
        emit_stats=emit_stats,
    )
    out_shapes = [jax.ShapeDtypeStruct((b, hh, w2, c2), x.dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, w2, c2), lambda bb, h: (bb, h, 0, 0), memory_space=pltpu.VMEM)
    ]
    # Stats accumulate in one revisited block per batch row (the grid is
    # sequential, so read-modify-write across h is safe).
    out_shapes.append(jax.ShapeDtypeStruct((b, 2, c2), jnp.float32))
    out_specs.append(
        pl.BlockSpec((1, 2, c2), lambda bb, h: (bb, 0, 0), memory_space=pltpu.VMEM)
    )

    y, stats = pl.pallas_call(
        kernel,
        grid=(b, hh),
        in_specs=[
            pl.BlockSpec(
                (3, 3, c2, c2), lambda bb, h: (0, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((1, c2), lambda bb, h: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, c2), lambda bb, h: (bb, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((_NSLOTS, w2, c2), x.dtype),
            pltpu.SemaphoreType.DMA((_NSLOTS,)),
        ],
        # Both grid dims are stateful (the DMA ring scratch persists across
        # h; the stats block accumulates across h and re-initializes per b)
        # — neither may be parallelized.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=pallas_interpret(),
        name="encoder_conv_s2d",
    )(w_dense, bias_tiled.reshape(1, c2), aff, x)
    return y, (stats if emit_stats else None)


def _join_kernel(
    skip_ref, y_ref, aff_s_ref, aff_y_ref, out_ref, *, skip_form: str, y_form: str
):
    skip = skip_ref[0, 0]
    if skip_form != "none":
        skip = _apply_affine(skip, aff_s_ref[0], skip_form)
    y = _apply_affine(y_ref[0, 0], aff_y_ref[0], y_form)
    out_ref[0, 0] = jnp.maximum(skip + y, jnp.zeros((), out_ref.dtype)).astype(
        out_ref.dtype
    )


def fused_join_s2d(
    skip: Array,
    y: Array,
    aff_y: Array,
    y_form: str,
    aff_skip: Optional[Array] = None,
    skip_form: str = "none",
) -> Array:
    """Block tail out = relu(skip' + relu(norm(y))) in one elementwise pass.
    skip' applies the skip's pending affine+relu in-register (the raw stem
    output case); both affines follow _AFFINE_FORMS."""
    b, hh, w2, c2 = skip.shape
    if y_form not in ("in", "bn") or skip_form not in _AFFINE_FORMS:
        raise ValueError((y_form, skip_form))
    if aff_skip is None:
        if skip_form != "none":
            raise ValueError("aff_skip required for skip_form != 'none'")
        aff_skip = jnp.zeros((b, 2, c2), jnp.float32)
    row = lambda bb, h: (bb, h, 0, 0)  # noqa: E731
    affmap = lambda bb, h: (bb, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_join_kernel, skip_form=skip_form, y_form=y_form),
        grid=(b, hh),
        in_specs=[
            pl.BlockSpec((1, 1, w2, c2), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, w2, c2), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, c2), affmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, c2), affmap, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, w2, c2), row, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(skip.shape, skip.dtype),
        interpret=pallas_interpret(),
        name="encoder_join",
    )(skip, y, aff_skip, aff_y)


def instance_affine_from_stats(
    stats: Array, n: int, phases: int = 2, epsilon: float = 1e-5
) -> Array:
    """(B, 2, 2C) [sum, sumsq] -> (B, 2, 2C) [mean, inv] affine rows,
    pooling phase blocks exactly like layers.s2d_instance_norm: original
    channel c's statistics combine s2d blocks c and c+C; the affine tiles
    back. fp32 throughout (cast to compute dtype happens at apply)."""
    b, _, c2 = stats.shape
    c = c2 // phases
    s = stats[:, 0].reshape(b, phases, c).sum(axis=1)
    sq = stats[:, 1].reshape(b, phases, c).sum(axis=1)
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + epsilon)
    return jnp.stack(
        [jnp.tile(mean, (1, phases)), jnp.tile(inv, (1, phases))], axis=1
    )


def bn_affine(inv: Array, shift: Array, batch: int) -> Array:
    """Frozen-BN folded affine -> (B, 2, 2C) kernel rows (batch-invariant,
    broadcast so the kernels index affines per batch element uniformly)."""
    return jnp.broadcast_to(
        jnp.stack([inv, shift], axis=0).astype(jnp.float32)[None],
        (batch, 2, inv.shape[-1]),
    )


def fused_layer1_s2d(
    stem_y: Array,
    stem_aff: Array,
    blocks: Sequence[
        Tuple[Array, Array, Array, Array, Optional[Array], Optional[Array]]
    ],
    norm_fn: str,
) -> Array:
    """The fused stem-norm -> layer1 chain in the s2d domain.

    stem_y: (B, H, W2, 2C) RAW stem conv output (pre-norm), s2d layout.
    stem_aff: (B, 2, 2C) pending stem affine (instance stats or BN affine).
    blocks: per residual block (w1_dense, bias1_tiled, w2_dense,
      bias2_tiled, aff_bn1, aff_bn2) with the BN affines None under
      instance norm (stats affines are produced by the conv kernels here).
    Returns the joined layer1 output, still in the s2d domain.
    """
    if norm_fn not in ("instance", "batch"):
        raise ValueError(norm_fn)
    form = "in" if norm_fn == "instance" else "bn"
    emit = norm_fn == "instance"
    b, hh, w2, _ = stem_y.shape
    n = hh * w2 * 2  # element count behind each original channel's stats

    cur, cur_aff, cur_form = stem_y, stem_aff, form
    for w1d, b1t, w2d, b2t, aff_bn1, aff_bn2 in blocks:
        y1, s1 = fused_conv_s2d(cur, w1d, b1t, cur_aff, cur_form, emit_stats=emit)
        aff1 = instance_affine_from_stats(s1, n) if emit else aff_bn1
        y2, s2 = fused_conv_s2d(y1, w2d, b2t, aff1, form, emit_stats=emit)
        aff2 = instance_affine_from_stats(s2, n) if emit else aff_bn2
        cur = fused_join_s2d(
            cur, y2, aff2, form, aff_skip=cur_aff, skip_form=cur_form
        )
        cur_aff, cur_form = None, "none"
    return cur
