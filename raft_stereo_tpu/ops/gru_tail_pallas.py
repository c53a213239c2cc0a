"""Fused ConvGRU tail + motion-encoder concat (config.fused_gru_tail).

Two single-pass VPU kernels for the per-iteration elementwise work around the
GRU convs (models/update.py):

  tail:   h' = (1-z) * h + z * tanh(qx + cq),  z = sigmoid(zx + cz)
  motion: cat[relu(conv_out 126ch), flow (1ch), zeros (1ch)] -> 128ch

A Pallas call forces its operands out of XLA's conv fusions (a three-call
gate fusion that put an r*h kernel and a combine kernel in every cell paid
that for every ~91 MB gate tensor and was retired; PERF.md section 6,
"Levers"). Here each cell makes ONE call, placed where a materialization
already exists — h' is the scan carry, so the tail's output buffer is a
boundary XLA pays either way — and the r-gate stays in the conv epilogue
fusion. The motion kernel replaces a relu + 128ch concat + zeros
materialization with one write of the already-boundary motion tensor feeding
the finest GRU. Hypothesis: one boundary a cell is cheap enough to win;
counter-hypothesis: any forced operand layout still loses to XLA's epilogue
fusion. Measured as the `fused_gru_tail` key of a configuration's `program`
group: PERF.md section 6, "Levers".

Activation: `RAFTStereoConfig.fused_gru_tail` (CLI `--fused_gru_tail`).
TEST-MODE forwards only (the kernels define no VJP; the
exact-gradient-equality test in tests/test_fast_path.py proves the training
graph untouched). On the CPU backend the kernels run in the Pallas
interpreter, so the tier-1 parity tests (`-m kernels`) cover identical
kernel bodies.

Math is fp32 in-register regardless of operand dtype; stores round once to
the operand dtype — under mixed precision that matches the XLA path, which
computes the same chain in bf16 only AFTER the conv outputs were already
rounded to bf16 (parity is exact in fp32, and agreement under bf16 is tested
at the kernel level where the operand rounding points coincide).
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array

_BLOCK_ROWS = 1024


def _tail_kernel(zx_ref, cz_ref, qx_ref, cq_ref, h_ref, out_ref):
    z = jax.nn.sigmoid(zx_ref[...].astype(jnp.float32) + cz_ref[...].astype(jnp.float32))
    q = jnp.tanh(qx_ref[...].astype(jnp.float32) + cq_ref[...].astype(jnp.float32))
    h = h_ref[...].astype(jnp.float32)
    out_ref[...] = ((1.0 - z) * h + z * q).astype(out_ref.dtype)


def _motion_tail_kernel(pre_ref, flow_ref, out_ref):
    pre = jnp.maximum(pre_ref[...].astype(jnp.float32), 0.0)
    flo = flow_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.concatenate(
        [pre, flo, jnp.zeros_like(flo)], axis=-1
    ).astype(out_ref.dtype)


def _row_flat(a: Array, c: int) -> Array:
    n = 1
    for d in a.shape[:-1]:
        n *= d
    return a.reshape(n, c)


def fused_gru_tail(zx: Array, cz: Array, qx: Array, cq: Array, h: Array) -> Array:
    """h' = (1-z)h + z*tanh(qx+cq), z = sigmoid(zx+cz), one VPU pass.

    The single per-cell Pallas call of the fused_gru_tail strategy; output
    dtype follows h (the scan carry it becomes)."""
    shape = h.shape
    c = shape[-1]
    flat = [_row_flat(a, c) for a in (zx, cz, qx, cq, h)]
    n = flat[0].shape[0]
    spec = pl.BlockSpec((_BLOCK_ROWS, c), lambda i: (i, 0))
    out = pl.pallas_call(
        _tail_kernel,
        grid=(pl.cdiv(n, _BLOCK_ROWS),),
        in_specs=[spec] * len(flat),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, c), h.dtype),
        interpret=pallas_interpret(),
        name="gru_tail",
    )(*flat)
    return out.reshape(shape)


def fused_motion_tail(pre: Array, flow: Array) -> Array:
    """cat[relu(pre), flow, zeros_like(flow)] on the channel axis, one pass.

    pre: (..., 126) pre-activation of the motion encoder's output conv;
    flow: (..., 1) disparity — together the 128ch motion features
    (models/update.py BasicMotionEncoder). The 1-lane flow block and the
    in-kernel lane concat are interpret-clean; their Mosaic cost is part of
    the pending TPU verdict."""
    shape = pre.shape
    c_pre = pre.shape[-1]
    c = c_pre + 2 * flow.shape[-1]
    pre_f = _row_flat(pre, c_pre)
    flow_f = _row_flat(flow, flow.shape[-1])
    n = pre_f.shape[0]
    out = pl.pallas_call(
        _motion_tail_kernel,
        grid=(pl.cdiv(n, _BLOCK_ROWS),),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, c_pre), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, flow_f.shape[-1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), pre.dtype),
        interpret=pallas_interpret(),
        name="motion_tail",
    )(pre_f, flow_f)
    return out.reshape(*shape[:-1], c)
