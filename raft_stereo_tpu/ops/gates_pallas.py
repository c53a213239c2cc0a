"""EXPERIMENT-ONLY Pallas fusion of the ConvGRU gating elementwise.

Round-4 verdict item 3b / ROADMAP round-5 candidate #3: the ~2.5 ms/iter of
gate chains (sigmoid/tanh/lerp between the GRU convs) is the one inference
lever never measured. This module fuses them into two single-pass VPU
kernels per cell:

  rh   = sigmoid(rx + cr) * h                      (feeds the q conv)
  h'   = (1-z) * h + z * tanh(qx + cq),  z = sigmoid(zx + cz)

replacing the XLA elementwise fusions that otherwise ride the conv
epilogues. The hypothesis to refute: XLA's fusion boundaries around the
split-W conv strategy leave enough stray buffer traffic that one fused pass
wins; the counter-hypothesis (ROADMAP) is that a Pallas call forces its own
operand layouts and re-pays the boundary copies that killed s2d-inference.

Activation: env var RAFT_STEREO_TPU_PALLAS_GATES=1 (read per trace), NOT a
config flag — round-4 review weak #5 flagged retired experiments living as
product config surface; this toggle exists for scripts/exp_gate_fusion.py
and dies with it if the measurement is negative. Inference-only (no custom
VJP; training keeps the XLA formulation) and TPU-only (interpret mode is
pathologically slow at full res) — the caller gates on both.

Verdict (measured 2026-08-01, v5e-1, Middlebury-F 32 iters, full context,
scripts/exp_gate_fusion.py): **RETIRED — catastrophically negative.**
Per-iteration 21.59 -> 51.14 ms (+29.6 ms/iter, 2.4x): the three Pallas
calls per cell force their operands out of XLA's split-W conv fusions, so
every gate tensor (~91 MB at scale 0) is materialized and re-read across a
kernel boundary — the same layout-boundary tax that killed s2d-inference,
at larger scale because it recurs 3x per cell per iteration. The kernels
themselves are bit-exact on TPU at all three GRU scales (standalone check,
same date); end-to-end flows diverge on random-noise inputs only through
bf16-order chaotic amplification. Kernels + env hook stay ONLY so the A/B
re-runs after a toolchain upgrade; nothing in the product path uses them.
"""

from __future__ import annotations

import os

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array

_BLOCK_ROWS = 1024


def enabled() -> bool:
    return os.environ.get("RAFT_STEREO_TPU_PALLAS_GATES") == "1"


def _rh_kernel(rx_ref, cr_ref, h_ref, out_ref):
    r = jax.nn.sigmoid(rx_ref[...].astype(jnp.float32) + cr_ref[...].astype(jnp.float32))
    out_ref[...] = (r * h_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _combine_kernel(zx_ref, cz_ref, qx_ref, cq_ref, h_ref, out_ref):
    z = jax.nn.sigmoid(zx_ref[...].astype(jnp.float32) + cz_ref[...].astype(jnp.float32))
    q = jnp.tanh(qx_ref[...].astype(jnp.float32) + cq_ref[...].astype(jnp.float32))
    h = h_ref[...].astype(jnp.float32)
    out_ref[...] = ((1.0 - z) * h + z * q).astype(out_ref.dtype)


def _run_elementwise(kernel, args, name):
    """Flatten (B,H,W,C) operands to (N, C) rows and grid over row blocks —
    elementwise math, so any aligned 2D tiling is fine; C stays on lanes."""
    shape = args[0].shape
    c = shape[-1]
    n = 1
    for d in shape[:-1]:
        n *= d
    flat = [a.reshape(n, c) for a in args]
    grid = (pl.cdiv(n, _BLOCK_ROWS),)
    spec = pl.BlockSpec((_BLOCK_ROWS, c), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(flat),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, c), args[0].dtype),
        interpret=pallas_interpret(),
        name=name,
    )(*flat)
    return out.reshape(shape)


def fused_rh(rx: Array, cr: Array, h: Array) -> Array:
    """sigmoid(rx + cr) * h in one VPU pass."""
    return _run_elementwise(_rh_kernel, (rx, cr, h), "gates_rh")


def fused_combine(zx: Array, cz: Array, qx: Array, cq: Array, h: Array) -> Array:
    """(1 - z) * h + z * tanh(qx + cq) with z = sigmoid(zx + cz), one pass."""
    return _run_elementwise(_combine_kernel, (zx, cz, qx, cq, h), "gates_combine")
