"""Sequence loss and metrics.

Re-design of the reference `sequence_loss` (/root/reference/train_stereo.py:35-70)
for 1-channel disparity flows and fully-jittable masked reductions (the
reference's boolean indexing `i_loss[valid].mean()` becomes a
sum-and-normalize, identical numerically and shape-static for XLA).

The reference's inline NaN/Inf asserts (train_stereo.py:47-57) have no jit
equivalent here; the trainer surfaces non-finite losses through its metrics
(`live_loss`, `grad_norm`) instead. The per-iteration weighting keeps the
reference's gamma adjustment `gamma ** (15 / (n - 1))` so the effective decay
is invariant to the iteration count.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from raft_stereo_tpu.obs.scopes import scoped

Array = jax.Array


@scoped("sequence_loss")
def sequence_loss(
    flow_preds: Array,
    flow_gt: Array,
    valid: Array,
    loss_gamma: float = 0.9,
    max_flow: float = 700.0,
) -> Tuple[Array, Dict[str, Array]]:
    """Exponentially weighted L1 over per-iteration predictions.

    flow_preds: (iters, B, H, W, 1) upsampled disparity-flow per iteration,
                OR the model's blocked train-mode output
                (iters, B, H/f, f, W/f, f) — see RAFTStereo docstring. The
                blocked form is the fast path: the ground truth is reshaped
                into the prediction's layout (free) instead of the
                22-prediction stack being transposed into the ground
                truth's (~19 ms/step of layout copies, round-5 trace).
    flow_gt:    (B, H, W, 1) ground-truth flow (x component; reference stores
                flow as (-disp, 0), core/stereo_datasets.py:218).
    valid:      (B, H, W) validity mask (>= 0.5 is valid).

    Returns (loss, metrics) with the reference's epe/1px/3px/5px metrics
    computed over the final prediction.
    """
    n_predictions = flow_preds.shape[0]
    gt = flow_gt[..., 0]  # (B, H, W); y component is structurally 0
    if flow_preds.ndim == 6:
        # Blocked layout: reshape gt/valid to (B, H/f, f, W/f, f) — pure
        # row-major reshapes — and drop the channel axis from the math.
        _, b_, hb, f1, wb, f2 = flow_preds.shape
        gt = gt.reshape(b_, hb, f1, wb, f2)
        valid = valid.reshape(b_, hb, f1, wb, f2)
        flow_preds = flow_preds[..., None]  # unify: trailing 1-ch axis
        gt = gt[..., None]
    else:
        gt = gt[..., None]
    mag = jnp.abs(gt[..., 0])
    mask = (valid >= 0.5) & (mag < max_flow)
    mask_f = mask.astype(jnp.float32)
    denom = jnp.maximum(mask_f.sum(), 1.0)

    if n_predictions > 1:
        adjusted_gamma = loss_gamma ** (15.0 / (n_predictions - 1))
    else:
        adjusted_gamma = loss_gamma
    # weight for prediction i: gamma^(n-1-i)
    weights = adjusted_gamma ** jnp.arange(n_predictions - 1, -1, -1, dtype=jnp.float32)

    abs_err = jnp.abs(flow_preds - gt[None])[..., 0]  # (iters, B, *spatial)
    # The reference loss runs on 1-CHANNEL flows: the dataset slices the gt
    # (`flow = flow[:1]`, stereo_datasets.py:247) and the model slices its
    # prediction (`flow_up[:,:1]`, core/raft_stereo.py:134) before
    # sequence_loss, so each per-iteration term is the plain mean of |err_x|
    # over valid pixels (train_stereo.py:46-58). (Round-2 note: an earlier
    # build carried a 0.5 "two-channel averaging" factor justified against a
    # hand-built 2-channel oracle; the round-3 gradient-parity test against
    # the reference's ACTUAL sequence_loss showed the reference never
    # averages over a zero y channel — the factor was a 2x loss-scale error
    # and is gone. AdamW updates are nearly scale-invariant, so trained
    # results are unaffected beyond weight-decay/eps coupling.)
    per_iter = (abs_err * mask_f[None]).sum(axis=tuple(range(1, abs_err.ndim))) / denom
    flow_loss = (weights * per_iter).sum()

    epe = jnp.abs(flow_preds[-1] - gt)[..., 0]  # 1D endpoint error
    metrics = {
        "epe": (epe * mask_f).sum() / denom,
        "1px": ((epe < 1) & mask).sum() / denom,
        "3px": ((epe < 3) & mask).sum() / denom,
        "5px": ((epe < 5) & mask).sum() / denom,
    }
    return flow_loss, metrics
