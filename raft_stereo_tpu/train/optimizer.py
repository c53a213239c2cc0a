"""Optimizer + LR schedule.

Reference recipe (/root/reference/train_stereo.py:73-80): AdamW(lr, wd=1e-5,
eps=1e-8) under a linear OneCycle schedule over `num_steps + 100` with
pct_start=0.01, plus global grad-norm clipping at 1.0 applied in the step
(train_stereo.py:195). torch OneCycle (anneal='linear') ramps max_lr/25 →
max_lr over the first 1% of steps, then decays linearly to
max_lr/(25·1e4); reproduced here with joined optax linear schedules.
"""

from __future__ import annotations

from typing import Tuple

import optax

from raft_stereo_tpu.obs.scopes import scoped


def onecycle_linear(
    peak_lr: float,
    total_steps: int,
    pct_start: float = 0.01,
    div_factor: float = 25.0,
    final_div_factor: float = 1e4,
) -> optax.Schedule:
    # torch reaches peak at step `pct_start*total - 1` and the floor exactly at
    # the last step (OneCycleLR phase arithmetic), hence the -1s.
    warmup_end = max(int(round(pct_start * total_steps)) - 1, 1)
    initial = peak_lr / div_factor
    final = initial / final_div_factor
    return optax.join_schedules(
        [
            optax.linear_schedule(initial, peak_lr, warmup_end),
            optax.linear_schedule(peak_lr, final, total_steps - 1 - warmup_end),
        ],
        [warmup_end],
    )


def make_optimizer(
    lr: float,
    num_steps: int,
    wdecay: float = 1e-5,
    grad_clip_norm: float = 1.0,
) -> Tuple[optax.GradientTransformation, optax.Schedule]:
    schedule = onecycle_linear(lr, num_steps + 100)
    clip = optax.clip_by_global_norm(grad_clip_norm)
    tx = optax.chain(
        # Same transformation, its update traced under the `grad_clip` scope
        # (the train step wraps the whole update in `optimizer`).
        optax.GradientTransformation(clip.init, scoped("grad_clip")(clip.update)),
        optax.adamw(schedule, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wdecay),
    )
    return tx, schedule
