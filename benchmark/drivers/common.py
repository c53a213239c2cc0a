"""What every driver shares: the program's model configuration from a
configuration file, the comparison record, and freeing the device."""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict

import numpy as np


def model_config(config: Dict):
    """The configuration file's `model` and `program` groups as the
    program's own config object."""
    from raft_stereo_tpu.config import RAFTStereoConfig

    model = dict(config["model"])
    model["hidden_dims"] = tuple(model["hidden_dims"])
    return RAFTStereoConfig(**model, **config["program"])


def compared(value: float, limit: float) -> Dict[str, object]:
    """One number beside its limit; NaN fails."""
    return {"value": value, "limit": limit, "ok": bool(value <= limit)}


def picked(seed: int, answers: list, size: int) -> list:
    """A sample of `answers`, drawn from the seed."""
    chosen = np.random.default_rng(seed).choice(len(answers), size=min(size, len(answers)), replace=False)
    return [answers[i] for i in chosen]


def map_mae_px(answers, reference_map) -> float:
    """The worst mean absolute error, in pixels, of `answers` ((frame index,
    map) pairs) against `reference_map(index)`, the float32 reference on the
    same pair and weights. NaN where there is no answer, or one of the wrong
    shape or not finite."""
    worst, sound, cache = 0.0, bool(answers), {}
    for index, got in answers:
        if index not in cache:
            cache[index] = reference_map(index)
        want = cache[index]
        if got.shape != want.shape or not np.isfinite(got).all():
            sound = False
            continue
        worst = max(worst, float(np.abs(got - want).mean()))
    return worst if sound else float("nan")


def free_device() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


class Phases(dict):
    """Seconds of each named part of a driver's set-up, for the builder's
    eyes (run.py prints them under "seconds")."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - start
