"""Seed -> inputs. One general generator per kind of traffic; a traffic mix is
a data file of parameters under `benchmark/workloads/`.

Stereo pairs are synthetic with a known disparity: one textured scene, the
right image resampled along each row by a smooth disparity plane, values in
the uint8 range as float32 (what the datasets' readers hand the model).
Every seed gives the same sizes, so the work of a run does not depend on it.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _texture(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Coarse blobs plus fine grain, (h, w, 3) float32 in [0, 255]."""
    cell = 16
    coarse = rng.uniform(0.0, 255.0, (-(-h // cell), -(-w // cell), 3)).astype(np.float32)
    coarse = np.repeat(np.repeat(coarse, cell, axis=0), cell, axis=1)[:h, :w]
    fine = rng.integers(0, 256, (h, w, 3), dtype=np.uint8).astype(np.float32)
    return np.round(0.5 * coarse + 0.5 * fine)


def stereo_frame(rng: np.random.Generator, h: int, w: int, max_disp: float) -> Dict[str, np.ndarray]:
    margin = int(np.ceil(max_disp)) + 2
    base = _texture(rng, h, w + margin)
    offset = rng.uniform(0.25 * max_disp, 0.75 * max_disp)
    slope_x = rng.uniform(-0.2, 0.2) * max_disp / w
    slope_y = rng.uniform(-0.2, 0.2) * max_disp / h
    xs = np.arange(w, dtype=np.float32)[None, :]
    ys = np.arange(h, dtype=np.float32)[:, None]
    disp = np.clip(offset + slope_x * xs + slope_y * ys, 0.5, max_disp).astype(np.float32)
    coords = xs + disp
    x0 = np.floor(coords).astype(np.int64)
    frac = (coords - x0)[..., None].astype(np.float32)
    rows = np.arange(h)[:, None]
    image2 = base[rows, x0] * (1.0 - frac) + base[rows, x0 + 1] * frac
    return {
        "image1": np.ascontiguousarray(base[:, :w]),
        "image2": np.ascontiguousarray(image2, np.float32),
        "flow": np.ascontiguousarray(-disp[..., None]),
        "valid": np.ones((h, w), np.float32),
    }


def stereo_frames(seed: int, n: int, hw: Sequence[int], max_disp: float) -> List[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    return [stereo_frame(rng, hw[0], hw[1], max_disp) for _ in range(n)]


def stereo_batches(seed: int, n_batches: int, batch: int, hw: Sequence[int], max_disp: float):
    """`n_batches` host batches whose rows all differ."""
    frames = stereo_frames(seed, n_batches * batch, hw, max_disp)
    return [
        {key: np.stack([f[key] for f in frames[i * batch : (i + 1) * batch]]) for key in frames[0]}
        for i in range(n_batches)
    ]
