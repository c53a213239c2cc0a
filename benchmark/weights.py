"""Seed -> the weight tree, drawn on the device in one jitted call.

The layout comes from `reference.param_shapes`; the program and the reference
are handed the same tree. The draw is what makes the 32-iteration refinement
something a comparison can judge: with a default (He) initialisation the
untrained update block is an expanding map, and two float32 executables of the
same model drift apart by hundreds of pixels (PERF.md, PR 22). Here every
convolution is drawn at `gain / sqrt(fan_in)`, and the layers that close the
loop estimate -> lookup -> GRU -> estimate (`LOOP_GAINS`) are drawn smaller, so
an iteration moves the estimate by a fraction of a pixel and damps a
perturbation instead of amplifying it. Widths and depths are untouched.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark import reference

DEFAULT_GAIN = math.sqrt(2.0)
# Matched against the leaf's path, first hit wins.
LOOP_GAINS = (
    ("flow_head/conv2", 0.25),
    ("encoder/convc1", 0.5),
    ("gru", 1.0),
    ("mask_conv2", 0.5),
)


def _gain(path: str) -> float:
    for needle, gain in LOOP_GAINS:
        if needle in path:
            return gain
    return DEFAULT_GAIN


def _scale(path: str, shape) -> float:
    """Standard deviation of a normally drawn leaf."""
    if path.endswith("/kernel"):
        fan_in = shape[0] * shape[1] * shape[2]
        return _gain(path) / math.sqrt(fan_in)
    return 0.1  # bias, mean


def _is_uniform(path: str) -> bool:
    return path.endswith(("/scale", "/var"))


def flatten(tree, prefix=""):
    """(path, leaf) of every leaf of a nested dict, paths joined by '/'."""
    for name, sub in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(sub, dict):
            yield from flatten(sub, path)
        else:
            yield path, sub


def _unflatten(flat: Dict[str, object]) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


def draw(model_cfg: Dict, seed: int) -> dict:
    """{"params": ..., "batch_stats": ...} as float32 device arrays: one
    normal and one uniform vector drawn in a single jitted call and cut into
    the leaves, in the order of their paths."""
    shapes = dict(sorted(flatten(reference.param_shapes(model_cfg))))
    sizes = {path: math.prod(shape) for path, shape in shapes.items()}
    n_uniform = sum(n for path, n in sizes.items() if _is_uniform(path))
    n_normal = sum(sizes.values()) - n_uniform

    @jax.jit
    def make(key):
        k_normal, k_uniform = jax.random.split(key)
        normal = jax.random.normal(k_normal, (n_normal,), jnp.float32)
        uniform = jax.random.uniform(k_uniform, (n_uniform,), jnp.float32, 0.8, 1.2)
        leaves, at = {}, {"normal": 0, "uniform": 0}
        for path, shape in shapes.items():
            kind = "uniform" if _is_uniform(path) else "normal"
            source = uniform if kind == "uniform" else normal
            flat = jax.lax.dynamic_slice_in_dim(source, at[kind], sizes[path])
            at[kind] += sizes[path]
            leaf = flat.reshape(shape)
            leaves[path] = leaf if kind == "uniform" else leaf * _scale(path, shape)
        return leaves

    # `seed` may exceed 32 signed bits; fold it in two halves.
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _unflatten(make(key))
