"""Seed -> the `sdar-moe` weight tree, drawn on the device in one jitted call.

The layout comes from `sdar_reference.param_shapes`; the program and the
reference are handed the same tree. Every matrix is N(0, gain^2 / fan_in)
(fan_in: the axis a product contracts, the second-to-last of a stacked
leaf), every norm weight uniform on [0.8, 1.2] times its scale.

Scales, and why (PERF.md section 4 has the readings). The residual stream is
normed before every sublayer, so a sublayer's output variance is its gain
squared: 1 for the attention and expert matrices and the head.

- **The router's gain is 2**: its logits then have a standard deviation of 2,
  the softmax over 128 experts is peaked, and the eight chosen weights fall
  off steeply, so the choice most likely to flip between bf16 and float32
  activations (the eighth against the ninth) carries the smallest weight.
  How MANY choices flip does not depend on the gain; what a flip moves does.
- **The embedding is N(0, 0.3^2) and the q norm's weight is scaled by 6 in
  the first two layers only**, so that routing follows a position's CONTEXT
  and not its token alone, and the gradient still means something in bf16.
  With a unit embedding and flat attention (scores of deviation 1 over
  thousands of keys average the values away) the residual stream stays its
  token's row through every layer: equal tokens pick equal experts, and under
  Zipf ids and one MASK row on a quarter of the positions the rows this
  chip's 16 experts take swing by 11% from seed to seed (max over mean load
  4-5, my chip runs, PR 30), and a step's time with them. Scores of deviation
  6 make a query attend a handful of keys, so what attention adds differs
  from position to position, and a small embedding lets it outweigh the
  token's row. But every peaked layer multiplies a rounding error by about
  its gain: with all five layers peaked the bf16 step's gradient stands 0.7
  of its norm away from the float32 one, as far as an fp8 step's does, and
  the comparison tells no precision from another (float32 emulation at
  hidden 256, 1024 tokens; PERF.md section 4). Two peaked layers keep most of
  the calm (rows swing 7% against 6% and 14%) at a gradient 0.09 away.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark import sdar_reference
from benchmark.weights import _unflatten, flatten

ROUTER_GAIN, EMBEDDING_SCALE, Q_NORM_SCALE, PEAKED_LAYERS = 2.0, 0.3, 6.0, 2


def _scale(path: str, shape):
    """Standard deviation of a normally drawn leaf; the factor on a
    uniformly drawn norm weight (the q norm's: one a layer)."""
    if path == "embed/embedding":
        return EMBEDDING_SCALE
    if path.endswith("q_norm/weight"):
        return jnp.where(jnp.arange(shape[0]) < PEAKED_LAYERS, Q_NORM_SCALE, 1.0)[:, None]
    if _is_uniform(path):
        return 1.0
    return (ROUTER_GAIN if "router/w_router" in path else 1.0) / math.sqrt(shape[-2])


def _is_uniform(path: str) -> bool:
    return path.endswith("/weight")


def draw(config: Dict, seed: int) -> dict:
    """{"params": ...} as float32 device arrays, a leaf a key folded from
    its place in the sorted paths (a 550M-parameter tree is not drawn as one
    vector: that would hold it twice)."""
    shapes = dict(sorted(flatten(sdar_reference.param_shapes(config))))

    @jax.jit
    def make(key):
        leaves = {}
        for index, (path, shape) in enumerate(shapes.items()):
            leaf_key = jax.random.fold_in(key, index)
            if _is_uniform(path):
                leaves[path] = jax.random.uniform(leaf_key, shape, jnp.float32, 0.8, 1.2) * _scale(path, shape)
            else:
                leaves[path] = jax.random.normal(leaf_key, shape, jnp.float32) * _scale(path, shape)
        return leaves

    # `seed` may exceed 32 signed bits; fold it in two halves.
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return {"params": _unflatten(make(key))}
