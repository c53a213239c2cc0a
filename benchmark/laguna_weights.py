"""Seed -> the `laguna-moe` weight tree, drawn on the device in one jitted
call.

The layout comes from `laguna_reference.param_shapes`; the program and the
reference are handed the same tree. What each leaf is drawn from, and why
(PERF.md section 4 has the readings):

- every matrix (`w_*`): N(0, gain^2 / fan_in), the fan-in being the axis a
  product contracts (the second-to-last of a stacked expert leaf). Every
  sublayer reads a normed stream, so its output's variance is about its gain
  squared: 1 for all but the router. The gate's logits then have deviation 1:
  g between 0.27 and 0.73 for most heads and positions, 0.5 in the mean;
- **the router's gain is ROUTER_GAIN = 1**: a sigmoid router's eight chosen
  scores are the upper tail of 256 and all lie near 1 at any gain worth the
  name (0.87-0.94 at gain 1, 0.98-1.0 at 2), so the renormalised weights are
  an eighth each whatever the gain and nothing is won by peaking it, as the
  `sdar` draw's softmax router did; a choice that flips between bf16 and
  float32 activations moves 2.5 / 8 of an expert's output;
- **the embedding is N(0, EMBEDDING_SCALE^2) with EMBEDDING_SCALE 0.3, and
  the q norm's weight is scaled by Q_NORM_SCALE = 6 in the first
  PEAKED_LAYERS = 2 layers** (the leading full-attention layer and the first
  window layer, both ahead of the first router), for the reason
  `sdar_weights` gives: with a unit embedding and flat attention the residual
  stream stays its token's row, equal tokens pick equal experts, and under
  Zipf ids the rows this chip's 32 experts take swing from seed to seed.
  Scores of deviation 6 make a query attend a handful of keys, a small
  embedding lets what attention adds outweigh the token's row, and two
  peaked layers (not all five) keep the bf16 step's gradient near the
  float32 one;
- every norm weight: uniform on [0.8, 1.2] (times the q norm's scale).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark import laguna_reference
from benchmark.weights import _unflatten, flatten

ROUTER_GAIN, EMBEDDING_SCALE, Q_NORM_SCALE, PEAKED_LAYERS = 1.0, 0.3, 6.0, 2


def _leaf(path: str, shape, key):
    name = path.rsplit("/", 1)[-1]
    if path == "embed/embedding":
        return jax.random.normal(key, shape, jnp.float32) * EMBEDDING_SCALE
    if name == "weight":
        peaked = path.endswith("q_norm/weight") and int(path.split("/")[0].split("_")[1]) < PEAKED_LAYERS
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2) * (Q_NORM_SCALE if peaked else 1.0)
    if name.startswith("w_"):
        gain = ROUTER_GAIN if name == "w_router" else 1.0
        return jax.random.normal(key, shape, jnp.float32) * (gain / math.sqrt(shape[-2]))
    raise ValueError(f"laguna_weights: no draw for the leaf {path}")


def draw(config: Dict, seed: int) -> dict:
    """{"params": ...} as float32 device arrays, a leaf a key folded from
    its place in the sorted paths."""
    shapes = dict(sorted(flatten(laguna_reference.param_shapes(config))))

    @jax.jit
    def make(key):
        return {path: _leaf(path, shape, jax.random.fold_in(key, index))
                for index, (path, shape) in enumerate(shapes.items())}

    # `seed` may exceed 32 signed bits; fold it in two halves.
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return {"params": _unflatten(make(key))}
