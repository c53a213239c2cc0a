"""Seed -> the `granite-hybrid` weight tree, drawn on the device in one jitted
call.

The layout comes from `granite_reference.param_shapes`; the program and the
reference are handed the same tree. What each leaf is drawn from, and why
(PERF.md section 4 has the readings):

- every matrix (`w_*`): N(0, 1 / fan_in), the fan-in being the axis a product
  contracts. Every sublayer reads a normed stream, so its output's variance
  is about 1, and `residual_multiplier` 0.22 scales what it adds;
- the Mamba-2 mixer's own leaves as its authors initialise them: `a_log =
  log U(1, 16)` (A between -16 and -1 a head), `dt_bias` the inverse softplus
  of a step drawn log-uniformly from [0.001, 0.1], `d` 1, the convolution's
  taps U(-1/2, 1/2) (1 / sqrt(mamba_d_conv)) and its bias N(0, 0.1^2), so
  that the bias carries a gradient;
- the embedding: N(0, EMBEDDING_SCALE^2) with EMBEDDING_SCALE 1 / 12: times
  `embedding_multiplier` 12 the stream starts at unit variance, so that the
  20 sublayers' additions (0.22 each, summed variance about 1) weigh as much
  as a position's own token, and what a position predicts follows its
  context. The tied head then gives logits of deviation
  sqrt(2048) / 12 / 8 = 0.47: a soft distribution over the 12,544 rows, a
  first loss near ln(12,544) = 9.44;
- every norm weight: uniform on [0.8, 1.2].
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark import granite_reference
from benchmark.weights import _unflatten, flatten

EMBEDDING_SCALE = 1.0 / 12.0
CONV_BIAS_SCALE = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1


def _leaf(path: str, shape, key):
    name = path.rsplit("/", 1)[-1]
    normal = lambda scale: jax.random.normal(key, shape, jnp.float32) * scale
    uniform = lambda low, high: jax.random.uniform(key, shape, jnp.float32, low, high)
    if path == "embed/embedding":
        return normal(EMBEDDING_SCALE)
    if name == "weight":
        return uniform(0.8, 1.2)
    if name == "a_log":
        return jnp.log(uniform(1.0, 16.0))
    if name == "dt_bias":
        step = jnp.exp(uniform(math.log(DT_MIN), math.log(DT_MAX)))
        return step + jnp.log(-jnp.expm1(-step))  # softplus(dt_bias) = step
    if name == "d":
        return jnp.ones(shape, jnp.float32)
    if name == "conv_w":
        return uniform(-0.5, 0.5)
    if name == "conv_b":
        return normal(CONV_BIAS_SCALE)
    if name.startswith("w_"):
        return normal(1.0 / math.sqrt(shape[-2]))
    raise ValueError(f"granite_weights: no draw for the leaf {path}")


def draw(config: Dict, seed: int) -> dict:
    """{"params": ...} as float32 device arrays, a leaf a key folded from
    its place in the sorted paths."""
    shapes = dict(sorted(flatten(granite_reference.param_shapes(config))))

    @jax.jit
    def make(key):
        return {path: _leaf(path, shape, jax.random.fold_in(key, index))
                for index, (path, shape) in enumerate(shapes.items())}

    # `seed` may exceed 32 signed bits; fold it in two halves.
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return {"params": _unflatten(make(key))}
