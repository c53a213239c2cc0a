"""Seed -> the toy regressor's weights, at the shapes its reference names."""

import math

import numpy as np

from benchmark import toy_mlp_reference


def draw(model, seed):
    rng = np.random.default_rng(seed)
    return {
        name: (rng.standard_normal(shape) / math.sqrt(shape[0])).astype(np.float32)
        for name, shape in toy_mlp_reference.param_shapes(model).items()
    }
