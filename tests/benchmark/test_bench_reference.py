"""The plain reference against the program at a small size on the CPU: the
weight tree's layout, the forward pass, the loss and its gradients, and the
control (one precision below the configuration's) failing the cell's limit."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, traffic, weights
from benchmark.drivers import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FULL = json.load(open(os.path.join(ROOT, "benchmark", "configs", "raftstereo-full.json")))
REALTIME_MODEL = dict(FULL["model"], n_gru_layers=2, n_downsample=3, slow_fast_gru=True, shared_backbone=True)
SEED = 2**31 + 11  # more than 32 signed bits hold


def _program(model, **program):
    from raft_stereo_tpu.models import RAFTStereo

    config = {"model": model, "program": dict(corr_implementation="reg", **program)}
    return RAFTStereo(common.model_config(config))


def _pair(seed, hw, batch=1, max_disp=6.0):
    frames = traffic.stereo_frames(seed, batch, hw, max_disp)
    return {k: jnp.asarray(np.stack([f[k] for f in frames])) for k in frames[0]}


def _shapes(tree):
    return jax.tree.map(lambda x: tuple(x.shape), dict(tree))


def test_draw_repeats_for_a_seed_and_differs_between_seeds():
    model = FULL["model"]
    got = weights.draw(model, SEED)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(got))
    kernel = lambda tree: np.asarray(tree["params"]["mask_head"]["mask_conv1"]["Conv_0"]["kernel"])
    assert np.array_equal(kernel(got), kernel(weights.draw(model, SEED)))
    assert not np.array_equal(kernel(got), kernel(weights.draw(model, SEED + 1)))
    var = np.asarray(got["batch_stats"]["cnet"]["trunk"]["FrozenBatchNorm_0"]["var"])
    assert var.min() >= 0.8 and var.max() <= 1.2


@pytest.mark.parametrize("model", [FULL["model"], REALTIME_MODEL], ids=["full", "realtime"])
def test_layout_and_forward_agree_with_the_program_in_float32(model):
    program = _program(model)
    image = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    want = jax.eval_shape(lambda a, b: program.init(jax.random.PRNGKey(0), a, b, iters=1), image, image)
    variables = weights.draw(model, SEED)
    assert _shapes(variables) == _shapes(want)

    pair = _pair(SEED, (64, 96), batch=2)
    iters = 4
    want = jax.jit(lambda v, a, b: reference.forward(model, v, a, b, iters))(
        variables, pair["image1"], pair["image2"])
    got = jax.jit(lambda v, a, b: program.apply(v, a, b, iters=iters, test_mode=True)[1])(
        variables, pair["image1"], pair["image2"])[..., 0]
    assert float(jnp.abs(want).mean()) > 0.5, "the draw moves the estimate"
    assert float(jnp.abs(got - want).max()) < 1e-3
    staged = reference.forward_staged(model, variables, pair["image1"], pair["image2"], iters)
    assert float(jnp.abs(staged - want).max()) < 1e-4


def test_loss_and_gradients_agree_with_the_program_in_float32():
    from raft_stereo_tpu.train.loss import sequence_loss

    model = FULL["model"]
    program = _program(model)
    variables = weights.draw(model, SEED)
    batch = _pair(SEED, (64, 96), batch=2)
    stats = variables["batch_stats"]

    def program_loss(params):
        flows = program.apply({"params": params, "batch_stats": stats},
                              batch["image1"], batch["image2"], iters=3)
        return sequence_loss(flows, batch["flow"], batch["valid"])[0]

    def reference_loss(params):
        preds = reference.predictions(model, {"params": params, "batch_stats": stats},
                                      batch["image1"], batch["image2"], 3)
        return reference.sequence_loss(preds, batch["flow"], batch["valid"])

    got_loss, got = jax.jit(jax.value_and_grad(program_loss))(variables["params"])
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(variables["params"])
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(want)]
    median = float(np.median(norms))
    gaps = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)) / max(float(jnp.linalg.norm(b)), median), got, want)
    assert max(jax.tree.leaves(gaps)) < 5e-3


def test_learning_rate_is_the_recipes_one_cycle():
    import optax

    from raft_stereo_tpu.train.optimizer import onecycle_linear

    theirs = onecycle_linear(2e-4, 200_000 + 100)
    for step in (0, 1, 2, 1999, 2000, 2001, 100_000, 200_099):
        assert float(reference.learning_rate(step, 2e-4, 200_000)) == pytest.approx(float(theirs(step)), rel=1e-5)


@pytest.mark.parametrize("model, iters", [(FULL["model"], None), (REALTIME_MODEL, 7)], ids=["full", "realtime"])
def test_the_control_fails_the_limit_the_program_passes(model, iters):
    """The inference cells' number at a size a test can hold (128x192, the
    offline cell's 32 iterations, the realtime model's published 7): the
    program as configured (bf16 compute and pyramid) passes the offline
    cell's limit, the reference computed in fp8 in its place does not."""
    spec = json.load(open(os.path.join(ROOT, "benchmark", "workloads", "full-offline-middlebury-f.json")))
    limit, iters = spec["limits"]["map_mae_px"], iters or spec["iters"]
    program = _program(model, mixed_precision=True, corr_dtype="bfloat16")
    variables = weights.draw(model, SEED)
    pair = _pair(SEED, (128, 192), max_disp=12.0)
    run = lambda fn: np.asarray(jax.jit(fn)(variables, pair["image1"], pair["image2"]))
    want = run(lambda v, a, b: reference.forward(model, v, a, b, iters))
    got = run(lambda v, a, b: program.apply(v, a, b, iters=iters, test_mode=True)[1])[..., 0]
    control = run(lambda v, a, b: reference.forward(model, v, a, b, iters, spec["control"]))
    assert np.abs(got - want).mean() < limit
    assert np.abs(control - want).mean() > limit
