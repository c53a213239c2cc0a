"""What the iteration body's checkpoint policy keeps across the backward.

`remat_save_corr` saves the lookup's taps and the pre-activation sums of the
GRU gates (`raft_stereo.REMAT_SAVED_NAMES`), so the recompute pass re-runs no
GRU convolution. Counted in the jaxpr of the gradient, where no compiler has yet
rewritten a convolution: every equation carries the name stack the trace
wrote, and `obs.scopes.component` reads it as it reads a compiled module's
`op_name` (the recompute runs under `rematted_computation`).
"""

import collections

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models import RAFTStereo
from raft_stereo_tpu.models.raft_stereo import REMAT_SAVED_NAMES
from raft_stereo_tpu.models.update import GATE_SUM
from raft_stereo_tpu.obs import scopes

SMALL = dict(hidden_dims=(16, 16, 16), corr_levels=2, corr_radius=2)
GRUS = ("gru08", "gru16", "gru32")  # finest scale first; `n_gru_layers` takes a prefix


def _inner_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield from _inner_jaxprs(value.jaxpr)
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _inner_jaxprs(item)


def _convolutions(jaxpr, outer=""):
    """The path of every convolution equation, through every nested jaxpr; a
    nested equation's name stack continues its parent's, as it does when the
    program is lowered."""
    for eqn in jaxpr.eqns:
        path = "/".join(p for p in (outer, str(eqn.source_info.name_stack)) if p)
        if eqn.primitive.name == "conv_general_dilated":
            yield path
        for value in eqn.params.values():
            for inner in _inner_jaxprs(value):
                yield from _convolutions(inner, path)


def _loss(model, iters):
    def loss(variables, image1, image2):
        outputs = model.apply(variables, image1, image2, iters=iters)
        return sum(jnp.sum(o ** 2) for o in jax.tree.leaves(outputs))

    return loss


def _recomputed(cfg):
    """Convolutions of the gradient's recompute pass by component."""
    model = RAFTStereo(cfg)
    image = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    variables = jax.eval_shape(lambda a: model.init(jax.random.PRNGKey(0), a, a, iters=1), image)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, 3)))(variables, image, image)
    counts = collections.Counter()
    for path in _convolutions(jaxpr.jaxpr):
        component, phase = scopes.component(path)
        if phase == "recompute":
            counts[component] += 1
    return counts


def test_saved_names_are_the_names_the_model_writes():
    assert REMAT_SAVED_NAMES == ("corr_taps", GATE_SUM)


@pytest.mark.parametrize(
    "n_gru_layers, slow_fast_gru", [(1, False), (2, False), (3, False), (2, True), (3, True)]
)
def test_recompute_runs_no_gru_convolution(n_gru_layers, slow_fast_gru):
    cfg = RAFTStereoConfig(n_gru_layers=n_gru_layers, slow_fast_gru=slow_fast_gru, **SMALL)
    counts = _recomputed(cfg)
    for gru in GRUS:
        assert counts[gru] == 0, counts
    # left recomputed on purpose (PERF.md section 6, PR 28): the motion
    # encoder's four inner convolutions and the two segments of its last, and
    # the flow head's first (nothing reads its second again)
    assert counts["motion_encoder"] == 6 and counts["flow_head"] == 1, counts


@pytest.mark.parametrize(
    "n_gru_layers, slow_fast_gru", [(1, False), (2, False), (3, False), (2, True), (3, True)]
)
def test_without_the_policy_every_convolution_is_recomputed(n_gru_layers, slow_fast_gru):
    """The control: `remat_save_corr=False` is plain remat, and the census
    above does see a recomputed convolution where there is one."""
    cfg = RAFTStereoConfig(
        n_gru_layers=n_gru_layers, slow_fast_gru=slow_fast_gru, remat_save_corr=False, **SMALL)
    counts = _recomputed(cfg)
    for gru in GRUS[:n_gru_layers]:
        # three gates, over the hidden state and each input
        assert counts[gru] >= 6 and counts[gru] % 3 == 0, counts
    assert counts["motion_encoder"] == 6 and counts["flow_head"] == 1, counts


NONLINEARITIES = {"sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "relu": jax.nn.relu}


@pytest.mark.parametrize("nonlinearity", sorted(NONLINEARITIES))
def test_a_name_after_the_nonlinearity_saves_no_convolution(nonlinearity):
    """Why the name sits on the sums and not on z, r, q: the derivative rule
    reads the nonlinearity's own output (for `relu`, which a later name may
    meet, its input) variable, `checkpoint_name` makes a new one, and a name on
    the result keeps a copy nobody asks for while the convolution is re-run to
    rebuild the variable the rule wants."""
    act = NONLINEARITIES[nonlinearity]

    def recomputed_convolutions(name_the_sum):
        def cell(kernel, x):
            total = jax.lax.conv_general_dilated(
                x, kernel, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if name_the_sum:
                return act(checkpoint_name(total, "kept")) * x
            return checkpoint_name(act(total), "kept") * x

        kept = jax.checkpoint(
            cell, prevent_cse=False, policy=jax.checkpoint_policies.save_only_these_names("kept"))
        jaxpr = jax.make_jaxpr(jax.grad(lambda k, x: jnp.sum(kept(k, x) ** 2)))(
            jnp.ones((3, 3, 4, 4)), jnp.ones((1, 8, 8, 4)))
        return sum("rematted_computation" in path for path in _convolutions(jaxpr.jaxpr))

    assert recomputed_convolutions(name_the_sum=True) == 0
    assert recomputed_convolutions(name_the_sum=False) == 1


@pytest.mark.parametrize("implementation", ["reg", "pallas"])
def test_gradients_equal_those_without_remat(implementation):
    """Saved or recomputed, the backward reads the same float32 sums: the
    gradients of the training forward under the policy equal those with no
    remat at all, leaf by leaf, to float32 rounding (the two programs fuse
    differently in the feature encoder, whose own remat goes with the switch:
    1e-6 of the largest gradient here; at 64x96 and 3 iterations every leaf
    was equal to the bit)."""
    image1, image2 = (
        jax.random.uniform(jax.random.PRNGKey(seed), (1, 32, 64, 3), jnp.float32, 0.0, 255.0)
        for seed in (1, 2)
    )
    grads = {}
    for remat in (True, False):
        cfg = RAFTStereoConfig(corr_implementation=implementation, remat_iterations=remat, **SMALL)
        model = RAFTStereo(cfg)
        variables = model.init(jax.random.PRNGKey(0), image1, image2, iters=1)
        grads[remat] = jax.jit(jax.grad(_loss(model, 2)))(variables, image1, image2)
    with_policy, without_remat = (jax.tree.leaves_with_path(grads[r]) for r in (True, False))
    assert len(with_policy) == len(without_remat) > 0
    # One scale for every leaf: a conv bias before an instance norm has a
    # true gradient of nought, and what it holds is rounding of this size.
    scale = max(float(jnp.abs(b).max()) for _, b in without_remat)
    for (path, a), (_, b) in zip(with_policy, without_remat):
        assert np.isfinite(a).all() and float(jnp.abs(a).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))
