"""The `laguna-moe` family at a small size on the CPU (hidden 64, heads of 16
on 2 key-value heads, 5 layers `F S S S F` with 6 and 8 query heads, window
8, 40 tokens a row, 8 experts of 32 of which 2 are held, 2 a token, dense
first): the window entry of the attention kernels against its dense form
against a dense softmax under an explicit mask, the partial rotary against
the `jax.numpy` form and a hand-built rotation, YaRN's tables against
transformers', the model against `benchmark/laguna_reference.py`, the share
tied to the model, the trainer's scopes and entry point for the family.
"""

import copy
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import walk_checks
from benchmark import laguna_reference
from benchmark.reference import rounder
from benchmark.weights import flatten
from raft_stereo_tpu.config import LagunaConfig, TrainConfig
from raft_stereo_tpu.models import laguna
from raft_stereo_tpu.ops import block_attention as ba
from raft_stereo_tpu.ops import qk_norm_rope as qnr

SEQ = 40
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_slow": 1, "beta_fast": 64, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}
PUBLISHED = dict(
    model_type="laguna", vocab_size=96, hidden_size=64, intermediate_size=96, num_hidden_layers=5,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, num_experts=2,
    num_experts_per_tok=2, moe_intermediate_size=32, shared_expert_intermediate_size=32, gating=True,
    sliding_window=8, rope_parameters=ROPE, moe_routed_scaling_factor=2.5, tie_word_embeddings=False,
    attention_bias=False, layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, num_attention_heads_per_layer=[6, 8, 8, 8, 6])
PROGRAM = dict(expert_parallel=4, expert_shard=1, mixed_precision=False, remat_layers=True, moe_chunk=16,
               moe_tile_rows=8, attention_tile=8, loss_chunk=16)
FILE = dict(PUBLISHED, program=PROGRAM)  # what the reference is handed


def _config(**program):
    return LagunaConfig.from_hf_config(PUBLISHED, **dict(PROGRAM, **program))


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) <= tol * scale


# -- the window entry of the attention kernels --------------------------------------------


@pytest.mark.parametrize("window,heads", [(3, (6, 1)), (8, (16, 2)), (11, (12, 2)), (100, (6, 1))],
                         ids=["under_a_tile-group6", "a_tile-group8", "over_a_tile-group6", "over_the_row-group6"])
def test_window_attention_matches_its_dense_form_and_a_dense_softmax(window, heads):
    """Forward and every gradient, tiles of 8 in a row of 40: the kernels
    (interpreted) against `window_attention_dense` against a softmax under
    the mask written out."""
    hq, hkv = heads
    keys = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(keys[0], (2, hq, SEQ, 16))
    k, v = (jax.random.normal(key, (2, hkv, SEQ, 16)) for key in keys[1:3])
    weight = jax.random.normal(keys[3], q.shape)
    i, j = np.arange(SEQ)[:, None], np.arange(SEQ)[None, :]
    mask = jnp.asarray((j <= i) & (i - window < j))

    def by_hand(q, k, v):
        k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), v)

    forms = {
        "kernels": lambda q, k, v: ba.window_attention(q, k, v, window, 0.25, 8),
        "dense": lambda q, k, v: ba.window_attention_dense(q, k, v, window, 0.25),
        "by_hand": by_hand,
    }
    got = {name: jax.jit(jax.value_and_grad(lambda q, k, v, fn=fn: jnp.sum(fn(q, k, v) * weight), argnums=(0, 1, 2)))(q, k, v)
           for name, fn in forms.items()}
    for name in ("kernels", "dense"):
        assert abs(float(got[name][0]) - float(got["by_hand"][0])) < 1e-4 * abs(float(got["by_hand"][0])) + 1e-4
        assert all(_close(a, b, 5e-5) for a, b in zip(got[name][1], got["by_hand"][1])), name


def test_the_window_walk_visits_the_tiles_the_window_reaches_and_no_other():
    """Tiles of 8 under a window of 8: a query tile sees its own and the one
    before; a window of 20 reaches three tiles back; a key tile's query tiles
    are the mirror image."""
    t, walk = ba._Mask(SEQ, 0, 8).walk(8)
    assert (t, walk.tiles, walk.reach, walk.fwd_max, walk.bwd_max) == (8, 5, 1, 2, 2)
    visited = lambda w: {(qt, int(w.fwd_key_tile(qt, s))) for qt in range(w.tiles) for s in range(int(w.fwd_steps(qt)))}
    mirrored = lambda w: {(int(w.bwd_query_tile(kt, u)), kt) for kt in range(w.tiles) for u in range(int(w.bwd_steps(kt)))}
    assert visited(walk) == mirrored(walk) == {(qt, kt) for qt in range(5) for kt in range(5) if qt - 1 <= kt <= qt}
    _, wide = ba._Mask(SEQ, 0, 20).walk(8)
    assert wide.reach == 3 and visited(wide) == mirrored(wide) == {
        (qt, kt) for qt in range(5) for kt in range(5) if qt - 3 <= kt <= qt}
    # a step past the last visible tile keeps the block index: nothing is copied for it
    assert int(walk.fwd_key_tile(0, 1)) == 0 and int(walk.bwd_query_tile(4, 1)) == 4
    _, over = ba._Mask(SEQ, 0, 1000).walk(8)
    assert over.fwd_max == 5  # no longer a walk than the row
    assert ba._Mask(SEQ, 0, 8).kernels[0] == "window_attention" and ba._Mask(SEQ, 0).kernels[0] == "block_attention"
    with pytest.raises(ValueError):
        ba.window_attention(jnp.zeros((1, 2, SEQ, 16)), jnp.zeros((1, 2, SEQ, 16)), jnp.zeros((1, 2, SEQ, 16)), 0, 1.0, 8)


@pytest.mark.parametrize("seq,window,tile", [(80, 40, 16), (64, 16, 16), (64, 32, 16), (64, 33, 16), (40, 20, 8),
                                             (40, 100, 8), (40, 3, 8), (2048, 512, 512)],
                         ids=["over-two-tiles", "a-tile", "two-tiles", "two-tiles-and-one", "two-and-a-half", "over-the-row",
                              "under-a-tile", "the-cells-window"])
def test_the_window_walk_calls_interior_the_tiles_wholly_inside_the_window_and_no_other(seq, window, tile):
    """Against the dense mask, from the query side and from the key side. A
    window under two tiles holds no whole tile but a query's own, which the
    causal edge cuts: the walk says so before any index is known."""
    t, walk = ba._Mask(seq, 0, window).walk(tile)
    pos = np.arange(seq)
    mask = ba._in_window(pos[:, None], pos[:, None] - window + 1, pos[None, :])
    interior, visited = walk_checks.interior_pairs(mask, walk, t, every=True)
    nt, whole = seq // t, max(window // t - 1, 0)  # whole tiles before a query tile's own that its last query still reaches
    assert visited == sum(min(qt, walk.reach) + 1 for qt in range(nt))
    assert interior == sum(min(qt, whole) for qt in range(nt)) and (whole > 0 or walk.interior(1, 0) is False)
    assert ba.interior_pair_share(seq, 0, window, tile) == interior / visited


def test_the_cells_interior_pair_shares_are_496_of_528_and_none():
    assert ba.interior_pair_share(16384) == 496 / 528 and ba.interior_pair_share(16384, window=512) == 0.0
    assert jax.jit(lambda: ba.interior_pair_share(16384, 0, 0, 512))() == np.float32(496 / 528)


@pytest.mark.parametrize("window,heads", [(20, (12, 2)), (16, (6, 1)), (100, (16, 2))],
                         ids=["two-and-a-half-group6", "two-tiles-group6", "over-the-row-group8"])
def test_window_attention_without_the_unmasked_body_keeps_every_bit(monkeypatch, window, heads):
    walk_checks.never_interior_keeps_the_bits(
        monkeypatch, ba._WindowWalk, lambda q, k, v: ba.window_attention(q, k, v, window, 0.25, 8), heads, SEQ, 16)


# -- the partial and scaled rotary ----------------------------------------------------------


@pytest.mark.parametrize("rotary", [16, 8, 4], ids=["whole_head", "half", "quarter"])
def test_partial_rotary_matches_the_jax_numpy_form_and_a_hand_built_rotation(rotary):
    heads, d = 6, 16
    keys = jax.random.split(jax.random.PRNGKey(rotary), 4)
    x = jax.random.normal(keys[0], (2, SEQ, heads * d))
    weight = jax.random.uniform(keys[1], (d,), minval=0.8, maxval=1.2)
    angles = jnp.tile(jax.random.normal(keys[2], (SEQ, rotary // 2)), (1, 2))
    cos, sin = 1.4 * jnp.cos(angles), 1.4 * jnp.sin(angles)  # scaled, as YaRN's are
    cotangent = jax.random.normal(keys[3], (2, heads, SEQ, d))
    value = lambda fn: jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(fn(x, w, cos, sin, heads, 1e-6) * cotangent), argnums=(0, 1)))
    kernels = value(lambda *a: qnr.qk_norm_rope(*a, 8))(x, weight)
    dense = value(qnr.qk_norm_rope_dense)(x, weight)
    assert abs(float(kernels[0]) - float(dense[0])) < 1e-4 and all(_close(a, b, 5e-5) for a, b in zip(kernels[1], dense[1]))
    y = x.reshape(2, SEQ, heads, d)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-6) * weight
    turned, passed = y[..., :rotary], y[..., rotary:]
    half = jnp.concatenate([-turned[..., rotary // 2:], turned[..., : rotary // 2]], axis=-1)
    by_hand = jnp.concatenate([turned * cos[None, :, None] + half * sin[None, :, None], passed], axis=-1)
    assert _close(qnr.qk_norm_rope_dense(x, weight, cos, sin, heads, 1e-6), by_hand.transpose(0, 2, 1, 3), 1e-6)
    with pytest.raises(ValueError):
        qnr.qk_norm_rope(x, weight, cos[:, :3], sin[:, :3], heads, 1e-6)


def test_yarn_inverse_frequencies_are_transformers_own_at_the_published_parameters():
    torch = pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    published = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                 "beta_slow": 1, "beta_fast": 64, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5}

    class Config:
        rope_theta, head_dim, partial_rotary_factor, max_position_embeddings = 500000, 128, 0.5, 262144
        hidden_size, num_attention_heads = 2048, 48
        rope_scaling = {k: v for k, v in published.items() if k not in ("rope_theta", "partial_rotary_factor")}

    want, factor = rope_utils._compute_yarn_parameters(Config(), torch.device("cpu"))
    want = want.numpy()
    assert want.shape == (32,) and factor == published["attention_factor"]
    program = laguna.yarn_inv_freq(64, 500000, 64, 4096, 64, 1)
    reference = laguna_reference.yarn_inv_freq(64, published)
    assert np.allclose(program, want, rtol=1e-6, atol=0) and np.allclose(reference, want, rtol=1e-6, atol=0)
    # the fastest frequencies are kept, the slowest divided by the factor
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.isclose(want[0], plain[0]) and np.isclose(want[-1], plain[-1] / 64)
    cos, sin = laguna.rotary_tables(8, 128, published)
    assert cos.shape == sin.shape == (8, 64) and np.isclose(float(cos[0, 0]), published["attention_factor"])
    cos, _ = laguna.rotary_tables(8, 128, ROPE["sliding_attention"])
    assert cos.shape == (8, 128) and float(cos[0, 0]) == 1.0


# -- the model against the reference -----------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    config = _config()
    variables = laguna.init_laguna_variables(config, jax.random.PRNGKey(0), SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 96)
    return config, variables["params"], tokens


@pytest.fixture(scope="module")
def reference_readings(seeded):
    """(logits, held rows of the forward, ((loss, (held rows, mean gate)),
    gradient)) of the sound reference, one program."""
    _, params, tokens = seeded

    @jax.jit
    def readings(p):
        logits, held = laguna_reference.forward(FILE, p, tokens)
        graded = jax.value_and_grad(lambda p: laguna_reference.loss(FILE, p, {"tokens": tokens}), has_aux=True)(p)
        return logits, held, graded

    return readings(params)


def test_program_and_reference_lay_the_weights_out_alike(seeded):
    _, params, _ = seeded
    assert {k: v.shape for k, v in flatten(params)} == dict(flatten(laguna_reference.param_shapes(FILE)))


def test_logits_loss_and_every_leafs_gradient_match_the_reference(seeded, reference_readings):
    config, params, tokens = seeded
    model = laguna.Laguna(config)

    @jax.jit
    def readings(p):
        graded = jax.value_and_grad(lambda p: model.apply({"params": p}, tokens, method="loss"), has_aux=True)(p)
        return model.apply({"params": p}, tokens), graded

    (logits, counts), ((loss, metrics), grads) = readings(params)
    want_logits, want_held, ((want, (held, gate_mean)), want_grads) = reference_readings
    assert logits.shape == (2, SEQ, 96) and _close(logits, want_logits)
    assert counts.shape == (4, 2) and int(counts.sum()) == int(want_held) > 0
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert float(metrics["moe_held_rows"]) == float(held) == float(want_held)
    assert abs(float(metrics["attn_gate_mean"]) - float(gate_mean)) < 1e-5 and 0.3 < float(gate_mean) < 0.7
    assert 0 < float(metrics["moe_live_row_share"]) <= 1 and float(metrics["moe_max_over_mean_load"]) >= 1
    # five tiles: a full layer's pairs under the diagonal; a window of one tile holds no whole tile
    assert float(metrics["attn_interior_pair_share"]) == np.float32(10 / 15)
    assert float(metrics["attn_window_interior_pair_share"]) == 0.0
    got, wanted = dict(flatten(grads)), dict(flatten(want_grads))
    assert sorted(got) == sorted(wanted) and all(_close(got[k], wanted[k], 5e-5) for k in wanted)


def test_from_hf_config_reads_the_published_keys_and_refuses_what_it_does_not_model():
    config = _config()
    assert config.num_hidden_layers == 5 and config.router_width == 8 and config.num_attention_heads_per_layer == (6, 8, 8, 8, 6)
    assert config.rope("full_attention") == ROPE["full_attention"] and config.rope("sliding_attention")["rope_theta"] == 10000
    assert hash(config) == hash(_config())  # the nested groups are kept hashable: a config keys the init cache
    assert LagunaConfig.from_hf_config(dict(PUBLISHED, gating="per-head"), **PROGRAM) == config
    for bad in (dict(gating="elementwise"), dict(moe_router_logit_softcapping=30.0), dict(attention_bias=True),
                dict(tie_word_embeddings=True), dict(moe_apply_router_weight_on_input=True)):
        with pytest.raises(NotImplementedError):
            LagunaConfig.from_hf_config(dict(PUBLISHED, **bad), **PROGRAM)
    with pytest.raises(ValueError):
        LagunaConfig.from_hf_config(dict(PUBLISHED, num_attention_heads_per_layer=[6, 8, 8, 8]), **PROGRAM)
    with pytest.raises(ValueError):
        LagunaConfig.from_hf_config(dict(PUBLISHED, num_attention_heads_per_layer=[6, 8, 8, 8, 7]), **PROGRAM)
    yarn_only = copy.deepcopy(ROPE)
    yarn_only["sliding_attention"]["rope_type"] = "llama3"
    with pytest.raises(NotImplementedError):
        LagunaConfig.from_hf_config(dict(PUBLISHED, rope_parameters=yarn_only), **PROGRAM)


# -- the share tied to the model ------------------------------------------------------------------


def test_the_four_shards_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """A sparse layer's second half, 8 experts: each of 4 shards (2 experts
    held) gives the program's routed part for its own experts; those four and
    the shared expert's output, counted once, are what the uncut reference
    (all 8 held) adds to the stream."""
    import flax.linen as nn

    uncut = dict(PUBLISHED, num_experts=8, program=dict(PROGRAM, expert_parallel=1, expert_shard=0))
    sizes = laguna_reference._dims(uncut)
    shapes = laguna_reference.param_shapes(uncut)["layers_1"]
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    draw = lambda shape: jax.random.normal(next(keys), shape) / math.sqrt(shape[-2] if len(shape) > 1 else 1)
    p = {name: jax.tree.map(draw, shapes[name], is_leaf=lambda s: isinstance(s, tuple))
         for name in ("router", "experts", "shared_expert")}
    p["post_attention_norm"] = {"weight": jnp.ones((64,))}
    h = jax.random.normal(next(keys), (SEQ, 64))
    want, held = laguna_reference.sparse_half(rounder("float32"), uncut, sizes, p, h)

    class RoutedPart(nn.Module):
        config: LagunaConfig

        @nn.compact
        def __call__(self, m):
            chosen, weights = laguna.SigmoidRouter(self.config, name="router")(m)
            return laguna.Experts(self.config, name="experts")(m, chosen, weights)[:2]

    m = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + 1e-6)

    @jax.jit
    def parts(m):
        shared = laguna.GatedMLP(32).apply({"params": p["shared_expert"]}, m)
        routed, rows = [], []
        for shard in range(4):
            mine = {"router": p["router"], "experts": jax.tree.map(lambda w: w[2 * shard:2 * shard + 2], p["experts"])}
            y, counts = RoutedPart(_config(expert_shard=shard)).apply({"params": mine}, m)
            routed.append(y)
            rows.append(jnp.sum(counts))
        return shared, routed, rows

    shared, routed, rows = parts(m)
    assert _close(h + shared + sum(routed), want, 1e-5)
    assert sum(int(r) for r in rows) == int(held) == SEQ * 2 and all(int(r) > 0 for r in rows)
    assert not _close(h + shared + sum(routed[:3]), want, 1e-3)  # every shard's part is needed


# -- the trainer's scopes and entry point -------------------------------------------------------------


def _tiny_train_config(tmp_path, **kwargs):
    """Two layers, one of each kind of everything: full attention with the
    dense MLP, window attention with the routed and the shared experts (a
    step of the five-layer model compiles two and a half times as long)."""
    two = {key: PUBLISHED[key][:2] for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    model = LagunaConfig.from_hf_config(dict(PUBLISHED, num_hidden_layers=2, **two), **PROGRAM)
    return TrainConfig(model=model, batch_size=2, num_steps=2, checkpoint_every=100, handle_signals=False,
                       checkpoint_dir=str(tmp_path / "ckpt"), log_dir=str(tmp_path / "logs"), **kwargs)


def test_two_device_step_gives_the_one_device_steps_loss_and_its_instructions_are_placed(tmp_path):
    """The new entries under `over_data_axis` (the window kernels, the
    partial rotary) on a two-device data mesh give the one-device step; and
    the one-device step's lowered instructions land in the family's rows of
    the ONE table, the two attention kinds apart, in every phase."""
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    batch = {"tokens": np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, 96), np.int32)}
    seen = []
    for devices in (1, 2):
        trainer = Trainer(_tiny_train_config(tmp_path / str(devices), mesh_shape=(devices, 1), seed=3), sample_shape=(SEQ,))
        step = trainer.train_step
        if devices == 1:  # compiled once, ahead of time: the text that is read is the program that runs
            step = step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).compile()
            text = step.as_text()
        _, metrics = step(trainer.state, trainer.sharding.place_batch(batch))
        seen.append(tuple(float(metrics[k]) for k in ("live_loss", "grad_norm", "moe_held_rows", "attn_gate_mean")))
    assert all(abs(a - b) < 1e-4 * abs(a) for a, b in zip(*seen)), seen
    placed = {}
    for op_name, opcode in scopes.instruction_scopes(text).values():
        component, phase = scopes.component(op_name, opcode)
        placed.setdefault(component, set()).add(phase)
        assert component != "other" or "/layers_" not in op_name, op_name  # a layer leaves nothing unplaced
    family = {"embed", "attention_full", "attention_window", "router", "experts", "shared_expert", "mlp", "lm_head",
              "loss", "optimizer"}
    assert family <= set(placed)
    for component in ("attention_full", "attention_window", "experts", "shared_expert", "mlp"):
        assert {"forward", "backward", "recompute"} <= placed[component], component
    assert not set(placed) & {"encoder", "lookup", "gru08", "attention", "ssm_scan"}


@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/embed/take", ("embed", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_0/attention_full/attention_norm/mul", ("attention_full", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_0/attention_full/qk_norm_rope/pallas_call", ("attention_full", "forward")),
    ("jit(step_fn)/transpose(jvp(Laguna.loss))/Laguna.hidden/checkpoint/layers_4/attention_full/block_attention/pallas_call", ("attention_full", "backward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_1/attention_window/window_attention/pallas_call", ("attention_window", "forward")),
    ("jit(step_fn)/transpose(jvp(Laguna.loss))/Laguna.hidden/checkpoint/rematted_computation/layers_2/attention_window/logistic", ("attention_window", "recompute")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_0/mlp_norm/mul", ("mlp", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_0/mlp/dot_general", ("mlp", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_1/post_attention_norm/mul", ("router", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_1/router/top_k", ("router", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_1/experts/while/body/checkpoint/grouped_matmul/pallas_call", ("experts", "forward")),
    ("jit(step_fn)/transpose(jvp(Laguna.loss))/Laguna.hidden/checkpoint/layers_3/shared_expert/dot_general", ("shared_expert", "backward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/layers_1/shared_expert/add", ("shared_expert", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/Laguna.hidden/norm/mul", ("lm_head", "forward")),
    ("jit(step_fn)/jvp(Laguna.loss)/lm_head/while/body/checkpoint/next_token_loss/reduce_max", ("loss", "forward")),
])
def test_the_one_table_places_the_familys_scopes(path, want):
    from raft_stereo_tpu.obs import scopes

    assert scopes.component(path, "fusion") == want


def test_the_new_rows_take_no_path_of_the_stereo_the_sdar_or_the_hybrid_tables():
    """Every path the stereo table's, the `sdar-moe` table's and the hybrid
    table's tests hold still lands where it did; none of them lands in a row
    this family added."""
    import test_granite_hybrid
    import test_scopes
    import test_sdar_moe
    from raft_stereo_tpu.obs import scopes

    new = {"attention_full", "attention_window", "shared_expert"}
    held = [mark.args[1] for mark in test_scopes.test_component_table.pytestmark if mark.name == "parametrize"][0]
    held = [(path, opcode, want) for path, opcode, want in held]
    for test in (test_sdar_moe.test_the_one_table_places_the_familys_scopes,
                 test_granite_hybrid.test_the_one_table_places_the_hybrid_familys_scopes):
        held += [(path, "fusion", want) for mark in test.pytestmark if mark.name == "parametrize"
                 for path, want in mark.args[1]]
    assert len(held) > 55
    for path, opcode, want in held:
        assert scopes.component(path, opcode) == want and want[0] not in new, path


def test_family_of_gives_the_config_the_hybrids_batch_and_make_loss_its_loss():
    from raft_stereo_tpu.train.families import LagunaFamily, family_of, make_loss

    family = family_of(_config(), (SEQ,))
    assert isinstance(family, LagunaFamily)
    shapes = family.batch_shapes(2)
    assert {k: (v[0], np.dtype(v[1]).name) for k, v in shapes.items()} == {"tokens": ((2, SEQ), "int32")}
    meta = family.audit_meta(TrainConfig(model=_config()))
    assert meta["sample"] == [SEQ] and meta["expert_parallel"] == 4 and len(meta["layer_types"]) == 5
    assert callable(make_loss(TrainConfig(model=_config())))


def test_token_config_picks_the_family_by_model_type(tmp_path):
    """The dispatch alone (the fit through `cli.run_training` is rehearsed by
    tests/benchmark/test_bench_laguna.py, through the cell's driver)."""
    from raft_stereo_tpu import cli
    from raft_stereo_tpu.config import TOKEN_FAMILIES

    assert TOKEN_FAMILIES["laguna"] is LagunaConfig
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, program=dict(PROGRAM, mixed_precision=True))))
    args = cli._train_parser().parse_args(["--token_config", str(path), "--seq_len", str(SEQ)])
    model = cli._token_model_config(args)
    assert model == _config(mixed_precision=True) and model.expert_shard == 1
    unknown = tmp_path / "unknown.json"
    unknown.write_text(json.dumps(dict(PUBLISHED, model_type="no_such_family")))
    with pytest.raises(ValueError, match="laguna"):
        cli._token_model_config(cli._train_parser().parse_args(["--token_config", str(unknown)]))
