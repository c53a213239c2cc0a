"""The `granite-hybrid` family at a small size on the CPU (hidden 64, 8 scan
heads of 16 with a state of 16 in chunks of 8, 4 attention heads of 16 on 2
key-value heads, layers `m m a m`, 40 tokens a row so that chunks carry
state): the state-space scan's kernels against its dense form against the
position-by-position recurrence, the causal entry of the attention kernels
against a dense softmax, the model against `benchmark/granite_reference.py`,
the trainer's scopes and entry point for the family.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import walk_checks
from benchmark import granite_reference
from benchmark.weights import flatten
from raft_stereo_tpu.config import GraniteHybridConfig, TrainConfig
from raft_stereo_tpu.models import granite_hybrid
from raft_stereo_tpu.ops import block_attention as ba
from raft_stereo_tpu.ops import ssd_scan as ss

SEQ = 40
PUBLISHED = dict(
    model_type="granitemoehybrid", vocab_size=96, hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
    num_hidden_layers=4, layer_types=["mamba", "mamba", "attention", "mamba"], num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=8, rms_norm_eps=1e-5, embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=8, num_local_experts=0, num_experts_per_tok=0,
    position_embedding_type="nope", tie_word_embeddings=True)
TILES = dict(attention_tile=8, loss_chunk=16, mixed_precision=False)


def _config(**program):
    return GraniteHybridConfig.from_hf_config(PUBLISHED, **dict(TILES, **program))


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) <= tol * scale


# -- the scan ---------------------------------------------------------------------------


def _recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T, y_t = S_t^T C_t + D x_t,
    one position at a time."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state + dt_t[..., None, None] * b_t[:, None, :, None] * x_t[:, :, None, :]
        return state, jnp.einsum("bhnp,bn->bhp", state, c_t) + d[:, None] * x_t

    start = jnp.zeros((x.shape[0], x.shape[2], b.shape[-1], x.shape[3]))
    final, y = jax.lax.scan(step, start, tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), final


def _scan_operands(seq, batch=2, heads=4, p=16, n=16, on_y=True):
    k = jax.random.split(jax.random.PRNGKey(seq), 8)
    x = jax.random.normal(k[0], (batch, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.0))
    b, c = jax.random.normal(k[3], (batch, seq, n)), jax.random.normal(k[4], (batch, seq, n))
    d = jax.random.uniform(k[7], (heads,), minval=0.5, maxval=2.0)
    weights = jax.random.normal(k[5], x.shape) * on_y, jax.random.normal(k[6], (batch, heads, n, p))
    return (x, dt, a, b, c, d), weights


# Chunks of 8 positions. `heads`: heads a grid step asks for; heads of 16
# lanes never fill a lane tile, so every head goes in one step, and 8 heads
# of 32 take two steps of 4.
SCAN_CASES = {
    "whole_chunks": dict(seq=SEQ),
    "ragged_last_chunk": dict(seq=37),
    "shorter_than_a_chunk": dict(seq=5),
    "many_chunks": dict(seq=56, batch=1),  # the state crosses six chunk boundaries, each way
    "two_head_groups": dict(seq=SEQ, heads=8, p=32, per_step=4),
    "two_rows_apart": dict(seq=48, batch=2),  # a state left over from row 0 would show in row 1
    "final_state_only": dict(seq=SEQ, on_y=False),  # the gradient enters through the last state alone
}


@pytest.mark.parametrize("case", list(SCAN_CASES))
@pytest.mark.parametrize("form", ["kernels", "dense"])
def test_ssd_scan_matches_the_recurrence_forward_and_backward(case, form):
    sizes = dict(SCAN_CASES[case])
    per_step = sizes.pop("per_step", 2)
    operands, (w_y, w_s) = _scan_operands(**sizes)
    if case == "two_head_groups":
        assert ss._heads_a_step(8, 32, per_step) == 4
    scan = {"kernels": lambda *o: ss.ssd_scan(*o, chunk=8, heads=per_step),
            "dense": lambda *o: ss.ssd_scan_dense(*o, chunk=8)}[form]

    def value(fn):
        def weighed(*o):
            y, final = fn(*o)
            return jnp.sum(y * w_y) + jnp.sum(final * w_s), (y, final)
        return jax.jit(jax.value_and_grad(weighed, argnums=tuple(range(6)), has_aux=True))(*operands)

    (_, (y, final)), grads = value(scan)
    (_, (want_y, want_final)), want_grads = value(_recurrence)
    assert y.shape == want_y.shape and _close(y, want_y) and _close(final, want_final)
    assert all(_close(g, w) for g, w in zip(grads, want_grads))


def test_ssd_scan_takes_the_heads_that_fill_whole_lane_tiles():
    assert ss._heads_a_step(64, 64, 4) == 4 and ss._heads_a_step(64, 64, 3) == 2 and ss._heads_a_step(64, 64, 64) == 64
    assert ss._heads_a_step(8, 16, 4) == 8  # no multiple of 128 lanes: every head in one step


# -- causal attention through the block kernels ----------------------------------------------


@pytest.mark.parametrize("tile", [16, 64])
def test_causal_attention_matches_a_dense_softmax_at_head_64(tile):
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(k[0], (2, 4, 64, 64))
    kk, v = jax.random.normal(k[1], (2, 2, 64, 64)), jax.random.normal(k[2], (2, 2, 64, 64))
    w = jax.random.normal(k[3], q.shape)
    value = lambda fn: jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))(q, kk, v)
    got, grads = value(lambda q, k, v: ba.causal_attention(q, k, v, 0.2, tile))
    want, want_grads = value(lambda q, k, v: ba.causal_attention_dense(q, k, v, 0.2))
    assert abs(float(got - want)) < 1e-4 * abs(float(want))
    assert all(_close(g, w_) for g, w_ in zip(grads, want_grads))


def test_the_causal_walk_visits_the_visible_tiles_and_no_other():
    walk = ba._CausalWalk(4)
    mask = np.tril(np.ones((4, 4), bool))
    for qt in range(4):
        visited = {int(walk.fwd_key_tile(jnp.int32(qt), jnp.int32(s))) for s in range(int(walk.fwd_steps(jnp.int32(qt))))}
        assert visited == set(np.flatnonzero(mask[qt]))
        assert int(walk.fwd_key_tile(jnp.int32(qt), jnp.int32(3))) in visited  # a dead step copies nothing new
    for kt in range(4):
        visited = {int(walk.bwd_query_tile(jnp.int32(kt), jnp.int32(u))) for u in range(int(walk.bwd_steps(jnp.int32(kt))))}
        assert visited == set(np.flatnonzero(mask[:, kt]))
    q_lim, q_eq, k_code = ba._Mask(8, 0).codes()
    assert np.array_equal(ba._visible(q_lim[:, None], q_eq[:, None], k_code[None, :]), np.tril(np.ones((8, 8), bool)))
    with pytest.raises(ValueError):
        ba.causal_attention(jnp.ones((1, 2, 24, 8)), jnp.ones((1, 1, 24, 8)), jnp.ones((1, 1, 24, 8)), 1.0, 16)


@pytest.mark.parametrize("seq,tile", [(64, 16), (64, 8), (32, 32), (8192, 512)],
                         ids=["four-tiles", "eight-tiles", "one-tile", "the-cell"])
def test_the_causal_walk_calls_interior_the_tiles_under_the_diagonal_and_no_other(seq, tile):
    t, walk = ba._Mask(seq, 0).walk(tile)
    nt = seq // t
    interior, visited = walk_checks.interior_pairs(np.tril(np.ones((seq, seq), bool)), walk, t, every=True)
    assert (interior, visited) == (nt * (nt - 1) // 2, nt * (nt + 1) // 2)
    assert ba.interior_pair_share(seq, tile=tile) == interior / visited


def test_the_cells_interior_pair_share_is_120_of_136():
    assert ba.interior_pair_share(8192) == 120 / 136 == jax.jit(lambda: ba.interior_pair_share(8192, 0, 0, 512))()


@pytest.mark.parametrize("tile,heads", [(16, (4, 2)), (8, (6, 1))], ids=["four-tiles-group2", "eight-tiles-group6"])
def test_causal_attention_without_the_unmasked_body_keeps_every_bit(monkeypatch, tile, heads):
    """The scale is a power of two, as 1 / sqrt(d) is in the block and window
    cases: XLA's CPU code contracts `s * scale - m` into one fused
    multiply-add where no `where` stands between the two, which rounds once;
    an exact product rounds alike both ways. The interpreter's backend, not
    the kernels' arithmetic: the chip is held to any scale (PERF.md, PR 37)."""
    walk_checks.never_interior_keeps_the_bits(
        monkeypatch, ba._CausalWalk, lambda q, k, v: ba.causal_attention(q, k, v, 0.25, tile), heads, 64, 64)


# -- the model against the reference -----------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    config = _config()
    variables = granite_hybrid.init_granite_variables(config, jax.random.PRNGKey(0), SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 96)
    return config, variables["params"], tokens


@pytest.fixture(scope="module")
def reference_gradient(seeded):
    """((loss, final-state rms), gradient) of the sound reference."""
    _, params, tokens = seeded
    return jax.jit(jax.value_and_grad(
        lambda p: granite_reference.loss(PUBLISHED, p, {"tokens": tokens}), has_aux=True))(params)


@pytest.fixture(scope="module")
def program(seeded):
    """The float32 model's two entries, each compiled once a module: logits
    of (params, tokens), and the loss with its metrics and gradient."""
    config, _, _ = seeded
    model = granite_hybrid.GraniteHybrid(config)
    logits = jax.jit(lambda p, tokens: model.apply({"params": p}, tokens))
    graded = jax.jit(jax.value_and_grad(lambda p, tokens: model.apply({"params": p}, tokens, method="loss"), has_aux=True))
    return logits, graded


def test_program_and_reference_lay_the_weights_out_alike(seeded):
    _, params, _ = seeded
    assert {k: v.shape for k, v in flatten(params)} == dict(flatten(granite_reference.param_shapes(PUBLISHED)))


def test_logits_loss_and_every_leafs_gradient_match_the_reference(seeded, reference_gradient, program):
    _, params, tokens = seeded
    logits = program[0](params, tokens)
    want_logits, want_rms = jax.jit(lambda p: granite_reference.forward(PUBLISHED, p, tokens))(params)
    assert logits.shape == (2, SEQ, 96) and _close(logits, want_logits)
    (loss, metrics), grads = program[1](params, tokens)
    (want, rms), want_grads = reference_gradient
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(metrics["ssm_final_state_rms"]) - float(rms)) < 1e-5 * float(rms) and float(rms) > 0
    assert abs(float(rms) - float(want_rms)) < 1e-6 * float(rms)
    # the step carries no constant of shapes; at its shapes, five tiles: the pairs under the diagonal
    assert set(metrics) == {"ssm_final_state_rms"}
    assert ba.interior_pair_share(SEQ, tile=TILES["attention_tile"]) == 10 / 15
    got, wanted = dict(flatten(grads)), dict(flatten(want_grads))
    assert sorted(got) == sorted(wanted) and all(_close(got[k], wanted[k], 5e-5) for k in wanted)


def test_the_loss_reads_position_t_against_id_t_plus_1(seeded, program):
    _, params, tokens = seeded
    logits = program[0](params, tokens)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, jnp.roll(tokens, -1, axis=1)[..., None], axis=-1)[..., 0]
    (loss, _), _ = program[1](params, tokens)
    assert abs(float(loss) - float(jnp.mean(nll[:, :-1]))) < 1e-5
    # causal: a later token changes no earlier position's logits
    later = tokens.at[:, -1].set((tokens[:, -1] + 1) % 96)
    assert _close(program[0](params, later)[:, :-1], logits[:, :-1], 1e-6)


@pytest.mark.parametrize("fault,moves", [("chunk_reset", "state"), ("bidirectional_attention", "gradient")])
def test_each_fault_of_the_reference_moves_its_number(seeded, reference_gradient, fault, moves):
    _, params, tokens = seeded
    (_, sound_rms), sound = reference_gradient
    (_, wrong_rms), wrong = jax.jit(jax.value_and_grad(
        lambda p: granite_reference.loss(PUBLISHED, p, {"tokens": tokens}, fault=fault), has_aux=True))(params)
    if moves == "state":
        assert abs(float(wrong_rms) - float(sound_rms)) > 1e-2 * float(sound_rms)
    else:  # the sound program's gradient stands 5e-5 from the reference's, leaf by leaf
        assert not _close(wrong["layers_2"]["attention"]["w_v"], sound["layers_2"]["attention"]["w_v"], 1e-1)
        assert float(wrong_rms) != float(sound_rms)  # the layer after attention reads another stream


def test_mixed_precision_keeps_the_decays_and_the_state_float32(seeded, reference_gradient):
    _, params, tokens = seeded
    model = granite_hybrid.GraniteHybrid(_config(mixed_precision=True))
    mixed = jax.jit(lambda p: model.apply({"params": p}, tokens, method="loss"))
    text = mixed.lower(params).as_text()
    assert "exponential" in text and "bf16" in text
    for line in text.splitlines():
        if "stablehlo.exponential" in line or "stablehlo.cumsum" in line or "stablehlo.log_plus_one" in line:
            assert "bf16" not in line, line  # softplus, running sums and every decay are float32
    loss, metrics = mixed(params)
    want = reference_gradient[0][0]
    assert abs(float(loss) - float(want)) < 2e-2 * float(want) and metrics["ssm_final_state_rms"].dtype == jnp.float32


# -- the config -----------------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_keys_and_refuses_what_it_does_not_model():
    config = _config()
    assert config.layer_types == ("mamba", "mamba", "attention", "mamba") and config.num_hidden_layers == 4
    assert config.intermediate_size == 96 and config.mamba_d_inner == 128 and config.head_dim == 16
    assert (config.embedding_multiplier, config.residual_multiplier, config.logits_scaling) == (12, 0.22, 8)
    assert hash(config) == hash(_config())  # a jit and lru_cache key
    default = GraniteHybridConfig()
    assert default.num_hidden_layers == 40 and [i for i, k in enumerate(default.layer_types) if k == "attention"] == [5, 15, 25, 35]
    for wrong, error in ((dict(num_local_experts=8), NotImplementedError), (dict(position_embedding_type="rope"), NotImplementedError),
                         (dict(mamba_n_groups=8), NotImplementedError), (dict(layer_types=["mamba", "conv"]), ValueError),
                         (dict(num_hidden_layers=5), ValueError), (dict(mamba_n_heads=4), ValueError)):
        with pytest.raises(error):
            GraniteHybridConfig.from_hf_config(dict(PUBLISHED, **wrong), **TILES)


# -- the trainer's scopes and entry point for the family ---------------------------------------


def _tiny_train_config(tmp_path, **kwargs):
    return TrainConfig(model=_config(), batch_size=2, num_steps=2, checkpoint_every=100, handle_signals=False,
                       checkpoint_dir=str(tmp_path / "ckpt"), log_dir=str(tmp_path / "logs"), **kwargs)


_STEP_METRICS = ("live_loss", "grad_norm", "ssm_final_state_rms")


def _step_batch():
    return {"tokens": np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, 96), np.int32)}


@pytest.fixture(scope="module")
def one_device_step(tmp_path_factory):
    """The tiny family's one-device step, built and compiled once a module
    (ahead of time, so the text read is the program that ran): (its
    optimized text, its metrics on `_step_batch()`)."""
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    trainer = Trainer(_tiny_train_config(tmp_path_factory.mktemp("one_device"), seed=3), sample_shape=(SEQ,))
    step = trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).compile()
    _, metrics = step(trainer.state, trainer.sharding.place_batch(_step_batch()))
    return step.as_text(), tuple(float(metrics[k]) for k in _STEP_METRICS)


def test_a_hybrid_steps_instructions_are_placed(one_device_step):
    """A step's lowered instructions land in the family's rows of the ONE
    table, in every phase the step has, and no other family's row takes one."""
    from raft_stereo_tpu.obs import scopes

    text = one_device_step[0]
    seen = {}
    for op_name, opcode in scopes.instruction_scopes(text).values():
        component, phase = scopes.component(op_name, opcode)
        seen.setdefault(component, set()).add(phase)
        assert component != "other" or "/layers_" not in op_name, op_name  # a layer leaves nothing unplaced
    family = {"embed", "attention", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "mlp", "lm_head", "loss", "optimizer"}
    assert family <= set(seen)
    for component in ("ssm_scan", "ssm_proj", "mlp", "attention"):
        assert {"forward", "backward", "recompute"} <= seen[component], component
    assert not set(seen) & {"encoder", "lookup", "gru08", "router", "experts"}


@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/embed/take", ("embed", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_0/ssm_norm/mul", ("ssm_proj", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_0/mixer/ssm_proj/dot_general", ("ssm_proj", "forward")),
    ("jit(step_fn)/transpose(jvp(GraniteHybrid.loss))/GraniteHybrid.hidden/checkpoint/rematted_computation/layers_3/mixer/ssm_conv/logistic", ("ssm_conv", "recompute")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_0/mixer/ssm_scan/ssd_chunk/pallas_call", ("ssm_scan", "forward")),
    ("jit(step_fn)/transpose(jvp(GraniteHybrid.loss))/GraniteHybrid.hidden/checkpoint/layers_0/mixer/ssm_scan/ssd_chunk_bwd/pallas_call", ("ssm_scan", "backward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_0/mixer/ssm_scan/while/body/mul", ("ssm_scan", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_0/mixer/ssm_gate_norm/gate_norm/rsqrt", ("ssm_gate_norm", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_2/input_norm/mul", ("attention", "forward")),
    ("jit(step_fn)/transpose(jvp(GraniteHybrid.loss))/GraniteHybrid.hidden/checkpoint/layers_2/attention/block_attention/pallas_call", ("attention", "backward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_2/mlp_norm/mul", ("mlp", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/layers_2/mlp/dot_general", ("mlp", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/GraniteHybrid.hidden/norm/mul", ("lm_head", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/lm_head/while/body/checkpoint/dot_general", ("lm_head", "forward")),
    ("jit(step_fn)/jvp(GraniteHybrid.loss)/lm_head/while/body/checkpoint/next_token_loss/reduce_max", ("loss", "forward")),
])
def test_the_one_table_places_the_hybrid_familys_scopes(path, want):
    from raft_stereo_tpu.obs import scopes

    assert scopes.component(path, "fusion") == want


def test_the_new_rows_take_no_path_of_the_stereo_or_the_sdar_tables():
    """Every path the stereo table's and the `sdar-moe` table's tests hold
    still lands where it did (those tests run beside this one); none of them
    lands in a row this family added."""
    import test_scopes
    import test_sdar_moe
    from raft_stereo_tpu.obs import scopes

    new = {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "mlp"}
    held = [mark.args[1] for mark in test_scopes.test_component_table.pytestmark if mark.name == "parametrize"][0]
    held = [(path, opcode, want) for path, opcode, want in held]
    held += [(path, "fusion", want) for mark in test_sdar_moe.test_the_one_table_places_the_familys_scopes.pytestmark
             if mark.name == "parametrize" for path, want in mark.args[1]]
    assert len(held) > 40
    for path, opcode, want in held:
        assert scopes.component(path, opcode) == want and want[0] not in new, path


def test_cmd_train_picks_the_family_by_model_type(tmp_path, monkeypatch):
    """The dispatch, apart from the fit (`test_sdar_moe.py` drives one fit
    through `cmd_train`; this family's goes through `cli.run_training` in
    tests/benchmark/test_bench_granite.py): the file's `model_type` picks the
    config class, `_token_trainer` hands the trainer that model, one sample's
    shape and a loader of ids alone, and an unknown type ends `cmd_train`."""
    from raft_stereo_tpu import cli
    from raft_stereo_tpu.train import trainer as trainer_module
    from raft_stereo_tpu.utils import run_report as rr

    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, program=dict(TILES, mixed_precision=True))))
    args = cli._train_parser().parse_args(["--token_config", str(path), "--seq_len", str(SEQ), "--batch_size", "2"])
    model = cli._token_model_config(args)
    assert model == _config(mixed_precision=True)
    monkeypatch.setattr(trainer_module, "Trainer", lambda config, sample_shape: (config.model, sample_shape))
    (built_for, sample_shape), loader = cli._token_trainer(args, TrainConfig(model=model, batch_size=2))
    assert built_for is model and sample_shape == (SEQ,)
    first = next(iter(loader))
    assert set(first) == {"tokens"} and first["tokens"].shape == (2, SEQ)
    monkeypatch.chdir(tmp_path)
    unknown = tmp_path / "unknown.json"
    unknown.write_text(json.dumps(dict(PUBLISHED, model_type="no_such_family")))
    assert cli.cmd_train(["--token_config", str(unknown), "--num_steps", "1"]) == rr.EXIT_ERROR


def test_token_batches_without_noise_and_the_familys_batch():
    from raft_stereo_tpu.data.tokens import TokenBatches
    from raft_stereo_tpu.train.families import family_of

    first, again = next(iter(TokenBatches(2, 37, 0, 96, seed=5))), next(iter(TokenBatches(2, 37, 0, 96, seed=5)))
    assert set(first) == {"tokens"} and first["tokens"].shape == (2, 37) and first["tokens"].dtype == np.int32
    assert np.array_equal(first["tokens"], again["tokens"]) and first["tokens"].max() < 96
    shapes = family_of(_config(), (SEQ,)).batch_shapes(2)
    assert {k: (v[0], np.dtype(v[1]).name) for k, v in shapes.items()} == {"tokens": ((2, SEQ), "int32")}


def test_two_device_step_gives_the_one_device_steps_loss(tmp_path, one_device_step):
    from raft_stereo_tpu.train.trainer import Trainer

    trainer = Trainer(_tiny_train_config(tmp_path, mesh_shape=(2, 1), seed=3), sample_shape=(SEQ,))
    _, metrics = trainer.train_step(trainer.state, trainer.sharding.place_batch(_step_batch()))
    seen = [one_device_step[1], tuple(float(metrics[k]) for k in _STEP_METRICS)]
    assert all(abs(a - b) < 1e-4 * abs(a) for a, b in zip(*seen)), seen
