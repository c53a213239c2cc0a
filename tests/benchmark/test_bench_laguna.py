"""The `laguna-moe` family's benchmark files: the configuration against the
published config, the plain reference's independence, counts against hand
arithmetic, the weight draw, and a CPU rehearsal of the cell's driver through
`run.measure` at a tiny size, sound and with each control or fault in the
program's place.

No number a rehearsal gives is written anywhere under a device metric's name.
"""

import copy
import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

import bench_checks
from bench_fixtures import compile_cache  # noqa: F401  (a fixture)
from benchmark import laguna_counts, laguna_reference, laguna_weights, run
from benchmark.weights import flatten

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
CELL = "laguna-xs2-train-causal-16k"
CONFIG = run.load_json(os.path.join(BENCH_DIR, "configs", "laguna-xs.2-ep8-shard.json"))
SPEC = run.load_json(os.path.join(BENCH_DIR, "workloads", CELL + ".json"))
SEED = 2**31 + 17

# https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json, the keys
# that shape the language model (the catalog's `config`).
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
            "beta_slow": 1, "beta_fast": 64, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [("full_attention" if i % 4 == 0 else "sliding_attention") for i in range(40)],
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [(48 if i % 4 == 0 else 64) for i in range(40)],
}
LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
# The CPU size: hidden 64, heads of 16 on 2 key-value heads, 5 layers F S S S F
# with 6 and 8 query heads, window 8, 8 experts of 32 of which 2 held, 2 a
# token, dense first, 96 rows; YaRN over 16 original positions.
TINY_ROPE = copy.deepcopy(PUBLISHED["rope_parameters"])
TINY_ROPE["full_attention"]["original_max_position_embeddings"] = 16
TINY_MODEL = dict(
    PUBLISHED, hidden_size=64, intermediate_size=96, head_dim=16, num_key_value_heads=2, num_attention_heads=6,
    num_hidden_layers=5, layer_types=PUBLISHED["layer_types"][:5], mlp_layer_types=PUBLISHED["mlp_layer_types"][:5],
    num_attention_heads_per_layer=[6, 8, 8, 8, 6], sliding_window=8, num_experts=2, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, vocab_size=96, rope_parameters=TINY_ROPE)
TINY_PROGRAM = dict(expert_parallel=4, expert_shard=1, mixed_precision=False, remat_layers=True, moe_chunk=16,
                    moe_tile_rows=8, attention_tile=8, loss_chunk=16)
TINY_SPEC = dict(seq_len=32, batch=1, batches=2, warm_steps=2, traffic="tiny-laguna")


# -- the configuration's file ---------------------------------------------------------


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_holds_the_published_value_or_lists_the_cut(key):
    assert key in CONFIG, f"{key} is not at the top level of the configuration's file"
    if key not in CONFIG["reduced"]:
        assert CONFIG[key] == PUBLISHED[key]
    elif key in LISTS:
        assert CONFIG[key] == PUBLISHED[key][:5] and "40 entries" in CONFIG["assumed"]["published"][key]
    else:
        assert CONFIG[key] < PUBLISHED[key] and CONFIG["assumed"]["published"][key] == PUBLISHED[key]


def test_the_cut_is_the_dense_layer_a_period_an_eighth_of_experts_and_vocabulary():
    assert sorted(CONFIG["reduced"]) == sorted(LISTS + ("num_hidden_layers", "num_experts", "vocab_size"))
    assert CONFIG["num_hidden_layers"] == 5 == len(CONFIG["layer_types"])
    assert CONFIG["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert CONFIG["num_experts"] * CONFIG["program"]["expert_parallel"] == PUBLISHED["num_experts"]
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"] and CONFIG["program"]["expert_shard"] == 0
    assumed = set(CONFIG["assumed"])
    assert {"published", "deployment", "counts", "gating", "qk_norm", "router", "shared_expert", "rotary", "window",
            "weights", "keys_without_effect"} <= assumed
    family = run.load_json(os.path.join(BENCH_DIR, "families", "laguna-moe.json"))
    assert family["group"] is None and set(family["keys"]) == set(PUBLISHED)
    assert {"head_dim", "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok",
            "sliding_window", "num_key_value_heads"} <= set(family["widths"])
    assert not any(bench_checks.reads_like_a_width(key) for key in CONFIG["reduced"])


def test_the_cell_is_the_issues():
    assert (SPEC["seq_len"], SPEC["batch"], SPEC["batches"], SPEC["zipf_exponent"]) == (16384, 1, 4, 1.0)
    assert (SPEC["lr"], SPEC["wdecay"], SPEC["num_steps"]) == (2e-4, 1e-5, 200000)
    assert (SPEC["warm_steps"], SPEC["trace_seconds"], SPEC["driver"], SPEC["control"]) == (3, 5, "train_laguna", "fp8")
    assert set(SPEC["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "held_rows_gap", "grad_gap"}
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 31 and all(name.endswith(".laguna") for name in mine)
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_samples_per_s")["workloads"]


def test_bench_checks_hold_the_new_files():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench_checks.everything(bench, ROOT, BENCH_DIR)


def test_program_reads_the_file_as_the_reference_does():
    from benchmark.drivers.train_laguna import model_config

    model = model_config(CONFIG)
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_key_value_heads",
                "head_dim", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "sliding_window", "rms_norm_eps", "moe_routed_scaling_factor"):
        assert getattr(model, key) == CONFIG[key], key
    for key in LISTS:
        assert getattr(model, key) == tuple(CONFIG[key]), key
    assert model.rope("full_attention") == CONFIG["rope_parameters"]["full_attention"]
    assert model.rope("sliding_attention") == CONFIG["rope_parameters"]["sliding_attention"]
    assert model.router_width == 256 and model.expert_shard == 0 and model.mixed_precision and model.remat_layers


def test_the_held_parameters_are_the_issues_hand_arithmetic():
    shapes = dict(flatten(laguna_reference.param_shapes(CONFIG)))
    count = lambda prefix: sum(int(np.prod(s)) for k, s in shapes.items() if k.startswith(prefix))
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 + 2 * 128
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 + 2 * 128
    sparse = 2048 * 256 + 3 * 2048 * 512 + 32 * 3 * 2048 * 512
    assert (full, sliding) == (29_458_688, 37_880_064)
    assert count("layers_0/") == full + 3 * 2048 * 8192 + 2 * 2048 == 79_794_432
    assert count("layers_1/") == count("layers_3/") == sliding + sparse + 2 * 2048 == 142_217_472
    assert count("layers_4/") == full + sparse + 2 * 2048 == 133_796_096
    assert count("embed/") == count("lm_head/") == 12_544 * 2048 == 25_690_112
    assert count("") == 691_625_216
    whole = dict(PUBLISHED, program=dict(CONFIG["program"], expert_parallel=1))
    published = sum(int(np.prod(s)) for _, s in flatten(laguna_reference.param_shapes(whole)))
    assert 33.3e9 < published < 33.5e9  # the published 33.4B


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH_DIR, "laguna_reference.py")).read()
    assert "raft_stereo_tpu" not in source.split('"""', 2)[2]
    assert "default_matmul_precision(\"highest\")" in source and "Precision.HIGHEST" in source
    assert all(f"ASSUMED ({letter})" in source for letter in "abcd")


# -- counts ----------------------------------------------------------------------------


def test_visible_pairs_are_counted_as_the_dense_masks_have_them():
    config, spec = dict(TINY_MODEL, program=TINY_PROGRAM), dict(seq_len=40, batch=3)
    pos = np.arange(40)
    causal = pos[None, :] <= pos[:, None]
    window = causal & (pos[None, :] > pos[:, None] - 8)
    assert laguna_counts.visible_pairs(config, spec) == {
        "full_attention": int(causal.sum()), "sliding_attention": int(window.sum())}
    assert laguna_counts.full_attention_flops_per_call(config, spec) == 3 * 4 * 16 * 6 * int(causal.sum())
    assert laguna_counts.window_attention_flops_per_call(config, spec) == 3 * 4 * 16 * 8 * int(window.sum())
    # a window wider than the row is the causal mask
    assert laguna_counts.visible_pairs(dict(config, sliding_window=64), spec)["sliding_attention"] == int(causal.sum())
    assert laguna_counts.visible_pairs(CONFIG, SPEC) == {"full_attention": 134_225_920, "sliding_attention": 8_257_792}


def test_train_flops_are_three_forwards_and_match_the_issues_arithmetic():
    forward = laguna_counts.forward_flops_per_sample(CONFIG, SPEC)
    assert laguna_counts.train_flops_per_sample(CONFIG, SPEC) == 3 * forward
    full = 2 * 134_225_920 * 4 * 128 * 48
    window = 3 * 8_257_792 * 4 * 128 * 64
    assert abs(full - 6.60e12) < 0.01e12 and abs(window - 0.81e12) < 0.01e12
    plain = forward - full - window
    # 275.9M parameters a position, the head at the 16,383 positions that predict
    assert abs(plain - 2 * 16384 * 275.9e6) < 0.01e12 and abs(forward - 16.45e12) < 0.01e12
    assert laguna_counts.full_attention_flops_per_call(CONFIG, SPEC) == full / 2
    assert laguna_counts.window_attention_flops_per_call(CONFIG, SPEC) == window / 3
    assert laguna_counts.full_attention_bytes_per_call(CONFIG, SPEC) == 16384 * 128 * (2 * 48 + 16) * 2
    assert laguna_counts.window_attention_bytes_per_call(CONFIG, SPEC) == 16384 * 128 * (2 * 64 + 16) * 2
    # each held expert sees 512 rows a step and layer: 16,384 rows over the 32
    assert laguna_counts.grouped_matmul_flops_per_call(CONFIG, SPEC) == 2 * 3 * 2048 * 512 * 16384
    assert laguna_counts.grouped_matmul_bytes_per_call(CONFIG, SPEC) == (32 * 3 * 2048 * 512 + 16384 * (4096 + 1536)) * 2
    operands = 16384 * 128 * ((2 * 48 + 3 * 64) / 5 + 8) * 2
    tables = 4 * 16384 * 128 * 4
    assert laguna_counts.qk_norm_rope_bytes_per_call(CONFIG, SPEC) == 2 * operands + tables
    assert laguna_counts.qk_norm_rope_bwd_bytes_per_call(CONFIG, SPEC) == 3 * operands + tables


# -- weights -----------------------------------------------------------------------------


def test_weights_are_seeded_and_shaped_as_the_reference_lays_them_out():
    three = {key: TINY_MODEL[key][:3] for key in LISTS}  # F S S: a peaked full, a peaked window, a plain window layer
    config = dict(TINY_MODEL, num_hidden_layers=3, program=TINY_PROGRAM, **three)
    a, b = laguna_weights.draw(config, SEED)["params"], laguna_weights.draw(config, SEED)["params"]
    c = laguna_weights.draw(config, SEED + 1)["params"]
    flat = {k: np.asarray(v) for k, v in flatten(a)}
    assert {k: v.shape for k, v in flat.items()} == dict(flatten(laguna_reference.param_shapes(config)))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(flat["embed/embedding"], np.asarray(dict(flatten(c))["embed/embedding"]))
    assert abs(flat["embed/embedding"].std() - laguna_weights.EMBEDDING_SCALE) < 0.02
    assert 0.8 <= flat["norm/weight"].min() and flat["layers_2/attention_window/attention_norm/weight"].max() <= 1.2
    # the q norm is peaked in the first two layers only, the k norm nowhere
    for layer, name, scale in ((0, "attention_full", 6.0), (1, "attention_window", 6.0), (2, "attention_window", 1.0)):
        q_norm = flat[f"layers_{layer}/{name}/q_norm/weight"]
        assert 0.8 * scale <= q_norm.min() and q_norm.max() <= 1.2 * scale
        assert flat[f"layers_{layer}/{name}/k_norm/weight"].max() <= 1.2
    assert abs(flat["layers_0/mlp/w_in"].std() * np.sqrt(64) - 1.0) < 0.1
    assert abs(flat["layers_1/experts/w_down"].std() * np.sqrt(32) - 1.0) < 0.1
    assert abs(flat["layers_1/router/w_router"].std() * np.sqrt(64) - laguna_weights.ROUTER_GAIN) < 0.15


# -- the driver, rehearsed on the CPU -------------------------------------------------


@pytest.fixture(scope="module")
def throwaway(tmp_path_factory, compile_cache):
    """BENCHMARK.json plus a tiny float32 configuration and cell of the
    family, as data files in a throwaway directory."""
    bench = copy.deepcopy(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    data_dir = str(tmp_path_factory.mktemp("bench_data"))
    for sub in ("layer_metrics", "families"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(data_dir, sub))
    os.makedirs(os.path.join(data_dir, "workloads"))
    config = dict(CONFIG, name="tiny-laguna", program=TINY_PROGRAM, **{k: TINY_MODEL[k] for k in PUBLISHED})
    config_file = os.path.join(data_dir, "tiny-laguna.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    bench["configs"].append(dict(entry, name="tiny-laguna", file=config_file))
    spec = dict(SPEC, config="tiny-laguna", **TINY_SPEC)
    with open(os.path.join(data_dir, "workloads", "tiny-laguna.json"), "w") as f:
        json.dump(spec, f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append(dict(cell, name="tiny-laguna", config="tiny-laguna", traffic="tiny-laguna"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-laguna")
    return bench, data_dir, config, spec


@pytest.fixture(scope="module")
def rehearsed(throwaway):
    """One run of the tiny cell through `run.measure`: (its result line, what
    the driver's `window` returned inside it, its batches)."""
    from benchmark.drivers import train_laguna

    bench, data_dir, _, _ = throwaway
    seen = {}
    window = train_laguna.Run.window

    def recorded(self, seconds):
        seen["window"], seen["batches"] = window(self, seconds), self.batches
        return seen["window"]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(train_laguna.Run, "window", recorded)
        result = run.measure(bench, "tiny-laguna", SEED, 0.3, False, jax.devices()[:1],
                             data_dir=data_dir, t0=time.perf_counter())
    return json.loads(json.dumps(result)), seen["window"], seen["batches"]


def test_rehearsal_is_correct_on_every_number(rehearsed):
    line = rehearsed[0]
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "held_rows_gap", "grad_gap"}
    # float32 on both sides
    assert line["compared"]["loss_gap"]["value"] < 1e-4 and line["compared"]["grad_gap"]["value"] < 1e-3
    assert line["compared"]["held_rows_gap"]["value"] == 0.0


@pytest.fixture(scope="module")
def tiny_run(throwaway):
    """A run with its weights and batches drawn and the sound reference's
    readings taken once, for every control to stand against."""
    from benchmark.drivers import train_laguna

    _, _, config, spec = throwaway
    one = train_laguna.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False))
    one.initial = jax.tree.map(np.asarray, laguna_weights.draw(config, SEED)["params"])
    one.batches = one._batches()
    return one, one.reference_readings()


@pytest.mark.parametrize("fault", [None, "window_off", "gate_off", "rotary_whole_head"])
def test_a_control_in_the_programs_place_moves_its_number(tiny_run, fault):
    one, sound = tiny_run
    stand_in = one.reference_readings("fp8") if fault is None else one.reference_readings(fault=fault)
    got = one._numbers(stand_in, sound)
    assert np.isfinite(list(got.values())).all()
    assert got["grad_gap"] > 1e-2, got  # the sound program reads under 1e-3 here
    with pytest.raises(ValueError):
        one.control("no_such_fault")


def test_window_counts_the_kernels_calls_as_the_compiled_step_holds_them(rehearsed, throwaway):
    _, window, batches = rehearsed
    spec = throwaway[3]
    steps = window["attempted"]
    # per-layer remat: two forwards of a layer's attention and prologue, one backward; 2 full and 3 window layers
    assert window["full_attention_forward_calls"] == 4 * steps and window["full_attention_backward_calls"] == 2 * steps
    assert window["window_attention_forward_calls"] == 6 * steps and window["window_attention_backward_calls"] == 3 * steps
    assert window["qk_norm_rope_calls"] == 10 * steps and window["qk_norm_rope_bwd_calls"] == 5 * steps
    # 32 positions in chunks of 16: forward, the chunk's rebuild, the backward's product; 4 sparse layers
    assert window["grouped_matmul_calls"] == 12 * steps and window["grouped_matmul_drhs_calls"] == 4 * steps
    assert 0.3 < window["attn_gate_mean"] < 0.7 and window["moe_held_rows_per_step"] > 0
    assert window["work"] == steps * spec["batch"]
    assert [set(b) for b in batches] == [{"tokens"}] * 2 and batches[0]["tokens"].shape == (1, 32)
