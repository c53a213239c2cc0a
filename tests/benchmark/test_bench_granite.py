"""The `granite-hybrid` family's benchmark files: the configuration against
the published config, the plain reference's independence, counts against hand
arithmetic, the traffic and the weight draw, and a CPU rehearsal of the cell's
driver through `run.measure` at a tiny size, sound and with each control or
fault in the program's place.

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
from benchmark import granite_counts, granite_reference, granite_weights, run
from benchmark.weights import flatten

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
CELL = "granite-h-micro-train-causal-8k"
CONFIG = run.load_json(os.path.join(BENCH_DIR, "configs", "granite-4.0-h-micro-pp4-stage.json"))
SPEC = run.load_json(os.path.join(BENCH_DIR, "workloads", CELL + ".json"))
SEED = 2**31 + 13

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
# the keys that shape the language model (the catalog's `config`).
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": [("attention" if i % 10 == 5 else "mamba") for i in range(40)], "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
# The CPU size: hidden 64, 8 scan heads of 16 with a state of 16 in chunks of
# 8, 4 attention heads of 16 on 2 key-value heads, layers m m a m, 96 rows.
TINY_MODEL = dict(
    PUBLISHED, hidden_size=64, intermediate_size=96, shared_intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
    attention_multiplier=0.0625, num_hidden_layers=4, layer_types=["mamba", "mamba", "attention", "mamba"],
    vocab_size=96)
TINY_PROGRAM = dict(mixed_precision=False, remat_layers=True, attention_tile=8, loss_chunk=16)
TINY_SPEC = dict(seq_len=40, batch=2, batches=2, warm_steps=2, traffic="tiny-causal")


# -- the configuration's file ---------------------------------------------------------


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_holds_the_published_value_or_lists_the_cut(key):
    assert key in CONFIG, f"{key} is not at the top level of the configuration's file"
    if key not in CONFIG["reduced"]:
        assert CONFIG[key] == PUBLISHED[key]
    elif key == "layer_types":
        assert CONFIG[key] == PUBLISHED[key][:10] and "5, 15, 25, 35" in CONFIG["assumed"]["published"][key]
    else:
        assert CONFIG[key] < PUBLISHED[key] and CONFIG["assumed"]["published"][key] == PUBLISHED[key]


def test_the_cut_is_one_period_and_an_eighth_of_the_vocabulary():
    assert sorted(CONFIG["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 10  # one whole period of the pattern
    assert CONFIG["layer_types"].count("attention") == 1 and CONFIG["layer_types"].index("attention") == 5
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert {"published", "deployment", "counts", "time_step", "weights", "keys_without_effect"} <= set(CONFIG["assumed"])
    family = run.load_json(os.path.join(BENCH_DIR, "families", "granite-hybrid.json"))
    assert family["group"] is None and set(family["keys"]) == set(PUBLISHED)
    assert {"mamba_d_state", "mamba_d_head", "mamba_n_heads", "mamba_chunk_size", "num_experts_per_tok"} <= set(family["widths"])


def test_the_cell_is_the_issues():
    assert (SPEC["seq_len"], SPEC["batch"], SPEC["batches"], SPEC["zipf_exponent"]) == (8192, 1, 4, 1.0)
    assert (SPEC["lr"], SPEC["wdecay"], SPEC["num_steps"]) == (2e-4, 1e-5, 200000)
    assert (SPEC["warm_steps"], SPEC["trace_seconds"], SPEC["driver"], SPEC["control"]) == (3, 5, "train_lm", "fp8")
    assert set(SPEC["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "grad_gap", "final_state_gap"}
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 23 and all(name.endswith(".granite") for name in mine)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_samples_per_s")["workloads"]


def test_bench_checks_hold_the_new_files():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench_checks.everything(bench, ROOT, BENCH_DIR)


def test_program_reads_the_file_as_the_reference_does():
    from benchmark.drivers.train_lm import model_config

    model = model_config(CONFIG)
    for key in ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_chunk_size", "rms_norm_eps",
                "embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling"):
        assert getattr(model, key) == CONFIG[key], key
    assert model.layer_types == tuple(CONFIG["layer_types"]) and model.intermediate_size == 8192
    assert model.mixed_precision and model.remat_layers and model.head_dim == 64


def test_the_held_parameters_are_the_issues_hand_arithmetic():
    shapes = dict(flatten(granite_reference.param_shapes(CONFIG)))
    count = lambda prefix: sum(int(np.prod(s)) for k, s in shapes.items() if k.startswith(prefix))
    mixer = 2048 * (4096 + 4352 + 64) + (4352 * 4 + 4352) + 3 * 64 + 4096 + 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    assert mixer == 25_847_232 and mlp == 50_331_648
    assert count("layers_0/") == mixer + mlp + 2 * 2048 == 76_182_976
    assert count("layers_5/") == 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp + 2 * 2048 == 60_821_504
    assert count("embed/") == 12_544 * 2048 == 25_690_112
    assert count("") == 9 * 76_182_976 + 60_821_504 + 25_690_112 + 2048 == 772_160_448
    whole = dict(flatten(granite_reference.param_shapes(dict(PUBLISHED))))
    assert 3.1e9 < sum(int(np.prod(s)) for s in whole.values()) < 3.3e9  # the published 3B


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH_DIR, "granite_reference.py")).read()
    assert "raft_stereo_tpu" not in source.split('"""', 2)[2]
    assert "default_matmul_precision(\"highest\")" in source and "Precision.HIGHEST" in source


# -- counts ----------------------------------------------------------------------------


def test_visible_pairs_are_counted_as_the_dense_masks_have_them():
    config, spec = dict(TINY_MODEL), dict(seq_len=40, batch=3)
    causal = int(np.tril(np.ones((40, 40), bool)).sum())
    assert granite_counts.attention_flops_per_call(config, spec) == 3 * 4 * 16 * 4 * causal
    in_chunk = int(np.tril(np.ones((8, 8), bool)).sum())
    per_head = 2 * 16 * in_chunk + 2 * 8 * 16 * 16  # a chunk's output over visible pairs, its own state
    assert granite_counts.ssd_chunk_flops_per_call(config, spec) == 3 * 5 * 8 * per_head
    assert granite_counts.ssd_chunk_bwd_flops_per_call(config, spec) == 2 * 3 * 5 * 8 * per_head
    with pytest.raises(ValueError):
        granite_counts.ssd_chunk_flops_per_call(config, dict(seq_len=37, batch=1))


def test_train_flops_are_three_forwards_and_match_the_issues_arithmetic():
    forward = granite_counts.forward_flops_per_sample(CONFIG, SPEC)
    assert granite_counts.train_flops_per_sample(CONFIG, SPEC) == 3 * forward
    # the issue: about 50 TFLOP a step with the rebuild, a fourth forward
    assert 48e12 < 4 * forward < 56e12
    head = 8191 * 2 * 2048 * 12544
    per_position_layer = (forward - head) / 10 / 8192
    # MLP 100.7 + a scan layer's projections 51.7 (or attention's 21.0 + 33.6 of visible pairs) + the scan 3.2 MFLOP
    assert 150e6 < per_position_layer < 160e6
    mlp_share = 10 * 8192 * 6 * 2048 * 8192 / forward
    assert 0.6 < mlp_share < 0.7  # the MLP's plain products are the largest single share
    assert granite_counts.attention_bytes_per_call(CONFIG, SPEC) == 8192 * 64 * 80 * 2
    assert granite_counts.attention_flops_per_call(CONFIG, SPEC) == 4 * 64 * 32 * 8192 * 8193 // 2
    scan = granite_counts.ssd_chunk_flops_per_call(CONFIG, SPEC)
    assert scan == 32 * 64 * (2 * 64 * 256 * 257 // 2 + 2 * 256 * 128 * 64)
    read = 8192 * (4096 * 2 + 64 * 4 + 256 * 4 + 128 * 2)
    assert granite_counts.ssd_chunk_bytes_per_call(CONFIG, SPEC) == read + 8192 * 4096 * 4 + 32 * 128 * 4096 * 4
    assert granite_counts.ssd_chunk_bwd_bytes_per_call(CONFIG, SPEC) > granite_counts.ssd_chunk_bytes_per_call(CONFIG, SPEC)


# -- weights -----------------------------------------------------------------------------


def test_weights_are_seeded_and_shaped_as_the_reference_lays_them_out():
    config = dict(TINY_MODEL, program=TINY_PROGRAM)
    a, b = granite_weights.draw(config, SEED)["params"], granite_weights.draw(config, SEED)["params"]
    c = granite_weights.draw(config, SEED + 1)["params"]
    flat = {k: np.asarray(v) for k, v in flatten(a)}
    assert {k: v.shape for k, v in flat.items()} == dict(flatten(granite_reference.param_shapes(config)))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(flat["embed/embedding"], np.asarray(dict(flatten(c))["embed/embedding"]))
    assert 0.8 <= flat["norm/weight"].min() and flat["layers_0/mixer/gate_norm/weight"].max() <= 1.2
    assert abs(flat["embed/embedding"].std() * 12 - 1.0) < 0.05
    a_head = -np.exp(flat["layers_0/mixer/a_log"])
    assert (-16 <= a_head).all() and (a_head <= -1).all() and (flat["layers_0/mixer/d"] == 1).all()
    step = np.log1p(np.exp(flat["layers_0/mixer/dt_bias"]))  # softplus
    assert (0.99e-3 <= step).all() and (step <= 1.01e-1).all()
    assert np.abs(flat["layers_0/mixer/conv_w"]).max() <= 0.5 and flat["layers_0/mixer/conv_b"].std() > 0
    assert abs(flat["layers_0/mlp/w_in"].std() * np.sqrt(64) - 1.0) < 0.1
    assert abs(flat["layers_2/attention/w_o"].std() * np.sqrt(64) - 1.0) < 0.1


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
    config = dict(CONFIG, name="tiny-granite", program=TINY_PROGRAM, **{k: TINY_MODEL[k] for k in PUBLISHED})
    config_file = os.path.join(data_dir, "tiny-granite.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    bench["configs"].append(dict(entry, name="tiny-granite", file=config_file))
    spec = dict(SPEC, config="tiny-granite", **TINY_SPEC)
    with open(os.path.join(data_dir, "workloads", "tiny-causal.json"), "w") as f:
        json.dump(spec, f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append(dict(cell, name="tiny-causal", config="tiny-granite", traffic="tiny-causal"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-causal")
    return bench, data_dir, config, spec


def test_rehearsal_is_correct_on_every_number(throwaway):
    bench, data_dir, _, _ = throwaway
    result = run.measure(bench, "tiny-causal", SEED, 0.5, False, jax.devices()[:1],
                         data_dir=data_dir, t0=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "grad_gap", "final_state_gap"}
    # float32 on both sides
    assert line["compared"]["loss_gap"]["value"] < 1e-4 and line["compared"]["grad_gap"]["value"] < 1e-3
    assert line["compared"]["final_state_gap"]["value"] < 1e-4


@pytest.fixture(scope="module")
def tiny_run(throwaway):
    from benchmark.drivers import train_lm

    _, _, config, spec = throwaway
    return train_lm.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False))


@pytest.mark.parametrize("fault,number", [(None, "grad_gap"), ("chunk_reset", "final_state_gap"),
                                          ("bidirectional_attention", "grad_gap")])
def test_a_control_in_the_programs_place_moves_its_number(tiny_run, fault, number):
    got = tiny_run.control(fault)
    assert np.isfinite(list(got.values())).all()
    assert got[number] > 1e-2, got  # the sound program reads under 1e-3 here
    with pytest.raises(ValueError):
        tiny_run.control("no_such_fault")


def test_window_counts_the_kernels_calls_as_the_program_is_built(throwaway):
    from benchmark.drivers import train_lm

    _, _, config, spec = throwaway
    one = train_lm.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False))
    one.setup()
    window = one.window(0.2)
    one._free()
    steps = window["attempted"]
    # per-layer remat: two forwards of each kernel, one backward; 3 scan layers, 1 attention layer
    assert window["ssd_chunk_calls"] == 2 * 3 * steps and window["ssd_chunk_bwd_calls"] == 3 * steps
    assert window["attention_forward_calls"] == 2 * steps and window["attention_backward_calls"] == steps
    assert window["ssm_final_state_rms"] > 0 and window["work"] == steps * spec["batch"]
    assert [set(b) for b in one.batches] == [{"tokens"}] * 2 and one.batches[0]["tokens"].shape == (2, 40)
