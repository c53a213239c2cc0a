"""The `sdar-moe` family's benchmark files: the configuration against the
published config, the plain reference's independence, counts against a
brute-force count, the traffic and the weight draw, and a CPU rehearsal of the
cell's driver through `run.measure` at a tiny size, sound and with each
control or fault in the program's place.

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

from bench_fixtures import compile_cache  # noqa: F401  (a fixture)
from benchmark import run, sdar_counts, sdar_reference, sdar_traffic, sdar_weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
CONFIG = run.load_json(os.path.join(BENCH_DIR, "configs", "sdar-30b-a3b-ep8-shard.json"))
SPEC = run.load_json(os.path.join(BENCH_DIR, "workloads", "sdar-a3b-train-blockdiff-4k.json"))
CELL = "sdar-a3b-train-blockdiff-4k"
SEED = 2**31 + 11

# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json, the
# keys that shape the language model (the catalog's `config`).
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
# The CPU size: hidden 64, 4 heads of 16 on 2 key-value heads, 8 experts of
# 32 with 2 a token (4 held here, shard 1 of 2), 2 layers, 96 rows.
TINY_MODEL = dict(
    PUBLISHED, head_dim=16, hidden_size=64, moe_intermediate_size=32, num_attention_heads=4, num_experts=4,
    num_experts_per_tok=2, num_hidden_layers=2, num_key_value_heads=2, vocab_size=96)
TINY_PROGRAM = dict(
    expert_parallel=2, expert_shard=1, block_length=4, mask_token_id=95, mixed_precision=False,
    moe_chunk=64, moe_tile_rows=8, attention_tile=16, loss_chunk=32)
TINY_SPEC = dict(seq_len=32, batch=2, batches=2, warm_steps=2, traffic="tiny-blockdiff")


# -- the configuration's file ---------------------------------------------------------


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_holds_the_published_value_or_lists_the_cut(key):
    assert key in CONFIG, f"{key} is not at the top level of the configuration's file"
    if key in CONFIG["reduced"]:
        assert CONFIG[key] < PUBLISHED[key] and CONFIG["assumed"]["published"][key] == PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_is_the_chips_share_and_keeps_the_floors():
    program = CONFIG["program"]
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert CONFIG["num_experts"] * program["expert_parallel"] == PUBLISHED["num_experts"]
    assert CONFIG["vocab_size"] * program["expert_parallel"] == PUBLISHED["vocab_size"]  # the same 8 chips
    assert CONFIG["num_experts"] >= 8 and 4 <= CONFIG["num_hidden_layers"] <= 6
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert program["mask_token_id"] == CONFIG["vocab_size"] - 1
    assert {"published", "deployment", "block_length", "noise_schedule", "weights"} <= set(CONFIG["assumed"])


def test_the_cell_is_the_issues():
    assert (SPEC["seq_len"], SPEC["batch"], SPEC["batches"], SPEC["zipf_exponent"]) == (4096, 4, 4, 1.0)
    assert (SPEC["lr"], SPEC["wdecay"], SPEC["num_steps"]) == (2e-4, 1e-5, 200000)
    assert (SPEC["warm_steps"], SPEC["trace_seconds"], SPEC["t_min"]) == (3, 5, 0.001)
    assert set(SPEC["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "held_rows_gap", "grad_gap"}


def test_program_reads_the_file_as_the_reference_does():
    from benchmark.drivers.train_tokens import model_config

    model = model_config(CONFIG)
    for key in ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob"):
        assert getattr(model, key) == CONFIG[key]
    assert model.router_width == PUBLISHED["num_experts"] and model.rope_theta == 1e6
    assert model.mixed_precision and model.block_length == 4 and model.mask_token_id == 18991
    shapes = dict(sdar_weights.flatten(sdar_reference.param_shapes(CONFIG)))
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert 4.5e8 < params < 6.5e8  # the issue's arithmetic: 456M / 551M / 646M at 4 / 5 / 6 layers


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH_DIR, "sdar_reference.py")).read()
    assert "raft_stereo_tpu" not in source.split('"""', 2)[2]
    assert "default_matmul_precision(\"highest\")" in source and "Precision.HIGHEST" in source


# -- counts ----------------------------------------------------------------------------


@pytest.mark.parametrize("seq_len,block", [(32, 4), (32, 16), (64, 8)])
def test_visible_pairs_are_counted_as_the_dense_mask_has_them(seq_len, block):
    config = dict(TINY_MODEL, program=dict(TINY_PROGRAM, block_length=block))
    spec = dict(seq_len=seq_len, batch=3)
    pairs = int(np.asarray(sdar_reference.block_mask(seq_len, block)).sum())
    assert pairs == seq_len * block + seq_len * seq_len
    hd, hq = config["head_dim"], config["num_attention_heads"]
    assert sdar_counts.attention_flops_per_call(config, spec) == 3 * 4 * hd * hq * pairs


def test_train_flops_are_three_forwards_and_match_the_issues_arithmetic():
    forward = sdar_counts.forward_flops_per_sample(CONFIG, SPEC)
    assert sdar_counts.train_flops_per_sample(CONFIG, SPEC) == 3 * forward
    layers, positions = CONFIG["num_hidden_layers"], 2 * SPEC["seq_len"]
    head = SPEC["seq_len"] * 2 * 2048 * 18992
    per_position_layer = (forward - head) / layers / positions
    # projections 37.7 + visible attention 33.6 + experts 9.4 + router 0.5 MFLOP
    assert 80e6 < per_position_layer < 83e6
    assert sdar_counts.grouped_matmul_flops_per_call(CONFIG, SPEC) == 4 * 2 * 3 * 2048 * 768 * 8192
    assert sdar_counts.grouped_matmul_bytes_per_call(CONFIG, SPEC) > 16 * 3 * 2048 * 768 * 2
    assert sdar_counts.attention_bytes_per_call(CONFIG, SPEC) == 4 * 8192 * 128 * 72 * 2


# -- traffic and weights ---------------------------------------------------------------


def test_traffic_is_seeded_zipf_over_the_data_rows():
    a = sdar_traffic.token_batches(SEED, 2, 4, 256, 4, 95)
    b = sdar_traffic.token_batches(SEED, 2, 4, 256, 4, 95)
    c = sdar_traffic.token_batches(SEED + 1, 2, 4, 256, 4, 95)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    tokens = np.concatenate([x["tokens"].ravel() for x in a])
    assert tokens.dtype == np.int32 and tokens.min() >= 0 and tokens.max() < 95  # never the mask row
    counts = np.sort(np.bincount(tokens, minlength=95))[::-1]
    assert counts[0] > 4 * counts[20]  # uneven, as text is
    for batch in a:
        assert batch["masked"].dtype == bool and batch["noise_t"].shape == (4, 64)
        assert (batch["noise_t"] >= 0.001).all() and (batch["noise_t"] <= 1).all()
    heavy = np.concatenate([np.repeat(x["noise_t"], 4, axis=1).ravel() > 0.5 for x in a])
    masked = np.concatenate([x["masked"].ravel() for x in a])
    assert masked[heavy].mean() > masked[~heavy].mean() + 0.3


def test_weights_are_seeded_and_shaped_as_the_reference_lays_them_out():
    config = dict(TINY_MODEL, program=TINY_PROGRAM)
    a, b = sdar_weights.draw(config, SEED)["params"], sdar_weights.draw(config, SEED)["params"]
    c = sdar_weights.draw(config, SEED + 1)["params"]
    flat = dict(sdar_weights.flatten(a))
    assert {k: v.shape for k, v in flat.items()} == dict(sdar_weights.flatten(sdar_reference.param_shapes(config)))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(flat["lm_head/w_head"], dict(sdar_weights.flatten(c))["lm_head/w_head"])
    assert 0.8 <= float(flat["norm/weight"].min()) and float(flat["norm/weight"].max()) <= 1.2
    # the first two layers' q norms are scaled by 6, the later ones not
    deep = dict(sdar_weights.flatten(sdar_weights.draw(dict(config, num_hidden_layers=4), SEED)["params"]))
    q_norm, embedding = np.asarray(deep["layers/attention/q_norm/weight"]), flat["embed/embedding"]
    assert 4.8 <= float(q_norm[:2].min()) and float(q_norm[:2].max()) <= 7.2
    assert 0.8 <= float(q_norm[2:].min()) and float(q_norm[2:].max()) <= 1.2
    assert abs(float(embedding.std()) - 0.3) < 0.03
    router, w_q = flat["layers/router/w_router"], flat["layers/attention/w_q"]
    assert abs(float(router.std()) * np.sqrt(64) - 2.0) < 0.3 and abs(float(w_q.std()) * np.sqrt(64) - 1.0) < 0.1


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
    config = dict(CONFIG, name="tiny-sdar", program=TINY_PROGRAM, **{k: TINY_MODEL[k] for k in PUBLISHED})
    config_file = os.path.join(data_dir, "tiny-sdar.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    bench["configs"].append(dict(entry, name="tiny-sdar", file=config_file))
    spec = dict(SPEC, config="tiny-sdar", **TINY_SPEC)
    with open(os.path.join(data_dir, "workloads", "tiny-blockdiff.json"), "w") as f:
        json.dump(spec, f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append(dict(cell, name="tiny-blockdiff", config="tiny-sdar", traffic="tiny-blockdiff"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-blockdiff")
    return bench, data_dir, config, spec


def test_rehearsal_is_correct_and_drops_no_row(throwaway):
    bench, data_dir, _, _ = throwaway
    result = run.measure(bench, "tiny-blockdiff", SEED, 0.5, False, jax.devices()[:1],
                         data_dir=data_dir, t0=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "held_rows_gap", "grad_gap"}
    # float32 on both sides: the same routing, so the same rows to the row
    assert line["compared"]["held_rows_gap"]["value"] == 0.0
    assert line["compared"]["loss_gap"]["value"] < 1e-4 and line["compared"]["grad_gap"]["value"] < 1e-3


@pytest.fixture(scope="module")
def tiny_run(throwaway):
    from benchmark.drivers import train_tokens

    _, _, config, spec = throwaway
    return train_tokens.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False))


@pytest.mark.parametrize("fault,number", [(None, "loss_gap"), ("causal_mask", "loss_gap"), ("capacity", "held_rows_gap"),
                                          (None, "grad_gap"), ("causal_mask", "grad_gap"), ("capacity", "grad_gap")])
def test_a_control_in_the_programs_place_moves_its_number(tiny_run, fault, number):
    got = tiny_run.control(fault)
    assert np.isfinite(list(got.values())).all()
    assert got[number] > 1e-3, got  # the sound program reads under 1e-4 here


def test_window_counts_the_kernels_calls_as_the_program_is_built(throwaway):
    from benchmark.drivers import train_tokens

    bench, data_dir, config, spec = throwaway
    one = train_tokens.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False))
    one.setup()
    window = one.window(0.2)
    one._free()
    steps, layers = window["attempted"], config["num_hidden_layers"]
    # per-layer remat: two forwards of attention; 128 positions in chunks of 64
    # rebuild the expert products once more; one backward of each
    assert window["attention_forward_calls"] == 2 * steps * layers
    assert window["attention_backward_calls"] == window["grouped_matmul_drhs_calls"] == steps * layers
    assert window["grouped_matmul_calls"] == 4 * steps * layers
    assert window["moe_held_rows_per_step"] > 0 and window["moe_max_over_mean_load"] >= 1.0
    assert window["work"] == steps * spec["batch"]
