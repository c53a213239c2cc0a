"""A configuration of another model family enters the benchmark as new files
only. The family under fixtures/toy_family/ shares no key with raft-stereo: a
family file, a configuration (one width, a depth listed in `reduced`, its keys
at the top level as a published config.json has them), a workload file, a
driver, a counts module, a reference, a weight draw, a share of the whole
step's peak and a kernel's roofline. It is laid over a copy of the
benchmark's data in a throwaway tree, goes through every check of
bench_checks.py and through `run.measure`, and no file that was there is
opened for writing. Its bad variants are refused.

Also here: the two readers that were rewritten with the family door
(`mfu`, `trace_kernel`) against PR 28's code, kept below as it was, on one
context: equal floats."""

import copy
import hashlib
import importlib
import json
import os
import re
import shutil
import sys
import time

import jax
import pytest

import benchmark
import benchmark.drivers
import bench_checks as checks
from bench_fixtures import compile_cache  # noqa: F401  (a fixture)
from benchmark import counts, run
from benchmark import trace_reduce as tr
from benchmark.peaks import peaks
from benchmark.readers import mfu, trace_kernel

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
TOY = os.path.join(HERE, "fixtures", "toy_family")
DATA = ("families", "configs", "workloads", "layer_metrics")
SEED = 2**31 + 7
V5E = "TPU v5 lite"


def _files(top):
    """{path relative to `top`: sha256} of every file under it."""
    out = {}
    for folder, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            path = os.path.join(folder, name)
            out[os.path.relpath(path, top)] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


@pytest.fixture
def overlay(tmp_path, monkeypatch, compile_cache):
    """(bench, root, data_dir): the benchmark's data copied as it is, the toy
    family's files added beside it, and its entries appended to a copy of
    BENCHMARK.json, as a PR that adds the family would leave the repo."""
    root = str(tmp_path)
    data_dir = os.path.join(root, "benchmark")
    for sub in DATA:
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(data_dir, sub))
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    there = _files(BENCH_DIR)
    added = _files(os.path.join(TOY, "benchmark"))
    assert not set(added) & set(there), "the family would have to edit a file that is there"
    shutil.copytree(os.path.join(TOY, "benchmark"), data_dir, dirs_exist_ok=True)

    bench = copy.deepcopy(run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    add = run.load_json(os.path.join(TOY, "BENCHMARK.add.json"))
    for group in ("configs", "workloads", "per_layer"):
        bench[group].extend(add[group])
    for metric in bench["end_to_end"]:
        metric.get("workloads", []).extend(add["end_to_end_workloads"].get(metric["name"], []))

    # The new modules are found where a PR would have put them.
    monkeypatch.setattr(benchmark, "__path__", list(benchmark.__path__) + [data_dir])
    monkeypatch.setattr(benchmark.drivers, "__path__",
                        list(benchmark.drivers.__path__) + [os.path.join(data_dir, "drivers")])
    monkeypatch.setattr(run, "ROOT", root)  # `configs[].file` is relative to it
    importlib.invalidate_caches()
    yield bench, root, data_dir
    for name in [n for n in sys.modules if re.match(r"benchmark\.(drivers\.)?toy_", n)]:
        del sys.modules[name]
    # nothing that was there changed, on disk or in the copy
    assert _files(BENCH_DIR) == there
    now = _files(data_dir)
    assert all(now[path] == digest for path, digest in there.items() if path in now)


class ChipStandIn(run.Tracer):
    """No profiler and no chip here: the window runs untraced, and the trace
    it would have left is written by hand."""

    reduced = None

    def __init__(self, enabled):
        super().__init__(False)

    def reduce(self):
        return self.reduced


def test_a_foreign_family_enters_as_new_files_only(overlay, monkeypatch):
    bench, root, data_dir = overlay
    checks.everything(bench, root, data_dir)

    result = run.measure(bench, "toy-mlp-train", SEED, 0.3, False, jax.devices()[:1],
                         data_dir=data_dir, t0=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and set(line["compared"]) == {"loss_gap"}
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["metrics"]["train_samples_per_s"]["value"] > 0 and line["failed"] == 0

    # the per-layer metrics, on a trace written by hand
    kernel_s = 0.25
    ChipStandIn.reduced = {
        "busy_s": 0.5, "window_s": 1.0,
        "device_time_by_name_s": {
            '%toy_dense.3 = f32[8,64]{1,0} custom-call(f32[8,64]{1,0} %p.1), custom_call_target="tpu_custom_call"': kernel_s,
            "%fusion.1 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %toy_dense.3), kind=kLoop": 0.25,
        },
        "breakdown": {"device_ops": [], "idle_gaps": []},
    }
    monkeypatch.setattr(run, "Tracer", ChipStandIn)
    monkeypatch.setattr(run, "device_block", lambda devices: {"platform": "tpu", "kind": V5E, "count": 1})
    traced = run.measure(bench, "toy-mlp-train", SEED, 0.3, True, jax.devices()[:1],
                         data_dir=data_dir, t0=time.perf_counter())
    assert traced["correct"] is True
    # every metric of the cell, and none of RAFT-Stereo's
    assert set(traced["metrics"]) == {"mfu_pct.toy", "dense_roofline.toy"}
    steps = traced["attempted"]
    flops_per_sample = 3 * 2 * (16 * 64 + 64 * 64 + 64 * 1)
    # `seconds.window` is the driver's own clock over the same steps
    rate = steps * 8 / traced["seconds"]["window"]
    assert traced["metrics"]["mfu_pct.toy"]["value"] == pytest.approx(100 * flops_per_sample * rate / 197e12, rel=1e-9)
    calls = steps * 1
    least = max(calls * 4 * (64 * 64 + 2 * 8 * 64) / 819e9, calls * 2 * 8 * 64 * 64 / 197e12)
    assert traced["metrics"]["dense_roofline.toy"]["value"] == pytest.approx(100 * least / kernel_s, rel=1e-9)


def _edit(path, change):
    body = run.load_json(path)
    change(body)
    with open(path, "w") as f:
        json.dump(body, f)


@pytest.mark.parametrize("case, message", [
    ("a width in reduced", "is a width of toy-mlp"),
    ("a model key the family does not list", "dropout"),
    ("an unclassified key that reads like a width in reduced", "reads like a width"),
    ("a key that reads like a width among the family's shares", "hidden_size reads like a width: toy-mlp may not list it"),
    ("a key in reduced that the family does not let be cut", "among what may be cut"),
])
def test_bad_variants_of_the_family_are_refused(overlay, case, message):
    bench, root, data_dir = overlay
    config_file = os.path.join(data_dir, "configs", "toy-mlp-2l.json")
    family_file = os.path.join(data_dir, "families", "toy-mlp.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "toy-mlp-2l"]

    def reduce_too(key):
        entry["reduced"] = entry["reduced"] + [key]
        _edit(config_file, lambda body: body["reduced"].append(key))

    if case == "a width in reduced":
        reduce_too("hidden_size")
    elif case == "a model key the family does not list":
        _edit(config_file, lambda body: body.update(dropout=0.1))
    elif case == "an unclassified key that reads like a width in reduced":
        _edit(family_file, lambda family: family["widths"].remove("hidden_size"))
        reduce_too("hidden_size")
    elif case == "a key that reads like a width among the family's shares":
        # the family's own file cannot take a width out of the rule by name
        def reclassify(family):
            family["widths"].remove("hidden_size")
            family["shares"].append("hidden_size")
        _edit(family_file, reclassify)
        reduce_too("hidden_size")
    else:
        reduce_too("act")  # no width by its name, and no share of the family's either
    with pytest.raises(AssertionError, match=message):
        checks.configs(bench, root, data_dir)


@pytest.mark.parametrize("key, width", [
    ("hidden_size", True), ("moe_intermediate_size", True), ("head_dim", True), ("kv_lora_rank", True),
    ("hidden_dims", True), ("num_experts_per_tok", True), ("ssm_state_size", True),
    ("num_hidden_layers", False), ("n_gru_layers", False), ("num_experts", False), ("vocab_size", False),
    ("num_key_value_heads", False),
])
def test_the_rule_by_name(key, width):
    """A count of layers is the one key that a width's word does not make a
    width; how many experts, heads or rows of the vocabulary a chip holds have
    no such word."""
    assert checks.reads_like_a_width(key) is width


# -- the rewritten readers against PR 28's, on one context --------------------

FIXTURE = os.path.join(HERE, "fixtures", "small.xplane.pb")
# Event names as the chip's traces of PR 28 have them, cut after the first operand.
LOOKUP_OFFLINE = ('%corr_lookup.7 = bf16[496,720,36]{2,1,0:T(8,128)(2,1)} custom-call(f32[496,720,1]{2,1,0:T(8,128)} '
                  '%copy.379), custom_call_target="tpu_custom_call"')
LOOKUP_TRAIN = ('%corr_lookup.7 = bf16[320,184,36]{2,1,0:T(8,128)(2,1)S(1)} custom-call(f32[320,184,1]{2,1,0:T(8,128)S(1)} '
                '%pad.5347), custom_call_target="tpu_custom_call"')
SCATTER_TRAIN = ('%corr_scatter.10 = (bf16[320,184,256]{2,1,0:T(8,128)(2,1)S(1)}, bf16[320,184,128]{2,1,0:T(8,128)(2,1)}, '
                 'bf16[320,184,128]{2,1,0:T(8,128)(2,1)}, bf16[320,184,128]{2,1,0:T(8,128)(2,1)}) '
                 'custom-call(f32[320,184,1]{2,1,0} %pad.1), custom_call_target="tpu_custom_call"')
# not a kernel of ours, and not a custom call at all
OTHERS = {
    '%custom-call.412 = f32[3,3,128,256]{3,2,1,0} custom-call(f32[8]{0} %p), custom_call_target="AllocateBuffer"': 0.011,
    "%fusion.4681 = bf16[4,80,180,36]{3,0,2,1:T(4,128)(2,1)S(1)} fusion(bf16[4,80,180,64]{3,0,2,1} %custom-call.153), kind=kOutput": 0.07,
}
PR28_ARGS = {
    "mfu_pct.offline": {"count": "inference_flops"},
    "mfu_pct.train": {"count": "train_sample_flops"},
    "lookup_roofline.offline": {"match": ['custom_call_target="tpu_custom_call"'],
                                "result": "^%[^ ]+ = [a-z0-9]+\\[[0-9,]*,{taps}\\]",
                                "bytes_fn": "lookup_bytes", "bytes_args": [2, 2]},
    "scatter_roofline.train": {"match": ['custom_call_target="tpu_custom_call"'], "result": "^%[^ ]+ = \\(",
                               "bytes_fn": "scatter_bytes", "bytes_args": [2, 2]},
}
PR28_ARGS["lookup_roofline.train"] = PR28_ARGS["lookup_roofline.offline"]


def _pr28_mfu(context, count):
    window, spec, model = context["window"], context["spec"], context["config"]["model"]
    h, w = spec["image_hw"]
    per_unit = getattr(counts, count)(model, h, w, spec["iters"])
    peak = peaks(context["device"]["kind"])["bf16_flops_per_s"]
    if window["work"] == 0:
        return None
    return 100.0 * per_unit * window["work"] / window["seconds"] / (context["chips"] * peak)


def _pr28_trace_kernel(context, match, result, bytes_fn, bytes_args=(), calls="kernel_calls"):
    spec, model = context["spec"], context["config"]["model"]
    taps = model["corr_levels"] * (2 * model["corr_radius"] + 1)
    pattern = re.compile(result.replace("{taps}", str(taps)))
    seconds = sum(
        s for name, s in context["trace"]["device_time_by_name_s"].items()
        if all(m in name for m in match) and pattern.search(name)
    )
    n_calls = context["window"].get(calls, 0)
    if seconds <= 0 or n_calls <= 0:
        return None
    h8, w8 = counts.coarse_hw(model, *spec["image_hw"])
    peak = peaks(context["device"]["kind"])
    moved = getattr(counts, bytes_fn)(model, h8, w8, *bytes_args) * n_calls
    ops = counts.lookup_flops(model, h8, w8) * n_calls
    least = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 100.0 * least / (seconds * context["chips"])


@pytest.mark.parametrize("metric, cell, kernels", [
    ("mfu_pct.offline", "full-offline-middlebury-f", {}),
    ("mfu_pct.train", "full-train-sceneflow-b4", {}),
    ("lookup_roofline.offline", "full-offline-middlebury-f", {LOOKUP_OFFLINE: 0.474045302}),
    ("lookup_roofline.train", "full-train-sceneflow-b4", {LOOKUP_TRAIN: 0.13173973, SCATTER_TRAIN: 0.124066472}),
    ("scatter_roofline.train", "full-train-sceneflow-b4", {LOOKUP_TRAIN: 0.13173973, SCATTER_TRAIN: 0.124066472}),
])
def test_rewritten_readers_read_what_pr28s_read(metric, cell, kernels):
    """The recorded trace's own events, the kernels' events beside them, a
    window made by hand: the old code and the new give the same float."""
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    config = run.load_json(os.path.join(BENCH_DIR, "configs", "raftstereo-full.json"))
    reduced = tr.summarize(tr.load(FIXTURE))
    reduced["device_time_by_name_s"] = {**reduced["device_time_by_name_s"], **OTHERS, **kernels}
    context = {
        "window": {"work": 44, "seconds": 4.47, "attempted": 11, "kernel_calls": 44 * 22},
        "trace": reduced, "device": {"kind": V5E}, "config": config, "chips": 1,
        "family": run.load_json(os.path.join(BENCH_DIR, "families", config["family"] + ".json")),
        "spec": run.load_json(os.path.join(BENCH_DIR, "workloads", cell + ".json")),
    }
    assert cell in next(m for m in bench["per_layer"] if m["name"] == metric)["workloads"]
    meta = run.load_json(os.path.join(BENCH_DIR, "layer_metrics", metric + ".json"))
    old, new = (_pr28_mfu, mfu) if meta["reader"] == "mfu" else (_pr28_trace_kernel, trace_kernel)
    was = old(context, **PR28_ARGS[metric])
    assert isinstance(was, float) and was > 0
    assert new.read(context, **meta["args"]) == was
    # nothing to read -> nothing, from both
    context["window"] = {"work": 0, "seconds": 4.47, "kernel_calls": 0}
    assert old(context, **PR28_ARGS[metric]) is None and new.read(context, **meta["args"]) is None


def test_stored_bytes_come_from_the_configurations_corr_dtype():
    """What fixes the stored pyramid is the configuration's
    `program.corr_dtype`, which the program is built from; a workload's
    `precision` is a statement no code reads, and moves no count."""
    config = run.load_json(os.path.join(BENCH_DIR, "configs", "raftstereo-full.json"))
    spec = run.load_json(os.path.join(BENCH_DIR, "workloads", "full-train-sceneflow-b4.json"))
    model = config["model"]
    h8, w8 = counts.coarse_hw(model, *spec["image_hw"])
    assert config["program"]["corr_dtype"] == "bfloat16"
    assert counts.lookup_bytes_per_call(config, spec) == counts.lookup_bytes(model, h8, w8, 2, 2)
    assert counts.scatter_bytes_per_call(config, dict(spec, precision="float32")) == counts.scatter_bytes(model, h8, w8, 2, 2)
    wide = dict(config, program=dict(config["program"], corr_dtype="float32"))
    assert counts.lookup_bytes_per_call(wide, spec) == counts.lookup_bytes(model, h8, w8, 4, 4)
    assert counts.scatter_bytes_per_call(wide, spec) == counts.scatter_bytes(model, h8, w8, 4, 4)


def test_kernel_of_an_event():
    assert trace_kernel.kernel_of(LOOKUP_TRAIN) == "corr_lookup"
    assert trace_kernel.kernel_of(SCATTER_TRAIN) == "corr_scatter"
    assert trace_kernel.kernel_of(LOOKUP_TRAIN.replace("corr_lookup.7", "corr_lookup_prefetch.3")) == "corr_lookup_prefetch"
    assert trace_kernel.kernel_of(LOOKUP_TRAIN.replace("corr_lookup.7", "corr_lookup")) == "corr_lookup"
    assert [trace_kernel.kernel_of(name) for name in OTHERS] == ["custom-call", None]
