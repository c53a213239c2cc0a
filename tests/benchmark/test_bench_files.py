"""BENCHMARK.json and every data file under benchmark/: they load, name only
things that exist, and keep to the contract's limits."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "expansion")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def _data_files(sub):
    return sorted(f for f in os.listdir(os.path.join(BENCH_DIR, sub)) if f.endswith(".json"))


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path)) and not path.startswith("/") and ".." not in path
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    # the check's budget with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_uniqueness(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for name in names:
            assert NAME.match(name), name
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["name"] in used, "a configuration no cell uses"
        assert config["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert config["file"] not in files
        files.add(config["file"])
        body = load(os.path.join(ROOT, config["file"]))
        assert body["name"] == config["name"] and body["reduced"] == config["reduced"]
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert not key.endswith(("_dim", "_dims", "_rank")) and not any(w in key for w in WIDTH_WORDS)
        for text in (config["source"], config["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
        assert set(body["model"]) == {
            "hidden_dims", "n_gru_layers", "n_downsample", "corr_levels", "corr_radius",
            "slow_fast_gru", "shared_backbone",
        }


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    four = 0
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] in configs and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        four += cell["chips"] == 4
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        spec = load(os.path.join(BENCH_DIR, "workloads", cell["name"] + ".json"))
        for key in ("config", "traffic", "chips"):
            assert spec[key] == cell[key], (cell["name"], key)
        driver = importlib.import_module("benchmark.drivers." + spec["driver"])
        assert hasattr(driver, "Run")
        assert spec["trace_seconds"] <= bench["run_seconds"]
        assert spec["limits"] and all(v >= 0 for v in spec["limits"].values())
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert set(_data_files("workloads")) == {c["name"] + ".json" for c in bench["workloads"]}


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end and end_to_end["setup_s"]["bound"] <= 0.1
    assert "workloads" not in end_to_end["setup_s"]
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        assert set(metric.get("workloads", cells)) <= cells
    for cell in cells:
        reported = [m for m in bench["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(reported) >= 2, f"{cell} reports setup_s and nothing else"
    assert 1 <= len(bench["per_layer"]) <= 128
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
        moved = end_to_end[metric["moves"]]
        for cell in metric.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (metric["name"], cell)
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and re.search(r"[a-z0-9]_roofline(\.|$)", metric["name"])
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])
    # a kernel's roofline moves a metric only beside the whole step's share
    for metric in bench["per_layer"]:
        if "roofline" in metric["name"]:
            assert any(
                "mfu" in re.split(r"[._]", other["name"]) and other["moves"] == metric["moves"]
                for other in bench["per_layer"]
            ), metric["name"]


def test_layer_metric_files(bench):
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert set(_data_files("layer_metrics")) == {name + ".json" for name in declared}
    for name, metric in declared.items():
        meta = load(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))
        for key in ("layer", "source", "moves"):
            assert meta[key] == metric[key], (name, key)
        reader = importlib.import_module("benchmark.readers." + meta["reader"])
        assert callable(reader.read)
        if meta["reader"] == "mfu":
            from benchmark import counts

            assert callable(getattr(counts, meta["args"]["count"]))
        if meta["reader"] == "trace_kernel":
            from benchmark import counts

            assert callable(getattr(counts, meta["args"]["bytes_fn"]))


def test_file_names_use_permitted_characters(bench):
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(folder, name)


def test_peaks_have_sources_and_unknown_kind_is_an_error():
    from benchmark import counts

    table = load(os.path.join(BENCH_DIR, "peaks.json"))
    for kind, row in table.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")
