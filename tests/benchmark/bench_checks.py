"""What BENCHMARK.json and the data files under a benchmark directory have to
hold: they load, name only things that exist, and keep to the contract's
limits. Each check takes the benchmark's description, the root that
`configs[].file` is relative to, and the directory that holds `workloads/`,
`layer_metrics/` and `families/`, so a test can hold a throwaway tree to the
same checks as the one that is committed (test_bench_files.py,
test_bench_family.py). A check raises AssertionError.
"""

from __future__ import annotations

import importlib
import os
import re
from typing import Dict, List

from benchmark import families

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "expansion", "per_tok")
# The one kind of key that a width's word in its name does not make a width: a count of layers.
LAYER_COUNT = re.compile(r"^(num|n)_([a-z0-9]+_)*layers$")
# What a configuration's file may hold beside its model's keys.
CONFIG_KEYS = {"name", "family", "source", "program", "precision", "reduced", "assumed"}
FAMILY_KEYS = {"name", "group", "keys", "widths", "shares", "modules", "reference_entry_points"}


load = families.load_json


def data_files(data_dir: str, sub: str) -> List[str]:
    return sorted(f for f in os.listdir(os.path.join(data_dir, sub)) if f.endswith(".json"))


def reads_like_a_width(key: str) -> bool:
    """The contract's rule by name: a hidden, intermediate, latent, state or
    projection size, a head size, an expansion factor, the experts per token.
    No family's file can switch it off: it holds for the keys a family lists
    as `shares` too, and only a count of layers (`num_hidden_layers`) is let
    through it."""
    if LAYER_COUNT.match(key):
        return False
    return key.endswith(("_dim", "_dims", "_rank")) or any(word in key for word in WIDTH_WORDS)


def top_level_keys_and_limits(bench: Dict, root: str) -> None:
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(root, path)) and not path.startswith("/") and ".." not in path
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    # the check's budget with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def names_units_and_uniqueness(bench: Dict) -> None:
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


def family_file(family: Dict, name: str) -> None:
    """A family's file: its keys classified (`group` says where in a
    configuration's file they sit: a nested group, or null for the top level,
    where the contract wants a catalog model's published keys), no share that
    reads like a width, its modules there, its reference with the entry
    points its drivers call."""
    assert set(family) == FAMILY_KEYS, sorted(set(family) ^ FAMILY_KEYS)
    assert family["name"] == name and NAME.match(name)
    keys = family["keys"]
    assert keys and len(keys) == len(set(keys)) and not set(keys) & CONFIG_KEYS
    assert family["group"] is None or family["group"] not in CONFIG_KEYS | set(keys)
    assert set(family["widths"]) <= set(keys) and set(family["shares"]) <= set(keys)
    assert not set(family["widths"]) & set(family["shares"]), "a width may never be cut"
    for key in family["shares"]:
        assert not reads_like_a_width(key), f"{key} reads like a width: {name} may not list it among what may be cut"
    assert set(family["modules"]) == {"counts", "reference", "weights"}
    for kind in family["modules"]:
        families.module(family, kind)
    reference = families.module(family, "reference")
    assert family["reference_entry_points"]
    for entry in family["reference_entry_points"]:
        assert callable(getattr(reference, entry, None)), f"{reference.__name__} has no {entry}()"


def configs(bench: Dict, root: str, data_dir: str) -> None:
    """Every configuration against ITS family's file."""
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["name"] in used, "a configuration no cell uses"
        assert config["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert config["file"] not in files
        files.add(config["file"])
        body = load(os.path.join(root, config["file"]))
        assert body["name"] == config["name"] and body["reduced"] == config["reduced"]
        for text in (config["source"], config["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
        family = families.load(data_dir, body["family"])
        family_file(family, body["family"])
        # the model's keys are the family's, no more and no fewer
        if family["group"] is None:
            model_keys = set(body) - CONFIG_KEYS
        else:
            assert set(body) - CONFIG_KEYS == {family["group"]}, sorted(set(body) - CONFIG_KEYS)
            model_keys = set(body[family["group"]])
        assert model_keys == set(family["keys"]), (config["name"], sorted(model_keys ^ set(family["keys"])))
        # what was cut: a share the family names, never a width
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert NAME.match(key), key
            assert key not in family["widths"], f"{key} is a width of {family['name']}"
            assert not reads_like_a_width(key), f"{key} reads like a width"
            assert key in family["shares"], f"{family['name']} does not list {key} among what may be cut"


def workloads(bench: Dict, data_dir: str) -> None:
    names = {c["name"] for c in bench["configs"]}
    pairs = set()
    four = 0
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] in names and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        four += cell["chips"] == 4
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        spec = load(os.path.join(data_dir, "workloads", cell["name"] + ".json"))
        for key in ("config", "traffic", "chips"):
            assert spec[key] == cell[key], (cell["name"], key)
        driver = importlib.import_module("benchmark.drivers." + spec["driver"])
        assert hasattr(driver, "Run")
        assert spec["trace_seconds"] <= bench["run_seconds"]
        assert spec["limits"] and all(v >= 0 for v in spec["limits"].values())
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert set(data_files(data_dir, "workloads")) == {c["name"] + ".json" for c in bench["workloads"]}


def metrics(bench: Dict) -> None:
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


def layer_metric_files(bench: Dict, root: str, data_dir: str) -> None:
    """Each per-layer metric has its file, the file its reader, and every
    count function the file names is in the counts module of the family of
    each cell the metric lists."""
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert set(data_files(data_dir, "layer_metrics")) == {name + ".json" for name in declared}
    config_files = {c["name"]: c["file"] for c in bench["configs"]}
    cell_configs = {w["name"]: w["config"] for w in bench["workloads"]}
    for name, metric in declared.items():
        meta = load(os.path.join(data_dir, "layer_metrics", name + ".json"))
        for key in ("layer", "source", "moves"):
            assert meta[key] == metric[key], (name, key)
        reader = importlib.import_module("benchmark.readers." + meta["reader"])
        assert callable(reader.read)
        for cell in metric.get("workloads", cell_configs):
            body = load(os.path.join(root, config_files[cell_configs[cell]]))
            counts = families.module(families.load(data_dir, body["family"]), "counts")
            for arg in getattr(reader, "COUNT_ARGS", ()):
                fn = meta["args"][arg]
                assert callable(getattr(counts, fn, None)), f"{name}: {counts.__name__} has no {fn}()"


def file_names_use_permitted_characters(bench: Dict, root: str) -> None:
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(folder, name)


def everything(bench: Dict, root: str, data_dir: str) -> None:
    top_level_keys_and_limits(bench, root)
    names_units_and_uniqueness(bench)
    configs(bench, root, data_dir)
    workloads(bench, data_dir)
    metrics(bench)
    layer_metric_files(bench, root, data_dir)
    file_names_use_permitted_characters(bench, root)
