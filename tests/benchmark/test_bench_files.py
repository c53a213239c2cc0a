"""BENCHMARK.json and every data file under benchmark/: they load, name only
things that exist, and keep to the contract's limits. The checks themselves
are bench_checks.py beside this file, so that a throwaway tree with a family
of its own is held to the same ones (test_bench_family.py)."""

import os

import pytest

import bench_checks as checks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def bench():
    return checks.load(os.path.join(ROOT, "BENCHMARK.json"))


def test_top_level_keys_and_limits(bench):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    checks.top_level_keys_and_limits(bench, ROOT)


def test_names_units_and_uniqueness(bench):
    checks.names_units_and_uniqueness(bench)


def test_configs(bench):
    checks.configs(bench, ROOT, BENCH_DIR)
    # every family's file is some configuration's
    named = {checks.load(os.path.join(ROOT, c["file"]))["family"] + ".json" for c in bench["configs"]}
    assert set(checks.data_files(BENCH_DIR, "families")) == named


def test_workloads(bench):
    checks.workloads(bench, BENCH_DIR)


def test_metrics(bench):
    checks.metrics(bench)


def test_layer_metric_files(bench):
    checks.layer_metric_files(bench, ROOT, BENCH_DIR)


def test_file_names_use_permitted_characters(bench):
    checks.file_names_use_permitted_characters(bench, ROOT)


def test_peaks_have_sources_and_unknown_kind_is_an_error():
    from benchmark.peaks import peaks

    table = checks.load(os.path.join(BENCH_DIR, "peaks.json"))
    for kind, row in table.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
