"""A CPU rehearsal of benchmark/run.py for each driver at a tiny size, with
throwaway cells added by data files alone; the same run with the timed path
broken underneath, which has to come out not correct; the gate; the traffic.

Everything is steered from here: no option or environment variable of
raft_stereo_tpu/ is involved, and no number a rehearsal gives is written
anywhere under a device metric's name."""

import copy
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench_fixtures import compile_cache  # noqa: F401  (a fixture)
from benchmark import run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
SEED = 2**31 + 5

TINY = {
    "tiny-offline": ("full-offline-middlebury-f", dict(image_hw=[64, 96], max_disp=8.0, iters=3)),
    "tiny-train": ("full-train-sceneflow-b4", dict(image_hw=[64, 96], max_disp=8.0, iters=2, batch=2)),
}
# A cell no file of the benchmark knows: the realtime model behind the
# serving driver. Its configuration, workload file and entries are all added
# by the fixture below; nothing that is there is edited.
TINY_SERVE = {
    "config": "tiny-realtime", "driver": "serve", "chips": 1, "traffic": "tiny-serve",
    "request_hw": [60, 90], "image_hw": [64, 96], "max_disp": 8.0, "iters": 7, "chunk_iters": 7,
    "max_batch": 2, "batch_window_ms": 2.0, "rate_hz": 6.0, "frames": 3, "checked_requests": 2,
    "trace_seconds": 1, "precision": "bfloat16", "control": "fp8",
    "limits": {"map_mae_px": 1.0, "unanswered": 0},
}
REALTIME = dict(n_gru_layers=2, n_downsample=3, slow_fast_gru=True, shared_backbone=True)
CELLS = {w["name"] for w in run.load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]}
TINY = {name: tiny for name, tiny in TINY.items() if tiny[0] in CELLS}
needs = lambda name: pytest.mark.skipif(name not in TINY, reason=f"{name}'s cell is not in BENCHMARK.json")


@pytest.fixture(scope="module")
def throwaway(tmp_path_factory, compile_cache):
    """BENCHMARK.json plus one entry per throwaway cell, and a data directory
    with one new workload file each: nothing that is there is edited."""
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench = copy.deepcopy(bench)
    data_dir = str(tmp_path_factory.mktemp("bench_data"))
    for sub in ("layer_metrics", "families"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(data_dir, sub))
    os.makedirs(os.path.join(data_dir, "workloads"))
    # A throwaway configuration too: the published model computed in float32,
    # since the CPU sums bf16 gradients in bf16 and reads gaps a TPU does not.
    full = run.load_json(os.path.join(BENCH_DIR, "configs", "raftstereo-full.json"))
    full["name"] = "tiny-float32"
    full["program"].update(mixed_precision=False, corr_dtype="float32")
    config_file = os.path.join(data_dir, "tiny-float32.json")
    with open(config_file, "w") as f:
        json.dump(full, f)
    bench["configs"].append(dict(bench["configs"][0], name="tiny-float32", file=config_file))
    realtime = run.load_json(os.path.join(BENCH_DIR, "configs", "raftstereo-full.json"))
    realtime["name"] = "tiny-realtime"
    realtime["model"].update(REALTIME)
    config_file = os.path.join(data_dir, "tiny-realtime.json")
    with open(config_file, "w") as f:
        json.dump(realtime, f)
    bench["configs"].append(dict(bench["configs"][0], name="tiny-realtime", file=config_file))
    with open(os.path.join(data_dir, "workloads", "tiny-serve.json"), "w") as f:
        json.dump(TINY_SERVE, f)
    bench["workloads"].append(dict(
        bench["workloads"][0], name="tiny-serve", config="tiny-realtime", traffic="tiny-serve"))
    for name in ("serve_p50_ms", "serve_p95_ms"):
        bench["end_to_end"].insert(0, {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
                                       "source": "host_clock", "workloads": ["tiny-serve"]})
    for name, (parent, sizes) in TINY.items():
        spec = run.load_json(os.path.join(BENCH_DIR, "workloads", parent + ".json"))
        spec.update(sizes, traffic=name)
        cell = dict(next(w for w in bench["workloads"] if w["name"] == parent), name=name, traffic=name)
        if spec["driver"] == "train":
            cell["config"] = spec["config"] = "tiny-float32"
        with open(os.path.join(data_dir, "workloads", name + ".json"), "w") as f:
            json.dump(spec, f)
        bench["workloads"].append(cell)
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if parent in metric.get("workloads", ()):
                metric["workloads"].append(name)
    return bench, data_dir


def rehearse(throwaway, name, seconds=0.5):
    import time

    bench, data_dir = throwaway
    return run.measure(bench, name, SEED, seconds, False, jax.devices()[:1],
                       data_dir=data_dir, t0=time.perf_counter())


def _check_result_line(result, metric):
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert line["metrics"][metric]["value"] > 0 and line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["attempted"] >= 1 and line["failed"] == 0
    for number in line["compared"].values():
        assert set(number) == {"value", "limit"}


@needs("tiny-offline")
def test_offline_rehearsal(throwaway):
    result = rehearse(throwaway, "tiny-offline")
    _check_result_line(result, "offline_maps_per_s")
    assert result["correct"] is True
    assert set(result["compared"]) == {"map_mae_px"}


@needs("tiny-offline")
def test_offline_answer_altered_where_it_is_produced(throwaway, monkeypatch):
    from raft_stereo_tpu.evaluate import Evaluator

    real = Evaluator.__call__

    def altered(self, image1, image2):
        disparity, seconds = real(self, image1, image2)
        disparity = disparity.copy()
        disparity[: disparity.shape[0] // 2] += 8.0  # half of every map, 8 px off
        return disparity, seconds

    monkeypatch.setattr(Evaluator, "__call__", altered)
    result = rehearse(throwaway, "tiny-offline")
    assert result["correct"] is False
    assert result["compared"]["map_mae_px"]["value"] > result["compared"]["map_mae_px"]["limit"]


@needs("tiny-train")
def test_train_rehearsal(throwaway):
    result = rehearse(throwaway, "tiny-train")
    _check_result_line(result, "train_samples_per_s")
    assert set(result["compared"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert result["correct"] is True


def _break_train_step(monkeypatch, broken):
    """Wrap the compiled step the trainer builds: `broken(step)` -> step."""
    from raft_stereo_tpu.train import trainer as trainer_module

    real_init = trainer_module.Trainer.__init__

    def init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        self.train_step = broken(self, self.train_step)

    monkeypatch.setattr(trainer_module.Trainer, "__init__", init)


@needs("tiny-train")
def test_train_step_that_returns_its_state_unchanged(throwaway, monkeypatch):
    def broken(trainer, step):
        def unchanged(state, batch):
            kept = jax.device_get(state)
            new, metrics = step(state, batch)
            return trainer.sharding.place_state(kept.replace(step=kept.step + 1)), metrics

        return unchanged

    _break_train_step(monkeypatch, broken)
    result = rehearse(throwaway, "tiny-train")
    assert result["correct"] is False
    # no leaf moved, none got a gradient into Adam's moment
    assert result["compared"]["update_norm_gap"]["value"] == pytest.approx(1.0, abs=0.05)


@needs("tiny-train")
def test_train_half_of_the_batch_left_out(throwaway, monkeypatch):
    def broken(trainer, step):
        def half(state, batch):
            n = batch["image1"].shape[0] // 2
            batch = {k: np.concatenate([np.asarray(v)[:n], np.asarray(v)[:n]]) for k, v in batch.items()}
            return step(state, trainer.sharding.place_batch(batch))

        return half

    _break_train_step(monkeypatch, broken)
    result = rehearse(throwaway, "tiny-train")
    assert result["correct"] is False


@needs("tiny-train")
@pytest.mark.parametrize("fault", [None, "half_batch"], ids=["fp8", "half_batch"])
def test_train_control_fails_a_limit(throwaway, fault):
    """The reference in fp8, or fed half of each batch, put in the program's
    place: one of the cell's numbers has to pass its limit."""
    from benchmark.drivers import train

    bench, data_dir = throwaway
    spec = run.load_json(os.path.join(data_dir, "workloads", "tiny-train.json"))
    config = run.load_json(os.path.join(BENCH_DIR, "configs", "raftstereo-full.json"))
    numbers = train.Run(spec, config, SEED, jax.devices()[:1], run.Tracer(False)).control(fault)
    assert any(numbers[name] > limit for name, limit in spec["limits"].items()), numbers


@pytest.fixture
def aot_store(tmp_path, monkeypatch):
    """The serve driver keeps its executables at a fixed path in the
    checkout; a rehearsal keeps them in a directory of its own."""
    from benchmark.drivers import serve

    monkeypatch.setattr(serve, "AOT_DIR", str(tmp_path / "aot"))


def test_serve_rehearsal(throwaway, aot_store):
    result = rehearse(throwaway, "tiny-serve", seconds=1.5)
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms", "setup_s"}
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] <= line["metrics"]["serve_p95_ms"]["value"]
    assert line["attempted"] == 9 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert result["correct"] is True and set(result["compared"]) == {"map_mae_px", "unanswered"}


def test_serve_answer_altered_where_it_is_produced(throwaway, aot_store, monkeypatch):
    from raft_stereo_tpu.serving import engine

    real = engine.AnytimeEngine.run_batch

    def altered(self, *args, **kwargs):
        results = real(self, *args, **kwargs)
        for result in results:
            result.flow_up = result.flow_up + 8.0  # every map, 8 px off
        return results

    monkeypatch.setattr(engine.AnytimeEngine, "run_batch", altered)
    result = rehearse(throwaway, "tiny-serve", seconds=1.5)
    assert result["correct"] is False


def test_arrivals_repeat_for_a_seed_and_keep_their_gaps_between_seeds():
    from benchmark.drivers import serve

    a, b, c = (serve.arrivals(seed, 40.0, 5.0) for seed in (SEED, SEED, SEED + 1))
    assert len(a) == 200 and np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0 < a[0] and a[-1] < 5.0 and (np.diff(a) > 0).all()
    gaps = lambda due: np.sort(np.diff(np.concatenate([[0.0], due])))
    assert np.allclose(gaps(a), gaps(c))


def test_gate_refuses_a_machine_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as caught:
        run.gate(1)
    assert caught.value.code not in (0, None)
    with pytest.raises(SystemExit) as caught:
        run.main(["--workload", "full-offline-middlebury-f", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert caught.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit) as caught:
        run.main(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"])
    assert caught.value.code not in (0, None)


def test_traffic_repeats_for_a_seed_and_differs_between_seeds():
    a = traffic.stereo_batches(SEED, 2, 2, (32, 48), 6.0)
    b = traffic.stereo_batches(SEED, 2, 2, (32, 48), 6.0)
    c = traffic.stereo_batches(SEED + 1, 2, 2, (32, 48), 6.0)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["image1"], c[0]["image1"])
    assert a[0]["image1"].shape == c[0]["image1"].shape == (2, 32, 48, 3)
    rows = np.concatenate([batch["image1"] for batch in a])
    assert len({row.tobytes() for row in rows}) == len(rows), "rows all differ"
    assert a[0]["image1"].min() >= 0 and a[0]["image1"].max() <= 255
    assert (a[0]["flow"] <= 0).all() and a[0]["flow"].min() >= -6.0
