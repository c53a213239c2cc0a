#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is comes from data: `BENCHMARK.json` names the cell, its
configuration's file and the metrics; the configuration's file names its
family, and `families/<family>.json` the modules that hold the family's
counts, reference and weight draw; `workloads/<cell>.json` names the driver
and holds the traffic's parameters; `layer_metrics/<metric>.json` names the
reader of each per-layer metric. This file holds no cell's constants.

The run: gate (a TPU with the chips the cell asks for, else exit 2 and no
result), set-up (weights from the seed, the program built, every shape warmed;
all of it `setup_s`), the window (`--seconds`, or the workload's
`trace_seconds` under the profiler with `--trace 1`), the memory reading, then
— the program's state freed — the comparison with the plain reference, whose
numbers are printed beside their limits on standard error and last in the
result line.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class GateError(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def gate(chips: int) -> Dict[str, object]:
    """The first jax call. Anything but a TPU with `chips` devices ends the
    process with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise GateError(
            f"benchmark: needs a TPU, jax found platform {devices[0].platform!r}; nothing ran"
        )
    if len(devices) < chips:
        raise GateError(
            f"benchmark: the cell needs {chips} chips, jax found {len(devices)}; nothing ran"
        )
    return device_block(devices[:chips])


def device_block(devices) -> Dict[str, object]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """The fullest chip's peak. `peak_bytes_in_use` counts arrays only on this
    runtime; a program's temporaries show as `peak_bytes_reserved`."""
    peak = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), int(stats.get("peak_bytes_reserved", 0)))
    return peak


class Tracer:
    """The profiler around the window, and the harness's spans. With tracing
    off, `span` costs a context manager and nothing else."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation("bench/" + name)

    def __enter__(self):
        if self.enabled:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            jax.profiler.stop_trace()
        return False

    def reduce(self) -> dict:
        from benchmark import trace_reduce

        try:
            return trace_reduce.summarize(trace_reduce.load(trace_reduce.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def measure(
    bench: dict,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    data_dir: str = HERE,
    t0: float = T0,
) -> dict:
    """Everything after the gate. `devices` are the jax devices the cell may
    use; `data_dir` holds workloads/, layer_metrics/, families/ (tests point
    it at a throwaway copy)."""
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    import jax

    setup_compile_cache()
    # The reference's small programs compile in under the default threshold;
    # a second run should find them in the cache too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cell = _entry(bench["workloads"], workload, "workload")
    config_entry = _entry(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    spec = load_json(os.path.join(data_dir, "workloads", workload + ".json"))
    if len(devices) < cell["chips"]:
        raise GateError(f"benchmark: {workload} needs {cell['chips']} devices, got {len(devices)}")
    devices = list(devices[: cell["chips"]])

    driver = importlib.import_module("benchmark.drivers." + spec["driver"])
    tracer = Tracer(trace)
    t_driver = time.perf_counter()
    run = driver.Run(spec, config, seed, devices, tracer)
    run.setup()
    setup_s = time.perf_counter() - t0

    window_seconds = min(seconds, spec["trace_seconds"]) if trace else seconds
    with tracer:
        with tracer.span("window"):
            window = run.window(window_seconds)
    peak = memory_peak_bytes(devices)

    t_check = time.perf_counter()
    compared = run.check()  # frees the program's state, then runs the reference
    gc.collect()
    check_s = time.perf_counter() - t_check

    device = dict(device_block(devices), memory_peak_bytes=peak)
    result = {
        "correct": all(c["ok"] for c in compared.values()),
        "attempted": window["attempted"],
        "failed": window["failed"],
    }
    if trace:
        reduced = tracer.reduce()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        from benchmark import families

        context = {
            "window": window, "trace": reduced, "device": device, "config": config,
            "family": families.load(data_dir, config["family"]), "spec": spec, "chips": cell["chips"],
        }
        metrics = {}
        for metric in bench["per_layer"]:
            if not _applies(metric, workload):
                continue
            meta = load_json(os.path.join(data_dir, "layer_metrics", metric["name"] + ".json"))
            reader = importlib.import_module("benchmark.readers." + meta["reader"])
            value = reader.read(context, **meta.get("args", {}))
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        result["breakdown"] = reduced["breakdown"]
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if _applies(m, workload)
        }
    result["metrics"] = metrics
    result["device"] = device
    # For the builder's eyes (the driver ignores them): where a run's time went.
    result["seconds"] = {
        "before_driver": t_driver - t0, "setup": setup_s, "window": window["seconds"], "check": check_s,
        **{k: window[k] for k in ("call_ms_p50", "call_ms_max") if k in window},
        "setup_phases": dict(getattr(run, "phases", {})),
    }
    result["compared"] = {
        name: {"value": c["value"], "limit": c["limit"]} for name, c in compared.items()
    }
    return result


def report(result: dict) -> None:
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = _entry(bench["workloads"], args.workload, "workload")
    import jax

    gate(cell["chips"])
    result = measure(bench, args.workload, args.seed, args.seconds, bool(args.trace), jax.devices())
    report(result)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except GateError as exc:
        print(exc, file=sys.stderr)
        sys.exit(2)
