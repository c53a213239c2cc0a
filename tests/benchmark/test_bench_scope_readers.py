"""The two readers of what the program itself says: `trace_scope` (device
time by model component, joined by instruction name) and `program_span` (host
time inside the program's spans), on contexts made by hand; every layer metric
this pair serves, run with its own `args`."""

import json
import os

import pytest

from benchmark.readers import program_span, trace_scope
from raft_stereo_tpu.obs import scopes, span
from raft_stereo_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRICS_DIR = os.path.join(ROOT, "benchmark", "layer_metrics")

HLO = '''HloModule jit_fwd

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %fusion.1 = bf16[8,8]{1,0} fusion(%Arg_0.1), kind=kOutput, metadata={op_name="jit(fwd)/RAFTStereo/cnet/trunk/conv1/conv_general_dilated"}
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(fwd)/RAFTStereo/while/body/closed_call/iteration/update_block/gru08/tanh"}
  %corr_lookup.7 = bf16[8,8,36]{2,1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(fwd)/RAFTStereo/while/body/closed_call/iteration/corr_lookup/corr_lookup/pallas_call"}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.2), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(RAFTStereo))/while/body/closed_call/checkpoint/iteration/update_block/gru08/mul"}
  %fusion.4 = f32[8,8]{1,0} fusion(%fusion.3), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(RAFTStereo))/while/body/closed_call/checkpoint/rematted_computation/iteration/update_block/flow_head/add"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, metadata={op_name="jit(step)/optimizer/add"}
  %copy.6 = f32[8]{0} copy(%fusion.5)
  ROOT %while.8 = f32[4]{0} while(%copy.6), metadata={op_name="jit(fwd)/RAFTStereo/while"}
}
'''
# seconds of self time by event name, as trace_reduce.summarize hands them on
EVENTS = {
    "%fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(bf16[4]{0} %Arg_0.1), kind=kOutput": 0.6,
    "%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %fusion.1), kind=kLoop": 1.2,
    '%corr_lookup.7 = bf16[8,8,36]{2,1,0} custom-call(bf16[8,8]{1,0} %fusion.2), custom_call_target="tpu_custom_call"': 0.4,
    "%fusion.3 = f32[8,8]{1,0} fusion(bf16[8,8]{1,0} %fusion.2), kind=kLoop": 0.8,
    "%fusion.4 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.3), kind=kLoop": 0.2,
    "%fusion.5 = f32[8]{0} fusion(f32[8,8]{1,0} %fusion.4), kind=kLoop": 0.1,
    "%copy.6 = f32[8]{0} copy(f32[8]{0} %fusion.5)": 0.3,
    "%while.8 = f32[4]{0} while(f32[8]{0} %copy.6)": 0.05,
    # the pad program of the same window: a name no registered module has ...
    "%pad.77 = f32[1,64,96,3]{3,2,1,0} pad(f32[1,60,90,3]{3,2,1,0} %p, f32[] %c)": 0.25,
    # ... and one that collides by name but not by opcode
    "%fusion.1 = f32[1,60,90,1]{3,2,1,0} slice(f32[1,64,96,1]{3,2,1,0} %p)": 0.1,
}
BUSY = sum(EVENTS.values())


def context(window=None):
    return {
        "trace": {"device_time_by_name_s": dict(EVENTS), "busy_s": BUSY, "window_s": BUSY + 1.0},
        "window": window or {"work": 2, "kernel_calls": 8, "attempted": 4, "seconds": 5.0},
    }


@pytest.fixture
def registered_module():
    scopes.clear()
    scopes.register("evaluate/forward/8x8", lambda: HLO)
    yield
    scopes.clear()


def test_trace_scope_reads_nothing_where_nothing_is_registered():
    scopes.clear()
    assert trace_scope.read(context(), components=["encoder"], per="work") is None
    scopes.register("a/module/that/ran/nothing", lambda: "HloModule empty\n")
    assert trace_scope.read(context(), components=["encoder"], per="work") is None
    scopes.clear()


@pytest.mark.parametrize(
    "args, expected",
    [
        (dict(components=["encoder"], per="work"), 1000 * 0.6 / 2),
        (dict(components=["gru08", "flow_head"], per="kernel_calls"), 1000 * (1.2 + 0.8 + 0.2) / 8),
        (dict(components=["gru08", "flow_head"], per="attempted", phases=["forward"]), 1000 * 1.2 / 4),
        (dict(components=["gru08", "flow_head"], per="attempted", phases=["backward", "recompute"]),
         1000 * (0.8 + 0.2) / 4),
        (dict(components=["lookup"], per="kernel_calls"), 1000 * 0.4 / 8),
        (dict(components=["optimizer"], per="attempted"), 1000 * 0.1 / 4),
        # unknown and colliding names are unscoped, beside the bare copy
        (dict(components=["unscoped"], per="busy"), 100 * (0.3 + 0.25 + 0.1) / BUSY),
        (dict(components=["unscoped", "other"], per="busy"), 100 * (0.3 + 0.25 + 0.1 + 0.05) / BUSY),
    ],
)
def test_trace_scope_arithmetic(registered_module, args, expected):
    assert trace_scope.read(context(), **args) == pytest.approx(expected, rel=1e-12)


def test_trace_scope_components_sum_to_busy_and_no_work_reads_none(registered_module):
    totals = trace_scope.by_component(context())
    assert sum(totals.values()) == pytest.approx(BUSY, rel=1e-12)
    assert {component for component, _ in totals} <= set(scopes.COMPONENTS)
    assert trace_scope.read(context({"work": 0}), components=["encoder"], per="work") is None
    assert trace_scope.read(context(), components=["encoder"], per="no_such_key") is None


def _fit_spans():
    """One set-up fit and one window's fit, as Trainer.fit leaves them."""
    for final_save_s in (0.5, 0.25):
        with span("train/fit"):
            t = 100.0
            for name, seconds in (("train/start", 0.002), ("train/steps", 4.0), ("train/drain", 0.5),
                                  ("train/final_save", final_save_s)):
                obs_trace.record_span(name, t, t + seconds)
                t += seconds


def test_program_span_reads_the_windows_fit_only():
    _fit_spans()
    fit = {"name": "train/fit", "count": 1}
    ctx = context()
    assert program_span.read(ctx, root=fit, name="train/final_save", stat="sum") == pytest.approx(250.0)
    assert program_span.read(ctx, root=fit, name="train/start", stat="sum") == pytest.approx(2.0)
    both = {"name": "train/fit", "count": 2}
    assert program_span.read(ctx, root=both, name="train/final_save", stat="sum") == pytest.approx(750.0)
    idle = program_span.read(ctx, root=fit, busy_over=["train/steps", "train/drain"])
    assert idle == pytest.approx(100 * (1 - BUSY / 4.5))
    assert program_span.read(ctx, root=fit, name="train/no_such_phase", stat="sum") is None
    assert program_span.read(ctx, root={"name": "no/such/root", "count": 1}, name="train/start") is None
    with pytest.raises(ValueError):
        program_span.read(ctx, root=fit, name="train/start", stat="p99")


def test_program_span_takes_the_newest_calls_of_the_window():
    durations = [0.5, 0.010, 0.030, 0.020]  # the first is set-up's call, not the window's
    for seconds in durations:
        with span("evaluate/call"):
            obs_trace.record_span("evaluate/stage", 0.0, seconds)
    calls = {"name": "evaluate/call", "count": "attempted"}
    ctx = context({"attempted": 3})
    assert program_span.read(ctx, root=calls, name="evaluate/stage", stat="p50") == pytest.approx(20.0)
    assert program_span.read(ctx, root=calls, name="evaluate/stage", stat="sum") == pytest.approx(60.0)
    assert program_span.read(context({"attempted": 0}), root=calls, name="evaluate/stage") is None


READERS = ("trace_scope", "program_span")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _reader(metric):
    return json.load(open(os.path.join(METRICS_DIR, metric + ".json")))["reader"]


def _family(cell):
    """The family of a cell's configuration."""
    (config,) = [w["config"] for w in BENCH["workloads"] if w["name"] == cell]
    (path,) = [c["file"] for c in BENCH["configs"] if c["name"] == config]
    return json.load(open(os.path.join(ROOT, path)))["family"]


# What the module and the spans above stand for is a RAFT-Stereo program: the
# metrics of this pair of readers in that family's cells are run on them. A
# metric of another family's cells comes with a fixture of its own.
NEW_METRICS = sorted(
    m["name"] for m in BENCH["per_layer"]
    if _reader(m["name"]) in READERS and {_family(cell) for cell in m.get("workloads", ())} == {"raft-stereo"}
)


def test_every_scope_and_span_metric_is_declared_and_is_run_below():
    """No count is pinned: the files these two readers serve are exactly the
    `per_layer` entries whose file names one of them; each lists its cells,
    all of one family; and each one of RAFT-Stereo's is a case of the
    parametrised test below, none left out."""
    on_disk = sorted(name[: -len(".json")] for name in os.listdir(METRICS_DIR)
                     if name.endswith(".json") and _reader(name[: -len(".json")]) in READERS)
    declared = sorted(m["name"] for m in BENCH["per_layer"] if _reader(m["name"]) in READERS)
    assert on_disk == declared and declared
    for metric in BENCH["per_layer"]:
        if metric["name"] in declared:
            assert metric.get("workloads"), metric["name"]
            assert len({_family(cell) for cell in metric["workloads"]}) == 1, metric["name"]
    (cases,) = [mark.args[1] for mark in test_new_layer_metric_reads_with_its_own_args.pytestmark
                if mark.name == "parametrize"]
    assert list(cases) == NEW_METRICS


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_layer_metric_reads_with_its_own_args(registered_module, metric):
    """Each file's `args` drive its reader on a context that has something
    to read; its `layer` is one PERF.md section 3 names; in BENCHMARK.json
    it lists the cells of its kind and nothing else."""
    meta = json.load(open(os.path.join(METRICS_DIR, metric + ".json")))
    with span("evaluate/call"):
        with span("evaluate/stage"):
            pass
        with span("evaluate/fetch"):
            pass
    _fit_spans()
    reader = {"trace_scope": trace_scope, "program_span": program_span}[meta["reader"]]
    value = reader.read(context({"work": 2, "kernel_calls": 8, "attempted": 1}), **meta["args"])
    assert isinstance(value, float) and value == value
    if meta["source"] == "device_trace" and meta["reader"] == "trace_scope":
        assert 0.0 <= value <= 1000 * BUSY
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    section = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    layers = {line.split("|")[1].strip() for line in section.splitlines() if line.startswith("| ")}
    assert meta["layer"] in layers, (meta["layer"], layers)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == metric]
    kind = metric.rsplit(".", 1)[1]
    assert entry["workloads"] and all(kind in cell for cell in entry["workloads"])
    assert entry["unit"] == ("%" if "_pct" in metric else "ms") and entry["better"] == "lower"
