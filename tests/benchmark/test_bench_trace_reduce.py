"""The trace reduction: interval arithmetic on hand-made events, and the same
numbers re-derived by brute force from a small trace recorded on the chip."""

import glob
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def test_union_and_busy():
    events = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30), ("d", 30, 31)]
    assert tr.union((s, e) for _, s, e in events) == [(0, 12), (20, 31)]
    assert tr.busy_ns(events) == 23


def test_clip_to_window():
    events = [("a", 0, 10), ("b", 8, 25), ("c", 40, 50)]
    assert tr.clip(events, (5, 20)) == [("a", 5, 10), ("b", 8, 20)]


def test_self_time_takes_children_out_of_a_loop():
    # a `while` of 100 ns encloses two body operations; one more op follows
    events = [("while", 0, 100), ("conv", 10, 40), ("fusion", 50, 70), ("conv", 120, 130)]
    assert tr.self_time_by_name(events) == {"while": 50, "conv": 40, "fusion": 20}
    # nested twice
    events = [("outer", 0, 100), ("inner", 10, 60), ("leaf", 20, 30)]
    assert tr.self_time_by_name(events) == {"outer": 50, "inner": 40, "leaf": 10}


def test_gaps_longest_first_and_attribution():
    events = [("a", 10, 20), ("b", 50, 60)]
    assert tr.gaps(events, (0, 100)) == [(60, 100), (20, 50), (0, 10)]
    spans = [("bench/window", 0, 100), ("bench/fetch", 18, 45), ("bench/pad", 44, 52), ("bench/tail", 55, 100)]
    assert tr.attribute((20, 50), spans) == "bench/fetch"
    assert tr.attribute((60, 100), spans) == "bench/tail"
    assert tr.attribute((0, 10), spans) == "unattributed"
    assert tr.idle_by_span(events, spans, (0, 100)) == {
        "bench/tail <1ms": 40, "bench/fetch <1ms": 30, "unattributed <1ms": 10}
    slow = [("a", 0, 10), ("b", 3_000_010, 3_000_020)]
    assert tr.idle_by_span(slow, [("bench/wait", 5, 3_000_015)], (0, 3_000_020)) == {"bench/wait >=1ms": 3_000_000}
    # of two spans covering a gap whole, the inner one is named
    nested = [("bench/call", 0, 100), ("bench/fetch", 20, 50)]
    assert tr.attribute((25, 45), nested) == "bench/fetch"


def test_summarize_averages_over_device_planes():
    trace = tr.Trace(
        device_ops={
            "/device:TPU:0": [("k", 1_000_000_000, 1_600_000_000)],
            "/device:TPU:1": [("k", 1_200_000_000, 1_400_000_000)],
        },
        spans=[("bench/window", 1_000_000_000, 2_000_000_000)],
    )
    out = tr.summarize(trace)
    assert out["window_s"] == 1.0
    assert out["busy_s"] == pytest.approx(0.4)
    assert out["device_time_by_name_s"] == {"k": pytest.approx(0.4)}
    assert out["breakdown"]["idle_gaps"] == [["unattributed >=1ms", pytest.approx(0.6)]]


def test_no_window_span_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(device_ops={"/device:TPU:0": [("k", 0, 1)]}, spans=[]))
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(device_ops={}, spans=[("bench/window", 0, 10)]))


def _fixture():
    found = sorted(glob.glob(os.path.join(FIXTURES, "*.xplane.pb")))
    assert found, "the recorded trace is missing from tests/benchmark/fixtures"
    return found[0]


def test_recorded_trace_reduces_to_what_brute_force_gives():
    """fixtures/*.xplane.pb: a few small matmul steps on one v5e chip under
    the harness's spans (recorded by tests/benchmark/record_fixture.py)."""
    trace = tr.load(_fixture())
    assert list(trace.device_ops) and all("/device:TPU:" in name for name in trace.device_ops)
    window = tr.window_of(trace.spans)
    assert {name for name, _, _ in trace.spans} >= {"bench/window", "bench/step", "bench/sleep"}
    (events,) = trace.device_ops.values()
    events = tr.clip(events, window)
    assert len(events) >= 8
    # busy union, by marking every nanosecond an operation covers
    t0, t1 = window
    marked = set()
    for _, start, end in events:
        marked.update(range(start, end))
    out = tr.summarize(trace)
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert out["busy_s"] == pytest.approx(len(marked) / 1e9, rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    # per-name self time adds up to the busy union (one ops line: no overlap but nesting)
    assert sum(out["device_time_by_name_s"].values()) == pytest.approx(out["busy_s"], rel=1e-6)
    # the harness slept between steps with the device idle: the sleep owns the longest gaps
    idle = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert max(idle, key=idle.get) == "bench/sleep >=1ms"
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
