"""Profiler trace (`.xplane.pb`) -> busy union, per-name device time, idle
gaps by what the harness was doing.

Reads the file with `jax.profiler.ProfileData` and nothing else. A device
plane is one whose name holds "/device:TPU:"; its "XLA Ops" line carries one
event per executed operation (a `while` encloses its body's operations, so
per-name time is SELF time: an event's duration less its children's). The
harness's own `TraceAnnotation` spans ("bench/<what>") are host-plane events
on the same clock; "bench/window" delimits the measured window.

The arithmetic works on plain tuples so tests can feed it by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]  # start_ns, end_ns
Event = Tuple[str, int, int]  # name, start_ns, end_ns

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]  # plane name -> events of its ops line
    spans: List[Event]  # the harness's host spans


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return Trace(device_ops, spans)


def window_of(spans: Sequence[Event]) -> Interval:
    for name, start, end in spans:
        if name == WINDOW_SPAN:
            return start, end
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    t0, t1 = window
    return [
        (name, max(start, t0), min(end, t1))
        for name, start, end in events
        if end > t0 and start < t1
    ]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event]) -> int:
    return sum(end - start for start, end in union((s, e) for _, s, e in events))


def self_time_by_name(events: Sequence[Event]) -> Dict[str, int]:
    """Sum of self time per name: nested events (a loop and its body) each
    keep only what no child covers."""
    totals: Dict[str, int] = {}
    stack: List[List] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0) + own

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(1 << 62)
    return totals


def gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The idle intervals of `window`, longest first."""
    t0, t1 = window
    out, cursor = [], t0
    for start, end in union((s, e) for _, s, e in events):
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < t1:
        out.append((cursor, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def attribute(gap: Interval, spans: Sequence[Event]) -> str:
    """The harness span (other than the window) covering most of `gap`; ties
    go to the innermost (shortest) span; "unattributed" where none overlaps."""
    best, best_key = "unattributed", (0, 0)
    for name, start, end in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(end, gap[1]) - max(start, gap[0])
        key = (overlap, -(end - start))
        if overlap > 0 and key > best_key:
            best, best_key = name, key
    return best


LONG_GAP_NS = 1_000_000


def idle_by_span(events: Sequence[Event], spans: Sequence[Event], window: Interval) -> Dict[str, int]:
    """Idle nanoseconds by the harness span that owns each gap, long gaps
    (a millisecond or more: the host kept the device waiting) apart from the
    bubbles between one operation and the next."""
    totals: Dict[str, int] = {}
    for gap in gaps(events, window):
        length = gap[1] - gap[0]
        name = attribute(gap, spans) + (" >=1ms" if length >= LONG_GAP_NS else " <1ms")
        totals[name] = totals.get(name, 0) + length
    return totals


_HLO = re.compile(r"^(%[^ ]+) = (\(?[a-z0-9]+\[[0-9,]*\])[^ ]* ([a-z\-]+)\(")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line; the breakdown keeps
    `%name result-type opcode` (and the target of a custom call)."""
    found = _HLO.match(name)
    if not found:
        return name[:120]
    short = " ".join(found.groups())
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} {target.group(1)}" if target else short


def summarize(trace: Trace, top: int = 10) -> dict:
    """What a traced run reports: busy seconds averaged over the device
    planes, the window's length, and the breakdown."""
    window = window_of(trace.spans)
    if not trace.device_ops:
        raise ValueError("the trace holds no device plane with an ops line")
    busy, by_name, idle = [], {}, {}
    for events in trace.device_ops.values():
        events = clip(events, window)
        busy.append(busy_ns(events))
        for name, ns in self_time_by_name(events).items():
            by_name[name] = by_name.get(name, 0) + ns
        for name, ns in idle_by_span(events, trace.spans, window).items():
            idle[name] = idle.get(name, 0) + ns
    n = len(trace.device_ops)

    def ranked(totals, label=lambda name: name):
        rows = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        return [[label(name), ns / n / 1e9] for name, ns in rows]

    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "device_time_by_name_s": {name: ns / n / 1e9 for name, ns in by_name.items()},
        "breakdown": {"device_ops": ranked(by_name, short_name), "idle_gaps": ranked(idle)},
    }
