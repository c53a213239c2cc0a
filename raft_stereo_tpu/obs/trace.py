"""Structured tracing + bounded flight recorder.

A `Tracer` stamps host-side spans (name, trace ID, start/end on the
monotonic clock) and point events into a ring-buffer `FlightRecorder`
capped at N records — O(1) memory forever, and the last N records are
exactly the "what was the system doing in the seconds before" evidence
the fault machinery lacked. Dump sites: the serving watchdog's hang
handler, every breaker transition, non-finite training events, and the
trainer's crash/exit path — each writes `flight_recorder.json` next to
the existing diagnostics via the same atomic-rename discipline as
run_report.json.

Span taxonomy (see README "Observability"):
  serving  request: admission -> queue -> stage -> chunk* -> finalize -> respond
  training step:    data-wait -> step -> (coord-sync | checkpoint-save)*

  evaluation call:  evaluate/call -> evaluate/stage, evaluate/forward,
                    evaluate/fetch
  fit:              train/fit -> train/start, train/steps, train/drain,
                    train/final_save

ONE span entry point, `span(name, **attrs)`, usable without a `Tracer`
(`Evaluator` has none; `Tracer.timed` goes through it): it enters a
`jax.profiler.TraceAnnotation("rs/<name>")` — a host-plane event on the
device trace's clock, nearly free while no profiler session is open — and
appends `name, t0, t1, id, parent, root, attrs` to ONE process-wide bounded
log that outlives the fit or the service that wrote it
(`process_spans(name=None)`). `parent` is the span that was open on this
thread when the span began, `root` the outermost one: the spans of one
evaluation call or one fit share it. The log holds plain scalars only,
never an array.

Hot-path cost: one `deque.append` (O(1)) per record plus two
`perf_counter` reads and one no-op annotation per span. No locks are held
across user code, no device work is ever dispatched — the
zero-sync/zero-executable serving and training contracts hold with tracing
fully enabled (asserted in tests/test_obs.py).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

FLIGHT_RECORDER_VERSION = 1

# -- the process-wide span log and the one span entry point -------------------

PROCESS_LOG_CAPACITY = 4096
ANNOTATION_PREFIX = "rs/"

_log: deque = deque(maxlen=PROCESS_LOG_CAPACITY)
_log_lock = threading.Lock()
_span_ids = itertools.count(1)
# The span open on this thread (threads start with none): (id, root id).
_open_span: contextvars.ContextVar = contextvars.ContextVar("rs_open_span", default=None)
_PLAIN = (bool, int, float, str, type(None))
_annotation = None


def _plain(value: Any) -> Any:
    """Attributes as the log keeps them: scalars and flat lists of scalars;
    anything else (an array, say) by the name of its type."""
    if isinstance(value, _PLAIN):
        return value
    if isinstance(value, (list, tuple)) and all(isinstance(v, _PLAIN) for v in value):
        return list(value)
    return f"<{type(value).__name__}>"


def _lineage():
    """(a new span's id, its parent's, its root's) under the span open on
    this thread; a span with none open is its own root."""
    outer, span_id = _open_span.get(), next(_span_ids)
    return (span_id, outer[0], outer[1]) if outer else (span_id, None, span_id)


def _log_span(name, t0, t1, span_id, parent, root, attrs) -> None:
    record = {"name": name, "t0": t0, "t1": t1, "id": span_id, "parent": parent, "root": root}
    if attrs:
        record["attrs"] = {k: _plain(v) for k, v in attrs.items()}
    with _log_lock:
        _log.append(record)


class Span:
    """One open span. A context manager; `begin()` / `end()` serve the spans
    whose start and end lie in different blocks (fit's phases). `end()` is
    idempotent; `drop()` ends without a record."""

    __slots__ = ("name", "attrs", "id", "parent", "root", "t0", "t1", "dropped", "_outer", "_note")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs
        self.t0 = self.t1 = None
        self.dropped = False

    def begin(self) -> "Span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._outer = _open_span.get()
        self.id, self.parent, self.root = _lineage()
        _open_span.set((self.id, self.root))
        self._note = _annotation(
            ANNOTATION_PREFIX + self.name, span=self.id, parent=self.parent or 0, root=self.root
        )
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def _close(self) -> bool:
        if self.t0 is None or self.t1 is not None:
            return False
        self.t1 = time.perf_counter()
        self._note.__exit__(None, None, None)
        _open_span.set(self._outer)
        return True

    def end(self) -> None:
        if self._close():
            _log_span(self.name, self.t0, self.t1, self.id, self.parent, self.root, self.attrs)

    def drop(self) -> None:
        self.dropped = True
        self._close()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def span(name: str, **attrs: Any) -> Span:
    """The span entry point: `with span("evaluate/stage"): ...`."""
    return Span(name, attrs)


def record_span(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """A span whose times were taken elsewhere: the log entry without the
    annotation (a profiler event cannot be backdated)."""
    _log_span(name, t0, t1, *_lineage(), attrs)


def _with_trace(attrs: Dict[str, Any], trace: Optional[int]) -> Dict[str, Any]:
    return attrs if trace is None else dict(attrs, trace=trace)


def process_spans(name: Optional[str] = None) -> List[Dict[str, Any]]:
    """The process-wide log, oldest first (`name`: only spans so named)."""
    with _log_lock:
        records = list(_log)
    return [dict(r) for r in records if name is None or r["name"] == name]


@contextmanager
def profile(logdir: str = "runs/profile"):
    """Capture a `jax.profiler` device trace of everything inside the block
    (the trainer's `profile_steps` window; usable around any jitted call).
    The `rs/` spans opened inside land in its host plane."""
    import logging

    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logging.getLogger(__name__).info("profiler trace written to %s", logdir)


class FlightRecorder:
    """Bounded ring of span/event records with lifetime counters.

    capacity <= 0 disables recording entirely (append is a cheap no-op);
    the counters still exist so the `observability` report block stays
    fully populated either way."""

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self._ring: Optional[deque] = (
            deque(maxlen=self.capacity) if self.capacity > 0 else None
        )
        self._lock = threading.Lock()
        self.spans_total = 0
        self.events_total = 0
        self.dropped_total = 0
        self.dumps_total = 0

    @property
    def enabled(self) -> bool:
        return self._ring is not None

    def append(self, record: Dict[str, Any]) -> None:
        ring = self._ring
        with self._lock:
            if record.get("kind") == "event":
                self.events_total += 1
            else:
                self.spans_total += 1
            if ring is None:
                self.dropped_total += 1
                return
            if len(ring) == self.capacity:
                self.dropped_total += 1
            ring.append(record)

    def records(self) -> List[Dict[str, Any]]:
        ring = self._ring
        if ring is None:
            return []
        with self._lock:
            return list(ring)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {
                "spans_total": self.spans_total,
                "events_total": self.events_total,
                "dropped_total": self.dropped_total,
                "dumps_total": self.dumps_total,
            }


class Tracer:
    """Span/event producer over one FlightRecorder.

    Trace IDs are process-local monotonically increasing ints
    (`itertools.count` — allocation is a single GIL-atomic `next`). A
    request's ID is minted at admission and rides every later record of
    its lifecycle; batch-level records (stage, chunk, finalize) carry the
    full ID list of the requests they cover under `traces`."""

    def __init__(self, capacity: int = 256, dump_path: Optional[str] = None):
        self.recorder = FlightRecorder(capacity)
        self._ids = itertools.count(1)
        self._traces_lock = threading.Lock()
        self.traces_total = 0
        # Default flight_recorder.json location; None = dumps are skipped
        # (counted as requested-but-unwritten is unnecessary — disabled
        # recorders simply never dump).
        self.dump_path = dump_path

    @property
    def enabled(self) -> bool:
        return self.recorder.enabled

    def start_trace(self) -> int:
        with self._traces_lock:
            self.traces_total += 1
        return next(self._ids)

    def span(
        self,
        name: str,
        trace: Optional[int] = None,
        t0: Optional[float] = None,
        t1: Optional[float] = None,
        **attrs: Any,
    ) -> None:
        """An after-the-fact span (its times handed in, so no annotation):
        into the process-wide log and this tracer's flight recorder."""
        now = time.perf_counter()
        t0 = now if t0 is None else t0
        t1 = now if t1 is None else t1
        record_span(name, t0, t1, **_with_trace(attrs, trace))
        self._record(name, trace, t0, t1, attrs)

    def _record(self, name: str, trace: Optional[int], t0: float, t1: float, attrs) -> None:
        if not self.recorder.enabled:
            # Still count (cheap) so the report block reflects intent.
            self.recorder.append({"kind": "span"})
            return
        record: Dict[str, Any] = {
            "kind": "span",
            "name": name,
            "t0": t0,
            "t1": t1,
            "ms": (t1 - t0) * 1e3,
        }
        if trace is not None:
            record["trace"] = trace
        if attrs:
            record["attrs"] = attrs
        self.recorder.append(record)

    @contextmanager
    def timed(self, name: str, trace: Optional[int] = None, **attrs: Any):
        """A span through the one entry point (annotation + process-wide
        log), written to this tracer's flight recorder as well."""
        entry = span(name, **_with_trace(attrs, trace))
        try:
            with entry:
                yield entry
        finally:
            if not entry.dropped:
                self._record(name, trace, entry.t0, entry.t1, attrs)

    def event(self, name: str, trace: Optional[int] = None, **attrs: Any) -> None:
        if not self.recorder.enabled:
            self.recorder.append({"kind": "event"})
            return
        record: Dict[str, Any] = {
            "kind": "event",
            "name": name,
            "t": time.perf_counter(),
        }
        if trace is not None:
            record["trace"] = trace
        if attrs:
            record["attrs"] = attrs
        self.recorder.append(record)

    def dump(self, reason: str, path: Optional[str] = None) -> Optional[str]:
        """Write the last-N records as flight_recorder.json (atomic
        rename, same discipline as run_report.json). Returns the path
        written, or None when no path is configured / recording is off.
        Never raises: dump sites are failure handlers — a failing dump
        must not mask the failure being recorded."""
        path = path if path is not None else self.dump_path
        if path is None or not self.recorder.enabled:
            return None
        payload = {
            "flight_recorder_version": FLIGHT_RECORDER_VERSION,
            "reason": str(reason),
            "dumped_at_unix": time.time(),
            "counters": self.recorder.counters(),
            "traces_total": int(self.traces_total),
            "records": self.recorder.records(),
        }
        try:
            from raft_stereo_tpu.utils.run_report import atomic_write_json

            atomic_write_json(path, payload)
        except Exception:  # noqa: BLE001 - see docstring
            import logging

            logging.getLogger(__name__).warning(
                "could not write flight recorder dump to %s", path, exc_info=True
            )
            return None
        with self.recorder._lock:
            self.recorder.dumps_total += 1
        return path


def observability_block(tracer: Optional[Tracer]) -> Dict[str, Any]:
    """The additive `observability` block for run_report.json (schema v2
    discipline: absent means "not measured"; present means complete and
    typed — scripts/check_run_report.py validates it)."""
    if tracer is None:
        return {
            "enabled": False,
            "capacity": 0,
            "traces_total": 0,
            "spans_total": 0,
            "events_total": 0,
            "dropped_total": 0,
            "dumps_total": 0,
        }
    counters = tracer.recorder.counters()
    return {
        "enabled": bool(tracer.enabled),
        "capacity": int(tracer.recorder.capacity if tracer.enabled else 0),
        "traces_total": int(tracer.traces_total),
        "spans_total": int(counters["spans_total"]),
        "events_total": int(counters["events_total"]),
        "dropped_total": int(counters["dropped_total"]),
        "dumps_total": int(counters["dumps_total"]),
    }


def load_flight_recorder(path: str) -> Dict[str, Any]:
    """Parse a flight_recorder.json dump (test/tooling helper)."""
    with open(path, "r") as f:
        payload = json.load(f)
    if payload.get("flight_recorder_version") != FLIGHT_RECORDER_VERSION:
        raise ValueError(
            f"unsupported flight recorder version in {path!r}: "
            f"{payload.get('flight_recorder_version')!r}"
        )
    return payload
