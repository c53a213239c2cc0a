"""Unified observability layer: spans, component scopes, metrics, memory.

Four pillars, all host-side:

- `trace`: ONE span entry point, `span(name)` — a
  `jax.profiler.TraceAnnotation("rs/<name>")` on the device trace's clock
  plus a record in one process-wide bounded log (`process_spans()`) — and
  the `Tracer` built on it: spans with trace IDs into a per-owner
  ring-buffer `FlightRecorder`, dumped as `flight_recorder.json` by the
  watchdog, breaker transitions, non-finite events, and crash/exit paths —
  the "what was the system doing in the seconds before" record.
  `profile(logdir)` captures a device trace around a block.
- `scopes`: which model component (encoder, corr_build, lookup, gru08, ...)
  and phase (forward, backward, recompute) each instruction of a compiled
  program belongs to, from the `op_name` its optimized HLO text carries;
  `Evaluator` and `Trainer` register their programs there, a trace reader
  joins device time to it by instruction name.
- `prom`: a Prometheus text-exposition (0.0.4) registry — counters,
  gauges, histograms with explicit buckets — behind `GET
  /metrics?format=prom` in serving and a stdlib HTTP sidecar
  (`--metrics_port`) in training.
- `memory`: guarded `device.memory_stats()` + live-buffer accounting
  (absent on CPU — degrades to zeros with `available: false`).

The hot-path contract that makes this TPU-native rather than bolted-on:
nothing here dispatches device work, transfers, or syncs. Spans timestamp
host events only; scopes are trace-time metadata, printed and parsed only
when a reader asks; device time comes from a profiler trace, or from the
wall clock around the already-present `block_until_ready` boundaries.
"""

from raft_stereo_tpu.obs.memory import (
    memory_block,
    sample_device_memory,
    set_memory_gauges,
)
from raft_stereo_tpu.obs.prom import (
    PROM_CONTENT_TYPE,
    Counter,
    Gauge,
    Histogram,
    Registry,
    serve_registry,
)
from raft_stereo_tpu.obs.trace import (
    FlightRecorder,
    Tracer,
    load_flight_recorder,
    observability_block,
    process_spans,
    profile,
    span,
)

__all__ = [
    "PROM_CONTENT_TYPE",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "Registry",
    "Tracer",
    "load_flight_recorder",
    "memory_block",
    "observability_block",
    "process_spans",
    "profile",
    "sample_device_memory",
    "serve_registry",
    "set_memory_gauges",
    "span",
]
