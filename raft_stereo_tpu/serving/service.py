"""The serving front: in-process submit API + stdlib HTTP endpoints.

`StereoService` composes the engine and batcher behind one object: boot
(`start()`) warms every executable, `submit()` admits a stereo pair into a
shape bucket and returns a Future, and `healthz()`/`metrics()` are the
payloads the HTTP front serializes. The HTTP layer is stdlib-only
(`http.server.ThreadingHTTPServer` — the repo adds no serving deps):

    POST /v1/predict   {"image1": [[[...]]], "image2": ..., "deadline_ms"?,
                        "max_iters"?, "stream_id"?} -> {"disparity": [[...]],
                        "iters_completed", "early_exit", "latency_ms",
                        "bucket"} (+ stream fields when "stream_id" is set)
    GET  /healthz      run_report-schema payload (validate_run_report-clean)
                       + an additive "serving" block
    GET  /metrics      ServingMetrics snapshot (queue depth, batch-fill,
                       p50/p99 latency, deadline-miss / early-exit counters)

Admission maps a request onto the SMALLEST configured bucket that fits both
dimensions (replicate-edge padding to the exact bucket shape via
InputPadder(target=...)); an image larger than every bucket is rejected —
HTTP 413 — because no warmed executable exists for it and compiling one
per stray shape is the exact failure mode the warmup design forbids.

The "disparity" field follows evaluate.py's convention: the unpadded
horizontal flow field (negative disparity), shape (H, W) of the ORIGINAL
input — bit-identical to what a direct padded model call returns.

Stream sessions (`ServeConfig.video` set): `submit_stream(stream_id, ...)`
admits consecutive frames of one video stream. The service keeps a
per-stream carry — the previous frame's low-res flow plus the warp error it
achieved on its own pair — and warm-starts the next frame through the
flow_init prelude executable warmed at boot, so streams add ZERO compiles to
the request path. The reset gate (video/session.py `should_reset`) runs at
admission on the already-host-resident padded images: a scene cut falls back
to a cold-start frame instead of refining from a wrong prior. Frames of one
stream must be submitted in order, each after the previous frame's future
resolves (the carry IS the previous result); distinct streams are
independent and freely concurrent, and the micro-batcher may mix warm and
cold rows in one batch (cold rows get zero flow_init — exact cold-start
semantics).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import os
import socket
import threading
import time
import urllib.parse
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

import numpy as np

from raft_stereo_tpu.config import ServeConfig
from raft_stereo_tpu.obs.memory import memory_block, set_memory_gauges
from raft_stereo_tpu.obs.prom import PROM_CONTENT_TYPE, Registry
from raft_stereo_tpu.obs.trace import Tracer, observability_block
from raft_stereo_tpu.serving.batcher import MicroBatcher, _Request
from raft_stereo_tpu.serving.engine import AnytimeEngine
from raft_stereo_tpu.serving.lifecycle import (
    HEALTH_STATES,
    CheckpointMismatchError,
    DeadlineInfeasibleError,
    ServiceUnavailableError,
    ServingLifecycle,
)
from raft_stereo_tpu.utils.padding import InputPadder
from raft_stereo_tpu.utils.run_report import build_run_report
from raft_stereo_tpu.video.session import flow_warp_error, should_reset

logger = logging.getLogger(__name__)


class BucketOverflowError(ValueError):
    """Input larger than every configured shape bucket (HTTP 413)."""


@dataclasses.dataclass
class _StreamEntry:
    """Per-stream carry: the previous frame's low-res flow and the warp
    error it achieved on its OWN frame pair (the reset-gate baseline)."""

    flow: np.ndarray  # (H/f, W/f) low-res flow at the padded bucket shape
    err: float
    bucket: Tuple[int, int]
    frames: int


class StereoService:
    def __init__(self, config: ServeConfig, variables=None):
        self.config = config
        # Persistent AOT executable cache (serving/aot.py): None when no
        # --aot_cache_dir was given (or it is unwritable); either engine
        # path below receives it and boots deserialize-first.
        from raft_stereo_tpu.serving.aot import maybe_cache

        self.aot_cache = maybe_cache(getattr(config, "aot_cache_dir", None), config)
        if config.replicas > 1:
            # Fleet path: one engine per device, per-replica breakers
            # aggregated by FleetLifecycle, failover requeue and rolling
            # hot-swap (serving/fleet.py). The engine/lifecycle surface is
            # identical, so everything below this branch is shared.
            from raft_stereo_tpu.serving.fleet import EngineFleet

            self.engine = EngineFleet(config, variables, aot_cache=self.aot_cache)
            self.lifecycle = self.engine.lifecycle
        else:
            # replicas=1 is NOT a one-replica fleet: it is the original
            # single-engine service, pinned bit-identical (uncommitted
            # default-device placement, one runner thread).
            self.lifecycle = ServingLifecycle(
                degrade_after=config.breaker_degrade_after,
                fail_after=config.breaker_fail_after,
                probation=config.breaker_probation,
            )
            self.engine = AnytimeEngine(
                config, variables, lifecycle=self.lifecycle,
                aot_cache=self.aot_cache,
            )
        self.batcher = MicroBatcher(config, self.engine, lifecycle=self.lifecycle)
        self.warm_summary: Optional[Dict[str, object]] = None
        self._started = False
        # The checkpoint path the served weights came from (None for an
        # in-memory boot). reload_checkpoint updates it; /healthz and the
        # /reload response surface it so a rollout orchestrator knows the
        # exact path to roll BACK to on abort.
        self.current_checkpoint: Optional[str] = (
            str(config.restore_ckpt) if config.restore_ckpt else None
        )
        self._streams: "collections.OrderedDict[str, _StreamEntry]" = (
            collections.OrderedDict()
        )
        self._streams_lock = threading.Lock()
        # -- observability (obs/ package) ----------------------------------
        # One tracer + one prom registry per service, wired post-construction
        # into the engine/batcher/lifecycle so none of their constructors
        # change. All hooks are host-side: zero device syncs, zero new
        # executables (tests/test_obs.py proves compiles are identical
        # obs-on vs obs-off).
        dump_path = None
        if config.log_dir:
            os.makedirs(config.log_dir, exist_ok=True)
            dump_path = os.path.join(config.log_dir, "flight_recorder.json")
        self.tracer = Tracer(
            capacity=config.flight_recorder_events, dump_path=dump_path
        )
        self.registry = Registry()
        self._last_memory: Optional[Dict[str, object]] = None
        self.engine.tracer = self.tracer
        self.batcher.tracer = self.tracer
        self.batcher.registry = self.registry
        self.batcher.memory_sampler = self._sample_memory
        self.lifecycle.on_transition = self._on_breaker_transition
        # A fleet aggregates per-replica breakers; each replica's own
        # transitions (and its engine's watchdog) must hit the same recorder.
        for replica_lc in getattr(self.engine, "replica_lifecycles", lambda: [])():
            replica_lc.on_transition = self._on_breaker_transition

    # -- observability plumbing -------------------------------------------
    def _on_breaker_transition(self, frm: str, to: str, reason: str) -> None:
        """Every breaker transition is recorded AND dumps the flight
        recorder — a breaker move is exactly the moment the last-N window
        is worth keeping."""
        self.tracer.event("breaker_transition", frm=frm, to=to, reason=reason)
        self.tracer.dump(f"breaker:{frm}->{to}")

    def _sample_memory(self) -> None:
        """Per-batch device-memory sample (batcher hook): prom gauges + the
        cached block /healthz serves without re-walking live buffers."""
        self._last_memory = set_memory_gauges(self.registry)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "StereoService":
        """Warm every (bucket, batch) executable, then open the batcher."""
        self.warm_summary = self.engine.warm()
        logger.info(
            "serving warmup: %d combos, %d compiles, %.1fs",
            self.warm_summary["combos"],
            self.warm_summary["compiles_total"],
            self.warm_summary["warm_seconds"],
        )
        self.batcher.start()
        self._started = True
        return self

    def close(self) -> None:
        if self._started:
            self.batcher.close()
            self._started = False
            # Exit-path dump: the last-N window at shutdown, next to
            # whatever diagnostics the deployment already writes.
            self.tracer.dump("service_close")
        self.engine.close()

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admission (new submits get 503), finish
        every queued + staged + running request, then close. Returns True
        if the backlog fully drained within the timeout; either way the
        service is closed afterwards (close() answers any stragglers with
        ServiceUnavailableError — no future is ever stranded)."""
        if timeout_s is None:
            timeout_s = self.config.drain_timeout_s
        self.lifecycle.start_drain()
        drained = True
        if self._started:
            drained = self.batcher.drain(timeout_s)
        self.close()
        return drained

    # -- HLO contract audit (tools/graftaudit) -----------------------------
    def audit_records(self) -> List[Dict[str, object]]:
        """Every graftaudit record collected at warm time (empty unless the
        config set hlo_audit=True). Fleet-aware: a fleet's records are the
        concatenation over replicas — each replica warmed its own per-device
        executables, and each must hold the contracts independently."""
        replicas = getattr(self.engine, "replicas", None)
        if replicas is not None:
            out: List[Dict[str, object]] = []
            for replica in replicas:
                out.extend(getattr(replica.engine, "audit_records", []))
            return out
        return list(getattr(self.engine, "audit_records", []))

    def hlo_audit_block(self) -> Dict[str, object]:
        """The CLI's `hlo_audit` block: contract stats over this boot's
        warmed executables plus rendered violation details (empty list on a
        healthy tree — `serve --warmup_only --audit` exits 4 otherwise)."""
        from tools.graftaudit.contracts import audit_records as _audit

        records = self.audit_records()
        violations, stats = _audit(records)
        block: Dict[str, object] = dict(stats)
        block["violation_details"] = [v.as_dict() for v in violations]
        return block

    def reload_checkpoint(self, path: str) -> Dict[str, object]:
        """Hot-swap the served weights from a checkpoint on disk (.pth or
        orbax dir) with zero recompiles — the POST /reload handler. With a
        fleet this is a ROLLING swap: one replica at a time while the rest
        keep serving; a mismatch on any replica aborts the roll and rolls
        the already-swapped replicas back (the fleet never serves mixed
        weights), surfacing as the same 409 the single engine returns."""
        import jax

        from raft_stereo_tpu.utils.checkpoints import load_variables

        new_vars = load_variables(path, self.config.model)
        prev_gen = self.engine.swap_generation
        prev_ckpt = self.current_checkpoint
        gen = self.engine.swap_variables(new_vars)
        self.current_checkpoint = str(path)
        logger.info("hot-swapped checkpoint %s -> generation %d", path, gen)
        return {
            "swap_generation": gen,
            "previous_generation": prev_gen,
            "checkpoint": str(path),
            "previous_checkpoint": prev_ckpt,
            "state": self.lifecycle.state,
            "replicas": self.engine.n_replicas,
            # What the swap actually validated before committing — the
            # rollout orchestrator records this, and an operator reading
            # the response knows the candidate matched the warmed
            # executables structurally (a mismatch would have been a 409).
            "validation": {
                "structure": "identical",
                "leaves": len(jax.tree.leaves(new_vars)),
            },
        }

    def __enter__(self) -> "StereoService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- admission ---------------------------------------------------------
    def pick_bucket(self, h: int, w: int) -> Tuple[int, int]:
        """Smallest configured bucket fitting (h, w), by padded area."""
        fits = [
            b
            for b in self.config.buckets
            if b[0] >= h and b[1] >= w
        ]
        if not fits:
            raise BucketOverflowError(
                f"input {h}x{w} exceeds every bucket "
                f"{list(self.config.buckets)}"
            )
        return min(fits, key=lambda b: b[0] * b[1])

    def _check_state(self) -> None:
        """Lifecycle gate, FIRST check on every submit: a draining or
        failed service sheds at admission (503) instead of queueing work it
        will fail or strand."""
        if not self.lifecycle.admissible():
            self.batcher.metrics.record_shed()
            raise ServiceUnavailableError(
                f"service not admitting requests (state={self.lifecycle.state})"
            )

    def _check_deadline(
        self, bucket: Tuple[int, int], deadline_s: Optional[float], now: float
    ) -> None:
        """Deadline-aware load shedding: if the queued work ahead of this
        request already uses up its whole budget (queue_depth × the warmed
        chunk estimate for its bucket), running it can only produce a
        guaranteed miss — shed at admission instead. Only fires when there
        IS a queue; an idle service admits every deadline and lets the
        engine's anytime early-exit do its best."""
        if deadline_s is None:
            return
        depth = self.batcher.queue_depth()
        if depth <= 0:
            return
        est = self.engine.chunk_estimate_s(bucket, 1)
        if est <= 0:
            return
        if now + depth * est > deadline_s:
            self.batcher.metrics.record_shed(deadline_infeasible=True)
            raise DeadlineInfeasibleError(
                f"deadline infeasible: {depth} queued request(s) x "
                f"{est * 1e3:.1f} ms/chunk exceeds the "
                f"{(deadline_s - now) * 1e3:.1f} ms budget"
            )

    def _admit(self, image1, image2):
        """Shared admission: validate, pick a bucket, pad host-side.
        Returns (bucket, padder, p1, p2)."""
        i1 = np.asarray(image1, np.float32)
        i2 = np.asarray(image2, np.float32)
        if i1.shape != i2.shape or i1.ndim != 3:
            raise ValueError(
                f"expected two equal (H, W, C) images, got {i1.shape} "
                f"and {i2.shape}"
            )
        h, w = i1.shape[0], i1.shape[1]
        try:
            bucket = self.pick_bucket(h, w)
        except BucketOverflowError:
            self.batcher.metrics.record_reject()
            raise
        padder = InputPadder(
            (1, h, w, i1.shape[2]),
            divis_by=self.config.divis_by,
            target=bucket,
        )
        # Pad host-side (np.pad, not padder.pad): jnp.pad on the submit
        # path would dispatch an eager jax op — one backend compile per
        # novel input shape, which the zero-post-warmup-recompiles
        # guarantee forbids. unpad stays pure numpy slicing.
        left, right, top, bottom = padder.pad_amounts
        p1 = np.pad(i1, ((top, bottom), (left, right), (0, 0)), mode="edge")
        p2 = np.pad(i2, ((top, bottom), (left, right), (0, 0)), mode="edge")
        return bucket, padder, p1, p2

    def submit(
        self,
        image1: np.ndarray,
        image2: np.ndarray,
        deadline_ms: Optional[float] = None,
        max_iters: Optional[int] = None,
    ) -> Future:
        """Admit one stereo pair; resolves to the response dict.

        `image1`/`image2` are (H, W, C) float or uint8 arrays of equal
        shape. `deadline_ms` is relative to NOW (None uses the config
        default; 0/None disables). The future's value:
        {"disparity": (H, W) float32, "iters_completed", "early_exit",
        "latency_ms", "bucket"}.
        """
        t_admit = time.monotonic()
        self._check_state()
        bucket, padder, p1, p2 = self._admit(image1, image2)
        now = time.monotonic()
        if deadline_ms is None:
            deadline_ms = self.config.deadline_ms
        deadline_s = now + deadline_ms / 1e3 if deadline_ms else None
        self._check_deadline(bucket, deadline_s, now)
        tid = None
        if self.tracer.enabled:
            # Trace ID minted at admission; the span covers validation +
            # host-side padding. Every later span of this request's
            # lifecycle (queue, chunk, respond) carries the same ID.
            tid = self.tracer.start_trace()
            self.tracer.span(
                "admission", trace=tid, t0=t_admit, t1=now, bucket=list(bucket)
            )
        req = _Request(
            image1=p1,
            image2=p2,
            bucket=bucket,
            deadline_s=deadline_s,
            max_iters=(
                self.config.max_iters if max_iters is None else int(max_iters)
            ),
            future=Future(),
            enqueue_t=now,
            trace_id=tid,
        )
        outer: Future = Future()

        def _deliver(inner: Future) -> None:
            exc = inner.exception()
            if exc is not None:
                outer.set_exception(exc)
                return
            res, latency_ms = inner.result()
            # GL005 waiver: res.flow_up is already HOST numpy — the engine
            # device_gets before building BatchResult. The cross-function
            # summary taints Padder.unpad's return because train-side call
            # sites pass device arrays; call-site-insensitive, so this
            # host-side use flags too.
            disparity = np.asarray(  # graftlint: disable=GL005
                padder.unpad(res.flow_up[None])[0, :, :, 0], np.float32
            )
            outer.set_result(
                {
                    "disparity": disparity,
                    "iters_completed": res.iters_completed,
                    "early_exit": res.early_exit,
                    "latency_ms": latency_ms,
                    "bucket": list(bucket),
                }
            )

        req.future.add_done_callback(_deliver)
        self.batcher.submit(req)
        return outer

    # -- stream sessions ---------------------------------------------------
    def submit_stream(
        self,
        stream_id: str,
        image1: np.ndarray,
        image2: np.ndarray,
        deadline_ms: Optional[float] = None,
        max_iters: Optional[int] = None,
    ) -> Future:
        """Admit one frame of a video stream (module docstring: ordering
        contract, warm-start + reset-gate semantics). The future's value is
        the `submit` response dict plus {"stream_id", "stream_frame",
        "warm_started", "reset"}. Warm frames default to
        `video.warm_iters`; cold frames to the serving `max_iters` budget;
        an explicit `max_iters` overrides either."""
        video = self.config.video
        if video is None:
            raise RuntimeError(
                "stream serving disabled: ServeConfig.video is None "
                "(serve with --stream)"
            )
        stream_id = str(stream_id)
        t_admit = time.monotonic()
        self._check_state()
        bucket, padder, p1, p2 = self._admit(image1, image2)
        factor = self.config.model.downsample_factor

        with self._streams_lock:
            entry = self._streams.get(stream_id)
            if entry is not None and entry.bucket != bucket:
                # Resolution change: carried flow is for another shape —
                # treat as a new scene.
                self._streams.pop(stream_id, None)
                entry = None
        warm = False
        reset = False
        flow_init = None
        if entry is not None and video.warm_start:
            err_candidate = flow_warp_error(p1, p2, entry.flow, factor)
            if should_reset(err_candidate, entry.err, video):
                reset = True
                with self._streams_lock:
                    self._streams.pop(stream_id, None)
            else:
                warm = True
                flow_init = entry.flow
        frame_idx = entry.frames if (entry is not None and not reset) else 0

        now = time.monotonic()
        if deadline_ms is None:
            deadline_ms = self.config.deadline_ms
        deadline_s = now + deadline_ms / 1e3 if deadline_ms else None
        self._check_deadline(bucket, deadline_s, now)
        if max_iters is None:
            max_iters = video.warm_iters if warm else self.config.max_iters
        tid = None
        if self.tracer.enabled:
            tid = self.tracer.start_trace()
            self.tracer.span(
                "admission",
                trace=tid,
                t0=t_admit,
                t1=now,
                bucket=list(bucket),
                stream_id=stream_id,
                warm=warm,
                reset=reset,
            )
        req = _Request(
            image1=p1,
            image2=p2,
            bucket=bucket,
            deadline_s=deadline_s,
            max_iters=int(max_iters),
            future=Future(),
            enqueue_t=now,
            flow_init=flow_init,
            trace_id=tid,
        )
        outer: Future = Future()

        def _deliver(inner: Future) -> None:
            exc = inner.exception()
            if exc is not None:
                # A failed frame leaves no trustworthy carry.
                with self._streams_lock:
                    self._streams.pop(stream_id, None)
                outer.set_exception(exc)
                return
            res, latency_ms = inner.result()
            err_out = flow_warp_error(p1, p2, res.flow_lowres, factor)
            with self._streams_lock:
                if np.isfinite(err_out):
                    self._streams[stream_id] = _StreamEntry(
                        flow=res.flow_lowres,
                        err=err_out,
                        bucket=bucket,
                        frames=frame_idx + 1,
                    )
                    self._streams.move_to_end(stream_id)
                    while len(self._streams) > self.config.max_streams:
                        # LRU eviction; the evicted stream's next frame
                        # simply cold-starts.
                        self._streams.popitem(last=False)
                else:
                    # Non-finite warp error means this frame's flow is not
                    # a trustworthy carry (NaN flow, degenerate warp): drop
                    # it so the NEXT frame cold-starts instead of refining
                    # from poison. This frame's own result still delivers.
                    self._streams.pop(stream_id, None)
            self.batcher.metrics.record_stream(warm, reset)
            # GL005 waiver: host numpy in, host numpy out — see the
            # identical non-stream deliver path above.
            disparity = np.asarray(  # graftlint: disable=GL005
                padder.unpad(res.flow_up[None])[0, :, :, 0], np.float32
            )
            outer.set_result(
                {
                    "disparity": disparity,
                    "iters_completed": res.iters_completed,
                    "early_exit": res.early_exit,
                    "latency_ms": latency_ms,
                    "bucket": list(bucket),
                    "stream_id": stream_id,
                    "stream_frame": frame_idx,
                    "warm_started": warm,
                    "reset": reset,
                }
            )

        req.future.add_done_callback(_deliver)
        self.batcher.submit(req)
        return outer

    def streams_active(self) -> int:
        with self._streams_lock:
            return len(self._streams)

    # -- observability -----------------------------------------------------
    def metrics(self) -> Dict[str, object]:
        return self.batcher.metrics.snapshot(
            queue_depth=self.batcher.queue_depth(),
            streams_active=self.streams_active(),
        )

    def boot_block(self) -> Dict[str, object]:
        """The instant-boot/recovery numbers: warmup wall time, AOT cache
        hit accounting and replica respawns — served in /healthz, mirrored
        into prom gauges (tests/report_checks.py `validate_boot` pins its
        invariants)."""
        ws = self.warm_summary or {}
        cache = ws.get("aot_cache") or {"enabled": False}
        return {
            "warmup_seconds": float(
                ws.get("warmup_seconds", ws.get("warm_seconds", 0.0)) or 0.0
            ),
            "cache_enabled": bool(cache.get("enabled", False)),
            "cache_hits": int(cache.get("cache_hits", 0)),
            "cache_misses": int(cache.get("cache_misses", 0)),
            "entries": int(cache.get("entries", 0)),
            "evictions": int(cache.get("evictions", 0)),
            "compiles_total": int(ws.get("compiles_total", 0)),
            "respawns_total": int(self.batcher.metrics.respawns_total),
        }

    # ServingMetrics counters mirrored into prom at render time (the
    # authority stays with ServingMetrics — set_total asserts monotonicity
    # instead of double-counting on the hot path).
    _PROM_COUNTER_KEYS = (
        "requests_total",
        "responses_total",
        "rejected_total",
        "shed_total",
        "deadline_infeasible_total",
        "failed_requests_total",
        "deadline_miss_total",
        "early_exit_total",
        "batches_total",
        "stream_requests_total",
        "warm_start_total",
        "stream_resets_total",
        "requeues_total",
        "respawns_total",
    )

    def render_prom(self) -> str:
        """Render the prom registry after syncing the snapshot-style series
        (counters, queue-depth and replica-state gauges) into it. The
        request-path histograms (queue-wait/device/host-gap) were observed
        live by the batcher; this only touches render-time mirrors."""
        reg = self.registry
        snap = self.metrics()
        for key in self._PROM_COUNTER_KEYS:
            reg.counter(
                f"raft_serving_{key}", f"ServingMetrics {key}"
            ).set_total(float(snap[key]))
        for bkey, v in snap["requests_by_bucket"].items():
            reg.counter(
                "raft_serving_requests_by_bucket",
                "Admitted requests per shape bucket",
            ).set_total(float(v), bucket=bkey)
        reg.gauge(
            "raft_serving_queue_depth", "Total queued requests across buckets"
        ).set(float(snap["queue_depth"]))
        for bucket, depth in self.batcher.queue_depths().items():
            reg.gauge(
                "raft_serving_queue_depth_bucket", "Queued requests per bucket"
            ).set(float(depth), bucket=f"{bucket[0]}x{bucket[1]}")
        reg.gauge("raft_serving_streams_active", "Live stream sessions").set(
            float(snap["streams_active"])
        )
        reg.gauge(
            "raft_serving_batch_fill_mean", "Mean real/padded batch fill"
        ).set(float(snap["batch_fill_mean"]))
        state_gauge = reg.gauge(
            "raft_serving_state_code",
            "Health state index: "
            + " ".join(f"{i}={s}" for i, s in enumerate(HEALTH_STATES)),
        )
        lc = self.lifecycle.snapshot()
        state_gauge.set(
            float(HEALTH_STATES.index(lc["state"])), replica="aggregate"
        )
        for idx, st in enumerate(lc.get("replica_states", [])):
            state_gauge.set(float(HEALTH_STATES.index(st)), replica=f"r{idx}")
        # Instant-boot/recovery gauges (PR 16): one scrape answers "did the
        # last boot hit the AOT cache, and how long did it take".
        boot = self.boot_block()
        reg.gauge(
            "raft_serving_warmup_seconds", "Wall time of the boot warmup"
        ).set(boot["warmup_seconds"])
        reg.gauge(
            "raft_serving_aot_cache_hits",
            "Warmup executables loaded from the AOT cache",
        ).set(float(boot["cache_hits"]))
        reg.gauge(
            "raft_serving_aot_cache_misses",
            "Warmup executables traced and compiled (cache miss)",
        ).set(float(boot["cache_misses"]))
        return reg.render()

    def healthz(self) -> Dict[str, object]:
        """A run_report-schema payload (the orchestrator contract the repo
        already validates) plus an additive `serving` block — the same
        trick the jit_hygiene block uses: validate_run_report ignores
        unknown keys, so one validator covers both trainer and server."""
        report = build_run_report(
            stop_cause="completed",
            final_step=self.engine.batches_total,
            jit_hygiene=self.engine.hygiene.report(),
            observability=observability_block(self.tracer),
        )
        report["serving"] = {
            "warmed": self.engine.warmed,
            "state": self.lifecycle.state,
            "lifecycle": self.lifecycle.snapshot(),
            "swap_generation": self.engine.swap_generation,
            "checkpoint": self.current_checkpoint,
            "replicas": self.engine.n_replicas,
            "buckets": [list(b) for b in self.config.buckets],
            "batch_sizes": list(self.config.batch_sizes),
            "chunk_iters": self.config.chunk_iters,
            "max_iters": self.config.max_iters,
            "stream_support": self.config.video is not None,
            # Instant-boot & self-heal numbers (PR 16): warmup wall time,
            # AOT cache hit accounting, replica respawns.
            "boot": self.boot_block(),
            # Latency attribution + the last per-batch device-memory sample
            # (fresh sample when no batch has run yet). Additive keys on the
            # serving block — the frozen legacy surface is /metrics JSON,
            # not /healthz.
            "attribution": self.batcher.metrics.attribution_summary(),
            "memory": (
                self._last_memory
                if self._last_memory is not None
                else memory_block()
            ),
            **self.metrics(),
        }
        return report


def _json_response(handler: BaseHTTPRequestHandler, code: int, payload) -> None:
    body = json.dumps(payload).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def _text_response(
    handler: BaseHTTPRequestHandler, code: int, body: str, content_type: str
) -> None:
    raw = body.encode("utf-8")
    handler.send_response(code)
    handler.send_header("Content-Type", content_type)
    handler.send_header("Content-Length", str(len(raw)))
    handler.end_headers()
    handler.wfile.write(raw)


def make_http_server(
    service: StereoService,
    host: str = "127.0.0.1",
    port: int = 0,
    handler_timeout_s: float = 30.0,
) -> ThreadingHTTPServer:
    """Bind (but don't run) the HTTP front; port 0 picks an ephemeral port
    (tests read it back from `server.server_address`).

    `handler_timeout_s` is the per-connection socket timeout (slowloris
    hardening): `BaseHTTPRequestHandler.timeout` makes `setup()` call
    `connection.settimeout()`, so a client that connects and stalls — on
    the request line, the headers, or mid-body — times out instead of
    wedging a handler thread forever. A stall before the request parses
    closes the connection silently (stdlib `handle_one_request` catches
    the timeout); a stall inside a POST body gets a clean 408 before the
    close, because by then the client spoke enough protocol to deserve an
    answer."""

    class Handler(BaseHTTPRequestHandler):
        timeout = handler_timeout_s

        def log_message(self, fmt, *args):  # quiet by default
            logger.debug("http: " + fmt, *args)

        def _read_body_or_408(self) -> Optional[bytes]:
            """Read Content-Length bytes; a mid-body stall answers 408 and
            closes (None return ends the request)."""
            try:
                length = int(self.headers.get("Content-Length", "0"))
                return self.rfile.read(length) if length else b""
            except (socket.timeout, TimeoutError):
                _json_response(
                    self, 408, {"error": "request body read timed out"}
                )
                self.close_connection = True
                return None

        def do_GET(self):
            parsed = urllib.parse.urlparse(self.path)
            if parsed.path == "/healthz":
                _json_response(self, 200, service.healthz())
            elif parsed.path == "/metrics":
                query = urllib.parse.parse_qs(parsed.query)
                fmt = query.get("format", ["json"])[0]
                if fmt == "prom":
                    # Prometheus text exposition 0.0.4; the JSON snapshot
                    # stays the default and byte-compatible — scrapers must
                    # opt in.
                    _text_response(
                        self, 200, service.render_prom(), PROM_CONTENT_TYPE
                    )
                elif fmt == "json":
                    _json_response(self, 200, service.metrics())
                else:
                    _json_response(
                        self,
                        400,
                        {"error": f"unknown metrics format {fmt!r}"},
                    )
            else:
                _json_response(self, 404, {"error": f"no route {self.path}"})

        def do_POST(self):
            raw = self._read_body_or_408()
            if raw is None:
                return
            if self.path == "/reload":
                try:
                    body = json.loads(raw) if raw else {}
                    ckpt = body["checkpoint"]
                except (KeyError, ValueError, json.JSONDecodeError) as exc:
                    _json_response(self, 400, {"error": f"bad request: {exc!r}"})
                    return
                try:
                    out = service.reload_checkpoint(ckpt)
                except CheckpointMismatchError as exc:
                    # The candidate would force a recompile — refused, old
                    # tree keeps serving. 409: the conflict is with server
                    # state, not request syntax.
                    _json_response(self, 409, {"error": str(exc)})
                    return
                except (OSError, ValueError) as exc:
                    _json_response(self, 400, {"error": repr(exc)})
                    return
                except Exception as exc:
                    logger.exception("reload failed")
                    _json_response(self, 500, {"error": repr(exc)})
                    return
                _json_response(self, 200, out)
                return
            if self.path != "/v1/predict":
                _json_response(self, 404, {"error": f"no route {self.path}"})
                return
            try:
                body = json.loads(raw)
                i1 = np.asarray(body["image1"], np.float32)
                i2 = np.asarray(body["image2"], np.float32)
            except (KeyError, ValueError, json.JSONDecodeError) as exc:
                _json_response(self, 400, {"error": f"bad request: {exc!r}"})
                return
            try:
                if body.get("stream_id") is not None:
                    fut = service.submit_stream(
                        body["stream_id"],
                        i1,
                        i2,
                        deadline_ms=body.get("deadline_ms"),
                        max_iters=body.get("max_iters"),
                    )
                else:
                    fut = service.submit(
                        i1,
                        i2,
                        deadline_ms=body.get("deadline_ms"),
                        max_iters=body.get("max_iters"),
                    )
                out = fut.result()
            except BucketOverflowError as exc:
                _json_response(self, 413, {"error": str(exc)})
                return
            except ServiceUnavailableError as exc:
                # Shed (draining/failed/deadline-infeasible): the service
                # state, not the request, is at fault — 503, never 413.
                _json_response(
                    self,
                    503,
                    {"error": str(exc), "state": service.lifecycle.state},
                )
                return
            except RuntimeError as exc:
                # stream_id against a service without ServeConfig.video
                _json_response(self, 400, {"error": str(exc)})
                return
            except Exception as exc:
                logger.exception("predict failed")
                _json_response(self, 500, {"error": repr(exc)})
                return
            out = dict(out, disparity=out["disparity"].tolist())
            # Generation stamp: which weight generation answered. The
            # frontier's response ledger folds these into its
            # mixed_generation_seconds proof, so the zero-mixed-weight
            # rollout claim is machine-checked per answer, not asserted.
            out["swap_generation"] = service.engine.swap_generation
            _json_response(self, 200, out)

    return ThreadingHTTPServer((host, port), Handler)


def serve_http(service: StereoService, host: str, port: int) -> None:
    """Blocking server loop (the `serve` CLI path); Ctrl-C shuts down
    cleanly."""
    server = make_http_server(service, host, port)
    logger.info("serving on http://%s:%d", *server.server_address)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        # Graceful: requests already admitted still get answers before the
        # executor tears down (drain() closes afterwards either way).
        service.drain()


__all__ = [
    "BucketOverflowError",
    "StereoService",
    "make_http_server",
    "serve_http",
]
