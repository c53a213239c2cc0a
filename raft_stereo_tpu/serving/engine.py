"""The anytime inference engine: warmed executables + chunked refinement.

Serving must never compile on the request path — an XLA compile is seconds,
a request budget is milliseconds. The engine therefore warms every
executable it will ever run at BOOT: for each configured shape bucket and
each warmed batch size, the three stage programs from models/anytime.py
(prelude, chunk, finalize) are traced and compiled against zero inputs, and
a per-(bucket, batch) chunk wall time is measured on the compiled code.
After warmup the engine's RecompileMonitor treats ANY further compile as a
violation — the serving e2e test asserts `compiles_post_grace == 0` after
traffic, which is the machine-checked form of "zero recompiles in steady
state".

Refinement runs as `ceil(max_iters / chunk_iters)` chunk calls. The host
blocks on each chunk's completion and checks deadlines between calls: a
request whose deadline would pass during the NEXT chunk (current time +
measured chunk estimate) is finalized NOW from the best-so-far state and
delivered early with its `iters_completed` recorded. Because every chunk
advances the same carried state the monolithic forward scans, k chunks +
finalize is bit-identical to a direct `iters = k * chunk_iters` call — the
anytime ladder costs no accuracy at any rung (tests/test_serving.py).

The per-chunk host sync is deliberate: deadline checks are only meaningful
against completed device work. On CPU it is free; on TPU it bounds the
dispatch pipeline at one chunk, which is exactly the deadline-check
granularity the config chose via `chunk_iters`.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from raft_stereo_tpu.config import ServeConfig
from raft_stereo_tpu.models.anytime import (
    AnytimeChunk,
    AnytimeFinalize,
    AnytimePrelude,
)
from raft_stereo_tpu.models.init_cache import init_model_variables
from raft_stereo_tpu.serving.aot import ExecutableCache, entry_key
from raft_stereo_tpu.serving.lifecycle import (
    CheckpointMismatchError,
    ServingLifecycle,
)
from raft_stereo_tpu.utils.jit_hygiene import JitHygiene
from raft_stereo_tpu.utils.resilience import StepWatchdog


@dataclasses.dataclass
class BatchResult:
    """Per-request outcome of one engine batch."""

    flow_up: np.ndarray  # (H, W, 1) padded-bucket resolution, float32
    iters_completed: int
    early_exit: bool
    # (H/f, W/f) low-res flow at delivery — the stream-session carry
    # (service.submit_stream feeds it back as the next frame's flow_init).
    # Tiny relative to flow_up, so it is fetched unconditionally.
    flow_lowres: Optional[np.ndarray] = None
    # Wall time this batch spent in completed device work up to this
    # request's delivery: the sum of per-chunk walls measured around the
    # chunk loop's EXISTING `block_until_ready` boundaries (plus the
    # blocking finalize fetch) — device-time attribution with zero new
    # syncs. The batcher subtracts it (and queue wait) from end-to-end
    # latency to get the host gap.
    device_time_s: float = 0.0


class AnytimeEngine:
    """Warmed, chunked, deadline-aware refinement over one parameter tree.

    Thread-safety: `run_batch` holds an internal lock — the device is one
    serial resource and interleaving two batches' chunk streams would
    corrupt neither but pipeline both worse. Staging (device_put) happens
    OUTSIDE the lock, in the batcher's stager thread, which is what makes
    the double-buffering overlap real.
    """

    # One engine is one fault domain; the fleet (serving/fleet.EngineFleet)
    # overrides this with its replica count so the batcher can size its
    # runner pool without knowing which it holds.
    n_replicas = 1

    # Flight-recorder tracer (obs/trace.Tracer), set post-construction by
    # the service so direct engine construction (tests, bench) needs no new
    # arguments. None = no spans, no dumps.
    tracer = None

    def __init__(
        self,
        config: ServeConfig,
        variables=None,
        lifecycle: Optional[ServingLifecycle] = None,
        device=None,
        hygiene: Optional[JitHygiene] = None,
        aot_cache: Optional[ExecutableCache] = None,
    ):
        self.config = config
        self.lifecycle = lifecycle if lifecycle is not None else ServingLifecycle()
        if variables is None:
            # Init with the UNMODIFIED model config: params are identical
            # either way and the init trace needs no activation-mesh scope.
            variables = init_model_variables(config.model)
        # `device` pins this engine to one chip (a fleet replica): the
        # variable tree is COMMITTED there and warmup traces against inputs
        # committed to the same device, so the whole warmed cache dispatches
        # onto that chip and nowhere else. None keeps the original
        # single-engine placement (uncommitted, default device) — the
        # `--replicas 1` path must stay bit-identical to the pre-fleet
        # service, and committing arrays would change the jit cache keys.
        self.device = device
        if device is not None:
            variables = jax.device_put(variables, device)
        self.variables = variables
        mcfg = config.model
        self.sharding = None
        n_local = len(jax.local_devices())
        if config.sharding_rules != "dp" and n_local > 1:
            from raft_stereo_tpu.parallel.mesh import make_mesh
            from raft_stereo_tpu.parallel.sharding import ShardingEngine

            # Serving batches are small (1..max_batch) and vary per request,
            # so every spatial preset maps to a pure-spatial mesh here: each
            # warmed executable — batch 1 included — H-shards its cost
            # volume and GRU state over ALL local devices instead of leaving
            # n-1 of them idle.
            self.sharding = ShardingEngine(make_mesh((1, n_local)), "spatial")
            mcfg = dataclasses.replace(mcfg, spatial_constraints=True)
        wrap = self.sharding.wrap if self.sharding is not None else (lambda f: f)
        self._prelude_fn = wrap(jax.jit(AnytimePrelude(mcfg).apply))
        self._chunk_fn = wrap(
            jax.jit(AnytimeChunk(mcfg, chunk_iters=config.chunk_iters).apply)
        )
        self._finalize_fn = wrap(jax.jit(AnytimeFinalize(mcfg).apply))
        # grace 0: every non-whitelisted compile counts. Warmup runs inside
        # a whitelist("warmup") window; after warm() returns, compiles_post_grace
        # staying 0 IS the zero-recompile serving guarantee. The monitor's
        # compile listener is PROCESS-WIDE, so a fleet passes one shared
        # JitHygiene to all its replicas — per-replica monitors would each
        # count every other replica's warmup as a violation.
        if hygiene is None:
            hygiene = JitHygiene(strict=False, recompile_grace=0)
            hygiene.monitor.label = "serving"
        self.hygiene = hygiene
        # AOT executable cache (serving/aot.py). None = legacy behavior:
        # warm() traces through the jit objects exactly as before. With a
        # cache, warm() resolves each stage executable deserialize-first
        # (zero compiles on a hit) and run_batch dispatches through the
        # resolved map in `self._exec`, keyed on concrete arg shapes, with
        # the jit objects as fallback — the cache-disabled path stays
        # bit-identical to the pre-cache engine.
        self.aot_cache = aot_cache
        self._exec: Dict[Tuple, object] = {}
        # HLO contract audit (tools/graftaudit; gated by config.hlo_audit):
        # one record per warmed executable — HLO text, carried-state
        # shardings, provenance meta — appended by _warm_stage. Cache HITS
        # replay the snapshot stored alongside the executable (deserialized
        # executables don't reliably expose as_text), so the record set
        # always covers exactly the executables this boot warmed.
        self.audit_records: List[dict] = []
        self._chunk_est_s: Dict[Tuple[Tuple[int, int], int], float] = {}
        self._lock = threading.Lock()
        self._warmed = False
        self.batches_total = 0
        # Monotone hot-swap counter: bumped by each successful
        # swap_variables; surfaced in /healthz so operators can verify a
        # POST /reload actually landed.
        self.swap_generation = 0

    # -- boot --------------------------------------------------------------
    def _device_tag(self) -> str:
        """Placement half of the AOT entry key: serialized executables
        encode their device assignment, so a committed replica's entries
        are per-device while the uncommitted single engine shares one."""
        return "host" if self.device is None else f"d{self.device.id}"

    def _execution_devices(self):
        """The device(s) this engine's executables are compiled for, and a
        cached executable must be loaded onto: the spatial mesh, the
        replica's chip, or the default device uncommitted inputs land on."""
        if self.sharding is not None:
            return list(self.sharding.mesh.devices.flat)
        if self.device is not None:
            return [self.device]
        return jax.local_devices()[:1]

    def _audit_entry_name(self, stage, hw, batch, warm_start) -> str:
        preset = "spatial" if self.sharding is not None else "dp"
        suffix = "+warm" if warm_start else ""
        return f"serve:{stage}:{hw[0]}x{hw[1]}:b{batch}{suffix}:{preset}"

    def _audit_snapshot(self, stage, hw, batch, warm_start, compiled):
        """tools/graftaudit record of one freshly compiled stage executable,
        or None when snapshotting fails (auditing must never break warmup).
        The chunk's carried state is arg 1 and its whole output — the GA001
        fixpoint pair; prelude/finalize have no carry (their records feed
        GA003/GA004/GA005 only)."""
        try:
            from tools.graftaudit.artifacts import snapshot_compiled

            carry_arg = 1 if stage == "chunk" else None
            return snapshot_compiled(
                compiled,
                entry=self._audit_entry_name(stage, hw, batch, warm_start),
                kind=stage,
                preset="spatial" if self.sharding is not None else "dp",
                carry_arg=carry_arg,
                meta={
                    "bucket": list(hw),
                    "batch": batch,
                    "warm_start": bool(warm_start),
                    "corr_dtype": self.config.model.corr_dtype,
                    "device_tag": self._device_tag(),
                },
            )
        except Exception as exc:  # noqa: BLE001 — audit is observability
            import logging

            logging.getLogger(__name__).warning(
                "hlo audit: could not snapshot %s %sx%s b%s: %r",
                stage, hw[0], hw[1], batch, exc,
            )
            return None

    def _warm_stage(self, stage, hw, batch, jit_fn, args, warm_start=False):
        """Resolve one stage executable during warmup.

        No cache: return the jit object — calling it traces and compiles
        exactly as the pre-cache engine did (with auditing on, warm()
        snapshots the STEADY-STATE executables separately once the carried
        state has settled; see _audit_warm_combo). With a cache:
        deserialize-first (a hit loads with ZERO compile events), falling
        back to `.lower().compile()` which rewrites the entry; either way
        the resolved executable is registered in `self._exec` under the same
        shape-derived key `run_batch` dispatch computes. With auditing
        (config.hlo_audit), every cache-path executable contributes a
        graftaudit record: compiles snapshot directly (and the snapshot
        rides into the cache entry); cache hits replay the stored snapshot;
        a hit whose entry predates auditing gets a loud placeholder record
        so GA001 reports the coverage gap instead of silently passing."""
        if self.aot_cache is None:
            return jit_fn
        audit = self.config.hlo_audit
        snap = None
        key = entry_key(
            stage, hw, batch, warm_start=warm_start, device_tag=self._device_tag()
        )
        fn = self.aot_cache.load(key, self._execution_devices())
        if fn is None:
            fn = jit_fn.lower(*args).compile()
            snap = self._audit_snapshot(stage, hw, batch, warm_start, fn) if audit else None
            self.aot_cache.store(key, fn, audit=snap)
        elif audit:
            snap = self.aot_cache.audit_snapshot(key)
            if snap is None:
                # Entry predates auditing: no HLO to re-derive. Emit a
                # carry-less record — GA001 flags it (chunk kinds), and
                # the operator repopulates the cache with auditing on.
                from tools.graftaudit.artifacts import make_record

                snap = make_record(
                    entry=self._audit_entry_name(stage, hw, batch, warm_start),
                    kind=stage,
                    preset="spatial" if self.sharding is not None else "dp",
                    hlo="",
                    meta={
                        "bucket": list(hw),
                        "batch": batch,
                        "warm_start": bool(warm_start),
                        "corr_dtype": self.config.model.corr_dtype,
                        "device_tag": self._device_tag(),
                        "missing_snapshot": True,
                    },
                )
        if snap is not None:
            self.audit_records.append(snap)
        if stage == "prelude":
            dispatch_key = (stage, tuple(args[1].shape), warm_start)
        else:
            dispatch_key = (stage, tuple(args[1]["coords1"].shape))
        self._exec[dispatch_key] = fn
        return fn

    def _audit_warm_combo(self, hw, batch, img, state, warm_args=None):
        """Cache-less audit snapshots for one (bucket, batch) combo, taken at
        the END of the combo's warm sequence: `state` has passed through the
        chunk at least twice, so lowering the chunk against it captures the
        STEADY-STATE specialization — the executable the refinement loop
        runs repeatedly, whose in/out shardings GA001 requires to be a
        fixpoint. (The first chunk call per request is the prelude→chunk
        transition, a different jit specialization; auditing it for the
        fixpoint would be a category error.) Each `.lower().compile()` is an
        AOT compile outside the jit cache — audit mode roughly doubles warm
        compile cost, which is the documented price of the opt-in flag."""
        todo = [
            ("prelude", self._prelude_fn, (self.variables, img, img), False),
            ("chunk", self._chunk_fn, (self.variables, state), False),
            ("finalize", self._finalize_fn, (self.variables, state), False),
        ]
        if warm_args is not None:
            todo.insert(1, ("prelude", self._prelude_fn, warm_args, True))
        for stage, fn, args, warm_start in todo:
            try:
                compiled = fn.lower(*args).compile()
            except Exception as exc:  # noqa: BLE001 — audit is observability
                import logging

                logging.getLogger(__name__).warning(
                    "hlo audit: could not lower %s %sx%s b%s: %r",
                    stage, hw[0], hw[1], batch, exc,
                )
                continue
            snap = self._audit_snapshot(stage, hw, batch, warm_start, compiled)
            if snap is not None:
                self.audit_records.append(snap)

    def _make_dispatch(self, stage, jit_fn):
        """Shape-keyed dispatcher over the AOT-resolved executables, bound
        over `self._prelude_fn`/`_chunk_fn`/`_finalize_fn` at the end of a
        cache-enabled warm(). Rebinding the ATTRIBUTES (instead of hiding
        the lookup in run_batch) keeps the fault-injection hooks honest:
        tests that patch `engine._chunk_fn` wrap the dispatcher and still
        intercept every chunk call. The original jit object stays as the
        fallback for any shape warm() never saw (which would be a
        zero-recompile violation — counted, not crashed)."""

        def dispatch(variables, *args):
            if stage == "prelude":
                key = (stage, tuple(args[0].shape), len(args) == 3)
            else:
                key = (stage, tuple(args[0]["coords1"].shape))
            fn = self._exec.get(key, jit_fn)
            return fn(variables, *args)

        return dispatch

    def warm(self) -> Dict[str, object]:
        """Resolve every (bucket, batch) × (prelude, chunk, finalize)
        executable — from the AOT cache when one is configured, traced and
        compiled otherwise — and measure compiled chunk wall time. Returns
        a summary {combos, compiles_total, warm_seconds, chunk_est_ms,
        aot_cache}."""
        cfg = self.config
        self.hygiene.monitor.start()
        t0 = time.monotonic()
        with self.hygiene.whitelist("warmup"):
            for hw in cfg.buckets:
                for batch in cfg.batch_sizes:
                    h, w = hw
                    # place(): warm against inputs with the SAME placement
                    # the request path stages (committed to this replica's
                    # device, or uncommitted default) — the jit dispatch
                    # cache keys on it, so a mismatch here would make every
                    # real batch a recompile. np.zeros + place, NOT
                    # jnp.zeros: eager jnp array creation fires its own
                    # backend-compile event, which would break the
                    # warm-cache boot's zero-compile proof (device_put of a
                    # host array is a pure transfer; the resulting aval and
                    # committed-ness are identical).
                    img = self.place(
                        np.zeros((batch, h, w, cfg.model.in_channels), np.float32)
                    )
                    prelude = self._warm_stage(
                        "prelude", hw, batch, self._prelude_fn,
                        (self.variables, img, img),
                    )
                    state = prelude(self.variables, img, img)
                    if cfg.video is not None:
                        # Streams call the prelude with a third flow_init
                        # argument — a separate executable (separate jit
                        # cache entry / separate AOT cache entry). Warm it
                        # here so a warm-started frame never compiles on
                        # the request path.
                        f = cfg.model.downsample_factor
                        flow0 = self.place(
                            np.zeros((batch, h // f, w // f), np.float32)
                        )
                        wprelude = self._warm_stage(
                            "prelude", hw, batch, self._prelude_fn,
                            (self.variables, img, img, flow0), warm_start=True,
                        )
                        wstate = wprelude(self.variables, img, img, flow0)
                        jax.block_until_ready(wstate["coords1"])
                    chunk = self._warm_stage(
                        "chunk", hw, batch, self._chunk_fn, (self.variables, state)
                    )
                    state = chunk(self.variables, state)
                    jax.block_until_ready(state["coords1"])
                    # Second chunk call runs fully compiled — its wall time
                    # is the deadline-check estimate for this combo.
                    t = time.monotonic()
                    state = chunk(self.variables, state)
                    jax.block_until_ready(state["coords1"])
                    self._chunk_est_s[(hw, batch)] = time.monotonic() - t
                    finalize = self._warm_stage(
                        "finalize", hw, batch, self._finalize_fn,
                        (self.variables, state),
                    )
                    out = finalize(self.variables, state)
                    jax.block_until_ready(out)
                    if cfg.hlo_audit and self.aot_cache is None:
                        # Cache-path snapshots were taken in _warm_stage;
                        # here the combo's call sequence is done and `state`
                        # is steady — snapshot the executables this combo
                        # actually serves with.
                        warm_args = (
                            (self.variables, img, img, flow0)
                            if cfg.video is not None
                            else None
                        )
                        self._audit_warm_combo(hw, batch, img, state, warm_args)
        if self._exec:
            # Populated by the AOT-cache path AND the audit-only path (which
            # also resolves concrete executables) — bind the shape-keyed
            # dispatcher whenever there is anything to dispatch to.
            self._prelude_fn = self._make_dispatch("prelude", self._prelude_fn)
            self._chunk_fn = self._make_dispatch("chunk", self._chunk_fn)
            self._finalize_fn = self._make_dispatch("finalize", self._finalize_fn)
        self._warmed = True
        stats = self.hygiene.monitor.stats()
        warm_seconds = time.monotonic() - t0
        return {
            "combos": len(cfg.buckets) * len(cfg.batch_sizes),
            "compiles_total": stats["compiles_total"],
            "warm_seconds": warm_seconds,
            "warmup_seconds": warm_seconds,
            "sharding": (
                f"spatial over {self.sharding.mesh.shape['spatial']} device(s)"
                if self.sharding is not None
                else "dp (single-program)"
            ),
            "chunk_est_ms": {
                f"{hw[0]}x{hw[1]}/b{b}": est * 1e3
                for (hw, b), est in self._chunk_est_s.items()
            },
            "aot_cache": (
                self.aot_cache.stats()
                if self.aot_cache is not None
                else {"enabled": False}
            ),
            "hlo_audit_records": len(self.audit_records),
        }

    def close(self) -> None:
        self.hygiene.monitor.stop()

    @property
    def warmed(self) -> bool:
        return self._warmed

    def chunk_estimate_s(self, bucket: Tuple[int, int], batch: int) -> float:
        return self._chunk_est_s.get((bucket, batch), 0.0)

    # -- staging -----------------------------------------------------------
    def place(self, x):
        """`jax.device_put` mirroring this engine's placement: committed to
        `self.device` for a fleet replica, bare (uncommitted, default
        device) otherwise — the exact pre-fleet staging call, pinned
        bit-identical for `--replicas 1`."""
        if self.device is not None:
            return jax.device_put(x, self.device)
        return jax.device_put(x)

    def stage(self, staged) -> None:
        """Land a host-assembled `_StagedBatch` (serving/batcher.py) on this
        engine's device — the transfer the batcher's stager thread overlaps
        with the running batch. Duck-typed to avoid a batcher import cycle;
        the fleet overrides this with replica routing."""
        staged.image1 = self.place(staged.i1_host)
        staged.image2 = self.place(staged.i2_host)
        if staged.flow_host is not None:
            staged.flow_init = self.place(staged.flow_host)

    def run_staged(self, staged) -> List[BatchResult]:
        """Run one staged batch — the runner-thread entry point. The fleet
        overrides this with failover requeue; here it is a plain delegate,
        so fault hooks patched over `run_batch` keep working."""
        return self.run_batch(
            staged.bucket,
            staged.image1,
            staged.image2,
            deadlines_s=[r.deadline_s for r in staged.reqs],
            max_iters=[r.max_iters for r in staged.reqs],
            flow_init=staged.flow_init,
            trace_ids=getattr(staged, "trace_ids", None),
        )

    # -- request path ------------------------------------------------------
    def run_batch(
        self,
        bucket: Tuple[int, int],
        image1,
        image2,
        deadlines_s: Sequence[Optional[float]],
        max_iters: Sequence[int],
        now=time.monotonic,
        flow_init=None,
        trace_ids: Optional[Sequence[int]] = None,
    ) -> List[BatchResult]:
        """Refine one padded device batch with per-request deadlines.

        `image1`/`image2` are (B, H, W, C) arrays already padded to
        `bucket`; rows beyond `len(deadlines_s)` are fill (the batcher pads
        partial batches up to a warmed size) and get no result.
        `deadlines_s[i]` is an ABSOLUTE `now()`-clock deadline or None;
        `max_iters[i]` is the request's refinement budget (rounded up to
        whole chunks). Always completes at least one chunk, so every
        response is a valid disparity field.

        `flow_init` is an optional (B, H/f, W/f) device array of low-res
        warm-start flows (stream sessions); all-zero rows are exact
        cold-start semantics for the non-stream requests sharing the batch.
        When None the plain prelude executable runs — never silently swap
        programs for plain traffic, b/c two compiled programs are not
        guaranteed bitwise-equal and the parity tests pin the plain one.

        `trace_ids` is the optional per-request flight-recorder trace-ID
        list (aligned with `deadlines_s`); batch-level spans carry it so a
        dump can follow one request from admission through its chunks.
        """
        cfg = self.config
        n = len(deadlines_s)
        batch = int(image1.shape[0])
        targets = [
            max(1, -(-min(int(m), cfg.max_iters) // cfg.chunk_iters))
            for m in max_iters
        ]
        est = self.chunk_estimate_s(bucket, batch)
        results: List[Optional[BatchResult]] = [None] * n
        watchdog = None
        if cfg.hang_timeout_s > 0:
            # Serving reuse of the training watchdog: exit_fn is a no-op
            # because a hung serving chunk must flip the replica to `failed`
            # (still answering /healthz with the stack dumps) rather than
            # kill the process; first_grace_s=0 because nothing compiles on
            # the request path — that is the whole point of warm().
            watchdog = StepWatchdog(
                timeout_s=cfg.hang_timeout_s,
                on_timeout=self._record_hang,
                exit_fn=lambda code: None,
                first_grace_s=0.0,
            )
        tracer = self.tracer
        tids = list(trace_ids) if trace_ids is not None else None
        with self._lock:
            # Arm INSIDE the lock: time spent waiting for another batch to
            # release the device is queueing, not hanging.
            if watchdog is not None:
                watchdog.start()
            # Device-time accumulator: wall clock over completed device work,
            # read only at the pre-existing sync points (per-chunk
            # block_until_ready, blocking finalize fetch) — attribution adds
            # no syncs of its own.
            device_s = 0.0
            try:
                t0 = time.perf_counter()
                if flow_init is not None:
                    state = self._prelude_fn(self.variables, image1, image2, flow_init)
                else:
                    state = self._prelude_fn(self.variables, image1, image2)
                if tracer is not None:
                    tracer.span(
                        "prelude",
                        t0=t0,
                        t1=time.perf_counter(),
                        bucket=list(bucket),
                        batch=batch,
                        warm=flow_init is not None,
                        traces=tids,
                    )
                pending = set(range(n))
                total_chunks = max(targets)
                for k in range(1, total_chunks + 1):
                    t0 = time.perf_counter()
                    state = self._chunk_fn(self.variables, state)
                    # GL014 waivers in this `with self._lock` block: _lock
                    # is the DEVICE-ownership mutex (one batch on the TPU
                    # at a time), not a microsecond-state lock — the chunk
                    # sync, the finalize fetch, and the watchdog join are
                    # exactly the work the lock exists to serialize.
                    jax.block_until_ready(state["coords1"])  # graftlint: disable=GL014
                    t1 = time.perf_counter()
                    device_s += t1 - t0
                    if tracer is not None:
                        tracer.span(
                            "chunk", t0=t0, t1=t1, k=k, bucket=list(bucket),
                            batch=batch, traces=tids,
                        )
                    if watchdog is not None:
                        watchdog.beat(k)
                    iters_done = k * cfg.chunk_iters
                    t = now()
                    deliver = [
                        i
                        for i in sorted(pending)
                        if targets[i] <= k
                        or (deadlines_s[i] is not None and t + est > deadlines_s[i])
                    ]
                    if not deliver:
                        continue
                    t0 = time.perf_counter()
                    flow_lo, flow_up = self._finalize_fn(self.variables, state)
                    flow_np = np.asarray(jax.device_get(flow_up), np.float32)  # graftlint: disable=GL014
                    lo_np = np.asarray(jax.device_get(flow_lo), np.float32)  # graftlint: disable=GL014
                    t1 = time.perf_counter()
                    device_s += t1 - t0
                    if tracer is not None:
                        tracer.span(
                            "finalize", t0=t0, t1=t1, k=k,
                            delivered=len(deliver), traces=tids,
                        )
                    if watchdog is not None:
                        watchdog.beat(k)
                    for i in deliver:
                        results[i] = BatchResult(
                            flow_up=flow_np[i],
                            iters_completed=iters_done,
                            early_exit=iters_done < min(int(max_iters[i]), cfg.max_iters),
                            flow_lowres=lo_np[i],
                            device_time_s=device_s,
                        )
                        pending.discard(i)
                    if not pending:
                        break
            finally:
                if watchdog is not None:
                    # Event-signaled join, bounded by the watchdog's poll
                    # interval — and it must finish before the lock
                    # releases so the next batch's arm can't race a stale
                    # timeout (see GL014 waiver rationale above).
                    watchdog.stop()  # graftlint: disable=GL014
            self.batches_total += 1
            self.hygiene.step(self.batches_total)
        assert not pending, "engine loop ended with undelivered requests"
        return results  # type: ignore[return-value]

    def _record_hang(self, info: Dict[str, object]) -> None:
        tracer = self.tracer
        if tracer is not None:
            tracer.event(
                "watchdog_fire",
                elapsed_s=float(info["elapsed_s"]),
                engine_batches_total=self.batches_total,
            )
        self.lifecycle.record_hang(float(info["elapsed_s"]), str(info["traces"]))
        if tracer is not None:
            # Dump AFTER record_hang so the breaker transition it causes is
            # in the recorded window too (the transition hook records it).
            tracer.dump("watchdog")

    # -- checkpoint hot-swap -----------------------------------------------
    def swap_variables(self, new_variables) -> int:
        """Swap the served parameter tree between batches, zero recompiles.

        The warmed executables were traced against `self.variables`, so a
        candidate tree is admissible only if it is structurally IDENTICAL —
        same treedef, same per-leaf shape and dtype. Anything else would
        force a retrace on the next batch, violating the machine-checked
        `compiles_post_grace == 0` guarantee; such trees are refused with
        `CheckpointMismatchError` and the old tree keeps serving.

        Leaves are placed with `jax.device_put` — a pure transfer, never a
        traced op — and the placement mirrors the old leaf's COMMITMENT as
        well as its sharding: the jit dispatch cache keys on committed-ness,
        so swapping a committed array in where the executables were warmed
        against an uncommitted one (the jitted-init default) would itself
        force a silent recompile on the next batch. The pointer swap happens
        under the run lock, so every batch sees one coherent tree. Returns
        the new swap generation.
        """
        old_leaves, old_treedef = jax.tree_util.tree_flatten(self.variables)
        new_leaves, new_treedef = jax.tree_util.tree_flatten(new_variables)
        if new_treedef != old_treedef:
            raise CheckpointMismatchError(
                f"checkpoint tree structure differs from the serving tree: "
                f"{new_treedef} != {old_treedef}"
            )
        placed = []
        for i, (o, nv) in enumerate(zip(old_leaves, new_leaves)):
            o_shape, o_dtype = tuple(o.shape), np.dtype(o.dtype)
            n_shape = tuple(np.shape(nv))
            n_dtype = np.dtype(getattr(nv, "dtype", None) or np.asarray(nv).dtype)
            if n_shape != o_shape or n_dtype != o_dtype:
                paths = jax.tree_util.tree_flatten_with_path(self.variables)[0]
                name = jax.tree_util.keystr(paths[i][0])
                raise CheckpointMismatchError(
                    f"leaf {name}: checkpoint has shape {n_shape} dtype "
                    f"{n_dtype}, serving tree expects {o_shape} {o_dtype}"
                )
            if isinstance(o, jax.Array):
                if getattr(o, "_committed", True):
                    placed.append(jax.device_put(nv, o.sharding))
                else:
                    # Uncommitted (default-device) leaf: a bare device_put
                    # stays uncommitted and hits the warmed cache entry.
                    placed.append(jax.device_put(nv))
            else:
                placed.append(np.asarray(nv))
        new_tree = jax.tree_util.tree_unflatten(old_treedef, placed)
        with self._lock:
            self.variables = new_tree
            self.swap_generation += 1
            gen = self.swap_generation
        self.lifecycle.note_swap(gen)
        return gen
