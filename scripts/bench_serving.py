"""Open-loop serving benchmark: drive the in-process service, emit JSON.

Closed-loop clients (wait for a response, then send) hide queueing collapse:
the arrival rate degrades to whatever the server sustains and latency looks
flat. This client is OPEN-LOOP — request i is dispatched at its scheduled
arrival time i/rate regardless of completions — so queue depth, batch fill
and tail latency respond to offered load the way production traffic makes
them.

Output is a JSON object with a `serving` block (validated by
scripts/check_bench_json.py, gated in ci_checks.sh):

    serve_maps_per_sec   responses / wall seconds, dispatch->last completion
    latency_p50_ms/p99_ms, batch_fill_mean, deadline_miss_total,
    early_exit_total, requests_total, responses_total, buckets, ...

plus a `batch_efficiency` A/B: per-map throughput at batch 1 vs max_batch on
one bucket, same iteration budget. This is the serving-tier answer to the
BENCH_r05 flat-batch-2 finding (b2 1.073 vs b1 1.084 maps/s): at FULL
resolution on one chip, batch scaling is structurally flat — the encoder
OOMs batched (sequential_batch_forward exists because of it) and the
refinement arithmetic is already MXU-bound, so per-map cost is
B-independent. At serving bucket shapes the same batch amortizes real fixed
overhead (dispatch, prelude epilogues, host sync per chunk), and the ratio
here makes that visible as a measured number instead of a claim.

With `--stream_frames N` the run also measures STREAMING stereo: an N-frame
synthetic drifting-disparity sequence (data/datasets.make_synthetic_sequence)
replayed closed-loop through ONE `submit_stream` session — closed-loop is
correct here because a video client by definition sends frame t+1 after
frame t resolves. The emitted `video` block (also schema-gated) carries
`video_maps_per_sec` (steady state, cold frame 0 excluded), warm/reset frame
counts, and the `iters_to_epe_parity` warm-vs-cold A/B from
video.warm_cold_parity — run BEFORE the service boots so its compiles stay
out of the serving RecompileMonitor's window.

With `--replicas N` the run also sweeps the ENGINE FLEET: one service per
replica count (1, 2, 4, ..., N), booted sequentially — never overlapping,
because each service's RecompileMonitor registers a process-wide compile
listener and a concurrent boot would pollute the other's counters — each
driven with the same open-loop arrival schedule. The emitted `serving_fleet`
block (schema-gated like the rest) carries the throughput curve
`{"r1": ..., "r2": ..., "rN": ...}` in maps/s plus the top fleet's final
replica health states and requeue/batch counters, so a replica that
degraded mid-bench is machine-visible in the record. `--replicas 0` means
one replica per visible device (same convention as `serve --replicas`).
The sweep's boots share one AOT executable cache (temp unless
--aot_cache_dir), and its `boot_curve` records each boot's warmup_seconds
with the cache hit/miss split — the cold-vs-warm restart-latency A/B.

With `--frontier N` the run also drives the FRONT-TIER ROUTER
(serving/frontier.py): N backend services booted sequentially behind the
real frontier HTTP server — sharing one AOT cache, so every boot after the
first deserializes and the N process-wide RecompileMonitors stay clean —
with the same open-loop schedule replayed over real HTTP through the
router. The emitted `frontier` block (validate_frontier-gated) is the
router's own metrics snapshot: per-backend health states, the
exactly-once request/response ledger, retry/hedge/migration/brownout/shed
counters and routed-latency percentiles, plus the drive's `http_200`
count and `route_maps_per_sec` — routing overhead included, so this
number is comparable to (and bounded by) `serve_maps_per_sec`.

Every run also emits a `boot` block (validate_boot-gated): the main
service's warmup_seconds, AOT-cache ledger and respawn counter — the
instant-boot record (PR 16).

Usage:
  python scripts/bench_serving.py --requests 32 --rate 4 \
      --buckets 64x96 96x128 --max_batch 2 --out serving.json
  python scripts/bench_serving.py ... --stream_frames 16   # + video block
  python scripts/bench_serving.py ... --replicas 4   # + serving_fleet block
  python scripts/bench_serving.py ... --frontier 2   # + frontier block
  python scripts/bench_serving.py ... --merge BENCH_r06.json   # add the
      serving (and video) block to an existing bench record (validated
      after merge)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np


def _parse_buckets(specs):
    return tuple(tuple(int(d) for d in s.lower().split("x")) for s in specs)


def make_pairs(buckets, n, rng, margin=4):
    """Stereo pairs cycling the buckets, each a little smaller than its
    bucket so the padding-admission path is exercised, not bypassed."""
    pairs = []
    for i in range(n):
        h, w = buckets[i % len(buckets)]
        shape = (h - margin, w - margin, 3)
        pairs.append(
            (
                rng.uniform(0, 255, shape).astype(np.float32),
                rng.uniform(0, 255, shape).astype(np.float32),
            )
        )
    return pairs


def open_loop(service, pairs, rate_hz, deadline_ms, max_iters):
    """Dispatch pairs at fixed arrivals; returns (responses, wall_s)."""
    futures = [None] * len(pairs)
    t0 = time.monotonic()

    def dispatch():
        for i, (a, b) in enumerate(pairs):
            target = t0 + i / rate_hz
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futures[i] = service.submit(
                a, b, deadline_ms=deadline_ms, max_iters=max_iters
            )

    th = threading.Thread(target=dispatch)
    th.start()
    th.join()
    results = [f.result(timeout=600) for f in futures]
    wall_s = time.monotonic() - t0
    return results, wall_s


def batch_efficiency(service, bucket, max_batch, iters, rng, rounds=3):
    """Per-map seconds at batch 1 vs max_batch on one bucket (closed-loop
    bursts; the batcher coalesces simultaneous same-bucket submits)."""
    h, w = bucket
    pair = lambda: (  # noqa: E731
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
        rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
    )

    def run(burst):
        t = time.monotonic()
        futs = [
            service.submit(*pair(), deadline_ms=0, max_iters=iters)
            for _ in range(burst)
        ]
        for f in futs:
            f.result(timeout=600)
        return (time.monotonic() - t) / burst

    run(1)  # settle the path (everything is compiled; this warms caches/allocs)
    b1 = min(run(1) for _ in range(rounds))
    bN = min(run(max_batch) for _ in range(rounds))
    return {
        "bucket": list(bucket),
        "iters": iters,
        "b1_maps_per_sec": 1.0 / b1,
        "bmax_maps_per_sec": 1.0 / bN,
        "bmax": max_batch,
        "speedup_per_map": b1 / bN,
    }


def stream_replay(service, frames, stream_id="bench-stream"):
    """Replay one frame sequence through a single stream session, closed
    loop (the session ordering contract: frame t+1 after frame t resolves).
    Frame 0 — the cold start — is excluded from the steady-state timing."""
    results = []
    t0 = time.monotonic()
    for i, frame in enumerate(frames):
        fut = service.submit_stream(stream_id, frame["image1"], frame["image2"])
        results.append(fut.result(timeout=600))
        if i == 0:
            t0 = time.monotonic()
    wall_s = time.monotonic() - t0
    n_timed = len(frames) - 1
    return {
        "video_maps_per_sec": (n_timed / wall_s) if (n_timed and wall_s > 0) else 0.0,
        "frames": len(frames),
        "warm_frames": sum(1 for r in results if r["warm_started"]),
        "resets": sum(1 for r in results if r["reset"]),
    }


def replica_sweep(cfg, args, rng, counts):
    """Throughput vs replica count: boot one service per count, strictly
    sequentially (close() unregisters the process-wide compile listener
    before the next boot), replay the same open-loop arrival schedule, and
    return the serving_fleet block. The health/requeue counters come from
    the LARGEST fleet — the configuration the curve is an argument for.

    The sweep shares one AOT executable cache across its boots (a temp dir
    unless --aot_cache_dir pins one), so `boot_curve` records each boot's
    wall-clock warmup COLD vs WARM: the first boot of each device's
    entries misses and compiles, later boots of the same entries
    deserialize — the restart-latency win the cache exists for, as a
    measured number per replica count."""
    import dataclasses
    import shutil
    import tempfile

    from raft_stereo_tpu.serving.service import StereoService

    cache_dir = cfg.aot_cache_dir
    scratch = None
    if cache_dir is None:
        scratch = cache_dir = tempfile.mkdtemp(prefix="bench_aot_cache_")
    curve = {}
    boot_curve = {}
    fleet_stats = None
    try:
        for k in counts:
            scfg = dataclasses.replace(cfg, replicas=k, aot_cache_dir=cache_dir)
            service = StereoService(scfg).start()
            try:
                boot = service.boot_block()
                boot_curve[f"r{k}"] = {
                    "warmup_seconds": boot["warmup_seconds"],
                    "cache_hits": boot["cache_hits"],
                    "cache_misses": boot["cache_misses"],
                }
                pairs = make_pairs(scfg.buckets, args.requests, rng)
                results, wall_s = open_loop(
                    service, pairs, args.rate, args.deadline_ms or None, args.max_iters
                )
                curve[f"r{k}"] = len(results) / wall_s
                if k == counts[-1]:
                    snap = service.metrics()
                    lc = service.lifecycle.snapshot()
                    # FleetLifecycle reports replica_states; the k=1 degenerate
                    # path is a plain ServingLifecycle, whose own state IS the
                    # one-replica fleet state.
                    fleet_stats = {
                        "replicas": k,
                        "replica_states": list(lc.get("replica_states", [lc["state"]])),
                        "requeues_total": snap["requeues_total"],
                        "batches_total": snap["batches_total"],
                    }
            finally:
                service.close()
    finally:
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    fleet_stats["curve"] = curve
    fleet_stats["boot_curve"] = boot_curve
    return fleet_stats


def frontier_drive(cfg, args, rng, n_backends):
    """Boot N backend services behind the real front-tier router
    (serving/frontier.py) and replay the open-loop arrival schedule
    through its HTTP front; returns the `frontier` block
    (frontier.metrics(), validate_frontier-gated).

    The backends boot strictly sequentially sharing one AOT executable
    cache (temp unless --aot_cache_dir): the first boot compiles inside
    its own warmup window, every later boot deserializes — the only
    arrangement where N process-wide RecompileMonitors coexist without
    polluting each other's counters. Traffic goes over real HTTP via the
    shared stdlib client (utils/http.py), so the emitted numbers include
    the frontier's routing + forwarding overhead, not just model time."""
    import dataclasses
    import shutil
    import tempfile

    from raft_stereo_tpu.config import FrontierConfig
    from raft_stereo_tpu.serving.frontier import (
        Frontier,
        make_frontier_http_server,
    )
    from raft_stereo_tpu.serving.service import StereoService, make_http_server
    from raft_stereo_tpu.utils.http import request_json

    cache_dir = cfg.aot_cache_dir
    scratch = None
    if cache_dir is None:
        scratch = cache_dir = tempfile.mkdtemp(prefix="bench_frontier_aot_")
    bcfg = dataclasses.replace(cfg, aot_cache_dir=cache_dir)
    backends = []
    frontier = None
    fserver = None
    server_threads = []
    try:
        for _ in range(n_backends):
            service = StereoService(bcfg).start()
            server = make_http_server(service, port=0)
            st = threading.Thread(target=server.serve_forever, daemon=True)
            st.start()
            server_threads.append(st)
            backends.append(
                (service, server, f"127.0.0.1:{server.server_address[1]}")
            )
        frontier = Frontier(
            FrontierConfig(
                backends=tuple(addr for _, _, addr in backends),
                health_interval_s=0.25,
            )
        ).start()
        fserver = make_frontier_http_server(frontier, port=0)
        st = threading.Thread(target=fserver.serve_forever, daemon=True)
        st.start()
        server_threads.append(st)
        url = "http://127.0.0.1:%d/predict" % fserver.server_address[1]

        pairs = make_pairs(cfg.buckets, args.requests, rng)
        statuses = [None] * len(pairs)
        threads = []
        t0 = time.monotonic()

        def send(i, left, right):
            payload = {
                "image1": left.tolist(),
                "image2": right.tolist(),
                "max_iters": args.max_iters,
            }
            if args.deadline_ms:
                payload["deadline_ms"] = args.deadline_ms
            statuses[i] = request_json(
                url, method="POST", payload=payload, timeout_s=600.0
            ).status

        for i, (left, right) in enumerate(pairs):
            target = t0 + i / args.rate
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=send, args=(i, left, right))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall_s = time.monotonic() - t0

        block = frontier.metrics()
        block["driven_requests"] = len(pairs)
        block["http_200"] = sum(1 for s in statuses if s == 200)
        block["route_maps_per_sec"] = block["http_200"] / wall_s

        rollout_block = None
        if getattr(args, "rollout_drill", False):
            rollout_block = _rollout_drill(
                backends, fserver.server_address[1], frontier
            )
        return block, rollout_block
    finally:
        if fserver is not None:
            fserver.shutdown()
            fserver.server_close()
        if frontier is not None:
            frontier.close()
        for service, server, _ in backends:
            server.shutdown()
            server.server_close()
            service.close()
        # shutdown() only signals serve_forever; join so the bench exits
        # with every server loop actually stopped.
        for st in server_threads:
            st.join(timeout=5.0)
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)


def _rollout_drill(backends, frontier_port, frontier):
    """Drive one real checkpoint rollout through the frontier's POST
    /rollout: save the served weights as the rollback baseline, save a
    perturbed copy (float leaves scaled — same treedef/shape/dtype, so
    the swap is recompile-free but the outputs provably change) as the
    new checkpoint, roll the fleet onto it, and return the `rollout`
    block (validate_rollout-gated)."""
    import shutil
    import tempfile

    import jax
    import orbax.checkpoint as ocp

    from raft_stereo_tpu.utils.http import request_json

    variables = jax.tree.map(np.asarray, backends[0][0].engine.variables)

    def scaled(x):
        arr = np.asarray(x)
        if np.issubdtype(arr.dtype, np.floating):
            return arr * np.asarray(1.05, dtype=arr.dtype)
        return arr

    root = tempfile.mkdtemp(prefix="bench_rollout_ckpt_")
    base_dir = os.path.join(root, "base")
    new_dir = os.path.join(root, "new")
    try:
        with ocp.StandardCheckpointer() as ckptr:
            for path, tree in (
                (base_dir, variables),
                (new_dir, jax.tree.map(scaled, variables)),
            ):
                ckptr.save(
                    path,
                    {
                        "params": tree["params"],
                        "batch_stats": tree.get("batch_stats", {}),
                    },
                )
            ckptr.wait_until_finished()
        resp = request_json(
            "http://127.0.0.1:%d/rollout" % frontier_port,
            method="POST",
            payload={"checkpoint": new_dir, "rollback_checkpoint": base_dir},
            timeout_s=600.0,
        )
        if resp.status != 200:
            print(
                f"rollout drill: /rollout answered {resp.status}: "
                f"{resp.body[:300]!r}",
                file=sys.stderr,
            )
        return frontier.rollout_block()
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--buckets", nargs="+", default=["64x96", "96x128"])
    ap.add_argument("--max_batch", type=int, default=2)
    ap.add_argument("--chunk_iters", type=int, default=4)
    ap.add_argument("--max_iters", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=2.0, help="arrivals per second")
    ap.add_argument("--deadline_ms", type=float, default=0.0)
    ap.add_argument("--batch_window_ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--stream_frames", type=int, default=0,
        help="also replay an N-frame synthetic sequence through one stream "
        "session and emit the `video` block (0 = off)",
    )
    ap.add_argument(
        "--stream_warm_iters", type=int, default=None,
        help="warm-frame refinement budget (default: one chunk)",
    )
    ap.add_argument(
        "--parity_frames", type=int, default=3,
        help="frames for the warm-vs-cold iters_to_epe_parity A/B",
    )
    ap.add_argument(
        "--replicas", type=int, default=None,
        help="also sweep the engine fleet: boot one service per replica "
        "count (1, 2, 4, ..., N) sequentially, measure serve_maps_per_sec "
        "for each, and emit the `serving_fleet` block (0 = one replica per "
        "visible device; default: no sweep)",
    )
    ap.add_argument(
        "--frontier", type=int, default=None,
        help="also boot N backend services behind the front-tier router "
        "(sequential boots sharing an AOT cache), replay the open-loop "
        "schedule through its HTTP front, and emit the `frontier` block "
        "(validate_frontier-gated; default: no frontier run)",
    )
    ap.add_argument(
        "--rollout_drill", action="store_true",
        help="with --frontier N: after the routed traffic, drive one real "
        "checkpoint rollout through POST /rollout (served weights saved "
        "as the rollback baseline, a perturbed copy as the new "
        "checkpoint) and emit the `rollout` block "
        "(validate_rollout-gated)",
    )
    ap.add_argument(
        "--aot_cache_dir", default=None,
        help="persistent AOT executable cache dir for every boot in this "
        "run (serve --aot_cache_dir); the --replicas sweep defaults to a "
        "shared TEMP cache so its boot_curve still measures cold-vs-warm "
        "warmup, this flag pins a real one instead",
    )
    ap.add_argument("--out", default=None, help="write the JSON here (default stdout)")
    ap.add_argument(
        "--merge", default=None,
        help="existing bench JSON to merge the serving block into (in place)",
    )
    args = ap.parse_args(argv)
    if args.rollout_drill and not (args.frontier and args.frontier > 0):
        ap.error("--rollout_drill requires --frontier N")

    from raft_stereo_tpu.config import ServeConfig, VideoConfig
    from raft_stereo_tpu.serving.service import StereoService
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    # The XLA compile cache is the fixed one; only the AOT executable caches
    # of the cold/warm boot curves below live in temporary directories.
    setup_compile_cache()
    video_cfg = None
    if args.stream_frames > 0:
        warm_iters = (
            args.stream_warm_iters
            if args.stream_warm_iters is not None
            else args.chunk_iters
        )
        video_cfg = VideoConfig(
            chunk_iters=args.chunk_iters,
            cold_iters=args.max_iters,
            warm_iters=min(warm_iters, args.max_iters),
        )
    cfg = ServeConfig(
        buckets=_parse_buckets(args.buckets),
        max_batch=args.max_batch,
        chunk_iters=args.chunk_iters,
        max_iters=args.max_iters,
        deadline_ms=args.deadline_ms,
        batch_window_ms=args.batch_window_ms,
        video=video_cfg,
        aot_cache_dir=args.aot_cache_dir,
        # HLO contract audit rides every bench boot: warm() snapshots each
        # executable and the hlo_audit block below records the verdict, so
        # a contract regression (resharding chunk boundary, stray
        # collective) shows up in the bench diff, not just in CI.
        hlo_audit=True,
    )
    rng = np.random.default_rng(args.seed)

    video = None
    stream_frames = None
    parity = None
    if video_cfg is not None:
        # Sequence + parity A/B BEFORE the service boots: warm_cold_parity
        # jits its own (prelude, chunk, finalize) triple, and running it
        # here keeps those compiles out of the serving monitor's window —
        # compiles_post_warmup below stays attributable to traffic alone.
        from raft_stereo_tpu.data.datasets import make_synthetic_sequence
        from raft_stereo_tpu.models.init_cache import init_model_variables
        from raft_stereo_tpu.video import warm_cold_parity

        h, w = cfg.buckets[0]
        stream_frames = make_synthetic_sequence(rng, args.stream_frames, h, w)
        variables = init_model_variables(cfg.model)
        parity = warm_cold_parity(
            cfg.model,
            variables,
            stream_frames[: max(2, args.parity_frames)],
            video_cfg,
        )

    service = StereoService(cfg).start()
    try:
        # Boot record FIRST: warmup_seconds and the cache hit/miss ledger
        # are facts about the boot that just happened, before traffic.
        boot = service.boot_block()
        pairs = make_pairs(cfg.buckets, args.requests, rng)
        results, wall_s = open_loop(
            service, pairs, args.rate, args.deadline_ms or None, args.max_iters
        )
        snap = service.metrics()
        eff = batch_efficiency(
            service, cfg.buckets[0], cfg.max_batch, args.max_iters, rng
        )
        if video_cfg is not None:
            video = stream_replay(service, stream_frames)
            video["iters_to_epe_parity"] = parity
            video["warm_iters"] = video_cfg.warm_iters
            video["cold_iters"] = video_cfg.cold_iters
        hygiene = service.engine.hygiene.monitor.stats()
        # Fault-lifecycle verdict AFTER all traffic (open loop + efficiency
        # probes + stream replay): the health state and shed/hang/swap
        # counters summarize the whole run, so a degraded/failed bench is
        # machine-visible in the merged record, not just in stderr noise.
        fault_snap = service.metrics()
        lifecycle = service.lifecycle.snapshot()
        swap_generation = service.engine.swap_generation
        # Latency attribution (queue wait vs device compute vs host gap)
        # over the run's response window, plus the device-memory verdict —
        # both sampled while the service is still up.
        attribution = service.batcher.metrics.attribution_summary()
        from raft_stereo_tpu.obs import memory_block

        memory = memory_block()
        hlo_audit = service.hlo_audit_block()
    finally:
        service.close()

    serving_fleet = None
    if args.replicas is not None:
        # AFTER service.close(): the sweep boots its own services, and two
        # live RecompileMonitors would double-count each other's compiles.
        import jax

        n_top = args.replicas if args.replicas > 0 else len(jax.local_devices())
        counts = sorted({1, n_top} | {2**i for i in range(20) if 2**i < n_top})
        serving_fleet = replica_sweep(cfg, args, rng, counts)

    frontier_block = None
    rollout_block = None
    if args.frontier is not None and args.frontier > 0:
        # Also after service.close(), for the same monitor reason.
        frontier_block, rollout_block = frontier_drive(
            cfg, args, rng, args.frontier
        )

    serving = {
        "serve_maps_per_sec": len(results) / wall_s,
        "wall_s": wall_s,
        "offered_rate_hz": args.rate,
        "latency_p50_ms": snap["latency_p50_ms"],
        "latency_p99_ms": snap["latency_p99_ms"],
        "batch_fill_mean": snap["batch_fill_mean"],
        "deadline_miss_total": snap["deadline_miss_total"],
        "early_exit_total": snap["early_exit_total"],
        "requests_total": snap["requests_total"],
        "responses_total": snap["responses_total"],
        "buckets": [list(b) for b in cfg.buckets],
        "chunk_iters": cfg.chunk_iters,
        "max_iters": cfg.max_iters,
        "batch_efficiency": eff,
        "compiles_post_warmup": hygiene["compiles_post_grace"],
        "attribution": attribution,
        "memory": memory,
    }
    serving_faults = {
        "state": lifecycle["state"],
        "breaker_consecutive_failures": lifecycle["breaker"]["consecutive_failures"],
        "batch_failures_total": lifecycle["batch_failures_total"],
        "hangs_total": lifecycle["hangs_total"],
        "shed_total": fault_snap["shed_total"],
        "deadline_infeasible_total": fault_snap["deadline_infeasible_total"],
        "swap_generation": swap_generation,
        # A shed IS a submission the service refused: admitted + shed.
        "submitted_total": fault_snap["requests_total"] + fault_snap["shed_total"],
    }
    doc = {
        "serving": serving,
        "serving_faults": serving_faults,
        "boot": boot,
        "hlo_audit": hlo_audit,
    }
    if video is not None:
        video["compiles_post_warmup"] = hygiene["compiles_post_grace"]
        doc["video"] = video
    if serving_fleet is not None:
        doc["serving_fleet"] = serving_fleet
    if frontier_block is not None:
        doc["frontier"] = frontier_block
    if rollout_block is not None:
        doc["rollout"] = rollout_block

    if args.merge:
        with open(args.merge) as f:
            merged = json.load(f)
        target = merged["parsed"] if "parsed" in merged else merged
        target["serving"] = serving
        target["serving_faults"] = serving_faults
        target["boot"] = boot
        target["hlo_audit"] = hlo_audit
        if video is not None:
            target["video"] = video
        if serving_fleet is not None:
            target["serving_fleet"] = serving_fleet
        if frontier_block is not None:
            target["frontier"] = frontier_block
        if rollout_block is not None:
            target["rollout"] = rollout_block
        with open(args.merge, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(
            f"merged serving + serving_faults + boot + hlo_audit"
            f"{' + video' if video is not None else ''}"
            f"{' + serving_fleet' if serving_fleet is not None else ''}"
            f"{' + frontier' if frontier_block is not None else ''}"
            f"{' + rollout' if rollout_block is not None else ''}"
            f" blocks into {args.merge}"
        )

    out = json.dumps(doc, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    else:
        print(out)

    from check_bench_json import (  # same scripts/ dir
        validate_boot,
        validate_frontier,
        validate_hlo_audit,
        validate_rollout,
        validate_serving,
        validate_serving_faults,
        validate_serving_fleet,
        validate_video,
    )

    errs = (
        validate_serving(serving)
        + validate_serving_faults(serving_faults)
        + validate_boot(boot)
        + validate_hlo_audit(hlo_audit)
    )
    if video is not None:
        errs += validate_video(video)
    if serving_fleet is not None:
        errs += validate_serving_fleet(serving_fleet)
    if frontier_block is not None:
        errs += validate_frontier(frontier_block)
    if rollout_block is not None:
        errs += validate_rollout(rollout_block)
    for e in errs:
        print(f"bench block invalid: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    # Runnable from anywhere: scripts/ for the check_bench_json import,
    # the repo root for the raft_stereo_tpu package.
    import os

    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, _here)
    sys.path.insert(0, os.path.dirname(_here))
    sys.exit(main())
