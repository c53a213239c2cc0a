"""Instant-boot resilience suite (tier-1, `-m boot`, PR 16).

The PR's acceptance claims, machine-checked on the 8-device virtual CPU
mesh (conftest):

- the AOT executable cache round-trips a compiled executable through disk
  (store → load → call, output bit-identical to the in-memory compiled),
  its fingerprint is stable for equal configs and moves for changed ones,
  and EVERY corruption mode (garbage bytes, wrong format, wrong embedded
  fingerprint) is evicted loudly with a counted miss — never an exception;
- a SECOND service boot against a populated cache performs ZERO traces:
  100% cache hits, `compiles_total == 0` on the boot's RecompileMonitor,
  and responses bit-identical to the first (freshly compiled) boot's;
- the fleet joins its disposable batch threads at close — the pre-PR-16
  fire-and-forget hung-replica threads could outlive service teardown
  (satellite regression);
- the respawn torture: a replica poisoned until sticky-`failed` is
  automatically replaced from the shared cache, the fleet returns to
  `healthy` through real probation traffic, outputs stay bit-identical to
  the pre-fault baseline, the requeue accounting is exact (zero dropped
  requests), and `compiles_post_grace == 0` fleet-wide because the
  replacement boot is pure deserialization.

Each test boots its own service (some twice — that is the subject under
test), so the module is collection-ordered dead last (conftest) and gated
in ci_checks.sh (exit 17).
"""

import threading
import time

import numpy as np
import pytest

from fault_injection import failing_run_batch, hung_chunk

from report_checks import validate_boot

pytestmark = pytest.mark.boot

BUCKET = (64, 96)
CHUNK_ITERS = 2
MAX_ITERS = 4

_rng = np.random.default_rng(20260807)
PAIR = (
    _rng.uniform(0, 255, (BUCKET[0], BUCKET[1], 3)).astype(np.float32),
    _rng.uniform(0, 255, (BUCKET[0], BUCKET[1], 3)).astype(np.float32),
)


def _config(**kw):
    from raft_stereo_tpu.config import ServeConfig

    kw.setdefault("buckets", (BUCKET,))
    kw.setdefault("max_batch", 1)
    kw.setdefault("chunk_iters", CHUNK_ITERS)
    kw.setdefault("max_iters", MAX_ITERS)
    kw.setdefault("batch_window_ms", 2.0)
    return ServeConfig(**kw)


def _submit(service):
    return service.submit(*PAIR, max_iters=MAX_ITERS).result(timeout=300)


def _quiesce(fleet, timeout_s: float = 30.0) -> None:
    """Wait until no batch holds a replica slot, so the next submit's
    least-loaded routing deterministically ties to the lowest admissible
    replica index."""
    deadline = time.monotonic() + timeout_s
    while any(r.in_flight for r in fleet.replicas):
        assert time.monotonic() < deadline, "fleet never quiesced"
        time.sleep(0.005)


# -- cache unit layer --------------------------------------------------------


def test_fingerprint_stable_and_config_sensitive():
    """Equal configs name the same cache world; any executable-shaping
    change (bucket table, model width) names a different one, so stale
    artifacts are unreachable rather than detected."""
    from raft_stereo_tpu.serving.aot import config_fingerprint

    a = config_fingerprint(_config())
    assert a == config_fingerprint(_config())
    assert a != config_fingerprint(_config(buckets=((64, 96), (96, 128))))
    assert a != config_fingerprint(_config(chunk_iters=CHUNK_ITERS + 2))


def test_entry_key_names_stage_shape_batch_variant_and_device():
    from raft_stereo_tpu.serving.aot import entry_key

    assert entry_key("chunk", (64, 96), 2) == "chunk-64x96-b2-host"
    assert (
        entry_key("prelude", (384, 512), 1, warm_start=True, device_tag="d3")
        == "prelude-384x512-b1-warm-d3"
    )


def test_cache_round_trip_and_corruption_eviction(tmp_path):
    """store → load returns a callable whose output is bit-identical to the
    in-memory compiled executable; every corruption mode evicts loudly
    (file unlinked, miss + eviction counted) and returns None — the
    caller's compile fallback, never an exception."""
    import jax

    from raft_stereo_tpu.serving.aot import ExecutableCache, maybe_cache

    cache = ExecutableCache(str(tmp_path), _config())
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    compiled = jax.jit(lambda v: v * 2.0 + 1.0).lower(x).compile()
    expect = np.asarray(jax.device_get(compiled(x)))

    assert cache.load("unit", jax.devices()[:1]) is None  # cold miss
    assert cache.store("unit", compiled)
    fn = cache.load("unit", jax.devices()[:1])
    assert fn is not None
    np.testing.assert_array_equal(np.asarray(jax.device_get(fn(x))), expect)
    stats = cache.stats()
    assert stats["cache_hits"] == 1 and stats["cache_misses"] == 1
    assert stats["entries"] == 2  # the hits+misses identity validate_boot pins
    assert stats["stores"] == 1 and stats["evictions"] == 0
    assert cache.files() == 1

    # Garbage bytes: unpicklable entry.
    with open(cache._path("unit"), "wb") as fh:
        fh.write(b"not a pickle")
    assert cache.load("unit", jax.devices()[:1]) is None
    assert cache.files() == 0  # evicted from disk
    # Wrong embedded fingerprint: a different toolchain/config world's
    # artifact copied into this directory must be rejected, not loaded.
    assert cache.store("unit", compiled)
    import pickle

    with open(cache._path("unit"), "rb") as fh:
        entry = pickle.load(fh)
    entry["fingerprint"] = "0" * 16
    with open(cache._path("unit"), "wb") as fh:
        pickle.dump(entry, fh)
    assert cache.load("unit", jax.devices()[:1]) is None
    stats = cache.stats()
    assert stats["evictions"] == 2
    assert stats["cache_hits"] + stats["cache_misses"] == stats["entries"]

    # maybe_cache gating: no dir configured -> no cache object at all.
    assert maybe_cache(None, _config()) is None
    assert maybe_cache(str(tmp_path), _config()) is not None


# -- warm-cache boot ---------------------------------------------------------


def test_second_boot_is_all_cache_hits_with_zero_compiles(tmp_path):
    """The tentpole claim: boot #1 compiles and populates the cache, boot
    #2 of the SAME config deserializes everything — 100% hits, zero
    backend-compile events on its RecompileMonitor, bit-identical
    responses. Both boot blocks satisfy the schema the bench/CI gate
    pins."""
    from raft_stereo_tpu.serving.service import StereoService

    cfg = _config(aot_cache_dir=str(tmp_path))

    s1 = StereoService(cfg).start()
    try:
        cold = s1.boot_block()
        baseline = _submit(s1)["disparity"]
    finally:
        s1.close()
    assert validate_boot(cold) == []
    assert cold["cache_enabled"]
    assert cold["cache_misses"] == cold["entries"] > 0
    assert cold["cache_hits"] == 0

    s2 = StereoService(cfg).start()
    try:
        warm = s2.boot_block()
        monitor = s2.engine.hygiene.monitor.stats()
        repeat = _submit(s2)["disparity"]
    finally:
        s2.close()
    assert validate_boot(warm) == []
    assert warm["cache_hits"] == warm["entries"] == cold["entries"]
    assert warm["cache_misses"] == 0
    # Zero traces: the warm boot never fired a backend compile, proven by
    # the monitor, not by timing.
    assert warm["compiles_total"] == 0
    assert monitor["compiles_total"] == 0
    np.testing.assert_array_equal(repeat, baseline)


# -- thread hygiene (satellite regression) -----------------------------------


def test_fleet_joins_disposable_run_threads_at_close():
    """Regression: the hung-replica path runs the wedged batch on a
    disposable thread; pre-PR-16 it was fire-and-forget and could outlive
    service teardown. Now every fleet-spawned thread is tracked and joined
    (bounded) by close()."""
    from raft_stereo_tpu.serving.service import StereoService

    cfg = _config(
        replicas=2,
        sharding_rules="dp",
        breaker_degrade_after=1,
        breaker_fail_after=2,
        hang_timeout_s=1.0,
    )
    service = StereoService(cfg).start()
    fleet = service.engine
    try:
        with hung_chunk(fleet, hang_s=3.0, replica=0):
            # Watchdog abandons replica 0 at ~1 s; the request completes
            # via requeue while the wedged call is still sleeping.
            res = _submit(service)
            assert res["disparity"].shape == BUCKET
        assert fleet.replicas[0].lifecycle.state == "failed"
    finally:
        service.close()
    # The 3 s sleeper fits inside close()'s 5 s join budget: nothing from
    # the fleet survives teardown.
    leaked = [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith(("fleet-run-", "fleet-respawn-"))
    ]
    assert leaked == []
    assert fleet.join_run_threads(timeout_s=0.1) == 0


# -- respawn torture ---------------------------------------------------------


def test_auto_respawn_heals_sticky_failed_replica(tmp_path):
    """The full self-heal walk: poison replica 0 until its breaker is
    sticky-`failed` (every poisoned batch requeues exactly once and
    completes on replica 1 — zero dropped requests), wait for the
    background respawn to swap in a cache-booted replacement, drive real
    probation traffic through it back to `healthy`, and assert outputs
    stayed bit-identical throughout with zero post-grace compiles — the
    replacement boot was pure deserialization."""
    from raft_stereo_tpu.serving.service import StereoService

    cfg = _config(
        replicas=2,
        sharding_rules="dp",
        auto_respawn=True,
        aot_cache_dir=str(tmp_path),
        breaker_degrade_after=1,
        breaker_fail_after=2,
        breaker_probation=2,
    )
    service = StereoService(cfg).start()
    fleet = service.engine
    try:
        cold = service.boot_block()
        assert cold["cache_misses"] == cold["entries"] > 0  # cold fleet boot
        baseline = _submit(service)["disparity"]
        old_engine = fleet.replicas[0].engine

        with failing_run_batch(fleet, failures=None, replica=0) as calls:
            # Two sequential submits: quiesced routing ties to replica 0
            # (lowest index), each poisoned dispatch fails, requeues to
            # replica 1, and still answers the client bit-identically.
            for _ in range(2):
                _quiesce(fleet)
                res = _submit(service)
                np.testing.assert_array_equal(res["disparity"], baseline)
        assert calls["calls"] == 2  # failed exactly twice -> sticky-failed

        # The failure handler kicked a background replacement boot.
        deadline = time.monotonic() + 120.0
        while fleet.respawns_total < 1 or fleet.replicas[0].respawning:
            assert time.monotonic() < deadline, "auto-respawn never landed"
            time.sleep(0.02)
        new_rep = fleet.replicas[0]
        assert new_rep.engine is not old_engine
        assert old_engine.lifecycle.state == "failed"  # retired breaker stays
        assert new_rep.lifecycle.state == "degraded"  # probation entry state

        # The replacement warmed from the shared cache: its lookups are
        # ALL hits (the predecessor wrote the per-device entries at boot).
        stats = fleet.aot_cache.stats()
        assert stats["cache_hits"] == cold["entries"] // 2
        assert stats["cache_misses"] == cold["entries"]

        # Probation traffic routes to replica 0 (lowest admissible index
        # once quiesced) and earns `healthy` back — the heal is proven by
        # served requests, not by construction.
        for _ in range(cfg.breaker_probation):
            _quiesce(fleet)
            res = _submit(service)
            np.testing.assert_array_equal(res["disparity"], baseline)
        assert new_rep.lifecycle.state == "healthy"
        assert service.lifecycle.state == "healthy"

        # Exactly-once failover accounting, zero dropped requests.
        snap = service.metrics()
        assert snap["requeues_total"] == 2
        assert snap["respawns_total"] == 1
        assert snap["responses_total"] == snap["requests_total"]
        assert snap["shed_total"] == 0 and snap["failed_requests_total"] == 0

        # Cache-hit respawn = zero compiles outside the sanctioned boot
        # window, fleet-wide.
        assert fleet.hygiene.monitor.stats()["compiles_post_grace"] == 0

        # Observability: the heal is machine-visible on every surface.
        boot = service.boot_block()
        assert validate_boot(boot) == []
        assert boot["respawns_total"] == 1
        assert service.healthz()["serving"]["boot"]["respawns_total"] == 1
        prom = service.render_prom()
        assert "raft_serving_warmup_seconds" in prom
        assert "raft_serving_aot_cache_hits" in prom
        assert "raft_serving_respawns_total 1" in prom
    finally:
        service.close()
