"""Test harness: run everything on a virtual 8-device CPU mesh.

Must set XLA flags before jax initializes its backends, hence the env mutation
at import time (pytest imports conftest before collecting test modules).

Compile-cost discipline: eager flax `init`/`apply` on CPU dispatches hundreds
of tiny XLA compiles (~200s for one init), so tests ALWAYS wrap init and
forward passes in `jax.jit` and share the default-config model through the
session-scoped fixture below.
"""

import os

# Force CPU even when a TPU platform is preset in the environment: the suite
# needs the 8-device virtual mesh, not an attached chip. The env var reaches
# the worker subprocesses; the config override after import covers a jax
# that something (a pytest plug-in) imported before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # Compile counts are assertions here (RecompileMonitor, cold-boot
    # ledgers). Tests that call a CLI entry point in-process run its
    # setup_compile_cache(), which would otherwise switch the in-checkout
    # persistent cache on for the rest of the session and turn later
    # compiles into silent cache hits.
    jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

TEST_H, TEST_W = 48, 64


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def jit_init(cfg, h=TEST_H, w=TEST_W, b=1):
    """One-compile model init (see module docstring)."""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.models import RAFTStereo

    model = RAFTStereo(cfg)
    img = jnp.zeros((b, h, w, cfg.in_channels))
    variables = jax.jit(lambda r: model.init(r, img, img, iters=1))(jax.random.PRNGKey(0))
    return model, variables


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="run @pytest.mark.slow tests (long-horizon convergence; ~20+ min on CPU)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-horizon tests run once per round via --runslow, skipped by default",
    )
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection suite (tests/test_resilience.py). "
        "Tier-1 — NOT slow-gated: the degradation paths run in the standard "
        "verify command; select just them with -m faults",
    )
    config.addinivalue_line(
        "markers",
        "distributed(timeout=N): multi-process jax.distributed tests "
        "(tests/test_distributed.py). Tier-1; each runs under a HARD "
        "SIGALRM timeout (default 600 s) so a wedged collective fails the "
        "test instead of hanging the harness. Select with -m distributed",
    )
    config.addinivalue_line(
        "markers",
        "lint: graftlint static-analysis self-tests (tests/test_graftlint.py). "
        "Tier-1; pure AST — no JAX device, no model compile. Select with "
        "-m lint",
    )
    config.addinivalue_line(
        "markers",
        "hygiene: runtime jit-hygiene tests (tests/test_jit_hygiene.py): "
        "strict-mode transfer guard + RecompileMonitor against real CPU "
        "training runs. Tier-1; select with -m hygiene",
    )
    config.addinivalue_line(
        "markers",
        "kernels: fused Pallas encoder/corr kernel parity tests "
        "(tests/test_encoder_pallas.py) run in interpreter mode on small "
        "shapes. Tier-1, CPU-safe; select with -m kernels",
    )
    config.addinivalue_line(
        "markers",
        "serving: inference serving tier tests (tests/test_serving.py): "
        "warmed anytime engine, micro-batcher, HTTP front — bit-identity "
        "vs direct inference, deadline early-exit, zero post-warmup "
        "recompiles. Tier-1, CPU; select with -m serving",
    )
    config.addinivalue_line(
        "markers",
        "sharding: rule-driven sharding engine tests (tests/test_sharding.py): "
        "rule matching, preset placements on the 8-device virtual mesh, "
        "dp bit-identity vs the legacy layout, spatial corr-chain "
        "collective audit, merged coordination fetch. Tier-1, CPU; select "
        "with -m sharding",
    )
    config.addinivalue_line(
        "markers",
        "video: streaming/video stereo tests (tests/test_video.py): "
        "flow_init warm-start bit-parity vs the monolithic forward, the "
        "iters-to-EPE-parity acceptance A/B, the photometric reset gate, "
        "and stream sessions through the warmed serving tier with zero "
        "post-warmup recompiles. Tier-1, CPU; select with -m video",
    )
    config.addinivalue_line(
        "markers",
        "faults_serving: serving fault-lifecycle suite "
        "(tests/test_serving_faults.py): circuit breaker to `failed` under "
        "persistent batch failure, hung-chunk watchdog with stack dumps, "
        "graceful drain, zero-recompile checkpoint hot-swap, poisoned-stream "
        "isolation. Tier-1, CPU; collection-ordered after `serving`. Select "
        "with -m faults_serving",
    )
    config.addinivalue_line(
        "markers",
        "faults_fleet: serving fleet fault-domain suite "
        "(tests/test_serving_fleet.py): per-replica breakers behind one "
        "batcher, failover requeue with bit-identical responses, hung-"
        "replica abandonment, rolling zero-downtime hot-swap with mid-roll "
        "rollback, fleet drain, --replicas 1 single-engine parity. Tier-1, "
        "CPU; collection-ordered after `faults_serving`. Select with "
        "-m faults_fleet",
    )
    config.addinivalue_line(
        "markers",
        "io_spine: training I/O spine heavy suite (PR 13): the strict-mode "
        "async-checkpoint + device-prefetch acceptance fit, the SIGKILL-"
        "mid-async-commit crash leg, the 2-process fsdp state spine, and "
        "the fsdp param-placement snapshot. Tier-1; collection-ordered dead "
        "last (each compiles its own trainer/pod — minutes of CPU). "
        "Select with -m io_spine",
    )
    config.addinivalue_line(
        "markers",
        "obs: observability suite (tests/test_obs.py, PR 14): prom text "
        "exposition round-trip, /metrics content-type + JSON snapshot "
        "compatibility, flight-recorder ring/dump semantics, attribution "
        "percentile edges, and the strict-mode obs-on serving + training "
        "acceptance runs (compiles_post_grace == 0 with every pillar on). "
        "Tier-1; collection-ordered dead last (warms its own service and "
        "trainer). Select with -m obs",
    )
    config.addinivalue_line(
        "markers",
        "boot: instant-boot resilience suite (tests/test_boot.py, PR 16): "
        "persistent AOT executable cache round-trip + eviction, warm-cache "
        "zero-compile second boot, fleet run-thread hygiene, and the "
        "replica auto-respawn torture test (sticky-failed replica healed "
        "under traffic, bit-identical outputs, compiles_post_grace == 0). "
        "Tier-1; collection-ordered dead last (boots whole services, some "
        "twice). Select with -m boot",
    )
    config.addinivalue_line(
        "markers",
        "frontier: front-tier router chaos suite (tests/test_frontier.py, "
        "PR 17): health-checked routing across N backend hosts, exactly-"
        "once retry on a different backend, hedging, stream-session "
        "affinity with cold-restart migration, overload brownout A/B, "
        "slowloris hardening, and the kill-a-backend-mid-traffic chaos "
        "drill against a real 2-backend fleet booted from a shared AOT "
        "cache. Tier-1; collection-ordered after `faults_fleet` (it boots "
        "whole services). Select with -m frontier",
    )
    config.addinivalue_line(
        "markers",
        "rollout: cross-host checkpoint rollout suite (tests/"
        "test_rollout.py, PR 18): the frontier-driven rolling /reload "
        "orchestrator — quiesce/reload/verify/probation walk, canary "
        "bit-identity, abort + rollback, drain-latch resume, mixed-"
        "generation detection — plus two chaos drills against a real "
        "3-backend fleet booted from a shared AOT cache (clean roll "
        "under mixed traffic with a ledger-proved zero mixed-weight "
        "window; mid-roll backend kill rolled BACK bit-identically). "
        "Tier-1; collection-ordered after `frontier` (it boots whole "
        "services). Select with -m rollout",
    )
    config.addinivalue_line(
        "markers",
        "audit: graftaudit HLO contract-audit suite (tests/test_graftaudit.py, "
        "PR 20): the single-parser delegation contrast vs the legacy "
        "sharding.py regexes, fixture selftest per contract class, donation "
        "on the real train step, the chunk-boundary sharding fixpoint for "
        "every warmed (bucket, batch) combo under dp AND spatial, and the "
        "scripts/audit.py CLI round-trip. Tier-1; collection-ordered dead "
        "last (warms real engines on the 8-device mesh); the fixture "
        "selftest alone is ci_checks' exit 20. Select with -m audit",
    )
    config.addinivalue_line(
        "markers",
        "crash(timeout=N): SIGKILL crash-recovery torture tests "
        "(tests/test_crash_recovery.py), driving subprocess training runs "
        "that are killed and auto-resumed. Tier-1; same HARD SIGALRM "
        "timeout discipline as `distributed` (a test about surviving kills "
        "must itself never hang the harness). Select with -m crash",
    )


def pytest_collection_modifyitems(config, items):
    # The serving suites warm real compile caches (~18 full-model XLA
    # compiles each) and are by far the most expensive modules; the video
    # suite warms its own (smaller) service. Run them after everything
    # else — fault-lifecycle late and the fleet suite dead last (it builds
    # on the single-engine fault evidence), after `serving` per its design (it
    # deliberately breaks its service; a shared wall-clock budget should
    # bank the happy-path serving evidence first) — so CI spends its time
    # on the older, broader coverage first; within each module the original
    # order is preserved (their final tests assert over the whole module's
    # traffic).
    items.sort(
        key=lambda item: 10 * ("audit" in item.keywords)
        + 9 * ("boot" in item.keywords)
        + 8 * ("obs" in item.keywords)
        + 7 * ("io_spine" in item.keywords)
        + 6 * ("rollout" in item.keywords)
        + 5 * ("frontier" in item.keywords)
        + 4 * ("faults_fleet" in item.keywords)
        + 3 * ("faults_serving" in item.keywords)
        + 2 * ("serving" in item.keywords)
        + ("video" in item.keywords)
    )
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow (once per round)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _distributed_hard_timeout(request):
    """HARD per-test timeout for @pytest.mark.distributed and
    @pytest.mark.crash tests: the whole point of those tests is proving
    hangs/kills get converted into failures, so the harness itself must
    never hang on them. SIGALRM fires in the main thread and raises — this
    backstops even a wedged subprocess.communicate. No pytest-timeout in
    the image, hence hand-rolled; POSIX-only, like the gloo collectives the
    tests exercise."""
    import signal as _signal

    marker = request.node.get_closest_marker("distributed") or request.node.get_closest_marker("crash")
    if marker is None:
        yield
        return
    seconds = int(marker.kwargs.get("timeout", 600))

    def _alarm(signum, frame):
        raise TimeoutError(
            f"hard distributed-test timeout after {seconds}s: {request.node.nodeid}"
        )

    prev = _signal.signal(_signal.SIGALRM, _alarm)
    _signal.alarm(seconds)
    try:
        yield
    finally:
        _signal.alarm(0)
        _signal.signal(_signal.SIGALRM, prev)


@pytest.fixture(scope="session")
def default_model_bundle():
    """(cfg, model, variables) for the default config, jit-initialized once."""
    from raft_stereo_tpu.config import RAFTStereoConfig

    cfg = RAFTStereoConfig()
    model, variables = jit_init(cfg)
    return cfg, model, variables
