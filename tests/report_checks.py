"""The oracles of tests/test_boot.py, tests/test_frontier.py and
tests/test_rollout.py: checks on three reports the PROGRAM emits: the boot
block of `serve --warmup_only` / `StereoService.boot_block`, the front-tier
router's metrics (`serving/frontier.py`), and its checkpoint-rollout block
(`Frontier.rollout_block`). Each returns the list of what is wrong with a
block; an empty list is a sound report. Stdlib only.
"""

from __future__ import annotations

from typing import List

_NUM = (int, float)

_HEALTH_STATES = ("healthy", "degraded", "failed", "draining")

# Required keys inside the boot block: the instant-boot record (wall-clock
# warmup plus the executable-cache hit/miss ledger and the respawn counter).
_BOOT_REQUIRED = {
    "warmup_seconds": _NUM,
    "cache_enabled": bool,
    "cache_hits": int,
    "cache_misses": int,
    "entries": int,
    "respawns_total": int,
}


def validate_boot(block) -> List[str]:
    """Validate one boot block. Contract: warmup took real wall-clock time
    (`warmup_seconds` > 0 — a zero means the timer never ran, not an
    instant boot), the cache ledger is exhaustive (every warmed entry was
    either a hit or a miss: hits + misses == entries, all non-negative),
    and the respawn counter is a non-negative int."""
    errs = []
    if not isinstance(block, dict):
        return ["boot block is not a JSON object"]
    for key, types in _BOOT_REQUIRED.items():
        if key not in block:
            errs.append(f"boot missing required key {key!r}")
        elif not isinstance(block[key], types) or (
            types is not bool and isinstance(block[key], bool)
        ):
            errs.append(f"boot[{key!r}] has type {type(block[key]).__name__}")
    if errs:
        return errs
    if block["warmup_seconds"] <= 0:
        errs.append(
            f"boot warmup_seconds must be > 0, got {block['warmup_seconds']} "
            "(a zero means the warmup timer never ran)"
        )
    for key in ("cache_hits", "cache_misses", "entries", "respawns_total"):
        if block[key] < 0:
            errs.append(f"boot[{key!r}] must be >= 0, got {block[key]}")
    if not errs and block["cache_hits"] + block["cache_misses"] != block["entries"]:
        errs.append(
            f"boot cache ledger does not balance: hits {block['cache_hits']} "
            f"+ misses {block['cache_misses']} != entries {block['entries']} "
            "(every warmed executable must be accounted a hit or a miss)"
        )
    return errs


_FRONTIER_REQUIRED = {
    "backends": int,
    "backend_states": list,
    "requests_total": int,
    "responses_total": int,
    "errors_total": int,
    "retries_total": int,
    "hedges_total": int,
    "hedge_wins_total": int,
    "migrations_total": int,
    "stream_requests_total": int,
    "shed_total": int,
    "brownout_engagements_total": int,
    "brownout_requests_total": int,
}
# Latency percentiles are required keys but may be null: a frontier that
# answered fewer than two requests has no percentile, and 0.0 would lie.
_FRONTIER_LATENCY_KEYS = ("latency_p50_ms", "latency_p99_ms")


def validate_frontier(block) -> List[str]:
    """Validate one front-tier router block (serving/frontier.py metrics).
    Contract: at least one routed
    backend with every state inside the lifecycle enum (one state per
    configured backend), the exactly-once ledger holds (responses never
    exceed requests), retry amplification is bounded by traffic (retries
    <= requests — the retry budget makes more impossible in steady state),
    hedge wins are a subset of hedges fired, every counter is a
    non-negative int, and the latency percentiles are ordered when
    present (null below two samples)."""
    errs = []
    if not isinstance(block, dict):
        return ["frontier block is not a JSON object"]
    for key, types in _FRONTIER_REQUIRED.items():
        if key not in block:
            errs.append(f"frontier missing required key {key!r}")
        elif not isinstance(block[key], types) or isinstance(block[key], bool):
            errs.append(f"frontier[{key!r}] has type {type(block[key]).__name__}")
    for key in _FRONTIER_LATENCY_KEYS:
        if key not in block:
            errs.append(f"frontier missing required key {key!r}")
        elif block[key] is not None and (
            not isinstance(block[key], _NUM) or isinstance(block[key], bool)
        ):
            errs.append(f"frontier[{key!r}] has type {type(block[key]).__name__}")
    if errs:
        return errs
    if block["backends"] < 1:
        errs.append(f"frontier backends must be >= 1, got {block['backends']}")
    states = block["backend_states"]
    if len(states) != block["backends"]:
        errs.append(
            f"frontier backend_states has {len(states)} entries for "
            f"{block['backends']} backends (one state per configured backend)"
        )
    for i, s in enumerate(states):
        if s not in _HEALTH_STATES:
            errs.append(
                f"frontier backend_states[{i}] {s!r} not in {_HEALTH_STATES}"
            )
    for key in _FRONTIER_REQUIRED:
        if key != "backend_states" and block[key] < 0:
            errs.append(f"frontier[{key!r}] must be >= 0, got {block[key]}")
    if errs:
        return errs
    if block["responses_total"] > block["requests_total"]:
        errs.append(
            f"frontier responses_total {block['responses_total']} > "
            f"requests_total {block['requests_total']} (exactly-once ledger: "
            "at most one answer per admitted request)"
        )
    if block["retries_total"] > block["requests_total"]:
        errs.append(
            f"frontier retries_total {block['retries_total']} > "
            f"requests_total {block['requests_total']} (the retry budget "
            "bounds amplification below traffic)"
        )
    if block["hedge_wins_total"] > block["hedges_total"]:
        errs.append(
            f"frontier hedge_wins_total {block['hedge_wins_total']} > "
            f"hedges_total {block['hedges_total']} (a win presumes a hedge)"
        )
    p50, p99 = block["latency_p50_ms"], block["latency_p99_ms"]
    if (p50 is None) != (p99 is None):
        errs.append(
            "frontier latency percentiles must be both null or both numeric"
        )
    elif p50 is not None and p50 > p99:
        errs.append(f"frontier latency_p50_ms {p50} > latency_p99_ms {p99}")
    return errs


# Rollout state machine of serving/frontier.py run_rollout: the block's
# phase must be one of these exact strings.
_ROLLOUT_PHASES = (
    "idle",
    "quiesce",
    "reload",
    "verify",
    "probation",
    "flip",
    "completed",
    "aborting",
    "aborted",
    "rolled_back",
)

_ROLLOUT_REQUIRED = {
    "phase": str,
    "rollouts_total": int,
    "aborts_total": int,
    "rollbacks_total": int,
    "fleet_generation": int,
    "backend_generations": list,
    "mixed_generation_seconds": _NUM,
    "generation_stamps_total": int,
    "generation_divergence": bool,
    "zero_mixed_window": bool,
}


def validate_rollout(block) -> List[str]:
    """Validate one checkpoint-rollout block (serving/frontier.py
    rollout_block). Contract:
    the phase is inside the orchestrator's state enum, the failure-path
    counters nest (a rollback presumes an abort, an abort presumes a
    rollout: rollbacks <= aborts <= rollouts), generations are
    non-negative ints with fleet_generation — the provable fleet floor —
    never above the best backend, a completed roll left every backend on
    the fleet generation, and the zero-mixed-weight-window verdict agrees
    exactly with the measured mixed_generation_seconds."""
    errs = []
    if not isinstance(block, dict):
        return ["rollout block is not a JSON object"]
    for key, types in _ROLLOUT_REQUIRED.items():
        if key not in block:
            errs.append(f"rollout missing required key {key!r}")
        elif types is bool:
            # Booleans validate as exactly bool (an int 0/1 would pass an
            # isinstance(int) check and hide a type regression).
            if not isinstance(block[key], bool):
                errs.append(
                    f"rollout[{key!r}] has type {type(block[key]).__name__}"
                )
        elif not isinstance(block[key], types) or isinstance(block[key], bool):
            errs.append(
                f"rollout[{key!r}] has type {type(block[key]).__name__}"
            )
    if errs:
        return errs
    if block["phase"] not in _ROLLOUT_PHASES:
        errs.append(
            f"rollout phase {block['phase']!r} not in {_ROLLOUT_PHASES}"
        )
    for key in (
        "rollouts_total",
        "aborts_total",
        "rollbacks_total",
        "fleet_generation",
        "generation_stamps_total",
        "mixed_generation_seconds",
    ):
        if block[key] < 0:
            errs.append(f"rollout[{key!r}] must be >= 0, got {block[key]}")
    gens = block["backend_generations"]
    for i, g in enumerate(gens):
        if not isinstance(g, int) or isinstance(g, bool) or g < 0:
            errs.append(
                f"rollout backend_generations[{i}] must be a non-negative "
                f"int, got {g!r}"
            )
    if errs:
        return errs
    if block["rollbacks_total"] > block["aborts_total"]:
        errs.append(
            f"rollout rollbacks_total {block['rollbacks_total']} > "
            f"aborts_total {block['aborts_total']} (a rollback presumes an "
            "aborted roll)"
        )
    if block["aborts_total"] > block["rollouts_total"]:
        errs.append(
            f"rollout aborts_total {block['aborts_total']} > "
            f"rollouts_total {block['rollouts_total']} (an abort presumes a "
            "started roll)"
        )
    if gens and block["fleet_generation"] > max(gens):
        errs.append(
            f"rollout fleet_generation {block['fleet_generation']} above the "
            f"best backend generation {max(gens)} (the fleet floor cannot "
            "exceed any member)"
        )
    if block["phase"] == "completed" and gens and (
        set(gens) != {block["fleet_generation"]}
    ):
        errs.append(
            f"rollout phase 'completed' with backend_generations {gens} not "
            f"all on fleet_generation {block['fleet_generation']} (a "
            "completed roll leaves one generation)"
        )
    if block["zero_mixed_window"] != (block["mixed_generation_seconds"] == 0):
        errs.append(
            f"rollout zero_mixed_window {block['zero_mixed_window']} "
            f"contradicts mixed_generation_seconds "
            f"{block['mixed_generation_seconds']} (the verdict must restate "
            "the measurement)"
        )
    return errs
