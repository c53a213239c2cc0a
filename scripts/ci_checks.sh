#!/usr/bin/env bash
# CI gate: ruff (style/pyflakes/isort) + graftlint (JAX hazards, whole-
# program) + graftlint baseline diff + run-report validator selftest.
# Distinct exit codes so an orchestrator (or a human reading a red CI job)
# knows WHICH gate failed without scraping:
#
#   0  all gates passed
#   3  ruff found violations
#   4  graftlint crashed on a file / usage error (analysis did not complete)
#   5  check_run_report --selftest failed (validator/builder drift)
#   6  NEW graftlint findings vs tools/graftlint/baseline.json
#   7  fused-kernel parity tests (-m kernels) failed
#   8  bench-JSON schema check failed (validator selftest)
#   9  serving tests (-m serving) failed
#  10  sharding_scaling check failed (newest MULTICHIP_r*.json wrapper)
#  11  video/streaming tests (-m video) failed
#  12  serving fault-lifecycle tests (-m faults_serving) failed
#  13  serving fleet fault-domain tests (-m faults_fleet) failed
#  14  input-loader bench gate failed (micro bench run or line schema)
#  15  training I/O spine heavy tests (-m io_spine) failed
#  16  observability tests (-m obs) failed
#  17  instant-boot resilience tests (-m boot) failed
#  18  front-tier router tests (-m frontier) failed
#  19  checkpoint rollout tests (-m rollout) failed
#  20  graftaudit HLO contract gate failed (fixture selftest or -m audit)
#   2  usage/environment error
#
# graftlint runs ONCE, as a baseline diff: findings recorded in the
# baseline (a reviewed legacy adoption via `scripts/lint.py --baseline
# write`) stay tracked without failing CI, anything NEW exits 6 — that is
# what lets a new rule land at full strictness on new code while a legacy
# backlog burns down. The shipped baseline is EMPTY, so today exit 6 fires
# on ANY finding. The same run writes the SARIF artifact ($SARIF_OUT,
# default /tmp/graftlint.sarif) for code-scanning UIs.
#
# ruff is configured in pyproject.toml ([tool.ruff]) but is NOT bundled in
# every image; when the binary is absent the gate is SKIPPED with a loud
# note rather than failed — graftlint (stdlib-only) and the selftest always
# run, so the JAX-hazard gate can never rot silently. Run from anywhere;
# paths resolve relative to the repo root. tests/test_graftlint.py shells
# out to this script so tier-1 exercises the real gate.

set -u -o pipefail
cd "$(dirname "$0")/.." || exit 2

PYTHON="${PYTHON:-python}"
# A broken interpreter must read as an ENVIRONMENT error (exit 2), not as a
# gate failure — exit 4/5 mean "this gate found problems", and an
# orchestrator keys on that distinction.
if ! "$PYTHON" -c 'pass' >/dev/null 2>&1; then
    echo "ci_checks: python interpreter '$PYTHON' is not runnable" >&2
    exit 2
fi

echo "== ci_checks: ruff =="
if command -v ruff >/dev/null 2>&1; then
    if ! ruff check raft_stereo_tpu scripts tests tools bench.py __graft_entry__.py; then
        echo "ci_checks: ruff FAILED" >&2
        exit 3
    fi
    echo "ruff: clean"
else
    echo "ruff: not installed — SKIPPED (config lives in pyproject [tool.ruff]; install ruff to enable this gate)"
fi

echo "== ci_checks: graftlint fixture selftest (every rule fires) =="
# A rule that silently stopped matching is indistinguishable from a clean
# tree in the baseline-diff gate — so prove each GLxxx still flags its bad
# fixture (and spares its good twin) before the one real lint run below.
if ! "$PYTHON" scripts/lint.py --fixture-selftest; then
    echo "ci_checks: graftlint fixture-selftest FAILED (a rule went dead)" >&2
    exit 4
fi

echo "== ci_checks: graftlint (whole-program, baseline diff, SARIF) =="
# --report-unused-suppressions makes a stale `# graftlint: disable=GLxxx`
# pragma fail THIS gate: a pragma whose rule no longer fires is a latent
# hole (the next real finding on that line would be silently waived), so
# it must be deleted the commit its reason disappears.
SARIF_OUT="${SARIF_OUT:-/tmp/graftlint.sarif}"
"$PYTHON" scripts/lint.py --baseline diff --report-unused-suppressions \
    --sarif "$SARIF_OUT" \
    raft_stereo_tpu scripts tools bench.py __graft_entry__.py
rc=$?
if [ "$rc" -eq 2 ]; then
    # Analysis did not complete (unreadable/unparsable file, bad usage):
    # the JAX-hazard gate gave no verdict — that is a graftlint failure
    # (exit 4), not a clean pass and not a "new findings" verdict.
    echo "ci_checks: graftlint FAILED (crash/usage — no verdict)" >&2
    exit 4
elif [ "$rc" -ne 0 ]; then
    echo "ci_checks: NEW graftlint findings vs tools/graftlint/baseline.json" >&2
    echo "(fix them, or — for a reviewed legacy adoption ONLY — rerun scripts/lint.py --baseline write)" >&2
    exit 6
fi
echo "graftlint: no new findings; SARIF artifact at $SARIF_OUT"

echo "== ci_checks: run-report validator selftest =="
if ! "$PYTHON" scripts/check_run_report.py --selftest --quiet; then
    echo "ci_checks: check_run_report --selftest FAILED" >&2
    exit 5
fi
echo "selftest: ok"

echo "== ci_checks: fused-kernel parity tests (-m kernels) =="
# Interpret-mode Pallas parity for ops/encoder_pallas.py +
# ops/corr_pallas.fused_pyramid_state — the same kernel bodies the TPU
# build compiles, on CPU-safe small shapes. graftlint above already covers
# the ops/ modules (incl. GL007 dtype pinning) via the raft_stereo_tpu path.
# CI_CHECKS_FAST=1 skips this gate LOUDLY — for callers that already run
# the kernel marker themselves (the tier-1 suite shells this script while
# also collecting `-m kernels` directly; running them twice would double
# several minutes of interpreter-mode compiles inside the tier-1 budget).
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "kernels: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m kernels itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m kernels \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: kernel parity tests FAILED" >&2
    exit 7
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "kernels: ok"

echo "== ci_checks: serving tests (-m serving) =="
# The serving tier's unit + e2e suite (tests/test_serving.py): warmed
# service, concurrent shape buckets bit-identical to direct inference,
# deadline early-exit, zero post-warmup recompiles, healthz/metrics
# schemas. Same CI_CHECKS_FAST contract as the kernels gate: the tier-1
# suite collects `-m serving` itself and shells this script, so running
# the (warmup-heavy) suite twice would double minutes inside the tier-1
# budget — skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "serving: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m serving itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m serving \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: serving tests FAILED" >&2
    exit 9
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "serving: ok"

echo "== ci_checks: video/streaming tests (-m video) =="
# The streaming-stereo subsystem (tests/test_video.py): flow_init warm-start
# bit-parity vs the monolithic forward, the iters-to-EPE-parity acceptance
# A/B, the photometric reset gate, and stream sessions through the warmed
# serving tier with zero post-warmup recompiles. Same CI_CHECKS_FAST
# contract as the kernels/serving gates: the tier-1 suite collects
# `-m video` itself and shells this script — skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "video: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m video itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m video \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: video/streaming tests FAILED" >&2
    exit 11
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "video: ok"

echo "== ci_checks: serving fault-lifecycle tests (-m faults_serving) =="
# The fault lifecycle (tests/test_serving_faults.py): circuit breaker to
# `failed` under persistent batch failure, hung-chunk watchdog with stack
# dumps, deadline-infeasible shedding, graceful drain, zero-recompile
# checkpoint hot-swap, poisoned-stream isolation. Same CI_CHECKS_FAST
# contract as the kernels/serving/video gates: the tier-1 suite collects
# `-m faults_serving` itself and shells this script — skip LOUDLY, never
# silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "faults_serving: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m faults_serving itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m faults_serving \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: serving fault-lifecycle tests FAILED" >&2
    exit 12
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "faults_serving: ok"

echo "== ci_checks: serving fleet fault-domain tests (-m faults_fleet) =="
# The replica fault-domain layer (tests/test_serving_fleet.py): poisoned/
# hung replica failover with bit-identical responses and zero fleet-wide
# shed, rolling zero-downtime hot-swap with mid-roll rollback, fleet drain,
# --replicas 1 single-engine parity. Same CI_CHECKS_FAST contract as the
# gates above: the tier-1 suite collects `-m faults_fleet` itself and
# shells this script — skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "faults_fleet: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m faults_fleet itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m faults_fleet \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: serving fleet fault-domain tests FAILED" >&2
    exit 13
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "faults_fleet: ok"

echo "== ci_checks: bench-JSON schema =="
# Selftest pins the schema contract (sub-timing keys, per-iter partition).
if ! "$PYTHON" scripts/check_bench_json.py --selftest --quiet; then
    echo "ci_checks: check_bench_json --selftest FAILED" >&2
    exit 8
fi
echo "bench schema: ok"

echo "== ci_checks: sharding-scaling (MULTICHIP) =="
# The multichip dry run prints its sharding_scaling record as the LAST
# stdout line; the driver wraps that stdout into MULTICHIP_r*.json's
# "tail". Validating the newest wrapper catches a curve that silently
# stopped being emitted or went malformed the round it happens. Rounds
# that predate the engine (empty tail) pass — absence is legal there.
newest_multichip=$(ls MULTICHIP_r*.json 2>/dev/null | sort -V | tail -n 1)
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "sharding scaling: SKIPPED (CI_CHECKS_FAST=1)"
elif [ -n "$newest_multichip" ]; then
    if ! "$PYTHON" scripts/check_bench_json.py --quiet "$newest_multichip"; then
        echo "ci_checks: sharding_scaling FAILED on $newest_multichip" >&2
        exit 10
    fi
    echo "sharding scaling: ok ($newest_multichip)"
else
    echo "sharding scaling: SKIPPED (no MULTICHIP_r*.json committed)"
fi

echo "== ci_checks: input-loader bench (micro run + line schema) =="
# bench_loader.py's JSONL lines are what operators size worker pools from
# (x_step_rate / input_bound verdicts); validate_loader in
# check_bench_json.py pins that line schema. This gate runs a MICRO bench
# (tiny synthetic trees, one epoch) and validates its real stdout, so a
# bench_loader key drift or an items/s-vs-batches/s inconsistency is
# caught the commit it happens — not the next TPU calibration round.
# Same CI_CHECKS_FAST contract as the kernels/serving gates: the micro
# bench builds image trees and spins worker pools (tens of seconds), so
# fast callers skip it LOUDLY, never silently — validate_loader itself
# stays covered by the check_bench_json --selftest gate above (exit 8).
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "loader bench: SKIPPED (CI_CHECKS_FAST=1 — schema still pinned by the selftest gate)"
else
    loader_jsonl="$(mktemp /tmp/loader_bench.XXXXXX.jsonl)" || exit 2
    if ! env JAX_PLATFORMS=cpu "$PYTHON" scripts/bench_loader.py \
        --frames 4 --epochs 1 --batch_size 2 --workers 2 > "$loader_jsonl"; then
        echo "ci_checks: bench_loader micro run FAILED" >&2
        rm -f "$loader_jsonl"
        exit 14
    fi
    if ! "$PYTHON" scripts/check_bench_json.py --quiet "$loader_jsonl"; then
        echo "ci_checks: loader bench line schema FAILED (kept at $loader_jsonl)" >&2
        exit 14
    fi
    rm -f "$loader_jsonl"
    echo "loader bench: ok"
fi

echo "== ci_checks: training I/O spine heavy tests (-m io_spine) =="
# The PR-13 spine acceptance set: the strict-mode async-checkpoint +
# device-prefetch fit (bit-identical params, t_async <= t_sync,
# compiles_post_grace == 0), the SIGKILL-mid-async-commit crash leg with a
# clean fsck, the 2-process fsdp state spine, and the fsdp param-placement
# snapshot. Each compiles its own trainer or pod (minutes of CPU), so the
# suite is collection-ordered dead last in tier-1 and REALLY runs here —
# same CI_CHECKS_FAST contract as the kernels/serving gates: skip LOUDLY,
# never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "io_spine: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m io_spine itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m io_spine \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: training I/O spine heavy tests FAILED" >&2
    exit 15
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "io_spine: ok"

echo "== ci_checks: observability tests (-m obs) =="
# The PR-14 observability acceptance set: prom text exposition round-trip,
# /metrics content-type + JSON snapshot compatibility, tracer ring/dump
# semantics, attribution percentile edges, and the strict-mode obs-on
# serving + training runs proving the pillars add zero recompiles and zero
# unsanctioned transfers (compiles_post_grace == 0 with everything on).
# Warmup-heavy, so collection-ordered last in tier-1 and re-run here under
# the same CI_CHECKS_FAST contract: skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "obs: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m obs itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m obs \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: observability tests FAILED" >&2
    exit 16
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "obs: ok"

echo "== ci_checks: instant-boot resilience tests (-m boot) =="
# The PR-16 instant-boot acceptance set: AOT executable cache round-trip +
# loud eviction of corrupt/mismatched entries, the warm-cache second boot
# proving zero traces (100% cache hits, compiles_total == 0), fleet
# run-thread hygiene at close, and the replica auto-respawn torture test
# (sticky-failed replica healed under traffic with bit-identical outputs
# and compiles_post_grace == 0). Boots whole services — some twice — so
# collection-ordered dead last in tier-1 and re-run here under the same
# CI_CHECKS_FAST contract: skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "boot: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m boot itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m boot \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: instant-boot resilience tests FAILED" >&2
    exit 17
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "boot: ok"

echo "== ci_checks: front-tier router tests (-m frontier) =="
# The PR-17 front-tier acceptance set: health-checked routing with
# per-backend breakers, exactly-once retry on a different backend with a
# budget cap, hedging, stream-session affinity with cold-restart
# migration, the overload brownout A/B (served-with-fewer-iters instead
# of shed), slowloris hardening of the backend HTTP server, and the
# kill-a-backend-mid-traffic chaos drill against a real 2-backend fleet
# booted from a shared AOT cache (zero lost plain requests, bit-identical
# retried answers, failed -> probation -> healthy walk,
# compiles_post_grace == 0). Boots whole services, so collection-ordered
# after faults_fleet in tier-1 and re-run here under the same
# CI_CHECKS_FAST contract: skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "frontier: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m frontier itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m frontier \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: front-tier router tests FAILED" >&2
    exit 18
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "frontier: ok"

echo "== ci_checks: checkpoint rollout tests (-m rollout) =="
# The PR-18 rollout acceptance set: the frontier-driven rolling /reload
# orchestrator (quiesce -> reload -> verify -> probation walk with the
# flip), canary bit-identity across a generation, abort + rollback to the
# pre-roll checkpoint, drain-latch resume, the hardened reload-client
# exit codes, mixed-generation detection, and the two chaos drills
# against a real 3-backend fleet booted from a shared AOT cache (clean
# roll under mixed plain+stream traffic with mixed_generation_seconds ==
# 0 as stamped by the ledger and compiles_post_grace == 0 fleet-wide;
# mid-roll backend kill rolled BACK bit-identically with the frontier
# serving again). Boots whole services, so collection-ordered after
# frontier in tier-1 and re-run here under the same CI_CHECKS_FAST
# contract: skip LOUDLY, never silently.
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "rollout: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m rollout itself)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m rollout \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: checkpoint rollout tests FAILED" >&2
    exit 19
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "rollout: ok"

echo "== ci_checks: graftaudit HLO contract gate (-m audit) =="
# The PR-20 compiled-artifact auditor (tools/graftaudit/): GA001 chunk-
# boundary sharding fixpoint, GA002 honored donation, GA003 collective
# whitelist, GA004 bf16 corr dtype pins, GA005 hot-path purity. Two legs:
# the fixture selftest (stdlib-only, seconds — proves every GA contract
# still fires on its seeded HLO and stays quiet on the clean twin) ALWAYS
# runs, mirroring the graftlint selftest gate above; the live `-m audit`
# suite warms real engines on the 8-device mesh (minutes), so it follows
# the same CI_CHECKS_FAST contract as the other heavy gates: skip LOUDLY,
# never silently — tier-1 collects `-m audit` itself.
if ! "$PYTHON" scripts/audit.py --fixture-selftest; then
    echo "ci_checks: graftaudit fixture-selftest FAILED (a contract went dead)" >&2
    exit 20
fi
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "audit: SKIPPED (CI_CHECKS_FAST=1 — caller runs -m audit itself; selftest above still ran)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m audit \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: graftaudit HLO contract tests FAILED" >&2
    exit 20
fi
[ "${CI_CHECKS_FAST:-0}" = "1" ] || echo "audit: ok"

echo "ci_checks: all gates passed"
exit 0
