#!/usr/bin/env bash
# CI gate: lint, audit, one pytest call. Distinct exit codes, so a red job
# says WHICH gate failed without scraping:
#
#   0  all gates passed
#   1  the test suite failed (pytest tests -m 'not slow')
#   2  usage/environment error
#   3  ruff found violations
#   4  graftlint crashed on a file / usage error (analysis did not complete),
#      or a rule no longer fires on its bad fixture
#   5  check_run_report --selftest failed (validator/builder drift)
#   6  NEW graftlint findings vs tools/graftlint/baseline.json
#  20  graftaudit fixture selftest failed (a compiled-artifact contract went dead)
#
# graftlint runs ONCE, as a baseline diff: findings recorded in the baseline
# (a reviewed legacy adoption via `scripts/lint.py --baseline write`) stay
# tracked without failing CI, anything NEW exits 6. The shipped baseline is
# EMPTY, so today exit 6 fires on ANY finding. The same run writes the SARIF
# artifact ($SARIF_OUT, default /tmp/graftlint.sarif) for code-scanning UIs.
#
# ruff is configured in pyproject.toml ([tool.ruff]) but is not bundled in
# every image; when the binary is absent the gate is SKIPPED with a loud note
# rather than failed. graftlint and the selftests are stdlib-only and always
# run. CI_CHECKS_FAST=1 skips the pytest call LOUDLY: tests/test_graftlint.py
# shells out to this script from inside that very suite.

set -u -o pipefail
cd "$(dirname "$0")/.." || exit 2

PYTHON="${PYTHON:-python}"
# A broken interpreter must read as an ENVIRONMENT error (exit 2), not as a
# gate failure: exit 4/5 mean "this gate found problems".
if ! "$PYTHON" -c 'pass' >/dev/null 2>&1; then
    echo "ci_checks: python interpreter '$PYTHON' is not runnable" >&2
    exit 2
fi

LINTED="raft_stereo_tpu scripts tools __graft_entry__.py"

echo "== ci_checks: ruff =="
if command -v ruff >/dev/null 2>&1; then
    # shellcheck disable=SC2086
    if ! ruff check $LINTED tests; then
        echo "ci_checks: ruff FAILED" >&2
        exit 3
    fi
    echo "ruff: clean"
else
    echo "ruff: not installed — SKIPPED (config lives in pyproject [tool.ruff]; install ruff to enable this gate)"
fi

echo "== ci_checks: graftlint fixture selftest (every rule fires) =="
# A rule that silently stopped matching is indistinguishable from a clean
# tree in the baseline-diff gate, so prove each GLxxx still flags its bad
# fixture (and spares its good twin) before the one real lint run below.
if ! "$PYTHON" scripts/lint.py --fixture-selftest; then
    echo "ci_checks: graftlint fixture-selftest FAILED (a rule went dead)" >&2
    exit 4
fi

echo "== ci_checks: graftlint (whole-program, baseline diff, SARIF) =="
# --report-unused-suppressions makes a stale `# graftlint: disable=GLxxx`
# pragma fail THIS gate: the next real finding on that line would be waived.
SARIF_OUT="${SARIF_OUT:-/tmp/graftlint.sarif}"
# shellcheck disable=SC2086
"$PYTHON" scripts/lint.py --baseline diff --report-unused-suppressions \
    --sarif "$SARIF_OUT" $LINTED
rc=$?
if [ "$rc" -eq 2 ]; then
    # Unreadable/unparsable file or bad usage: the gate gave no verdict.
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

echo "== ci_checks: graftaudit fixture selftest (every contract fires) =="
# GA001-GA005 on seeded HLO and its clean twin (stdlib-only, seconds); the
# live audit of warmed engines is tests/test_graftaudit.py, in the suite.
if ! "$PYTHON" scripts/audit.py --fixture-selftest; then
    echo "ci_checks: graftaudit fixture-selftest FAILED (a contract went dead)" >&2
    exit 20
fi

echo "== ci_checks: the test suite =="
if [ "${CI_CHECKS_FAST:-0}" = "1" ]; then
    echo "pytest: SKIPPED (CI_CHECKS_FAST=1 — the caller is the suite)"
elif ! env JAX_PLATFORMS=cpu "$PYTHON" -m pytest tests -q -m 'not slow' \
    -p no:cacheprovider -p no:randomly; then
    echo "ci_checks: the test suite FAILED" >&2
    exit 1
fi

echo "ci_checks: all gates passed"
exit 0
