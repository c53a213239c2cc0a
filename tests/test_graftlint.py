"""graftlint self-tests (tier-1, `-m lint`): one fixture pair per rule
GL001-GL010 (bad snippet flagged / good snippet clean), the cross-module
fixture package (traced-ness through a jitted factory in another file,
call-graph cycles, device taint through helper returns), suppression-pragma
behavior incl. stale-pragma reporting, the baseline write/diff round-trip,
SARIF output, machine-readable JSON output, the CI gate script, and — the
acceptance criterion — the shipped tree linting clean under whole-program
analysis.

Pure AST: no JAX device, no model import; the whole module runs in
milliseconds."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tools", "graftlint", "fixtures")
sys.path.insert(0, REPO)

from tools.graftlint import (  # noqa: E402
    ALL_RULES,
    RULE_TABLE,
    lint_source,
    lint_sources,
)

pytestmark = pytest.mark.lint

RULE_IDS = sorted(RULE_TABLE)


def run_lint_file(path):
    with open(path, encoding="utf-8") as f:
        source = f.read()
    return lint_source(path, source, ALL_RULES)


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_bad_fixture_is_flagged(rule_id):
    """Each rule's bad fixture must produce >= 1 finding OF THAT RULE (a
    finding from another rule would mean the fixture tests nothing)."""
    findings, _ = run_lint_file(os.path.join(FIXTURES, f"{rule_id.lower()}_bad.py"))
    rules_hit = {f.rule for f in findings}
    assert rule_id in rules_hit, (
        f"{rule_id} bad fixture produced no {rule_id} finding: {findings}"
    )


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_good_fixture_is_clean(rule_id):
    """The good twin demonstrates the sanctioned pattern — it must be clean
    under EVERY rule, not just its own (one rule's fix must not trip
    another)."""
    findings, suppressed = run_lint_file(
        os.path.join(FIXTURES, f"{rule_id.lower()}_good.py")
    )
    assert findings == [], f"{rule_id} good fixture flagged: {findings}"
    assert suppressed == 0


def test_gl007_augmented_store_coverage():
    """The mixed-precision accumulation hole (PR 15): `o_ref[...] += acc`
    promotes through jnp rules exactly like a plain store, so GL007 must
    flag the bare augmented store (gl007_bad.py:24) while both sanctioned
    forms — `.astype(o_ref.dtype)` on the accumulated value and a bare
    ref-to-ref accumulate — stay clean (covered by the good twin, which
    test_good_fixture_is_clean already runs; this pins the exact bad line
    so the AugAssign branch can't silently stop matching)."""
    findings, _ = run_lint_file(os.path.join(FIXTURES, "gl007_bad.py"))
    aug = [f for f in findings if f.rule == "GL007" and "augmented store" in f.message]
    assert [f.line for f in aug] == [24], (
        f"expected exactly one augmented-store finding at line 24: {findings}"
    )


def test_bad_fixtures_flag_only_their_own_rule():
    """Cross-talk check: a bad fixture may only trigger its own rule —
    anything else is a false positive in another rule's logic."""
    for rule_id in RULE_IDS:
        findings, _ = run_lint_file(
            os.path.join(FIXTURES, f"{rule_id.lower()}_bad.py")
        )
        assert {f.rule for f in findings} == {rule_id}, (
            f"{rule_id} fixture cross-triggered: {findings}"
        )


def test_line_suppression_is_counted():
    findings, suppressed = run_lint_file(os.path.join(FIXTURES, "suppressed.py"))
    assert findings == []
    assert suppressed == 3  # GL001 + GL004 + GL005, each pragma'd in place


def test_file_level_suppression_is_selective():
    """disable-file silences only the named rule; others still fire."""
    findings, suppressed = run_lint_file(
        os.path.join(FIXTURES, "suppressed_file.py")
    )
    assert suppressed == 1  # the GL001 np call
    assert [f.rule for f in findings] == ["GL004"]  # untouched by the pragma


def test_gl005_taint_is_flow_sensitive():
    """Taint queries must use the state AS OF the queried line: a name
    rebound from a jitted call after a host use must not retro-flag the
    earlier (clean) use, and laundering through device_get later must not
    excuse an implicit sync that already happened."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s, b: s)\n"
        "\n"
        "\n"
        "def rebound_after_use(batch, x):\n"
        "    a = float(x)  # x is a host arg HERE: clean\n"
        "    x = step(x, batch)\n"
        "    return x, a\n"
        "\n"
        "\n"
        "def laundered_after_use(state, batch):\n"
        "    m = step(state, batch)\n"
        "    v = float(m)  # implicit sync BEFORE the laundering: flagged\n"
        "    m = jax.device_get(m)\n"
        "    return v, m\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL005"})
    assert [(f.rule, f.line) for f in findings] == [("GL005", 13)], findings


def test_gl005_taint_sees_across_loop_iterations():
    """Inside a loop the may-taint state is the loop body's END state: an
    assignment later in the body taints textually-earlier uses on the next
    iteration."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s, b: (s, s))\n"
        "\n"
        "\n"
        "def fit(state, batches):\n"
        "    m = None\n"
        "    for b in batches:\n"
        "        if m is not None:\n"
        "            v = float(m)  # m from step() on iteration 2+: flagged\n"
        "        state, m = step(state, b)\n"
        "    return state\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL005"})
    assert [(f.rule, f.line) for f in findings] == [("GL005", 9)], findings


def test_gl005_host_scalar_cast_launders():
    """float()/int() ARE the flagged sync — but their RESULT is a host
    scalar, so taint must not propagate through them (the f-string on the
    cast's result is host math, not a second sync)."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s, b: s)\n"
        "\n"
        "\n"
        "def drive(state, batch):\n"
        "    m = step(state, batch)\n"
        "    loss = float(m)  # the one real sync\n"
        "    print(f'loss={loss:.3f}')  # host float: clean\n"
        "    return loss\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL005"})
    assert [(f.rule, f.line) for f in findings] == [("GL005", 7)], findings


def test_pragma_in_string_or_docstring_is_inert():
    """A pragma QUOTED in a docstring or string literal (e.g. prose that
    documents the suppression syntax) must NOT activate a suppression —
    only real comment tokens count. Regression: the engine once regex-
    scanned raw lines and its own docstring self-suppressed GL001."""
    source = (
        '"""Docs: waive a file with `# graftlint: disable-file=GL001`."""\n'
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.sum(x)\n"
    )
    findings, suppressed = lint_source("<mem>", source, ALL_RULES)
    assert [f.rule for f in findings] == ["GL001"]
    assert suppressed == 0


def test_traced_pragma_marks_function():
    """`# graftlint: traced` must pull a function the inference cannot see
    into GL001-003 scope (factories whose product is jitted elsewhere)."""
    source = (
        "import numpy as np\n"
        "def body(x):  # graftlint: traced\n"
        "    return np.sum(x)\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES)
    assert [f.rule for f in findings] == ["GL001"]
    # Without the pragma the same function is host code and clean.
    findings, _ = lint_source("<mem>", source.replace("  # graftlint: traced", ""), ALL_RULES)
    assert findings == []


def test_json_output_schema():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), "--json",
         os.path.join(FIXTURES, "gl001_bad.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1  # findings present
    report = json.loads(proc.stdout)
    assert report["version"] == 1
    assert report["files_checked"] == 1
    assert report["rules"] == RULE_TABLE
    for f in report["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message"}
        assert f["rule"] == "GL001"
        assert f["line"] > 0 and f["col"] > 0


def test_runner_exit_codes():
    lint = os.path.join(REPO, "scripts", "lint.py")
    clean = subprocess.run(
        [sys.executable, lint, os.path.join(FIXTURES, "gl001_good.py")],
        capture_output=True, cwd=REPO,
    )
    assert clean.returncode == 0
    usage = subprocess.run(
        [sys.executable, lint, "no/such/path.py"], capture_output=True, cwd=REPO
    )
    assert usage.returncode == 2
    bad_rule = subprocess.run(
        [sys.executable, lint, "--select", "GL999", "raft_stereo_tpu"],
        capture_output=True, cwd=REPO,
    )
    assert bad_rule.returncode == 2


def test_select_subset_of_rules():
    source = (
        "import jax\nimport numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)\n"
        "    return np.sum(x)\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL003"})
    assert {f.rule for f in findings} == {"GL003"}


def test_shipped_tree_is_lint_clean():
    """THE acceptance criterion: `python scripts/lint.py raft_stereo_tpu`
    exits 0 on the shipped tree. Runs the real runner over the real
    package + tooling, exactly as scripts/ci_checks.sh does."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "raft_stereo_tpu", "scripts", "tools", "__graft_entry__.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, f"tree not lint-clean:\n{proc.stdout}{proc.stderr}"


def test_ci_checks_script_passes():
    """The CI gate (ruff when available + graftlint + the validator and
    audit selftests) must pass on the shipped tree — and this test is what
    keeps the gate itself from rotting. CI_CHECKS_FAST skips only the
    script's one pytest call: this suite IS that call."""
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "ci_checks.sh")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "CI_CHECKS_FAST": "1"},
    )
    assert proc.returncode == 0, (
        f"ci_checks.sh failed rc={proc.returncode}:\n{proc.stdout}{proc.stderr}"
    )


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_new_bad_fixtures_produce_exactly_their_seeded_findings():
    """GL008-GL014 bad fixtures: EXACT (rule, line) sets — the seeded
    hazards, nothing more, nothing less (acceptance criterion)."""
    expected = {
        "gl008_bad.py": [("GL008", 14), ("GL008", 19)],
        "gl008_returns_bad.py": [("GL008", 28), ("GL008", 34), ("GL008", 39)],
        "gl009_bad.py": [("GL009", 11), ("GL009", 17), ("GL009", 24)],
        "gl010_bad.py": [("GL010", 18), ("GL010", 27), ("GL010", 34)],
        "gl010_alias_bad.py": [("GL010", 19), ("GL010", 26)],
        # the unguarded `self._count += 1` in the thread-reachable worker
        "gl011_bad.py": [("GL011", 31)],
        # ONE finding per cyclic SCC, anchored at its earliest edge site
        # (the nested `with self._audit:` inside credit)
        "gl012_bad.py": [("GL012", 14)],
        # the chained fire-and-forget + the never-joined local handle
        "gl013_bad.py": [("GL013", 11), ("GL013", 15)],
        # queue.get under the lock, device sync under the lock, and the
        # interprocedural call into the may-block helper
        "gl014_bad.py": [("GL014", 15), ("GL014", 19), ("GL014", 24)],
    }
    for name, want in expected.items():
        findings, suppressed = run_lint_file(os.path.join(FIXTURES, name))
        assert [(f.rule, f.line) for f in findings] == want, (name, findings)
        assert suppressed == 0


def test_cross_module_fixture_package():
    """The xmod package, linted AS ONE PROJECT: the factory's step_fn is
    traced because driver.py jits the factory's RETURN VALUE (no pragma);
    device taint flows consumer <- helpers <- driver across three modules;
    the entry->_ping->_pong->_ping cycle converges and still reaches the
    numpy call inside it."""
    xmod = os.path.join(FIXTURES, "xmod")
    files = sorted(
        os.path.join(xmod, n) for n in os.listdir(xmod) if n.endswith(".py")
    )
    sources = [(p, _read(p)) for p in files]
    findings, suppressed, project = lint_sources(sources, ALL_RULES, root=REPO)
    got = sorted((os.path.basename(f.path), f.rule, f.line) for f in findings)
    assert got == [
        ("consumer.py", "GL005", 8),
        ("cycles.py", "GL001", 15),
        ("factory.py", "GL001", 11),
        # locks_a nests LOCK_A->LOCK_B, locks_b nests LOCK_B->LOCK_A: the
        # ring only closes when both modules resolve in one project; the
        # single finding anchors at the earliest edge site.
        ("locks_a.py", "GL012", 14),
    ], findings
    assert suppressed == 0
    # Per-file, WITHOUT the cross-module project, the factory/consumer
    # hazards are invisible (their trace boundary / jit lives in another
    # file) and each lock module sees only half the ring. cycles.py stays
    # visible solo by design: even a single-module project propagates
    # traced-ness through its own call graph.
    solo = []
    for p in files:
        f, _ = run_lint_file(p)
        solo.extend(f)
    assert [(os.path.basename(f.path), f.rule) for f in solo] == [
        ("cycles.py", "GL001")
    ], solo


def test_stale_traced_pragma_is_reported():
    """A `traced` pragma on a function the cross-module inference already
    sees must be reported stale; a pragma marking no function too."""
    factory = (
        "import numpy as np\n"
        "def make_body(s):\n"
        "    def body(x):  # graftlint: traced\n"
        "        return np.sum(x) * s\n"
        "    return body\n"
        "# graftlint: traced\n"
    )
    driver = (
        "import jax\n"
        "from .factory import make_body\n"
        "run = jax.jit(make_body(2.0))\n"
    )
    base = os.path.join("tools", "graftlint", "fixtures", "xmod2")
    findings, _, project = lint_sources(
        [
            (os.path.join(base, "factory.py"), factory),
            (os.path.join(base, "driver.py"), driver),
        ],
        ALL_RULES,
    )
    # the pragma'd function IS traced (finding fires) ...
    assert [(f.rule, f.line) for f in findings] == [("GL001", 4)]
    stale = project.stale_traced_pragmas()
    # ... and both pragmas are stale: line 3 redundant (inference sees the
    # jit-of-factory), line 6 marks nothing.
    assert [(os.path.basename(p), line) for p, line, _ in stale] == [
        ("factory.py", 3),
        ("factory.py", 6),
    ], stale


def test_trainer_step_fn_needs_no_pragma():
    """Regression for the removed pragma: the shipped trainer's step_fn is
    inferred traced through `jax.jit(make_train_step(...))` — a GL001-style
    hazard inside it would be caught with no pragma present."""
    path = os.path.join(REPO, "raft_stereo_tpu", "train", "trainer.py")
    source = _read(path)
    assert "graftlint: traced" not in source
    findings, _, project = lint_sources([(path, source)], ALL_RULES, root=REPO)
    assert findings == []
    analysis = project.analyses[0]
    step_fns = [
        fn
        for fn in analysis.functions
        if getattr(fn, "name", None) == "step_fn"
    ]
    assert step_fns and all(analysis.is_traced(fn) for fn in step_fns)


def test_gl009_exclusive_branches_are_one_consumer():
    """A key consumed once in EACH arm of an if/else is one consumer per
    run — no stream correlation, no finding. Reuse AFTER the If (against
    either arm) still flags."""
    clean = (
        "import jax\n"
        "def f(key, cond, shape):\n"
        "    if cond:\n"
        "        x = jax.random.normal(key, shape)\n"
        "    else:\n"
        "        x = jax.random.uniform(key, shape)\n"
        "    return x\n"
    )
    findings, _ = lint_source("<mem>", clean, ALL_RULES, select={"GL009"})
    assert findings == [], findings
    dirty = clean.replace(
        "    return x\n",
        "    y = jax.random.bits(key)\n    return x, y\n",
    )
    findings, _ = lint_source("<mem>", dirty, ALL_RULES, select={"GL009"})
    assert [(f.rule, f.line) for f in findings] == [("GL009", 7)], findings


def test_gl010_donation_through_method_helper():
    """A METHOD that forwards its parameter into a donated position donates
    its caller's argument — summary positions must be in bound-call space
    (the `self` slot dropped)."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s: s, donate_argnums=(0,))\n"
        "\n"
        "\n"
        "class Runner:\n"
        "    def helper(self, state):\n"
        "        return step(state)\n"
        "\n"
        "\n"
        "def drive(state):\n"
        "    r = Runner()\n"
        "    out = r.helper(state)\n"
        "    print(state)  # read after donation through the method\n"
        "    return out\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL010"})
    assert [(f.rule, f.line) for f in findings] == [("GL010", 13)], findings


def test_gl010_alias_fixture_pair():
    """The alias fixtures: bad twin flags exactly its seeded lines, good
    twin (device_get copy; alias rebound from the result) stays clean."""
    findings, _ = run_lint_file(os.path.join(FIXTURES, "gl010_alias_bad.py"))
    assert [(f.rule, f.line) for f in findings] == [("GL010", 19), ("GL010", 26)]
    findings, suppressed = run_lint_file(
        os.path.join(FIXTURES, "gl010_alias_good.py")
    )
    assert findings == [], f"alias good fixture flagged: {findings}"
    assert suppressed == 0


def test_gl010_alias_before_donation_flags():
    """`snapshot = state` BEFORE the donation: rebinding `state` from the
    call's result must not clear the alias — snapshot still points at the
    deleted buffers."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "\n"
        "\n"
        "def drive(state, batch):\n"
        "    snapshot = state\n"
        "    state = step(state, batch)\n"
        "    return state, snapshot.step\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL010"})
    assert [(f.rule, f.line) for f in findings] == [("GL010", 8)], findings


def test_gl010_rebound_alias_is_clean():
    """Rebinding the alias itself (to anything) removes it from the group:
    no stale flag on a name that no longer shares the donated buffers."""
    source = (
        "import jax\n"
        "step = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "\n"
        "\n"
        "def drive(state, batch):\n"
        "    snapshot = state\n"
        "    snapshot = batch\n"
        "    state = step(state, batch)\n"
        "    return state, snapshot\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL010"})
    assert findings == [], findings


def test_gl010_exclusive_branches_do_not_flag():
    source = (
        "import jax\n"
        "step = jax.jit(lambda s: s, donate_argnums=(0,))\n"
        "\n"
        "\n"
        "def drive(state, batch, warm):\n"
        "    if warm:\n"
        "        out = step(state)\n"
        "    else:\n"
        "        out = repr(state)  # other arm: the donation never happened\n"
        "    return out\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL010"})
    assert findings == [], findings


def test_runner_is_cwd_independent(tmp_path):
    """Cross-module analysis must anchor module names to the REPO root, not
    the invoker's cwd: the xmod relative-import findings appear identically
    when lint.py runs from an unrelated directory."""
    xmod = os.path.join(FIXTURES, "xmod")
    files = sorted(
        os.path.join(xmod, n) for n in os.listdir(xmod) if n.endswith(".py")
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *files],
        capture_output=True, text=True, cwd=str(tmp_path),
    )
    assert proc.returncode == 1
    assert "consumer.py" in proc.stdout and "GL005" in proc.stdout
    assert "factory.py" in proc.stdout and "GL001" in proc.stdout


def test_unused_suppression_reporting(tmp_path):
    """--report-unused-suppressions: a pragma that suppressed nothing is
    flagged (exit 1); a load-bearing one is not."""
    target = tmp_path / "mod.py"
    target.write_text(
        "import jax\n"
        "import numpy as np\n"
        "# graftlint: disable-file=GL007\n"  # nothing Pallas here: stale
        "\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.sum(x)  # graftlint: disable=GL001\n"  # load-bearing
        "\n"
        "\n"
        "def g(x):\n"
        "    return x  # graftlint: disable=GL005\n"  # stale: no finding here
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--report-unused-suppressions", str(target)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "disable-file=GL007" in proc.stdout
    assert "disable=GL005" in proc.stdout
    assert "disable=GL001" not in proc.stdout  # the used one stays silent
    # ...and the shipped tree carries ZERO stale pragmas.
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--report-unused-suppressions",
         "raft_stereo_tpu", "scripts", "tools", "__graft_entry__.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_stale_pragma_fails_baseline_diff_mode(tmp_path):
    """A stale pragma must fail `--baseline diff --report-unused-suppressions`
    too — diff mode's "no new findings" early-exit used to return 0 before
    the stale check ran, which is exactly the invocation ci_checks uses, so
    a dead pragma could ride through the one gate meant to catch it."""
    lint = os.path.join(REPO, "scripts", "lint.py")
    target = tmp_path / "mod.py"
    target.write_text(
        "def g(x):\n"
        "    return x  # graftlint: disable=GL005\n"  # stale: no finding here
    )
    baseline = str(tmp_path / "baseline.json")
    write = subprocess.run(
        [sys.executable, lint, "--baseline", "write",
         "--baseline-file", baseline, str(target)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert write.returncode == 0, write.stderr

    # diff alone: clean (no findings at all, stale pragmas not requested)
    plain = subprocess.run(
        [sys.executable, lint, "--baseline", "diff",
         "--baseline-file", baseline, str(target)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert plain.returncode == 0, plain.stdout + plain.stderr

    # diff + the flag: the stale pragma fails the run despite zero new findings
    strict = subprocess.run(
        [sys.executable, lint, "--baseline", "diff",
         "--report-unused-suppressions", "--baseline-file", baseline,
         str(target)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert strict.returncode == 1, strict.stdout + strict.stderr
    assert "disable=GL005" in strict.stdout


def test_baseline_write_diff_roundtrip(tmp_path):
    """Baseline workflow: write adopts legacy findings (exit 0 despite
    findings), diff against the same tree is clean (exit 0), and a NEW
    finding — a file outside the baseline — fails the diff (exit 1) while
    the legacy ones stay tracked."""
    lint = os.path.join(REPO, "scripts", "lint.py")
    baseline = str(tmp_path / "baseline.json")
    legacy = os.path.join(FIXTURES, "gl001_bad.py")
    fresh = os.path.join(FIXTURES, "gl003_bad.py")

    write = subprocess.run(
        [sys.executable, lint, "--baseline", "write",
         "--baseline-file", baseline, legacy],
        capture_output=True, text=True, cwd=REPO,
    )
    assert write.returncode == 0, write.stderr
    stored = json.loads(open(baseline).read())
    assert stored["fingerprints"], "legacy findings must be recorded"

    clean = subprocess.run(
        [sys.executable, lint, "--baseline", "diff",
         "--baseline-file", baseline, legacy],
        capture_output=True, text=True, cwd=REPO,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr

    dirty = subprocess.run(
        [sys.executable, lint, "--json", "--baseline", "diff",
         "--baseline-file", baseline, legacy, fresh],
        capture_output=True, text=True, cwd=REPO,
    )
    assert dirty.returncode == 1
    report = json.loads(dirty.stdout)
    assert report["baseline"]["new"] > 0
    assert report["baseline"]["legacy_matched"] == len(stored["fingerprints"]) or (
        report["baseline"]["legacy_matched"]
        == sum(stored["fingerprints"].values())
    )
    # only the NEW findings are reported in diff mode
    assert all(f["rule"] == "GL003" for f in report["findings"])

    missing = subprocess.run(
        [sys.executable, lint, "--baseline", "diff",
         "--baseline-file", str(tmp_path / "nope.json"), legacy],
        capture_output=True, text=True, cwd=REPO,
    )
    assert missing.returncode == 2  # usage error, not a silent pass


def test_shipped_baseline_is_empty():
    """The tree ships lint-clean, so the committed baseline must be EMPTY —
    a non-empty baseline landing in review means someone adopted a
    regression instead of fixing it."""
    stored = json.loads(
        _read(os.path.join(REPO, "tools", "graftlint", "baseline.json"))
    )
    assert stored["fingerprints"] == {}


def test_sarif_output(tmp_path):
    sarif_path = str(tmp_path / "out.sarif")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--sarif", sarif_path, os.path.join(FIXTURES, "gl001_bad.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1  # findings still reported normally
    doc = json.loads(open(sarif_path).read())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids == set(RULE_TABLE)
    assert run["results"], "findings must appear as SARIF results"
    for res in run["results"]:
        assert res["ruleId"] == "GL001"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] > 0


def test_ci_checks_distinct_exit_code_for_lint_failure(tmp_path):
    """Break the tree (a copy of it is too slow — use a scratch file inside
    a temp clone of the lint target? No: point graftlint at a bad file via
    a wrapper) — cheaper: assert the script's documented graftlint exit
    code by running lint.py directly on a bad fixture and matching the
    mapping table."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         os.path.join(FIXTURES, "gl002_bad.py")],
        capture_output=True, cwd=REPO,
    )
    # ci_checks.sh maps the baseline diff's rc=1 -> its own exit 6 (new
    # findings) and rc=2 -> exit 4 (analysis crashed, no verdict); the
    # mapping is a shell conditional, so proving lint.py's rc here plus the
    # script's grep-able mapping lines keeps the contract tested without a
    # slow full-tree mutation run.
    assert proc.returncode == 1
    script = open(os.path.join(REPO, "scripts", "ci_checks.sh")).read()
    assert "exit 4" in script and "exit 3" in script and "exit 5" in script
    # the baseline-diff gate has its own distinct code + SARIF artifact
    assert "exit 6" in script and "--baseline diff" in script
    assert "--sarif" in script


def test_gl002_is_none_identity_comparison_is_static():
    """Launder-set entry: `x is None` on a traced parameter is host-static
    (tracers are never None) — the Optional[Array] kernel-wrapper pattern.
    Value comparisons on the same parameter still flag."""
    source = (
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, bias=None):\n"
        "    if bias is None:\n"
        "        return x * 2\n"
        "    return x + bias\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL002"})
    assert findings == []
    value_cmp = source.replace("if bias is None:", "if bias == 0:")
    findings, _ = lint_source("<mem>", value_cmp, ALL_RULES, select={"GL002"})
    assert {f.rule for f in findings} == {"GL002"}


def test_gl002_str_annotated_params_are_static_bool_int_are_not():
    """Launder-set entry: a `str`-annotated parameter cannot be a tracer
    (strings are never device values). `bool`/`int` annotations get no
    exemption — annotations are unenforced and both genuinely arrive as
    tracers (`flip=jnp.any(mask)`, loop carries) — and must keep
    flagging."""
    source = (
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, mode: str):\n"
        "    if mode == 'relu':\n"
        "        x = jnp.maximum(x, 0)\n"
        "    return x\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL002"})
    assert findings == []
    bool_param = (
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, flip: bool = False):\n"
        "    if flip:\n"
        "        return -x\n"
        "    return x\n"
    )
    findings, _ = lint_source("<mem>", bool_param, ALL_RULES, select={"GL002"})
    assert {f.rule for f in findings} == {"GL002"}
    int_param = (
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def g(x, n: int):\n"
        "    if n > 3:\n"
        "        return x * 2\n"
        "    return x\n"
    )
    findings, _ = lint_source("<mem>", int_param, ALL_RULES, select={"GL002"})
    assert {f.rule for f in findings} == {"GL002"}


def test_gl008_returned_verdict_good_twin_is_clean():
    """The interprocedural pair's good twin (gl008_returns_good.py):
    helpers returning POD-UNIFORM verdicts — process_count, explicitly
    seeded RNG, a multihost collective's own (allgather) result — must not
    taint their callers' branches. The bad twin's exact seeded lines are
    pinned in test_new_bad_fixtures_produce_exactly_their_seeded_findings."""
    findings, suppressed = run_lint_file(
        os.path.join(FIXTURES, "gl008_returns_good.py")
    )
    assert findings == [], findings
    assert suppressed == 0


def test_gl008_returned_verdict_crosses_modules():
    """The returns-divergent summary is PROJECT-level, not per-file: the
    filesystem-probing helper lives in one module, the guarded collective
    in another — the carried ROADMAP gap ('returned verdicts not tracked
    into callers'), closed. Solo-linting the driver (helper invisible)
    must stay clean: the summary adds knowledge, never guesses."""
    probe = (
        "import os\n"
        "\n"
        "def has_ckpt(path):\n"
        "    return os.path.exists(path)\n"
    )
    driver = (
        "from probe import has_ckpt\n"
        "from jax.experimental import multihost_utils\n"
        "\n"
        "def resume(path):\n"
        "    if has_ckpt(path):\n"
        "        multihost_utils.sync_global_devices('restore')\n"
    )
    findings, suppressed, _ = lint_sources(
        [("probe.py", probe), ("driver.py", driver)], ALL_RULES, root="."
    )
    assert [(os.path.basename(f.path), f.rule, f.line) for f in findings] == [
        ("driver.py", "GL008", 6)
    ], findings
    assert suppressed == 0
    solo, _ = lint_source("driver.py", driver, ALL_RULES)
    assert solo == [], solo


def test_gl008_is_none_on_divergent_value_still_flags():
    """The identity-comparison launder is policy-scoped: `step is None` on
    a host-divergent filesystem probe is still a divergent branch, and a
    collective behind it must keep flagging (the checkpoint-resume pattern
    GL008 exists for). Only the tracer/device policies treat identity
    tests as clean."""
    source = (
        "import os\n"
        "\n"
        "from jax.experimental import multihost_utils\n"
        "\n"
        "\n"
        "def resume(ckpt, state):\n"
        "    step = os.path.exists(ckpt)\n"
        "    if step is None:\n"
        "        multihost_utils.sync_global_devices('restore')\n"
        "    return state\n"
    )
    findings, _ = lint_source("<mem>", source, ALL_RULES, select={"GL008"})
    assert {f.rule for f in findings} == {"GL008"}, findings
    # The tracer-policy launder is untouched: the same identity test under
    # GL002 stays clean (see test_gl002_is_none_identity_comparison_is_static).


# -- GL011-GL014: whole-program concurrency analysis ----------------------


def test_serving_lock_graph_is_cycle_free():
    """Regression pin (acceptance criterion): the frontier/fleet/batcher
    serving tier builds a NON-EMPTY lock acquisition-order graph — the
    analysis demonstrably sees the serving locks — and that graph has no
    cycle. A future PR introducing an opposite-order nesting breaks this
    test before it deadlocks production."""
    pkg = os.path.join(REPO, "raft_stereo_tpu")
    files = []
    for root, dirs, names in os.walk(pkg):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        files.extend(os.path.join(root, n) for n in sorted(names) if n.endswith(".py"))
    sources = [(os.path.relpath(p, REPO), _read(p)) for p in files]
    _, _, project = lint_sources(sources, ALL_RULES, root=REPO)
    conc = project.concurrency
    graph = conc.lock_order_graph()
    assert graph, "serving tier produced an EMPTY lock-order graph"
    tokens = " ".join(sorted(conc.lock_kinds))
    for expected_lock in (
        "frontier:Frontier._lock",
        "frontier:Frontier._sessions_lock",
        "batcher:MicroBatcher.",
        "fleet:",
    ):
        assert expected_lock in tokens, (expected_lock, tokens)
    assert not conc.has_cycles(), conc.cycle_findings
    assert not conc.cycle_findings


def test_gl005_cross_function_param_taint():
    """GL005 closes the carried item: the device value reaches float()
    through a PARAMETER — the helper never calls a jit itself, the taint
    arrives via the per-function summaries' combined fixed point."""
    source = (
        "import jax\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x\n"
        "\n"
        "\n"
        "def log_loss(metrics):\n"
        "    return float(metrics)  # device value arrives via the parameter\n"
        "\n"
        "\n"
        "def drive(x):\n"
        "    m = step(x)\n"
        "    return log_loss(m)\n"
    )
    findings, _, _ = lint_sources([("m.py", source)], ALL_RULES, root=REPO)
    assert [(f.rule, f.line) for f in findings] == [("GL005", 10)], findings


def test_class_aware_instance_method_resolution():
    """Closes the other carried item: two classes bind the SAME attribute
    name to different jits — the donating class's caller flags GL010, the
    non-donating class's caller does not. The old name-flat union gave both
    classes one merged summary."""
    source = (
        "import jax\n"
        "\n"
        "\n"
        "def _step(state, batch):\n"
        "    return state\n"
        "\n"
        "\n"
        "def _eval(state, batch):\n"
        "    return state\n"
        "\n"
        "\n"
        "class Donating:\n"
        "    def __init__(self):\n"
        "        self.step = jax.jit(_step, donate_argnums=(0,))\n"
        "\n"
        "    def drive(self, state, batch):\n"
        "        out = self.step(state, batch)\n"
        "        return out, state.x  # GL010 via THIS class's binding\n"
        "\n"
        "\n"
        "class Plain:\n"
        "    def __init__(self):\n"
        "        self.step = jax.jit(_eval)\n"
        "\n"
        "    def drive(self, state, batch):\n"
        "        out = self.step(state, batch)\n"
        "        return out, state.x  # clean: no donation on Plain.step\n"
    )
    findings, _, _ = lint_sources([("m.py", source)], ALL_RULES, root=REPO)
    gl010 = [(f.rule, f.line) for f in findings if f.rule == "GL010"]
    assert gl010 == [("GL010", 18)], findings


def test_gl011_condition_wrapping_lock_shares_guard():
    """The frontier pattern: `Condition(self._lock)` aliases the lock — an
    attribute maintained under the condition in some methods and under the
    raw lock in others is ONE guard discipline, not a violation."""
    source = (
        "import threading\n"
        "\n"
        "\n"
        "class Gate:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "        self._in_flight = 0\n"
        "        self._t = None\n"
        "\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run, daemon=True)\n"
        "        self._t.start()\n"
        "\n"
        "    def close(self):\n"
        "        if self._t is not None:\n"
        "            self._t.join(timeout=1.0)\n"
        "\n"
        "    def admit(self):\n"
        "        with self._lock:\n"
        "            self._in_flight += 1\n"
        "\n"
        "    def release(self):\n"
        "        with self._cv:\n"
        "            self._in_flight -= 1\n"
        "            self._cv.notify_all()\n"
        "\n"
        "    def _run(self):\n"
        "        with self._cv:\n"
        "            self._in_flight += 1\n"
    )
    findings, _, _ = lint_sources([("m.py", source)], ALL_RULES, root=REPO)
    assert findings == [], findings


def test_fixture_selftest_gate():
    """scripts/lint.py --fixture-selftest: passes on the shipped fixtures
    (every rule fires on its bad twin, spares its good twin) — the CI
    assertion that no rule went silently dead."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--fixture-selftest"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "0 failure(s)" in proc.stderr, proc.stderr


def test_fixture_selftest_detects_missing_fixture(tmp_path, monkeypatch):
    """A rule whose fixture vanished must FAIL the selftest — a dead rule
    and a deleted fixture are the same blindness."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_under_test", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "REPO_ROOT", str(tmp_path))  # no fixtures there
    rc = mod.fixture_selftest()
    assert rc == 1


def test_jobs_parallel_matches_serial():
    """--jobs fan-out is an implementation detail: identical findings,
    identical suppression counts, and the stats dict accumulates every
    selected rule."""
    xmod = os.path.join(FIXTURES, "xmod")
    files = sorted(
        os.path.join(xmod, n) for n in os.listdir(xmod) if n.endswith(".py")
    )
    bad = sorted(
        os.path.join(FIXTURES, n)
        for n in os.listdir(FIXTURES)
        if n.endswith("_bad.py")
    )
    sources = [(p, _read(p)) for p in files + bad]
    serial, s_sup, _ = lint_sources(sources, ALL_RULES, root=REPO, jobs=1)
    stats = {}
    parallel, p_sup, _ = lint_sources(
        sources, ALL_RULES, root=REPO, jobs=4, stats=stats
    )
    key = lambda f: (f.path, f.line, f.col, f.rule, f.message)  # noqa: E731
    assert [key(f) for f in serial] == [key(f) for f in parallel]
    assert s_sup == p_sup
    assert set(stats) == set(RULE_TABLE)


def test_runner_jobs_and_stats_flags(tmp_path):
    """The CLI surface: --jobs N lints the tree identically and --stats
    prints a per-rule timing line for every rule."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--jobs", "4", "--stats",
         os.path.join(FIXTURES, "gl011_bad.py"),
         os.path.join(FIXTURES, "gl013_bad.py")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1  # the seeded findings
    assert "GL011" in proc.stdout and "GL013" in proc.stdout
    for rule_id in RULE_TABLE:
        assert f"stats: {rule_id}" in proc.stderr, proc.stderr


def test_sarif_rules_carry_full_help_text(tmp_path):
    """SARIF satellite: every rule entry ships its full docstring as
    fullDescription/help so GL011-GL014 findings are self-explanatory in
    code-scanning UIs."""
    out = tmp_path / "lint.sarif"
    subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--sarif", str(out), os.path.join(FIXTURES, "gl012_bad.py")],
        capture_output=True, text=True,
    )
    doc = json.loads(out.read_text())
    rules = {r["id"]: r for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert set(rules) == set(RULE_TABLE)
    for rule_id, entry in rules.items():
        help_text = entry["help"]["text"]
        assert entry["fullDescription"]["text"] == help_text
        # Full docstring, not the one-liner: it explains the WHY.
        assert len(help_text) > len(entry["shortDescription"]["text"]), rule_id
    assert "deadlock" in rules["GL012"]["help"]["text"]
    assert "guard" in rules["GL011"]["help"]["text"].lower()
