"""graftaudit self-tests (tier-1, `-m audit`): the compiled-artifact contract
auditor (ISSUE 20).

Four layers, cheap to expensive:

1. Parser units over tools/graftaudit/hlo.py — the tree's SINGLE HLO-text
   parser — pinning the exact text shapes this jax build renders (alias
   headers, tuple-shaped send/recv, op_name provenance, benign backend
   custom-calls).
2. The single-parser delegation contract: parallel/sharding.py's collective
   helpers must be THE SAME function objects as tools/graftaudit/hlo.py's,
   and both must agree bit-for-bit with the legacy regex bodies (embedded
   verbatim below, copied from the pre-refactor sharding.py) over the
   fixture corpus AND a real compiled module.
3. Fixture selftest + scripts/audit.py CLI round-trip (artifacts replay,
   JSON/SARIF, baseline write/diff) — the acceptance criterion "exits
   nonzero on a seeded violation of each contract class a-e".
4. Live executables: donation honored on THE production train step (and an
   un-donated twin of the same step failing GA002), plus the GA001 chunk-
   boundary sharding fixpoint green for EVERY warmed (bucket, batch) combo
   on the 8-device mesh under dp AND spatial — the ROADMAP item-1 assert.

The live layer compiles real engines/trainers (minutes of CPU), so the
module is collection-ordered dead last (tests/conftest.py) and re-run by
ci_checks under the exit-20 gate."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.graftaudit import hlo as H  # noqa: E402
from tools.graftaudit.contracts import (  # noqa: E402
    ALL_CONTRACTS,
    CONTRACT_TABLE,
    audit_records,
    expected_collectives,
)
from tools.graftaudit.fixtures import (  # noqa: E402
    fixture_selftest,
    good_records,
    seeded_records,
)

pytestmark = pytest.mark.audit

AUDIT_PY = os.path.join(REPO, "scripts", "audit.py")


def run_audit(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, AUDIT_PY, *argv], capture_output=True, text=True, cwd=cwd
    )


# ---------------------------------------------------------------------------
# 1. Parser units (pure stdlib)
# ---------------------------------------------------------------------------


def test_collective_counts_families():
    hlo = "\n".join(
        [
            "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %p0), to_apply=%add",
            "%ars.2 = f32[4]{0} all-reduce-start(f32[4]{0} %p0)",
            "%ard.3 = f32[4]{0} all-reduce-done(f32[4]{0} %ars.2)",
            "%ag.4 = f32[8]{0} all-gather(f32[4]{0} %p0), dimensions={0}",
            "%cp.5 = f32[4]{0} collective-permute(f32[4]{0} %p0)",
            "%f.6 = f32[4]{0} fusion(f32[4]{0} %p0), calls=%my-all-to-all-helper",
        ]
    )
    counts = H.collective_counts(hlo)
    # `-start` counts toward the family; `-done` halves are NOT double-
    # counted; `my-all-to-all-helper` (hyphen-joined superset) never matches.
    assert counts["all-gather"] == 1
    assert counts["collective-permute"] == 1
    assert counts["all-to-all"] == 0
    # line 1 carries the family twice (value name + opcode), line 2 once
    assert counts["all-reduce"] == 3
    assert H.collective_counts("") == {op: 0 for op in H.COLLECTIVE_OPS}


def test_unexpected_collectives_filters_whitelist():
    hlo = "%ar = f32[] all-reduce(f32[] %x)\n%cp = f32[] collective-permute(f32[] %x)"
    assert set(H.unexpected_collectives(hlo, ("all-reduce",))) == {"collective-permute"}
    assert H.unexpected_collectives(hlo, ("all-reduce", "collective-permute")) == {}


def test_corr_collective_lines_needs_both():
    corr_coll = '%ar.1 = f32[] all-reduce(f32[] %x), metadata={op_name="jit(f)/corr_pyramid/sum"}'
    plain_coll = '%ar.2 = f32[] all-reduce(f32[] %x), metadata={op_name="jit(f)/norm"}'
    corr_only = '%add.3 = f32[] add(f32[] %x, f32[] %x), metadata={op_name="jit(f)/corr_lookup"}'
    lines = H.corr_collective_lines("\n".join([corr_coll, plain_coll, corr_only]))
    assert lines == [corr_coll]


def test_input_output_aliases_header_parse():
    hlo = (
        "HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias), "
        "{1}: (2, {1, 3}, must-alias) }, entry_computation_layout={...}\n"
        "ENTRY %main { ... }\n"
    )
    assert H.input_output_aliases(hlo) == [
        ((0,), 0, ()),
        ((1,), 2, (1, 3)),
    ]
    assert H.aliased_param_numbers(hlo) == {0, 2}
    # absent header = nothing aliased (donation dropped), never a crash
    assert H.input_output_aliases("HloModule jit_step\nENTRY %main { }") == []


def test_host_transfer_lines_opcode_position():
    tuple_send = (
        "%send.1 = (f32[4]{0}, u32[]{0}, token[]) send(f32[4]{0} %x, token[] "
        "%tok), channel_id=1, is_host_transfer=true"
    )
    value_name_decoy = "%send_buffer = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %x)"
    benign_backend = (
        '%custom-call.2 = f32[4]{0} custom-call(f32[4]{0} %x), '
        'custom_call_target="__onednn$matmul"'
    )
    callback = (
        '%custom-call.3 = f32[4]{0} custom-call(f32[4]{0} %x), '
        'custom_call_target="xla_python_cpu_callback"'
    )
    infeed = "%infeed.4 = ((f32[2]{0}), token[]) infeed(token[] %tok)"
    lines = H.host_transfer_lines(
        "\n".join([tuple_send, value_name_decoy, benign_backend, callback, infeed])
    )
    assert lines == [tuple_send, callback, infeed]


def test_is_host_callback_target():
    assert H.is_host_callback_target("xla_python_cpu_callback")
    assert H.is_host_callback_target("xla_ffi_python_gpu_callback")
    assert H.is_host_callback_target("SendToHost")
    assert not H.is_host_callback_target("__onednn$matmul")
    assert not H.is_host_callback_target("TopK")


def test_upcast_convert_lines_direction_and_provenance():
    upcast_corr = (
        "%convert.1 = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %x), "
        'metadata={op_name="jit(f)/corr_pyramid/convert_element_type"}'
    )
    upcast_other = (
        "%convert.2 = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %x), "
        'metadata={op_name="jit(f)/gru/convert_element_type"}'
    )
    downcast_corr = (
        "%convert.3 = bf16[8,16]{1,0} convert(f32[8,16]{1,0} %x), "
        'metadata={op_name="jit(f)/corr_pyramid/convert_element_type"}'
    )
    hlo = "\n".join([upcast_corr, upcast_other, downcast_corr])
    # only the upcast WITH corr provenance fires; the sanctioned downcast
    # (building the bf16 pyramid) and non-corr upcasts stay silent
    assert H.upcast_convert_lines(hlo) == [upcast_corr]


# ---------------------------------------------------------------------------
# 2. Single-parser delegation + bit-for-bit legacy contrast
# ---------------------------------------------------------------------------

# The pre-refactor bodies from raft_stereo_tpu/parallel/sharding.py, embedded
# VERBATIM (regexes included): the refactor moved them to tools/graftaudit/
# hlo.py, and this contrast pins that the move changed no verdict anywhere.

_LEGACY_OPS = ("all-reduce", "all-gather", "collective-permute", "all-to-all")
_LEGACY_LINE = re.compile(
    r"(?<![\w-])(?:" + "|".join(_LEGACY_OPS) + r")(?:-start)?(?![\w-])"
)


def _legacy_collective_counts(hlo):
    counts = {}
    for op in _LEGACY_OPS:
        counts[op] = len(re.findall(rf"(?<![\w-]){op}(?:-start)?(?![\w-])", hlo))
    return counts


def _legacy_unexpected_collectives(hlo, expected=()):
    return {k: v for k, v in _legacy_collective_counts(hlo).items() if v and k not in expected}


def _legacy_corr_collective_lines(hlo):
    return [
        line for line in hlo.splitlines() if _LEGACY_LINE.search(line) and "corr" in line.lower()
    ]


def _contrast_corpus():
    corpus = [r["hlo"] for r in good_records()]
    corpus += [r["hlo"] for r, _ in seeded_records()]
    corpus += [
        "",
        "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %p0)",
        '%a2a = f32[8]{0} all-to-all(f32[8]{0} %x), metadata={op_name="corr/reshard"}',
        "%ag = f32[8]{0} all-gather(f32[4]{0} %x), dimensions={0}",
        "%cp = f32[4]{0} collective-permute(f32[4]{0} %x), source_target_pairs={{0,1}}",
        "calls=%my-all-to-all-helper %collective-permute-done.2",
    ]
    return corpus


def test_sharding_helpers_are_the_graftaudit_parser():
    """Exactly one HLO-parsing implementation: parallel/sharding.py's
    collective helpers must be the SAME objects as the graftaudit parser's —
    a re-divergence (someone pasting a local copy back) fails identity, not
    just equality."""
    from raft_stereo_tpu.parallel import sharding as S

    assert S.collective_counts is H.collective_counts
    assert S.unexpected_collectives is H.unexpected_collectives
    assert S.corr_collective_lines is H.corr_collective_lines
    assert S.COLLECTIVE_OPS is H.COLLECTIVE_OPS


def test_contrast_legacy_vs_refactored_corpus():
    """Bit-for-bit: the refactored helpers agree with the verbatim legacy
    bodies on every corpus entry, and the corpus is non-trivial (it
    exercises every family and both zero/nonzero verdicts)."""
    families_hit = set()
    for hlo in _contrast_corpus():
        assert H.collective_counts(hlo) == _legacy_collective_counts(hlo)
        assert H.unexpected_collectives(hlo) == _legacy_unexpected_collectives(hlo)
        assert H.unexpected_collectives(hlo, ("all-reduce",)) == (
            _legacy_unexpected_collectives(hlo, ("all-reduce",))
        )
        assert H.corr_collective_lines(hlo) == _legacy_corr_collective_lines(hlo)
        families_hit |= {k for k, v in H.collective_counts(hlo).items() if v}
    assert families_hit == set(_LEGACY_OPS)


def test_contrast_legacy_vs_refactored_real_module():
    """Same contrast over a REAL compiled module (a sharded sum whose
    gradient-style reduction lowers to an all-reduce on the 8-device mesh) —
    the corpus above is synthetic; this pins agreement on actual XLA text."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("d",))
    fn = jax.jit(
        lambda x: jnp.sum(x, axis=0),
        in_shardings=NamedSharding(mesh, P("d")),
        out_shardings=NamedSharding(mesh, P()),
    )
    hlo = fn.lower(jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile().as_text()
    counts = H.collective_counts(hlo)
    assert counts == _legacy_collective_counts(hlo)
    assert sum(counts.values()) > 0, "expected at least one collective in the real module"
    assert H.corr_collective_lines(hlo) == _legacy_corr_collective_lines(hlo)


def test_assert_no_collectives_still_raises():
    """The sharding.py convenience wrapper survived the refactor: raises
    with the family counts on collective-carrying HLO, silent on clean."""
    from raft_stereo_tpu.parallel.sharding import assert_no_collectives

    assert_no_collectives("%add = f32[] add(f32[] %x, f32[] %x)", "ctx")
    with pytest.raises(AssertionError, match="all-reduce"):
        assert_no_collectives("%ar = f32[] all-reduce(f32[] %x)", "ctx")


# ---------------------------------------------------------------------------
# 3. Contracts: fixture selftest + CLI round-trip
# ---------------------------------------------------------------------------


def test_fixture_selftest_clean():
    assert fixture_selftest() == []


@pytest.mark.parametrize(
    "record,expected",
    seeded_records(),
    ids=[cid for _, cid in seeded_records()],
)
def test_each_contract_class_fires_exactly(record, expected):
    """Acceptance a-e: each seeded record trips EXACTLY its own contract —
    pins both a dead rule and an over-eager rule."""
    violations, _ = audit_records([record])
    assert {v.contract for v in violations} == {expected}


@pytest.mark.parametrize("record", good_records(), ids=lambda r: r["entry"])
def test_good_records_stay_quiet(record):
    violations, _ = audit_records([record])
    assert violations == []


def test_collective_whitelist_table():
    """The declarative whitelist: dp serving/eval is single-program,
    all-to-all is sanctioned in exactly one (kind, preset) cell — the
    OFFLINE spatial eval forward — and nowhere on a serving or train path."""
    assert expected_collectives("chunk", "dp") == ()
    assert expected_collectives("prelude", "dp") == ()
    assert expected_collectives("eval_forward", "dp") == ()
    # train steps: grad all-reduce + the partitioner's slice/pad-edge
    # permutes and small gathers (measured even under dp) — never all-to-all
    assert "all-reduce" in expected_collectives("train_step", "dp")
    for preset in ("dp", "spatial", "fsdp"):
        assert "all-to-all" not in expected_collectives("train_step", preset)
    for kind in ("prelude", "chunk", "finalize", "train_step"):
        assert "all-to-all" not in expected_collectives(kind, "spatial"), kind
    assert "all-to-all" in expected_collectives("eval_forward", "spatial")


def test_train_step_all_to_all_is_allowed_by_provenance_only():
    """The dp train step's all-to-alls come from the batch-axis join of the
    image pair (`.../jvp(RAFTStereo)/concatenate` and its transpose): GA003
    lets those through in a train step, names any other provenance, and
    lets nothing through in a serving stage."""
    from tools.graftaudit.contracts import _check_collectives

    def a2a(n, provenance):
        return (f"%all-to-all.{n} = (f32[1,8]{{1,0}}, f32[1,8]{{1,0}}) all-to-all(%a.{n}, %b.{n}), "
                f'dimensions={{0}}, metadata={{op_name="{provenance}"}}\n'
                f"%get-tuple-element.{n} = f32[1,8]{{1,0}} get-tuple-element(%all-to-all.{n}), index=0\n")

    join = a2a(1, "jit(step_fn)/jvp(RAFTStereo)/concatenate") + a2a(
        2, "jit(step_fn)/transpose(jvp(RAFTStereo))/concatenate")
    step = {"entry": "train:step:dp", "kind": "train_step", "preset": "dp", "hlo": join}
    assert _check_collectives(step) == []
    foreign = _check_collectives(dict(step, hlo=join + a2a(3, "jit(step_fn)/jvp(RAFTStereo)/reshape")))
    assert len(foreign) == 1 and foreign[0].contract == "GA003"
    assert "jvp(RAFTStereo)/reshape" in foreign[0].render() and "concatenate" not in foreign[0].detail
    served = _check_collectives(dict(step, kind="chunk", hlo=join))
    assert len(served) == 1 and served[0].message.startswith("unexpected collective family all-to-all")


def test_missing_snapshot_placeholder_fails_ga001():
    """A cache-hit chunk whose entry predates auditing gets a carry-less
    placeholder record (engine._warm_stage) — GA001 must flag the coverage
    gap instead of silently passing."""
    from tools.graftaudit.artifacts import make_record

    placeholder = make_record(
        entry="serve:chunk:64x96:b1:dp",
        kind="chunk",
        preset="dp",
        hlo="",
        meta={"missing_snapshot": True},
    )
    violations, _ = audit_records([placeholder])
    assert any(v.contract == "GA001" for v in violations)


@pytest.fixture(scope="module")
def record_files(tmp_path_factory):
    base = tmp_path_factory.mktemp("graftaudit-cli")
    good = base / "good.json"
    seeded = base / "seeded.json"
    good.write_text(json.dumps({"records": good_records()}))
    seeded.write_text(json.dumps({"records": [r for r, _ in seeded_records()]}))
    return str(good), str(seeded)


def test_cli_exits_nonzero_on_each_seeded_class(record_files):
    """The acceptance criterion, end to end: audit.py exits 1 on artifacts
    seeding every contract class, and names all five GA ids."""
    _, seeded = record_files
    proc = run_audit("--artifacts", seeded)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    for cid in CONTRACT_TABLE:
        assert cid in proc.stdout, f"{cid} missing from report:\n{proc.stdout}"


def test_cli_exits_zero_on_good_records(record_files):
    good, _ = record_files
    proc = run_audit("--artifacts", good)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_fixture_selftest_and_list_contracts():
    proc = run_audit("--fixture-selftest")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    listing = run_audit("--list-contracts")
    assert listing.returncode == 0
    for cid in CONTRACT_TABLE:
        assert cid in listing.stdout


def test_cli_json_and_select(record_files):
    _, seeded = record_files
    proc = run_audit("--artifacts", seeded, "--json", "--select", "GA002")
    assert proc.returncode == 1
    report = json.loads(proc.stdout)
    assert report["stats"]["records"] == len(seeded_records())
    assert {v["contract"] for v in report["violations"]} == {"GA002"}
    unknown = run_audit("--artifacts", seeded, "--select", "GA999")
    assert unknown.returncode == 2


def test_cli_sarif(record_files, tmp_path):
    _, seeded = record_files
    sarif_path = str(tmp_path / "audit.sarif")
    proc = run_audit("--artifacts", seeded, "--sarif", sarif_path)
    assert proc.returncode == 1
    doc = json.loads(open(sarif_path).read())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == set(CONTRACT_TABLE)
    hit = {r["ruleId"] for r in run["results"]}
    assert hit == set(CONTRACT_TABLE)
    # the audited entry name is the SARIF artifact location
    uris = {
        r["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]
        for r in run["results"]
    }
    assert any(uri.startswith("fixture:") for uri in uris)


def test_cli_baseline_write_diff_roundtrip(record_files, tmp_path):
    """write adopts the seeded violations (exit 0); diff against the same
    records is clean; a record seeding a NEW violation fails the diff while
    the legacy ones stay tracked."""
    _, seeded = record_files
    baseline = str(tmp_path / "baseline.json")
    write = run_audit("--artifacts", seeded, "--baseline", "write",
                      "--baseline-file", baseline)
    assert write.returncode == 0, write.stdout + write.stderr
    stored = json.loads(open(baseline).read())
    assert stored["fingerprints"], "seeded violations must be recorded"

    clean = run_audit("--artifacts", seeded, "--baseline", "diff",
                      "--baseline-file", baseline)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    fresh_record = dict(seeded_records()[0][0], entry="fixture:chunk:NEW-entry")
    both = tmp_path / "both.json"
    both.write_text(
        json.dumps({"records": [r for r, _ in seeded_records()] + [fresh_record]})
    )
    dirty = run_audit("--artifacts", str(both), "--json", "--baseline", "diff",
                      "--baseline-file", baseline)
    assert dirty.returncode == 1
    report = json.loads(dirty.stdout)
    assert report["baseline"]["new"] >= 1
    assert all(v["entry"] == "fixture:chunk:NEW-entry" for v in report["violations"])

    missing = run_audit("--artifacts", seeded, "--baseline", "diff",
                        "--baseline-file", str(tmp_path / "nope.json"))
    assert missing.returncode == 2  # usage error, not a silent pass


def test_shipped_audit_baseline_is_empty():
    """The tree holds every contract, so the committed baseline must be
    EMPTY — a non-empty baseline landing in review means someone adopted a
    violation instead of fixing it."""
    stored = json.loads(
        open(os.path.join(REPO, "tools", "graftaudit", "baseline.json")).read()
    )
    assert stored["fingerprints"] == {}


def test_contract_table_is_documented():
    """Every contract ships a doc (SARIF help text + README catalog source)
    and binds at least one kind."""
    for c in ALL_CONTRACTS:
        assert c.doc, c.id
        assert c.kinds, c.id
        assert c.summary, c.id


# ---------------------------------------------------------------------------
# 4. Live executables (compiles real trainers/engines — the expensive layer)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def slim_trainer(tmp_path_factory):
    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.train.trainer import Trainer
    from tools.graftaudit.live import slim_model_config

    cfg = TrainConfig(
        model=slim_model_config(),
        batch_size=4,
        num_steps=1,
        train_iters=2,
        mesh_shape=(4, 1),
        sharding_rules="dp",
        checkpoint_every=10**9,
        checkpoint_dir=str(tmp_path_factory.mktemp("graftaudit-train")),
    )
    return Trainer(cfg, sample_shape=(32, 48, 3))


def test_train_step_donation_honored_live(slim_trainer):
    """GA002 on THE production train step: every donated state leaf appears
    in the executable's input_output_alias table — and the whole record
    audits clean (fixpoint + collective whitelist included)."""
    record = slim_trainer.hlo_audit_record()
    assert record["donated_params"], "train step must donate its state"
    aliased = H.aliased_param_numbers(record["hlo"])
    missing = set(record["donated_params"]) - aliased
    assert not missing, f"donated-but-unaliased params: {sorted(missing)[:12]}"
    violations, stats = audit_records([record])
    assert violations == [], [v.render() for v in violations]
    assert stats["contracts_checked"] >= 3  # GA001 + GA002 + GA003 apply


def test_undonated_twin_fails_donation_contract(slim_trainer):
    """The negative control: the SAME step fn jitted WITHOUT donate_argnums
    compiles to a module with no alias table — GA002 must fire. (This is
    the regression a jaxlib upgrade dropping donation would look like.)"""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.train.trainer import make_train_step
    from tools.graftaudit.artifacts import donated_param_numbers, snapshot_compiled

    t = slim_trainer
    state_shardings = t.sharding.state_shardings(t.state)
    twin = t.sharding.wrap(
        jax.jit(
            make_train_step(t.config, t.tx, t.schedule),
            in_shardings=(state_shardings, t.sharding.batch_shardings()),
            out_shardings=(state_shardings, t.sharding.replicated()),
            # deliberately NO donate_argnums
        )
    )
    h, w, c = 32, 48, 3
    b = t.config.batch_size
    batch = {
        "image1": jax.ShapeDtypeStruct((b, h, w, c), jnp.float32),
        "image2": jax.ShapeDtypeStruct((b, h, w, c), jnp.float32),
        "flow": jax.ShapeDtypeStruct((b, h, w, 1), jnp.float32),
        "valid": jax.ShapeDtypeStruct((b, h, w), jnp.float32),
    }
    compiled = twin.lower(t.state, batch).compile()
    record = snapshot_compiled(
        compiled,
        entry="train:step:undonated-twin:dp",
        kind="train_step",
        preset="dp",
        carry_arg=0,
        carry_out_index=0,
        donated_params=donated_param_numbers((t.state, batch), (0,)),
        meta={"corr_dtype": t.config.model.corr_dtype},
    )
    violations, _ = audit_records([record], select={"GA002"})
    assert violations, "un-donated twin must fail GA002"
    assert all(v.contract == "GA002" for v in violations)


_FIXPOINT_BUCKETS = ((32, 64), (64, 96))
_FIXPOINT_MAX_BATCH = 2


@pytest.mark.parametrize("preset", ["dp", "spatial"])
def test_chunk_fixpoint_every_warmed_combo(preset):
    """ROADMAP item 1, asserted at the executable level: for EVERY warmed
    (bucket, batch) combo, the steady-state chunk executable's carried-state
    out_shardings equal its in_shardings leaf-for-leaf — under dp AND
    spatial on the 8-device mesh. Also: one chunk record per combo (the
    audit covers the full warm set, no silent gaps) and the whole serving
    warm set audits clean across all five contracts."""
    from tools.graftaudit.live import serving_records

    records = serving_records(
        preset=preset,
        buckets=_FIXPOINT_BUCKETS,
        max_batch=_FIXPOINT_MAX_BATCH,
        chunk_iters=2,
    )
    chunks = [r for r in records if r["kind"] == "chunk"]
    combos = {(tuple(r["meta"]["bucket"]), r["meta"]["batch"]) for r in chunks}
    expected = {(hw, b) for hw in _FIXPOINT_BUCKETS for b in (1, 2)}
    assert combos == expected, f"warmed combos missing a chunk record: {combos}"
    for r in chunks:
        assert r["preset"] == preset
        assert r["carry_in"] and r["carry_out"], (
            f"{r['entry']}: chunk record lost its carried-state snapshot"
        )
    violations, stats = audit_records(records)
    assert [v for v in violations if v.contract == "GA001"] == [], [
        v.render() for v in violations
    ]
    assert violations == [], [v.render() for v in violations]
    assert stats["records"] == len(records)
    # dp serving is single-program: its collective table must be all zeros
    if preset == "dp":
        assert all(n == 0 for n in stats["collectives"]["dp"].values())
