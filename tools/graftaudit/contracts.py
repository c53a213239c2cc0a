"""Declarative contract table over compiled-artifact records (ISSUE 20).

graftlint checks the Python half of the stack; these contracts check the
half that actually serves traffic — the compiled executable. Each contract
is a named, documented check bound declaratively to entry-point *kinds*
(train_step, prelude, chunk, finalize, eval_forward); ``audit_records``
walks a list of artifact records (tools/graftaudit/artifacts.py) and
evaluates every applicable contract, returning violations plus the stats
block bench emits as ``hlo_audit``.

Contract catalog
----------------
GA001 sharding-fixpoint   Carried-state out_shardings == in_shardings
                          leaf-for-leaf (chunk and train step). The ROADMAP
                          item-1 perf contract: anything else reshards every
                          chunk boundary / train step in steady state.
GA002 donation-honored    Every ``donate_argnums`` parameter appears in the
                          executable's input_output_alias table. A jaxlib
                          upgrade silently dropping aliasing is an HBM
                          doubling today's numeric tests can't see.
GA003 collective-whitelist Only the preset's expected collective families
                          appear; on the pure-spatial mesh, zero collectives
                          carry corr provenance (the per-row epipolar
                          independence claim). all-to-all is whitelisted on
                          no serving or train path — it means a spec is
                          fighting the partitioner — but for the train
                          step's batch-axis join of the image pair, allowed
                          by its op_name provenance (TRAIN_STEP_PAIR_JOIN).
GA004 corr-dtype-pin      With corr_dtype=bfloat16, no f32-from-bf16 convert
                          carries corr provenance (no silent upcast-then-
                          store of pyramid-scale tensors).
GA005 hot-path-purity     Serving-stage executables contain zero host
                          transfers: no infeed/outfeed/send/recv, no host-
                          callback custom-calls. A host round-trip inside a
                          warmed chunk is a silent latency cliff.

Expected-collective tables are per (kind, preset): serving under ``dp`` is
single-program (zero collectives); spatial presets legitimately carry halo
collective-permutes, norm all-reduces and coarse-level all-gathers; TRAIN
steps carry gradient all-reduces plus the partitioner's slice/pad-edge
permutes and small gathers (even under dp); fsdp adds parameter gathers.
The corr-provenance line check applies only on the pure-``spatial`` mesh:
with a dp axis in the mesh, fusion metadata can attribute a batch-axis
collective to a corr-named op (see __graft_entry__._sharding_scaling).

Pure stdlib: records are dicts, checks are regex passes over saved HLO text
(tools/graftaudit/hlo.py — the tree's single HLO parser).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tools.graftaudit import hlo as H

SERVING_KINDS = ("prelude", "chunk", "finalize")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken contract on one audited executable."""

    contract: str
    entry: str
    message: str
    detail: str = ""

    @property
    def fingerprint(self) -> str:
        """Line-number-free identity for baseline tracking (the graftlint
        convention: path::rule::message, with the entry name as the path)."""
        return f"{self.entry}::{self.contract}::{self.message}"

    def as_dict(self) -> Dict[str, str]:
        return {
            "contract": self.contract,
            "entry": self.entry,
            "message": self.message,
            "detail": self.detail,
        }

    def render(self) -> str:
        line = f"{self.entry}: {self.contract} {self.message}"
        if self.detail:
            line += f"\n    {self.detail}"
        return line


@dataclasses.dataclass(frozen=True)
class Contract:
    id: str
    summary: str
    kinds: Tuple[str, ...]
    check: Callable[[dict], List[Violation]]
    doc: str = ""

    def applies(self, record: dict) -> bool:
        return record.get("kind") in self.kinds


# ---------------------------------------------------------------------------
# Expected-collective tables (contract c)
# ---------------------------------------------------------------------------

_SPATIAL_LEGIT = ("collective-permute", "all-reduce", "all-gather")


def expected_collectives(kind: str, preset: str) -> Tuple[str, ...]:
    """Collective families the (kind, preset) pair is ALLOWED to contain."""
    if kind == "train_step":
        # Every preset's train step: gradient all-reduces, plus the small
        # all-gathers (broadcast/reshape of coords grids over the sharded
        # batch) and slice/pad-edge collective-permutes the partitioner
        # inserts even under plain dp — measured on the real step, op_name
        # provenance jvp(RAFTStereo)/slice|pad. fsdp adds param gathers.
        # all-to-all is no family a train step may hold, with ONE exception
        # by provenance, TRAIN_STEP_PAIR_JOIN below: anywhere else it means a
        # spec is fighting the partitioner.
        return _SPATIAL_LEGIT
    # Serving stages and the eval forward: dp is single-program — any
    # collective means the partitioner disagreed with the deployment.
    if preset == "dp":
        return ()
    if kind == "eval_forward":
        # The offline eval forward pins an H-sharded out_sharding on the
        # full-res disparity, and the convex-upsample pixel shuffle reshards
        # into it with all-to-alls — a one-time layout change at the tail of
        # an OFFLINE path, measured clean of them in every warmed serving
        # stage (where all-to-all stays whitelisted nowhere).
        return _SPATIAL_LEGIT + ("all-to-all",)
    return _SPATIAL_LEGIT


# The all-to-alls of the dp train step (6 at the audit's slim size, 3 at the
# recipe's). `encode_features` joins the image pair on the BATCH axis so both
# ride one 2B batch through the feature encoder, and splits the feature maps
# again; the batch axis is the sharded one, so the join moves half of each
# device's rows (3-channel images going in) and the split's transpose moves
# the feature maps' gradient back. The batch spec is right and this is the
# partitioner's answer to it; XLA stamps the instructions
# `.../jvp(RAFTStereo)/concatenate` and
# `.../transpose(jvp(RAFTStereo))/concatenate`. An all-to-all of any other
# provenance in a train step is still a violation, and no serving stage has
# this exception. (A join that interleaves the pair would need none.)
TRAIN_STEP_PAIR_JOIN = re.compile(r"jvp\(RAFTStereo\)\)?/concatenate$")


def corr_line_check_applies(record: dict) -> bool:
    """Corr-provenance collective-line check: pure-spatial mesh only (a dp
    mesh axis lets fusion metadata misattribute batch collectives to
    corr-named ops). Callers can force it off via meta.corr_line_check."""
    override = record.get("meta", {}).get("corr_line_check")
    if override is not None:
        return bool(override)
    return record.get("preset") == "spatial"


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------


def _check_sharding_fixpoint(record: dict) -> List[Violation]:
    entry = record["entry"]
    carry_in, carry_out = record.get("carry_in"), record.get("carry_out")
    if carry_in is None or carry_out is None:
        return [
            Violation(
                "GA001",
                entry,
                "no carried-state sharding snapshot",
                "the executable was registered without in/out sharding maps — "
                "the fixpoint cannot be verified (re-warm with auditing on, or "
                "repopulate the AOT cache)",
            )
        ]
    out: List[Violation] = []
    for leaf in sorted(set(carry_in) | set(carry_out)):
        sin, sout = carry_in.get(leaf), carry_out.get(leaf)
        if sin is None or sout is None:
            out.append(
                Violation(
                    "GA001",
                    entry,
                    f"carried leaf {leaf} present on only one side",
                    f"in={sin!r} out={sout!r} — carry trees diverged",
                )
            )
        elif sin != sout:
            out.append(
                Violation(
                    "GA001",
                    entry,
                    f"carried leaf {leaf} reshards at the boundary",
                    f"in={sin}  out={sout}",
                )
            )
    return out


def _check_donation(record: dict) -> List[Violation]:
    donated = record.get("donated_params")
    if not donated:
        return []
    aliased = H.aliased_param_numbers(record["hlo"])
    missing = sorted(set(donated) - aliased)
    if not missing:
        return []
    return [
        Violation(
            "GA002",
            record["entry"],
            f"{len(missing)}/{len(donated)} donated parameter(s) not aliased",
            f"param numbers missing from input_output_alias: "
            f"{missing[:12]}{'…' if len(missing) > 12 else ''} — donation was "
            "dropped; peak memory holds both copies",
        )
    ]


def _check_collectives(record: dict) -> List[Violation]:
    entry, text = record["entry"], record["hlo"]
    expected = expected_collectives(record["kind"], record.get("preset", "dp"))
    out: List[Violation] = []
    unexpected = H.unexpected_collectives(text, expected)
    if record["kind"] == "train_step" and "all-to-all" in unexpected:
        del unexpected["all-to-all"]
        names = [H.op_name(line) for line in H.collective_definitions(text, "all-to-all")]
        foreign = [name or "no op_name" for name in names if not TRAIN_STEP_PAIR_JOIN.search(name)]
        if foreign:
            out.append(
                Violation(
                    "GA003",
                    entry,
                    f"{len(foreign)} all-to-all(s) that are not the image pair's batch-axis join",
                    f"resharding: {sorted(set(foreign))[:6]}",
                )
            )
    for family, count in sorted(unexpected.items()):
        out.append(
            Violation(
                "GA003",
                entry,
                f"unexpected collective family {family} (x{count})",
                f"whitelist for kind={record['kind']} preset={record.get('preset')}: "
                f"{list(expected) or 'none'}",
            )
        )
    if corr_line_check_applies(record):
        lines = H.corr_collective_lines(text)
        if lines:
            out.append(
                Violation(
                    "GA003",
                    entry,
                    f"{len(lines)} collective(s) inside the corr chain",
                    lines[0].strip()[:200],
                )
            )
    return out


def _check_corr_dtype(record: dict) -> List[Violation]:
    if record.get("meta", {}).get("corr_dtype") != "bfloat16":
        return []
    lines = H.upcast_convert_lines(record["hlo"], frm="bf16", to="f32", needle="corr")
    if not lines:
        return []
    return [
        Violation(
            "GA004",
            record["entry"],
            f"{len(lines)} f32-from-bf16 convert(s) with corr provenance",
            lines[0].strip()[:200],
        )
    ]


def _check_purity(record: dict) -> List[Violation]:
    lines = H.host_transfer_lines(record["hlo"])
    if not lines:
        return []
    return [
        Violation(
            "GA005",
            record["entry"],
            f"{len(lines)} host transfer(s) in a hot-path executable",
            lines[0].strip()[:200],
        )
    ]


# ---------------------------------------------------------------------------
# The declarative table
# ---------------------------------------------------------------------------

ALL_CONTRACTS: Tuple[Contract, ...] = (
    Contract(
        "GA001",
        "carried-state out_shardings == in_shardings leaf-for-leaf",
        ("chunk", "train_step"),
        _check_sharding_fixpoint,
        doc=(
            "The chunk executable's carried state (net/coords1/context/corr/"
            "coords0) and the train step's TrainState must leave the "
            "executable with exactly the shardings they entered with. Any "
            "mismatch means GSPMD inserts a resharding copy at EVERY chunk "
            "boundary / train step in steady state — the ROADMAP item-1 "
            "contract the continuous-batching scheduler builds on. Fix: pin "
            "out_shardings to the in_shardings tree at jit time (the trainer "
            "does) or constrain the offending leaf inside the model."
        ),
    ),
    Contract(
        "GA002",
        "every donate_argnums parameter appears in input_output_alias",
        ("train_step",),
        _check_donation,
        doc=(
            "donate_argnums=(0,) promises the optimizer-state/param buffers "
            "are reused in place; the compiled proof is the module header's "
            "input_output_alias table covering every donated flat leaf. A "
            "jaxlib upgrade (or an added output that blocks aliasing) "
            "silently doubles train-step peak memory with no numeric "
            "signature. Fix: restore the alias (check output dtypes/layouts "
            "match the donated inputs) or re-budget HBM explicitly."
        ),
    ),
    Contract(
        "GA003",
        "only the preset's whitelisted collective families appear",
        ("train_step", "prelude", "chunk", "finalize", "eval_forward"),
        _check_collectives,
        doc=(
            "Per-(kind, preset) expected-collective tables: serving under dp "
            "is single-program (zero collectives); spatial presets carry "
            "halo collective-permutes, norm all-reduces and coarse-level "
            "all-gathers; train steps carry gradient all-reduces plus the "
            "partitioner's slice/pad-edge permutes and small gathers. "
            "all-to-all is whitelisted in exactly one place — the OFFLINE "
            "spatial eval forward, whose pinned out_sharding makes the "
            "convex-upsample pixel shuffle reshard — and nowhere on a "
            "serving or train hot path, except, by op_name provenance, the "
            "train step's batch-axis join of the image pair "
            "(TRAIN_STEP_PAIR_JOIN). On the pure-spatial mesh the "
            "corr chain must additionally carry ZERO collectives (per-row "
            "epipolar independence). Fix: find the op whose sharding "
            "constraint forces the communication (the HLO line's op_name "
            "metadata names it) rather than widening the whitelist."
        ),
    ),
    Contract(
        "GA004",
        "corr_dtype=bfloat16 stores no f32-upcast corr tensors",
        ("prelude", "chunk", "eval_forward"),
        _check_corr_dtype,
        doc=(
            "The bf16 corr pyramid halves the dominant memory term; the "
            "lookup casts per-tap AFTER the gather (O(taps), not O(H·W·W)). "
            "A f32[...] convert(bf16[...]) with corr provenance means "
            "pyramid-scale data was silently upcast and stored — the memory "
            "claim (and the BF16_CORR_EPE_BUDGET_PX trade) is gone. Fix: "
            "keep the pyramid bf16 end-to-end; cast only gathered taps."
        ),
    ),
    Contract(
        "GA005",
        "serving executables contain zero host transfers",
        SERVING_KINDS,
        _check_purity,
        doc=(
            "A warmed serving executable must be pure device code: no "
            "infeed/outfeed/send/recv, no host-callback custom-calls "
            "(io_callback, pure_callback, debug.print land here). A host "
            "round-trip inside the chunk loop serializes the pipeline and "
            "is invisible to the zero-recompile monitor. Fix: hoist the "
            "callback out of the jitted stage or behind a debug flag."
        ),
    ),
)

CONTRACT_TABLE: Dict[str, str] = {c.id: c.summary for c in ALL_CONTRACTS}
CONTRACT_DOCS: Dict[str, str] = {c.id: c.doc for c in ALL_CONTRACTS}


def contracts_for(kind: str) -> List[Contract]:
    return [c for c in ALL_CONTRACTS if kind in c.kinds]


def audit_records(
    records: Sequence[dict], select: Optional[Sequence[str]] = None
) -> Tuple[List[Violation], Dict[str, object]]:
    """Evaluate every applicable contract over every record.

    Returns ``(violations, stats)`` where stats is the bench ``hlo_audit``
    block shape: contracts_checked (record×contract evaluations), records,
    violations (count), and per-preset collective-family totals.
    """
    violations: List[Violation] = []
    checked = 0
    collectives: Dict[str, Dict[str, int]] = {}
    for record in records:
        for contract in ALL_CONTRACTS:
            if select is not None and contract.id not in select:
                continue
            if not contract.applies(record):
                continue
            checked += 1
            violations.extend(contract.check(record))
        preset = str(record.get("preset", "dp"))
        bucket = collectives.setdefault(preset, {op: 0 for op in H.COLLECTIVE_OPS})
        for op, n in H.collective_counts(record.get("hlo", "")).items():
            bucket[op] += n
    stats = {
        "contracts_checked": checked,
        "records": len(records),
        "violations": len(violations),
        "collectives": collectives,
    }
    return violations, stats


__all__ = [
    "ALL_CONTRACTS",
    "CONTRACT_DOCS",
    "CONTRACT_TABLE",
    "Contract",
    "SERVING_KINDS",
    "Violation",
    "audit_records",
    "contracts_for",
    "corr_line_check_applies",
    "expected_collectives",
]
