"""The tree's ONE HLO-text parser (ISSUE 20).

Every helper that reads compiled/optimized HLO text — the collective-family
counters that used to live in ``parallel/sharding.py:380-421`` (those are now
thin wrappers over this module), the donation ``input_output_alias`` header
parse, the host-transfer scan and the dtype-upcast scan — lives here, so a
change to how XLA renders an instruction is fixed in exactly one place and
every audit verdict in the tree moves together.

Pure stdlib + regex: no JAX import, no device. Importable from the tier-1
CPU test environment, from ``scripts/audit.py`` run standalone, and from
product modules (``parallel/sharding.py`` delegates here at import time).

Parsing notes (pinned by tests/test_graftaudit.py against real modules):

- Collective families: ``-start`` async halves count toward their family,
  ``-done`` halves are NOT double-counted. The lookbehind/lookahead guards
  keep ``all-reduce-scatter``-style supersets and value names like
  ``%all-reduce.3`` from misattributing (``%`` is a word boundary; the
  negative classes exclude ``-`` and word chars on both sides).
- ``input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {1,2}, ...) }``
  is the module-header rendering of honored donation: ``{out_index}:
  (param_number, {param_index}, kind)``. Absent header = nothing aliased.
- Host transfers: opcode position is ``= <shape> opcode(`` — matching the
  opcode token anywhere in the line would false-positive on value names
  (``%send_buffer``). ``custom-call`` is only a host transfer when its
  target looks like a host callback (``xla_python_cpu_callback`` et al.);
  CPU convolutions legitimately lower to benign custom-calls.
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence, Set, Tuple

# Collective families audited across the tree. Order is the reporting order.
COLLECTIVE_OPS: Tuple[str, ...] = (
    "all-reduce",
    "all-gather",
    "collective-permute",
    "all-to-all",
)

_COLLECTIVE_LINE = re.compile(
    r"(?<![\w-])(?:" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?(?![\w-])"
)


# ---------------------------------------------------------------------------
# Collectives
# ---------------------------------------------------------------------------


def collective_counts(hlo: str) -> Dict[str, int]:
    """Occurrences of each collective family in an HLO dump. `start` ops
    ("all-reduce-start") count toward their family; "-done" halves are not
    double-counted."""
    counts = {}
    for op in COLLECTIVE_OPS:
        counts[op] = len(re.findall(rf"(?<![\w-]){op}(?:-start)?(?![\w-])", hlo))
    return counts


def unexpected_collectives(hlo: str, expected: Sequence[str] = ()) -> Dict[str, int]:
    """Collective families present in the HLO that are NOT in `expected` —
    the no-UNEXPECTED-collectives audit for spatial configs, where halo
    collective-permutes and norm all-reduces are legitimate but an
    all-to-all would mean a spec is fighting the partitioner."""
    return {k: v for k, v in collective_counts(hlo).items() if v and k not in expected}


def collective_lines(hlo: str) -> List[str]:
    """Every HLO line carrying a collective-family op (any provenance)."""
    return [line for line in hlo.splitlines() if _COLLECTIVE_LINE.search(line)]


def collective_definitions(hlo: str, family: str) -> List[str]:
    """The lines that DEFINE a collective of `family` (its opcode, or the
    `-start` half, followed by its operand list), without the lines that
    merely read such an instruction's result by name."""
    opcode = re.compile(rf"(?<![\w%.-]){re.escape(family)}(?:-start)?\(")
    return [line for line in hlo.splitlines() if opcode.search(line)]


def op_name(line: str) -> str:
    """The `op_name` provenance XLA stamped on an instruction line ("" where
    there is none): for a collective, the op whose tensor it reshards."""
    found = re.search(r'op_name="([^"]*)"', line)
    return found.group(1) if found else ""


def corr_collective_lines(hlo: str) -> List[str]:
    """HLO instruction lines that carry BOTH a collective op and corr-chain
    provenance (op_name / value names mentioning ``corr``). XLA stamps every
    collective with the op_name of the op whose tensor it reshards, so a
    non-empty result means the partitioner inserted communication INSIDE the
    corr volume/pyramid/lookup chain — the zero-communication claim
    (per-row-independent epipolar matching) is violated. The full forward
    legitimately carries collectives elsewhere (conv halos, norm reductions,
    coarse-level gathers), which a whole-module count cannot separate."""
    return [
        line
        for line in hlo.splitlines()
        if _COLLECTIVE_LINE.search(line) and "corr" in line.lower()
    ]


# ---------------------------------------------------------------------------
# Donation / input-output aliasing
# ---------------------------------------------------------------------------

_ALIAS_ENTRY = re.compile(
    r"\{\s*([0-9,\s]*)\}\s*:\s*\(\s*(\d+)\s*,\s*\{\s*([0-9,\s]*)\}"
)


def _index_tuple(text: str) -> Tuple[int, ...]:
    return tuple(int(t) for t in text.replace(",", " ").split())


def input_output_aliases(hlo: str) -> List[Tuple[Tuple[int, ...], int, Tuple[int, ...]]]:
    """Parse the module header's ``input_output_alias={...}`` table into
    ``[(output_index, param_number, param_index), ...]``. An absent header
    means the executable aliases NOTHING — donation was dropped."""
    start = hlo.find("input_output_alias=")
    if start < 0:
        return []
    brace = hlo.find("{", start)
    if brace < 0:
        return []
    depth = 0
    end = brace
    for end in range(brace, min(len(hlo), brace + 1_000_000)):
        ch = hlo[end]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo[brace + 1 : end]
    return [
        (_index_tuple(out_idx), int(param_number), _index_tuple(param_idx))
        for out_idx, param_number, param_idx in _ALIAS_ENTRY.findall(body)
    ]


def aliased_param_numbers(hlo: str) -> Set[int]:
    """Parameter numbers the executable donates INTO some output buffer."""
    return {param_number for _, param_number, _ in input_output_aliases(hlo)}


# ---------------------------------------------------------------------------
# Host transfers / hot-path purity
# ---------------------------------------------------------------------------

# Opcode directly after `= <shape>` and directly before `(` — value names
# like %send_buffer or metadata strings never match this position. The shape
# alternative covers tuple shapes (send/recv/infeed return `(f32[..], u32[],
# token[])`, spaces included, one nesting level) as well as plain shapes.
_HOST_OPCODE = re.compile(
    r"=\s*(?:\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(infeed|outfeed|send-done|recv-done|send|recv)\("
)
_CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')

# Substrings that mark a custom-call target as a host round-trip. CPU/GPU
# python callbacks (io_callback/pure_callback/debug.print) and explicit host
# transfers match; backend math custom-calls (convolutions, topk, sort
# comparators) do not.
HOST_CALLBACK_TARGET_MARKERS: Tuple[str, ...] = (
    "callback",
    "host_transfer",
    "infeed",
    "outfeed",
    "SendToHost",
    "RecvFromHost",
)


def is_host_callback_target(target: str) -> bool:
    low = target.lower()
    return any(marker.lower() in low for marker in HOST_CALLBACK_TARGET_MARKERS)


def host_transfer_lines(hlo: str) -> List[str]:
    """Instruction lines that move data between host and device mid-module:
    infeed/outfeed/send/recv opcodes, plus custom-calls whose target is a
    host callback. Benign backend custom-calls (CPU convolutions etc.) are
    NOT flagged — purity is about host round-trips, not lowering choices."""
    out = []
    for line in hlo.splitlines():
        if _HOST_OPCODE.search(line):
            out.append(line)
            continue
        m = _CUSTOM_TARGET.search(line)
        if m and is_host_callback_target(m.group(1)):
            out.append(line)
    return out


# ---------------------------------------------------------------------------
# Dtype upcasts
# ---------------------------------------------------------------------------


def upcast_convert_lines(
    hlo: str, *, frm: str = "bf16", to: str = "f32", needle: str = "corr"
) -> List[str]:
    """Instruction lines that CONVERT a `frm` tensor up to `to` and carry
    `needle` provenance (value name or op_name metadata). The bf16-corr
    dtype-pin audit: with ``corr_dtype=bfloat16`` the pyramid is built,
    stored and gathered in bf16 (ops/corr.py casts per-tap AFTER the gather,
    which converts O(taps) elements, not the O(H·W·W) volume) — so a
    ``f32[...] convert(bf16[...])`` with corr provenance means something
    upcast-and-stored pyramid-scale data and the memory claim is gone."""
    pattern = re.compile(rf"=\s*{to}\[[^\]]*\][^\s]*\s+convert\(")
    return [
        line
        for line in hlo.splitlines()
        if pattern.search(line) and f"{frm}[" in line and needle in line.lower()
    ]


__all__ = [
    "COLLECTIVE_OPS",
    "HOST_CALLBACK_TARGET_MARKERS",
    "aliased_param_numbers",
    "collective_counts",
    "collective_lines",
    "corr_collective_lines",
    "host_transfer_lines",
    "input_output_aliases",
    "is_host_callback_target",
    "unexpected_collectives",
    "upcast_convert_lines",
]
