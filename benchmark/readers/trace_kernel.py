"""A kernel's share of its roofline: the least time the chip could take for
the bytes and operations the kernel's calls require (the family's counts
module), over the summed device time of its trace events.

An event is the kernel's by the name the program gave the kernel: a trace
event is named by its HLO line, `%<kernel>.<n> = <result> custom-call(...)`,
and the instruction of a Pallas call carries the call's `name`. `bytes_fn`
and `ops_fn` name the counts of ONE call, each `fn(config, spec)`; `calls`
names the window key that counts the calls the kernel served. Both bounds are
worked out and the larger holds. No event of that name, or no call -> nothing
to read -> None."""

import re

from benchmark import families
from benchmark.peaks import peaks

# The arguments that name a function of the family's counts module.
COUNT_ARGS = ("bytes_fn", "ops_fn")
_CUSTOM_CALL = re.compile(r"^%([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)* = .*? custom-call\(")


def kernel_of(event_name):
    """The kernel's name of a custom call's trace event; None for any other
    event."""
    found = _CUSTOM_CALL.match(event_name)
    return found.group(1) if found else None


def read(context, kernel, bytes_fn, ops_fn, calls="kernel_calls"):
    seconds = sum(
        s for name, s in context["trace"]["device_time_by_name_s"].items() if kernel_of(name) == kernel
    )
    n_calls = context["window"].get(calls, 0)
    if seconds <= 0 or n_calls <= 0:
        return None
    peak = peaks(context["device"]["kind"])
    moved = families.count(context, bytes_fn) * n_calls
    ops = families.count(context, ops_fn) * n_calls
    least = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 100.0 * least / (seconds * context["chips"])
