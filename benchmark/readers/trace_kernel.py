"""A kernel's share of its roofline: the least time the chip could take for
the bytes and operations the kernel's calls require (benchmark/counts.py),
over the summed device time of its trace events.

The program gives its Pallas calls no stable name yet, so an event is the
kernel's when its HLO line holds every string of `match` and its result type
fits `result`, a pattern in which `{taps}` stands for levels x (2r+1): the
lookup returns one array of taps, its backward a tuple of pyramid levels.
`calls` names the window key that counts pairs x iterations the kernel
served. Both bounds are worked out and the larger holds: for the lookup and
its backward that is memory (about 0.3 operations a byte). No event matched
-> nothing to read -> None."""

import re

from benchmark import counts


def read(context, match, result, bytes_fn, bytes_args=(), calls="kernel_calls"):
    spec, model = context["spec"], context["config"]["model"]
    taps = model["corr_levels"] * (2 * model["corr_radius"] + 1)
    pattern = re.compile(result.replace("{taps}", str(taps)))
    seconds = sum(
        s for name, s in context["trace"]["device_time_by_name_s"].items()
        if all(m in name for m in match) and pattern.search(name)
    )
    n_calls = context["window"].get(calls, 0)
    if seconds <= 0 or n_calls <= 0:
        return None
    h8, w8 = counts.coarse_hw(model, *spec["image_hw"])
    peaks = counts.peaks(context["device"]["kind"])
    moved = getattr(counts, bytes_fn)(model, h8, w8, *bytes_args) * n_calls
    ops = counts.lookup_flops(model, h8, w8) * n_calls
    least = max(moved / peaks["hbm_bytes_per_s"], ops / peaks["bf16_flops_per_s"])
    return 100.0 * least / (seconds * context["chips"])
