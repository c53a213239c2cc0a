"""The whole step's share of the chips' peak: required operations of the work
the window completed (the family's counts module, from shapes) over the
window's seconds, over chips x the peak in benchmark/peaks.json."""

from benchmark import families
from benchmark.peaks import peaks

# The arguments that name a function of the family's counts module.
COUNT_ARGS = ("count",)


def read(context, count):
    """`count` gives one unit of work's operations: `fn(config, spec)`."""
    window = context["window"]
    if window["work"] == 0:
        return None
    per_unit = families.count(context, count)
    peak = peaks(context["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_unit * window["work"] / window["seconds"] / (context["chips"] * peak)
