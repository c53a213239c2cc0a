"""The whole step's share of the chips' peak: required operations of the work
the window completed (benchmark/counts.py, from shapes) over the window's
seconds, over chips x the peak in benchmark/peaks.json."""

from benchmark import counts


def read(context, count):
    """`count` names the function of counts.py that gives one unit of work's
    operations."""
    window, spec, model = context["window"], context["spec"], context["config"]["model"]
    h, w = spec["image_hw"]
    per_unit = getattr(counts, count)(model, h, w, spec["iters"])
    peak = counts.peaks(context["device"]["kind"])["bf16_flops_per_s"]
    if window["work"] == 0:
        return None
    return 100.0 * per_unit * window["work"] / window["seconds"] / (context["chips"] * peak)
