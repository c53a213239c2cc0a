"""Host time inside the program's own spans (`raft_stereo_tpu.obs.trace`):
the process-wide log of `name, t0, t1, id, parent, root` the program keeps of
an evaluation call's or a fit's parts.

`root`: {"name": the outermost span, "count": how many of its newest
instances belong to the window — a window key (`attempted`) or a number}.
`name`: the child span read under those roots. `stat`: `p50` or `sum`, in
milliseconds. With `busy_over` (a list of child names) the value is instead
the device's idle share of those spans' seconds: 100 x (1 - busy_s / their
sum). A program without the log, or no such span -> None.
"""

import statistics


def _seconds(context, root, names):
    """Durations of the spans called one of `names` under the window's
    `root` spans, oldest first."""
    try:
        from raft_stereo_tpu.obs.trace import process_spans
    except ImportError:
        return []
    count = root["count"]
    if isinstance(count, str):
        count = context["window"].get(count, 0)
    spans = process_spans()
    roots = {s["id"] for s in [s for s in spans if s["name"] == root["name"]][-count:]} if count > 0 else set()
    return [s["t1"] - s["t0"] for s in spans if s["name"] in names and s["root"] in roots]


def read(context, root, name=None, stat="p50", busy_over=None):
    if busy_over is not None:
        seconds = sum(_seconds(context, root, busy_over))
        if seconds <= 0:
            return None
        return 100.0 * (1.0 - context["trace"]["busy_s"] / seconds)
    found = _seconds(context, root, [name])
    if not found:
        return None
    if stat == "sum":
        return 1000.0 * sum(found)
    if stat == "p50":
        return 1000.0 * statistics.median(found)
    raise ValueError(f"unknown stat {stat!r}")
