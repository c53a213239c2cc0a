"""Device idle share of the traced window: 1 - busy union / window."""


def read(context):
    trace = context["trace"]
    if trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
