"""Host time outside the forward, per unit of work: the window's seconds less
the sum of a series of seconds the program itself reported, over the work."""


def read(context, series, scale=1000.0):
    window = context["window"]
    if window["work"] == 0 or series not in window:
        return None
    return scale * (window["seconds"] - sum(window[series])) / window["work"]
