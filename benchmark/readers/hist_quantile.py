"""The ``args.q`` quantile, in ms, of what the program's histogram
``args.histogram`` (seconds) gained over the window, interpolated inside
its bucket."""
from harness import counters


def read(metric: dict, ctx: dict):
    w = ctx["window"]
    d = counters.hist_delta(w["before"], w["after"],
                            metric["args"]["histogram"])
    if d is None:
        return None
    return 1e3 * counters.hist_quantile(d, metric["args"]["q"])
