"""What the histograms ``args.histograms`` of the program's registry
gained in seconds over the window, per step of the window, in ms.  A
histogram that the window never observed gives nothing to read."""
from harness import counters


def read(metric: dict, ctx: dict):
    w = ctx["window"]
    total, seen = 0.0, False
    for name in metric["args"]["histograms"]:
        d = counters.hist_delta(w["before"], w["after"], name)
        if d is not None:
            total += d["sum"]
            seen = True
    if not seen or not w.get("steps"):
        return None
    return 1e3 * total / w["steps"]
