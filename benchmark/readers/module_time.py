"""Mean device duration, in ms, of the XLA programs that the
configuration file lists under ``trace_modules[args.module]``."""


def totals(ctx: dict, which: str):
    """``(calls, seconds)`` of the named group of modules on device 0."""
    tr = ctx["trace"]
    if tr is None:
        return 0, 0.0
    names = ctx["cell"].config["trace_modules"][which]
    found = [tr["modules"][n] for n in names if n in tr["modules"]]
    return (sum(m["count"] for m in found),
            sum(m["total_s"] for m in found))


def read(metric: dict, ctx: dict):
    calls, seconds = totals(ctx, metric["args"]["module"])
    if not calls:
        return None
    return 1e3 * seconds / calls
