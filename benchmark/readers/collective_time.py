"""Summed device duration, per call of ``trace_modules[args.module]``, of
the operations XLA names all-reduce*, all-gather*, reduce-scatter*,
collective-permute* or all-to-all* on device 0, in ms.  A trace without
any (one chip) gives nothing to read."""
from harness import cells

_modules = cells.load_module("readers", "module_time")


def read(metric: dict, ctx: dict):
    tr = ctx["trace"]
    calls, _s = _modules.totals(ctx, metric["args"]["module"])
    if tr is None or not calls or tr["collective_s"] <= 0.0:
        return None
    return 1e3 * tr["collective_s"] / calls
