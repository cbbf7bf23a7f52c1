"""The KDA layers' state update's share of its roofline: the bytes the
delta states and convolution windows of the live slots cost a decode step
(read once, written once: ``references/<family>.py:kda_state_bytes``)
over the peak HBM bandwidth, over the device time a step of the step's
ops that carry the scope the configuration's ``trace_scopes[args.scope]``
names.

The scope's ops are kept by the cell's driver beside the reduced trace
(``drivers/serve_closed_ordered_scoped.py``: the union of their intervals
over the traced stretch).  That driver counts as calls only the ops NAMED
for the scope; the update is all six layers' work of one step, XLA ops
and kernels alike, so its time a step is the scope's seconds over the
``trace_modules[args.module]`` programs' executions in the stretch, not
over that count.  Live slots come from the program's gauge sampled over
the window.  A trace without such ops (a program without the scope, a
driver that does not keep them) gives nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
_live = cells.load_module("readers", "delta_decode_roofline")


def gauges(metric: dict) -> dict:
    return {_live.SLOTS: (_live.SLOTS, {})}


def read(metric: dict, ctx: dict):
    cell, tr = ctx["cell"], ctx["trace"]
    k = (tr or {}).get("kernels", {}).get(metric["args"]["scope"])
    steps, _seconds = _modules.totals(ctx, metric["args"]["module"])
    slots = ctx["window"].get("samples", {}).get(_live.SLOTS)
    if not k or not k["total_s"] or not steps or not slots:
        return None
    least = cell.reference.kda_state_bytes(
        cell.config, sum(slots) / len(slots)) / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, k["total_s"] / steps, metric["name"])
