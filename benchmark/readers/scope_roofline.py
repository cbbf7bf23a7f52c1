"""A scope's share of its roofline in a decode step: the bytes the ops
under one ``jax.named_scope`` of the step have to move, over the peak HBM
bandwidth, over the device time a step of the step's ops that carry the
scope the configuration's ``trace_scopes[args.scope]`` names.

The bytes are a function kept with the configuration's reference,
``references/<family>.py:<args.bytes>(config, n)``, of ONE quantity of
the window's mean step, ``args.of``:

- ``live_slots``: the program's gauge of slots that hold a sequence,
  sampled over the window (a matrix state a slot, read once and written
  once: ``ssd_state_bytes``);
- ``experts_hit``: the program's counter of held experts with at least
  one token, its ``step`` phase, over the decode steps counted in the
  window (an expert nobody chose is not read: ``expert_bytes``).

The scope's ops are kept by the cell's driver beside the reduced trace
(``drivers/serve_closed_ordered_scoped.py``: the union of their intervals
over the traced stretch: XLA ops and kernels alike, all layers' work of
one step), so the time a step is the scope's seconds over the
``trace_modules[args.module]`` programs' executions in the stretch.  A
trace without such ops (a program without the scope, a driver that does
not keep them), or a program without the gauge or the counter, gives
nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
_live = cells.load_module("readers", "delta_decode_roofline")
_moe = cells.load_module("readers", "moe_decode_roofline")


def gauges(metric: dict) -> dict:
    if metric["args"]["of"] == "live_slots":
        return {_live.SLOTS: (_live.SLOTS, {})}
    return {}


def _quantity(of: str, window: dict):
    if of == "live_slots":
        slots = window.get("samples", {}).get(_live.SLOTS)
        return sum(slots) / len(slots) if slots else None
    if of == "experts_hit":
        return _moe.per_step(window, _moe.HIT)
    raise ValueError(f"scope_roofline counts live_slots or experts_hit, "
                     f"not {of!r}")


def read(metric: dict, ctx: dict):
    cell, tr, args = ctx["cell"], ctx["trace"], metric["args"]
    k = (tr or {}).get("kernels", {}).get(args["scope"])
    steps, _seconds = _modules.totals(ctx, args["module"])
    n = _quantity(args["of"], ctx["window"])
    count = getattr(cell.reference, args["bytes"], None)
    if not k or not k["total_s"] or not steps or not n or count is None:
        return None
    least = count(cell.config, n) / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, k["total_s"] / steps, metric["name"])
