"""A decode step's share of its roofline where an expert layer holds a
share of the experts and the attention reads only the rows a selector
picks: the LARGER of the step's least bytes over the peak HBM bandwidth
and its least operations over the peak of the MXU, over the step's mean
device duration in the trace.

The bytes (functions kept with the configuration's reference): the
weights outside the routed experts, the routed experts that a step HIT,
the index rows it SCORED and the K and V rows it SELECTED — the last
three the program's counters a decode step (their ``step`` phase), not
what is held or live: an expert no token chose and a row the selector
passed over need not be read.  The operations: two a weight outside the
experts a live slot, two a weight of an expert a pair routed to it, the
index scores of the rows scored, scores and context of the rows selected.

Live slots come from the program's gauge sampled over the window.  A
program without the counters gives nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
_moe = cells.load_module("readers", "moe_decode_roofline")
_live = cells.load_module("readers", "delta_decode_roofline")
SCORED = "dl4j_tpu_serving_sparse_rows_scored_total"
SELECTED = "dl4j_tpu_serving_sparse_rows_selected_total"


def gauges(metric: dict) -> dict:
    return {_live.SLOTS: (_live.SLOTS, {})}


def counted(window: dict):
    """``(experts hit, pairs routed, rows scored, rows selected)`` a
    decode step of the window; None where the program lacks a counter."""
    got = tuple(_moe.per_step(window, name)
                for name in (_moe.HIT, _moe.ROUTED, SCORED, SELECTED))
    return None if any(g is None for g in got) else got


def read(metric: dict, ctx: dict):
    cell, w = ctx["cell"], ctx["window"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    slots = w.get("samples", {}).get(_live.SLOTS)
    got = counted(w)
    if not calls or not slots or got is None:
        return None
    hit, routed, scored, selected = got
    ref = cell.reference
    least = max(ref.decode_step_bytes(cell.config, 0.0, hit, scored, selected)
                / ctx["peaks"]["hbm_bytes_per_s"],
                ref.decode_step_flops(cell.config, sum(slots) / len(slots),
                                      routed, scored, selected)
                / ctx["peaks"]["flops_bf16"])
    return peaks.share_pct(least, seconds / calls, metric["name"])
