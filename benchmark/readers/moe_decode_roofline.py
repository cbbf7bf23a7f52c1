"""A decode step's share of its roofline where an expert layer holds a
share of the experts and the cache is one latent row a position: the
LARGER of the step's least bytes over the peak HBM bandwidth and its
least operations over the peak of the MXU, over the step's mean device
duration in the trace.

The bytes (functions kept with the configuration's reference): the
weights outside the routed experts, the routed experts that a step HIT —
the program's counter, not all that are held: an expert no live token
chose need not be read — and the live latent rows at the lanes that mean
something.  The operations: two a weight outside the experts a live
slot, two a weight of an expert a pair routed to it, the absorbed
attention over the live rows.

What is live comes from the program's gauges sampled over the window
(pages in use, slots in use) less the slots' left padding
(``delta_decode_roofline``'s reckoning from the two prefill counters);
experts hit and pairs routed a step from the three counters of the
routing over the steps counted in the window.  A program without them
gives nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
_live = cells.load_module("readers", "delta_decode_roofline")
STEPS = "dl4j_tpu_serving_decode_steps_total"
HIT = "dl4j_tpu_serving_moe_experts_hit_total"
ROUTED = "dl4j_tpu_serving_moe_pairs_routed_total"


def gauges(metric: dict) -> dict:
    return _live.gauges(metric)


def live_rows_and_slots(ctx: dict):
    """``(live latent rows, live slots)`` of the window's mean step; None
    where the program lacks a gauge or a counter."""
    cell, w = ctx["cell"], ctx["window"]
    samples = w.get("samples", {})
    pages, slots = samples.get(_live.PAGES), samples.get(_live.SLOTS)
    pad = _live.mean_padding(w, cell.workload["traffic"]["prompt_buckets"])
    if not pages or not slots or pad is None:
        return None
    slots = sum(slots) / len(slots)
    return (cell.config["serving"]["page_size"] * sum(pages) / len(pages)
            - slots * pad, slots)


def per_step(window: dict, name: str):
    """What the counter gained a decode step of the window (its ``step``
    phase); None without the counter or without steps."""
    steps = _live.gained(window, STEPS)
    got = _live.gained(window, name, phase="step")
    if not steps or got is None:
        return None
    return got / steps


def read(metric: dict, ctx: dict):
    cell, w = ctx["cell"], ctx["window"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    live = live_rows_and_slots(ctx)
    hit, routed = per_step(w, HIT), per_step(w, ROUTED)
    if not calls or live is None or hit is None or routed is None:
        return None
    rows, slots = live
    ref = cell.reference
    least = max(ref.decode_step_bytes(cell.config, rows, hit)
                / ctx["peaks"]["hbm_bytes_per_s"],
                ref.decode_step_flops(cell.config, rows, slots, routed)
                / ctx["peaks"]["flops_bf16"])
    return peaks.share_pct(least, seconds / calls, metric["name"])
