"""A decode step's share of its roofline where latent rows in pages, a
matrix-valued delta state a slot and an expert layer that holds a share
lie side by side: the step's least bytes over the peak HBM bandwidth,
over the step's mean device duration in the trace.

The bytes (``references/<family>.py:decode_step_bytes`` with all four
arguments): the weights outside the routed experts, the routed experts
that a step HIT — the program's counter, not all that are held: an expert
no live token chose need not be read — the live latent rows at the lanes
that mean something, and the live slots' delta states and convolution
windows read once and written once.  The step is bound by bytes (64 rows
against the weights and a 12.6 MB state a slot).

What is live comes from the program's gauges sampled over the window
(pages in use, slots in use) less the slots' left padding
(``delta_decode_roofline``'s reckoning from the two prefill counters);
experts hit a step from the routing's counter over the steps counted in
the window.  A program without them gives nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
_moe = cells.load_module("readers", "moe_decode_roofline")


def gauges(metric: dict) -> dict:
    return _moe.gauges(metric)


def read(metric: dict, ctx: dict):
    cell = ctx["cell"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    live = _moe.live_rows_and_slots(ctx)
    hit = _moe.per_step(ctx["window"], _moe.HIT)
    if not calls or live is None or hit is None:
        return None
    rows, slots = live
    least = cell.reference.decode_step_bytes(cell.config, rows, hit, slots) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, seconds / calls, metric["name"])
