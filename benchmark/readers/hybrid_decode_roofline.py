"""A decode step's share of its roofline where the layers keep more than
one kind of state: the bytes the step needs — the weights once, the live
paged rows once for each layer that reads them, the live ring rows once,
the live slots' recurrent state read and written (a function kept with
the configuration's reference) — over the peak HBM bandwidth of the
table, over the step's mean device duration in the trace.  What is live
comes from the program's gauges, sampled over the window; a program
without them gives nothing to read."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
PAGES = "dl4j_tpu_serving_kv_pages_in_use"
RING = "dl4j_tpu_serving_ring_rows_in_use"
SLOTS = "dl4j_tpu_serving_state_slots_in_use"


def gauges(metric: dict) -> dict:
    return {PAGES: (PAGES, {"pool": "target"}), RING: (RING, {}),
            SLOTS: (SLOTS, {})}


def live(samples: dict, page_size: int):
    """``(paged positions, ring rows, slots)``, each the mean of its
    gauge's samples; None when a gauge was never seen."""
    means = []
    for name in (PAGES, RING, SLOTS):
        seen = samples.get(name)
        if not seen:
            return None
        means.append(sum(seen) / len(seen))
    return page_size * means[0], means[1], means[2]


def read(metric: dict, ctx: dict):
    cell = ctx["cell"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    found = live(ctx["window"].get("samples", {}),
                 cell.config["serving"]["page_size"])
    if not calls or found is None:
        return None
    need = cell.reference.decode_step_bytes(cell.config, *found)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, seconds / calls, metric["name"])
