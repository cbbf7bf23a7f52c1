"""A decode step's share of its roofline: the bytes the step needs (the
weights once and the live keys and values, a function kept with the
configuration's reference) over the peak HBM bandwidth of the table, over
the step's mean device duration in the trace.  The step is bound by
bytes: 8 rows against the weights are a few operations per byte."""
import peaks
from harness import cells

_modules = cells.load_module("readers", "module_time")
PAGES = "dl4j_tpu_serving_kv_pages_in_use"


def gauges(metric: dict) -> dict:
    return {PAGES: (PAGES, {"pool": "target"})}


def read(metric: dict, ctx: dict):
    cell = ctx["cell"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    pages = ctx["window"].get("samples", {}).get(PAGES)
    if not calls or not pages:
        return None
    live = cell.config["serving"]["page_size"] * sum(pages) / len(pages)
    need = cell.reference.decode_step_bytes(cell.config, live)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, seconds / calls, metric["name"])
