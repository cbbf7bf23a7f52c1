"""A decode step's share of its roofline where pages and a recurrent state
lie side by side and there is no ring: the bytes the step needs — the
weights once less the embedding table, the live paged rows once (each
full layer reads its own), the live slots' delta state and convolution
windows read and written (a function kept with the configuration's
reference) — over the peak HBM bandwidth of the table, over the step's
mean device duration in the trace.

What is live comes from the program's gauges, sampled over the window:
pages in use and slots in use.  A page in use may lie in a prompt's left
padding, which the step's kernel skips; the two prefill counters (padded
positions and real prompt tokens) give the window's mean padding a
request, and that many rows a live slot are taken off.  A program without
the gauges or the counters gives nothing to read."""
import peaks
from harness import cells, counters

_modules = cells.load_module("readers", "module_time")
PAGES = "dl4j_tpu_serving_kv_pages_in_use"
SLOTS = "dl4j_tpu_serving_state_slots_in_use"
POSITIONS = "dl4j_tpu_serving_prefill_positions_total"
PROMPT = "dl4j_tpu_serving_prefill_prompt_tokens_total"


def gauges(metric: dict) -> dict:
    return {PAGES: (PAGES, {"pool": "target"}), SLOTS: (SLOTS, {})}


def gained(window: dict, name: str, **labels):
    after = counters.scalar(window["after"], name, **labels)
    if after is None:
        return None
    return after - (counters.scalar(window["before"], name, **labels) or 0.0)


def prefills(window: dict, buckets) -> dict:
    """``{bucket: prefills of that shape in the window}`` from the padded
    positions counted by bucket; None where the program has no such
    counter."""
    out = {}
    for b in buckets:
        got = gained(window, POSITIONS, bucket=str(b))
        if got:
            out[b] = got / b
    return out or None


def mean_padding(window: dict, buckets):
    """Left-padding rows of the window's mean request."""
    n = prefills(window, buckets)
    real = gained(window, PROMPT)
    if n is None or real is None:
        return None
    padded = sum(b * k for b, k in n.items())
    return (padded - real) / sum(n.values())


def read(metric: dict, ctx: dict):
    cell, w = ctx["cell"], ctx["window"]
    calls, seconds = _modules.totals(ctx, metric["args"]["module"])
    samples = w.get("samples", {})
    pages, slots = samples.get(PAGES), samples.get(SLOTS)
    pad = mean_padding(w, cell.workload["traffic"]["prompt_buckets"])
    if not calls or not pages or not slots or pad is None:
        return None
    slots = sum(slots) / len(slots)
    live = cell.config["serving"]["page_size"] * sum(pages) / len(pages) \
        - slots * pad
    need = cell.reference.decode_step_bytes(cell.config, live, slots)
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, seconds / calls, metric["name"])
