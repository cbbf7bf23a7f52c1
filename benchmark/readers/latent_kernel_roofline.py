"""The latent attention kernel's share of its roofline: for one call (one
layer of one step) the LARGER of the live rows' bytes over the peak HBM
bandwidth and of their operations over the peak of the MXU (both
functions kept with the configuration's reference: 576 bfloat16 lanes a
row read once; a score over 576 lanes and a context over 512 a head a
row), over the mean device duration of the kernel's calls in the trace.

The calls are the device ops that the configuration's
``trace_kernels[args.kernel]`` names, counted by the cell's driver beside
the reduced trace (``drivers/serve_closed_ordered_kernels.py``); the live
rows are ``moe_decode_roofline``'s.  A trace without such ops (a program
without the kernel, a driver that does not keep them) gives nothing to
read."""
import peaks
from harness import cells

_step = cells.load_module("readers", "moe_decode_roofline")


def gauges(metric: dict) -> dict:
    return _step.gauges(metric)


def read(metric: dict, ctx: dict):
    cell, tr = ctx["cell"], ctx["trace"]
    k = (tr or {}).get("kernels", {}).get(metric["args"]["kernel"])
    live = _step.live_rows_and_slots(ctx)
    if not k or not k["count"] or live is None:
        return None
    ref = cell.reference
    least = max(ref.latent_attention_bytes(cell.config, live[0])
                / ctx["peaks"]["hbm_bytes_per_s"],
                ref.latent_attention_flops(cell.config, live[0])
                / ctx["peaks"]["flops_bf16"])
    return peaks.share_pct(least, k["total_s"] / k["count"], metric["name"])
