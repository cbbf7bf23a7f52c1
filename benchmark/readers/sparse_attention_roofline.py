"""The sparse read's share of its roofline: for one call (one layer of
one step) the bytes it has to move — the index rows it scores and the K
and V rows it selects, ``references/<family>.py:sparse_attention_bytes``
of the program's two counters a step a layer — over the peak HBM
bandwidth, over the mean device duration of the read's calls in the
trace.

The calls are the step's device ops that carry the scope the
configuration's ``trace_scopes[args.scope]`` names, kept by the cell's
driver beside the reduced trace (``drivers/serve_closed_ordered_scoped
.py``): the scoring kernel and the XLA ops of the selection and the row
gather alike.  A trace without such ops (a program without the read, a
driver that does not keep them) gives nothing to read."""
import peaks
from harness import cells

_step = cells.load_module("readers", "sparse_decode_roofline")


def read(metric: dict, ctx: dict):
    cell, tr = ctx["cell"], ctx["trace"]
    k = (tr or {}).get("kernels", {}).get(metric["args"]["scope"])
    got = _step.counted(ctx["window"])
    if not k or not k["count"] or got is None:
        return None
    ref = cell.reference
    layers = cell.config["num_hidden_layers"]      # each reads once
    _hit, _routed, scored, selected = got
    least = ref.sparse_attention_bytes(cell.config, scored / layers,
                                       selected / layers) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return peaks.share_pct(least, k["total_s"] / k["count"], metric["name"])
