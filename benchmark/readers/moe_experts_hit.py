"""Share of the held experts that a decode step's tokens reach, in
percent: the program's counter of held experts with at least one token
(summed over the expert layers and the steps; its ``step`` phase) over
experts held x expert layers x the decode steps counted in the window.
It is what of the routed experts' weights a step has to read.  A program
without the counter gives nothing to read."""
from harness import cells

_step = cells.load_module("readers", "moe_decode_roofline")


def read(metric: dict, ctx: dict):
    cfg = ctx["cell"].config
    hit = _step.per_step(ctx["window"], _step.HIT)
    if hit is None:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * hit / (layers * cfg["n_routed_experts"])
