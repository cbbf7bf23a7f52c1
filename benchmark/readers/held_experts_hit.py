"""Share of the held experts that a decode step's tokens reach, in
percent, for a configuration whose reference says how many experts this
chip holds and in how many layers (``references/<family>.py:experts_held``
and ``expert_layers``; ``moe_experts_hit`` divides by DeepSeek's keys,
``n_routed_experts`` and ``first_k_dense_replace``, which a Qwen3-MoE
configuration does not have): the program's counter of held experts with
at least one token (summed over the expert layers and the steps; its
``step`` phase) over experts held x expert layers x the decode steps
counted in the window.  It is what of the routed experts' weights a step
has to read.  A program without the counter gives nothing to read."""
from harness import cells

_step = cells.load_module("readers", "moe_decode_roofline")


def read(metric: dict, ctx: dict):
    cell = ctx["cell"]
    hit = _step.per_step(ctx["window"], _step.HIT)
    if hit is None:
        return None
    ref = cell.reference
    return 100.0 * hit / (ref.expert_layers(cell.config)
                          * ref.experts_held(cell.config))
