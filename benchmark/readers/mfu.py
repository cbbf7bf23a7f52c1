"""Model FLOP/s utilisation: the operations the forward and backward
passes require per item (a function of the configuration, kept with its
reference) times items per second, over chips times the peak of the
table.  Recomputed operations do not count."""
import peaks


def read(metric: dict, ctx: dict):
    cell = ctx["cell"]
    rate = ctx["outcome"]["measurements"].get("items_per_s")
    if rate is None:
        return None
    flops = cell.reference.train_flops_per_item(cell.config) * rate
    peak = cell.workload["chips"] * ctx["peaks"]["flops_bf16"]
    return peaks.share_pct(flops, peak, metric["name"])
