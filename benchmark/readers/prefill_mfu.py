"""Model FLOP/s utilisation of the prefill: the operations the traced
stretch's prefills require (a function of the padded length, kept with
the configuration's reference) over the device time of those prefills in
the trace, over the peak of the table.

Which prefills the stretch held is the trace's own count.  The program
jits its prefill once a prompt bucket under the bucket's name, the
configuration lists those programs under ``trace_modules[args.module]``
and gives each one's padded length under ``prefill_positions``: a
program's executions in the trace times the operations of its length,
summed, over the programs' summed device time.  No clock of the host and
no counter places anything.  A trace that holds none of the programs
(a program that names its prefill otherwise, as the parent does) gives
nothing to read."""
import peaks


def read(metric: dict, ctx: dict):
    cell, tr = ctx["cell"], ctx["trace"]
    if tr is None:
        return None
    lengths = cell.config.get("prefill_positions", {})
    flops = seconds = 0.0
    for name in cell.config["trace_modules"][metric["args"]["module"]]:
        m = tr["modules"].get(name)
        if m and name in lengths:
            flops += m["count"] * cell.reference.prefill_flops(
                cell.config, lengths[name])
            seconds += m["total_s"]
    if not seconds:
        return None
    return peaks.share_pct(flops / seconds, ctx["peaks"]["flops_bf16"],
                           metric["name"])
