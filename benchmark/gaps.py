"""The idle gaps of a traced run named by the PROGRAM's spans — run by
hand, on the chip; no check runs it:

    python benchmark/gaps.py --workload <cell> --seed N --seconds S

One traced run of the cell, exactly as ``run.py --trace 1`` makes it; the
trace is then reduced a second time with ``cover_prefix="dl4j."`` (every
``telemetry.tracer().span(name)`` of the program is a profiler annotation
``dl4j.<name>``), so that each of the longest gaps of device 0 carries the
innermost program span that covers its middle, beside the ``bench.*`` name
that the result line's ``breakdown`` keeps.  Also says, for every program
span, how much of the idle time it covers innermost.  Against a program
without such annotations every gap reads ``unattributed``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import collections
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import run as runner
from harness import cells, device, stats, trace as tracelib

PREFIX = "dl4j."


def idle_by_span(trace) -> dict:
    """Idle seconds of device 0 under each innermost ``dl4j.*`` span (the
    rule of ``reduce_trace``: the span that covers the gap's middle)."""
    dev0 = trace.devices[sorted(trace.devices)[0]]
    ops = [(a, b) for _n, a, b in dev0["ops"]]
    covers = [(n, a, b) for n, a, b in trace.host if n.startswith(PREFIX)]
    per_span = collections.Counter()
    for a, b in stats.gaps(ops, min(a for a, _b in ops),
                           max(b for _a, b in ops)):
        mid = 0.5 * (a + b)
        inside = [(cb - ca, n) for n, ca, cb in covers if ca <= mid <= cb]
        per_span[min(inside)[1] if inside else "unattributed"] += b - a
    return dict(per_span.most_common())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    workload = cells.load_workload(args.workload)
    cell = runner.Cell(workload, cells.load_config(workload["config"]),
                       args.seed, args.seconds, True, T_START)
    by_program = {}
    reduce_trace = tracelib.reduce_trace

    def reduce_twice(trace, chips, **kw):
        by_program["longest"] = reduce_trace(
            trace, chips, cover_prefix=PREFIX)["idle_gaps"]
        by_program["idle_s"] = idle_by_span(trace)
        return reduce_trace(trace, chips, **kw)

    tracelib.reduce_trace = reduce_twice
    runner.look_for_chip(cell)
    line = runner.execute(cell)
    device.say("gaps by bench.* span: "
               + json.dumps(line["breakdown"]["idle_gaps"]))
    device.say("gaps by dl4j.* span:  " + json.dumps(by_program["longest"]))
    device.say("idle seconds of device 0 by dl4j.* span: "
               + json.dumps(by_program["idle_s"]))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
