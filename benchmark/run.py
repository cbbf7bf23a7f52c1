"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses to run without the chips the cell asks for,
sets up (weights and inputs from the seed, every shape warmed), measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of its standard
output.  With ``--trace 0`` its metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  Everything that belongs to one
cell, configuration or metric is in a file found by name (see README.md).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import peaks
from harness import cells, device


class Cell:
    """What a driver is handed: the cell's files, the run's arguments and
    the tools of the harness."""

    def __init__(self, workload: dict, config: dict, seed: int,
                 seconds: float, trace: bool, t_start: float = T_START):
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.t_start = t_start
        # where the load generator's process reads the traffic mix from
        self.workload_file = os.path.join(
            cells.BENCH_DIR, "workloads", workload["name"] + ".json")
        self.trace_dir = os.path.join(cells.REPO_DIR, ".bench_trace")
        self.control = False
        self.family = self.reference = self.compiles = None
        self.device = self.peaks = None


def judge(compared: list) -> bool:
    """Print each number compared beside its limit; all must hold."""
    ok = True
    for c in compared:
        holds = c["limit"] is not None and math.isfinite(c["value"]) \
            and c["value"] <= c["limit"]
        ok = ok and holds
        device.say(f"compared: {c['name']} = {c['value']:.6g}  limit "
                   f"{c['limit']}  {'ok' if holds else 'NOT CORRECT'}")
    return ok


def layer_values(cell: Cell, outcome: dict) -> dict:
    ctx = {"cell": cell, "outcome": outcome, "trace": outcome.get("trace"),
           "window": outcome["window"], "peaks": cell.peaks}
    out = {}
    for m in cells.layer_metrics_for(cell.workload):
        value = cells.load_module("readers", m["reader"]).read(m, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compute the control's numbers (the "
                    "reference in the precision below the stated one) and "
                    "print them on earlier lines; not part of a run")
    args = ap.parse_args(argv)
    workload = cells.load_workload(args.workload)
    cell = Cell(workload, cells.load_config(workload["config"]), args.seed,
                args.seconds, args.trace)
    cell.control = bool(args.control)
    look_for_chip(cell)
    print(json.dumps(execute(cell)), flush=True)
    return 0


def look_for_chip(cell: Cell) -> None:
    """Refuse (exit code 2, nothing printed) unless the machine holds the
    chips the cell asks for; then turn the compile cache on."""
    # the program is imported before JAX is asked for a device, so that a
    # directory without the program ends here
    import deeplearning4j_tpu  # noqa: F401
    try:
        cell.device = device.require_chips(cell.workload["chips"],
                                           peaks.peaks_for)
    except device.NoChip as e:
        device.fail(str(e))
    cell.peaks = peaks.peaks_for(cell.device["kind"])
    device.say(json.dumps({"compile_cache_dir": device.enable_cache()}))


def attach(cell: Cell) -> None:
    """The configuration's builder and reference, and the count of
    compilations, found by the names the cell's files give."""
    cell.compiles = device.CompileCounter()
    cell.family = cells.load_module("configs", cell.config["family"])
    cell.reference = cells.load_module("references", cell.config["family"])


def execute(cell: Cell) -> dict:
    """Everything of a run after the look for a chip: returns the result
    line (the tests drive this with a described device)."""
    attach(cell)
    device.say(json.dumps({"workload": cell.workload["name"],
                           "seed": cell.seed, "seconds": cell.seconds,
                           "trace": int(cell.trace),
                           "device": cell.device}))
    outcome = cells.load_module("drivers", cell.workload["driver"]).run(cell)
    device.say("compile: " + json.dumps(cell.compiles.snapshot()))
    correct = judge(outcome["compared"])
    dev = dict(cell.device, memory_peak_bytes=outcome["memory_peak_bytes"])
    if cell.trace:
        metrics = layer_values(cell, outcome)
        tr = outcome["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        device.say("traced programs on device 0: "
                   + json.dumps(tr["modules"]))
    else:
        units = cell.workload["end_to_end"]
        metrics = {name: {"value": outcome["measurements"][spec["from"]],
                          "unit": spec["unit"]}
                   for name, spec in units.items()}
    line = {"correct": correct, "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": metrics, "device": dev}
    if cell.trace:
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
