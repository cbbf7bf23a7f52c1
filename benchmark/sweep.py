"""The knee sweep of an open-loop serving cell — run once, by hand, on the
chip, when the cell is defined; not part of a cell's run:

    python benchmark/sweep.py --workload <cell> --seed N --seconds S --rates 3,4,5,6

Sets the system up once and offers the cell's traffic at each rate in
turn.  The knee is the highest offered rate at which the requests
completed per second inside the window stay within 3% of those offered
and no more than ``max_slots`` requests wait in the queue when the window
ends.  The cell's fixed rate is 0.8 x the knee, written into the cell's
file as a number, with the sweep's lines recorded in PERF.md.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import run as runner
from harness import cells, device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    wl = cells.load_workload(args.workload)
    cell = runner.Cell(wl, cells.load_config(wl["config"]), args.seed,
                       args.seconds, False, T_START)
    runner.look_for_chip(cell)
    runner.attach(cell)
    common = cells.load_module("drivers", "serve_common")
    opened = cells.load_module("drivers", wl["driver"])
    served = common.Served(cell)
    slots = cell.config["serving"]["max_slots"]
    vocab = cell.config["vocab_size"]
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        sched = common.schedule.build(
            dict(wl["traffic"], arrivals=dict(wl["traffic"]["arrivals"],
                                              rate=rate)),
            vocab, args.seed, args.seconds)
        win = common.window(served, args.seconds, rate=rate)
        m, attempted, failed, _fin, _lines = opened.measure(sched, win, vocab)
        done_inside = sum(
            1 for r in win["results"]
            if r["done"] and win["w0"] <= r["t_tokens"][-1] < win["w1"])
        offered = attempted / args.seconds
        completed = done_inside / args.seconds
        holds = completed >= 0.97 * offered \
            and win["queued_at_end"] <= slots and not failed
        if holds:
            knee = rate
        device.say(json.dumps({
            "rate": rate, "offered_per_s": offered,
            "completed_per_s": completed, "failed": failed,
            "queue_at_end": win["queued_at_end"],
            "ttft_p95_ms": m["ttft_p95_ms"], "itl_p95_ms": m["itl_p95_ms"],
            "sustained": holds}))
        while served.batcher.pool.usedPages():
            time.sleep(0.1)
    device.say(json.dumps({"knee": knee,
                           "fixed_rate": None if knee is None
                           else 0.8 * knee}))
    served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
