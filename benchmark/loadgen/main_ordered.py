"""``main.py``'s closed loop over ``ordered.py``'s schedule, its clients
started one after another.

    python benchmark/loadgen/main_ordered.py --url URL --workload-file F
                                             --vocab V --seed N --seconds S

The arguments, the two printed lines and every request's record are
``main.py``'s (its ``Client`` sends them).  Two things differ.  The
schedule is ``ordered.build``'s: the mix's lengths in the mix's own order.
And client ``c`` sends its first request ``c * traffic["stagger_s"]`` after
the clock starts: twenty threads that send at once reach the server's
queue in an order that the interpreter's scheduling decides, that order
decides which request is prefilled beside which for the rest of the run,
and a run's tokens per second move by 0.5-1% with it (a model of the loop,
PERF.md section 4).  A closed loop only; never imports JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import main
import ordered


def run_closed(sched: dict, client: main.Client, clients: int,
               stagger_s: float) -> list:
    results = []
    lock = threading.Lock()

    def loop(c):
        time.sleep(max(0.0, client.t0 + c * stagger_s - time.monotonic()))
        for req in (r for r in sched["requests"] if r["client"] == c):
            if time.monotonic() >= client.stop_at:
                return
            r = client.send(req, cut_at_stop=True)
            r["client"] = c
            with lock:
                results.append(r)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(max(0.0, client.stop_at + 30.0 - time.monotonic()))
    with lock:
        return sorted(results, key=lambda r: r["id"])


def run(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--workload-file", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(args.workload_file, encoding="utf-8") as f:
        traffic = json.load(f)["traffic"]
    sched = ordered.build(traffic, args.vocab, args.seed, args.seconds)
    t0 = time.monotonic()
    print(json.dumps({"t0": t0, "requests": len(sched["requests"])}),
          flush=True)
    stop_at = t0 + sched["ramp_s"] + sched["window_s"]
    client = main.Client(args.url, t0, stop_at, timeout=traffic["timeout_s"])
    results = run_closed(sched, client, traffic["arrivals"]["clients"],
                         float(traffic["stagger_s"]))
    print(json.dumps({"t0": t0, "results": results}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
