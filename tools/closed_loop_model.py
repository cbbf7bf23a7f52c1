"""A model of the continuous batcher's loop under a closed-loop traffic
mix, for choosing a mix's ``order_seed`` before any chip run of it (PR
30's rule: of the orders 1..N, the one whose window serves nearest the
median of all of them and moves least when every prefill and step is
jittered).  No JAX; reads the benchmark's own schedule.

    python tools/closed_loop_model.py benchmark/workloads/<cell>.json \
        --slots 16 --step-ms 7.0 --step-ns-per-row 12 \
        --prefill-ms 8192=350,16384=800,32768=1900 --admit-ms 5

The loop: a free slot takes the queue's head (its prefill holds the
device for the bucket's time, during which no slot decodes), then every
live slot gains a token a step of ``step-ms + ns-per-row x live rows``.
Clients start ``stagger_s`` apart and send their next request when the
last has ended.  Prints tokens/s inside the window for each order, the
median, and the orders nearest it with their spread under jitter."""
from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "loadgen"))
import schedule  # noqa: E402


def lengths(traffic, order):
    """A client's requests ``{prompt_len, max_new}`` in the order
    ``schedule.build(traffic, vocab, order, seconds)`` gives them: its two
    shuffles of the mix's quantiles, without the prompts' tokens, which it
    draws after both (2 M draws at this cell's lengths: a second a call)."""
    arr = traffic["arrivals"]
    n = arr["clients"] * arr["per_client"]
    rng = random.Random(order)
    prompts = schedule.lognormal_set(n, traffic["prompt_len"])
    outputs = schedule.lognormal_set(n, traffic["output_len"])
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return [[{"prompt_len": p, "max_new": o}
             for i, (p, o) in enumerate(zip(prompts, outputs))
             if i % arr["clients"] == c] for c in range(arr["clients"])]


def simulate(traffic, order, slots, step_s, row_s, prefill_s, admit_s,
             seconds, jitter=0.0, rng=None):
    """Tokens a second delivered inside ``[ramp_s, ramp_s + seconds)``."""
    rng = rng or random.Random(0)
    jit = lambda x: x * (1.0 + jitter * (2.0 * rng.random() - 1.0))
    clients = traffic["arrivals"]["clients"]
    mine = [list(reqs) for reqs in lengths(traffic, order)]
    ready = sorted((c * traffic.get("stagger_s", 0.0), c)
                   for c in range(clients))       # (time a client sends, c)
    buckets = sorted(prefill_s)
    w0 = traffic["ramp_s"]
    w1 = w0 + seconds
    t, tokens = 0.0, 0
    live = {}                  # slot -> [client, rows, tokens left]
    while t < w1:
        # admissions: every free slot takes a waiting request
        while len(live) < slots and ready and ready[0][0] <= t:
            _at, c = ready.pop(0)
            if not mine[c]:
                continue
            r = mine[c].pop(0)
            b = next(b for b in buckets if b >= r["prompt_len"])
            t += jit(prefill_s[b]) + admit_s
            if w0 <= t < w1:
                tokens += 1                       # the prefill's own token
            slot = next(s for s in range(slots) if s not in live)
            live[slot] = [c, r["prompt_len"], r["max_new"] - 1]
        if not live:
            if not ready:
                break
            t = max(t, ready[0][0])
            continue
        rows = sum(v[1] for v in live.values())
        t += jit(step_s + row_s * rows)
        for slot in list(live):
            v = live[slot]
            v[1] += 1
            v[2] -= 1
            if w0 <= t < w1:
                tokens += 1
            if v[2] <= 0:
                del live[slot]
                ready.append((t, v[0]))
        ready.sort()
    return tokens / seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--step-ns-per-row", type=float, default=0.0)
    ap.add_argument("--prefill-ms", required=True,
                    help="bucket=ms,bucket=ms,...")
    ap.add_argument("--admit-ms", type=float, default=5.0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--orders", type=int, default=160)
    ap.add_argument("--nearest", type=int, default=16)
    ap.add_argument("--jitter", type=float, default=0.07)
    args = ap.parse_args(argv)
    with open(args.workload, encoding="utf-8") as f:
        traffic = json.load(f)["traffic"]
    prefill = {int(b): float(ms) * 1e-3 for b, ms in
               (p.split("=") for p in args.prefill_ms.split(","))}
    run = lambda order, **kw: simulate(
        traffic, order, args.slots, args.step_ms * 1e-3,
        args.step_ns_per_row * 1e-9, prefill, args.admit_ms * 1e-3,
        args.seconds, **kw)
    base = {o: run(o) for o in range(1, args.orders + 1)}
    med = statistics.median(base.values())
    q = statistics.quantiles(base.values(), n=4)
    print(f"orders 1-{args.orders}: median {med:.1f} tokens/s, "
          f"{100 * (q[2] - q[0]) / med:.2f}% between the quartiles")
    near = sorted(base, key=lambda o: abs(base[o] - med))[:args.nearest]
    for o in near:
        moved = [run(o, jitter=args.jitter, rng=random.Random(k))
                 for k in range(8)]
        qq = statistics.quantiles(moved, n=4)
        print(f"order {o}: {base[o]:.1f} ({100 * (base[o] / med - 1):+.2f}%)"
              f", under +-{100 * args.jitter:.0f}% jitter "
              f"{statistics.median(moved):.1f}, "
              f"{100 * (qq[2] - qq[0]) / statistics.median(moved):.2f}% "
              "between the quartiles")
    return 0


if __name__ == "__main__":
    sys.exit(main())
