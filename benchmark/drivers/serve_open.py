"""Serving under an open loop: requests arrive on the seed's schedule
whether or not earlier ones have finished; the tails of time to first
token (from the instant a request was due) and of the gaps between
streamed tokens are what the window measures."""
from __future__ import annotations

import math

from harness import cells, stats

_common = cells.load_module("drivers", "serve_common")


def measure(sched: dict, win: dict, vocab: int):
    by_id = {q["id"]: q for q in sched["requests"]}
    inside = [r for r in win["results"]
              if win["w0"] <= r["due"] < win["w1"]]
    ttft, gaps, finished, late = [], [], [], []
    for r in inside:
        ok = _common.complete(r, by_id[r["id"]], vocab)
        # a failed or refused request counts as a miss of any limit
        ttft.append(r["t_tokens"][0] - r["due"] if ok else math.inf)
        if r["sent"] is not None:
            late.append(r["sent"] - r["due"])
        if ok:
            finished.append(r)
            tt = r["t_tokens"]
            gaps.extend(b - a for a, b in zip(tt, tt[1:]))
    failed = len(inside) - len(finished)
    m = {"ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
         "itl_p95_ms": 1e3 * stats.percentile(gaps, 95) if gaps
         else math.inf,
         "completed_per_s": len(finished) / win["seconds"]}
    lines = [
        f"window: {len(inside)} requests due, {len(finished)} complete, "
        f"{len(gaps)} gaps; queue at the end {win['queued_at_end']}",
        f"medians: ttft_p50_ms {1e3 * stats.percentile(ttft, 50):.2f}, "
        f"itl_p50_ms {1e3 * stats.percentile(gaps, 50) if gaps else 0:.2f}",
        f"generator lateness p95: "
        f"{1e3 * stats.percentile(late, 95) if late else 0:.3f} ms"]
    return m, len(inside), failed, finished, lines


def run(cell) -> dict:
    return _common.run(cell, measure)
