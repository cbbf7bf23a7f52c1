"""Serving under a closed loop: a fixed number of clients, each sending
its next request when the last has ended; the tokens that reached the
clients inside the window, per second, are what it measures.  Requests
still streaming when the window ends are cut, and are not failures."""
from __future__ import annotations

from harness import cells, stats

_common = cells.load_module("drivers", "serve_common")


def measure(sched: dict, win: dict, vocab: int):
    by_id = {q["id"]: q for q in sched["requests"]}
    w0, w1 = win["w0"], win["w1"]
    tokens = 0
    finished, failed, touched = [], 0, 0
    ttft, gaps = [], []
    for r in win["results"]:
        tt = r["t_tokens"]
        n_in = sum(1 for t in tt if w0 <= t < w1)
        tokens += n_in
        if not n_in and not (r["sent"] is not None and w0 <= r["sent"] < w1):
            continue
        touched += 1
        if _common.complete(r, by_id[r["id"]], vocab):
            if w0 <= tt[-1] < w1:
                finished.append(r)
        elif not r["cut"]:
            failed += 1
        if tt and w0 <= r["sent"]:
            ttft.append(tt[0] - r["sent"])
        gaps.extend(b - a for a, b in zip(tt, tt[1:]) if w0 <= b < w1)
    m = {"tokens_per_s": tokens / win["seconds"]}
    lines = [
        f"window: {touched} requests in it, {len(finished)} finished in it, "
        f"{tokens} tokens; queue at the end {win['queued_at_end']}",
        "recorded, not judged (the queue never empties): "
        f"ttft_p50_ms {1e3 * stats.percentile(ttft, 50) if ttft else 0:.2f}, "
        f"itl_p50_ms {1e3 * stats.percentile(gaps, 50) if gaps else 0:.2f}, "
        f"itl_p95_ms {1e3 * stats.percentile(gaps, 95) if gaps else 0:.2f}"]
    return m, touched, failed, finished, lines


def run(cell) -> dict:
    return _common.run(cell, measure)
