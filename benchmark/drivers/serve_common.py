"""What the two serving drivers share: seeded weights, the model behind
the program's batcher and HTTP server, the load generator's process, the
window with its counters, samples and trace, and the comparison of what
was served with the plain reference once the window has closed."""
from __future__ import annotations

import gc
import json
import os
import random
import shutil
import subprocess
import sys
import time
import urllib.request

import jax

from harness import cells, counters, device, stats, trace as tracelib

sys.path.insert(0, os.path.join(cells.BENCH_DIR, "loadgen"))
import schedule  # noqa: E402  (the generator's own module, no JAX in it)

ROUTE = "lm"


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


class Served:
    """The system under test, set up once: weights from the seed, the
    program's model, batcher and HTTP server, every shape warmed."""

    def __init__(self, cell):
        cfg, wl = cell.config, cell.workload
        self.cell = cell
        key = device.seed_key(cell.seed)
        self.weights = jax.block_until_ready(
            cell.reference.make_weights(cfg, jax.random.fold_in(key, 1)))
        serving = dict(cfg["serving"],
                       prompt_buckets=wl["traffic"]["prompt_buckets"])
        self.server, self.batcher = cell.family.build_server(
            cfg, self.weights, ROUTE, serving)
        self.server.start()
        self.url = (f"http://127.0.0.1:{self.server.port}"
                    f"/v1/serving/{ROUTE}")
        # one real request in every prompt bucket: the batcher's own
        # warm-up leaves the admission of a bucket's first request to
        # compile a small conversion (seen inside half the windows of
        # PR 23's first sets), and nothing may compile in the window
        for bucket in wl["traffic"]["prompt_buckets"]:
            body = json.dumps({"tokens": [1] * bucket, "maxNewTokens": 2})
            req = urllib.request.Request(
                self.url, data=body.encode("utf-8"),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                resp.read()

    def stop(self) -> int:
        """Stop serving; returns the KV pages still held once every
        sequence had its chance to retire."""
        deadline = time.monotonic() + 10.0
        while self.batcher.pool.usedPages() and time.monotonic() < deadline:
            time.sleep(0.05)
        held = self.batcher.pool.usedPages()
        self.server.stop()
        self.batcher.shutdown()
        return held

    def free(self) -> None:
        """Drop the program's state (model, pool) and keep the weights,
        which the reference reads next."""
        self.batcher.pool.k = self.batcher.pool.v = None
        self.server = self.batcher = None
        gc.collect()


def sampled_gauges(workload: dict) -> dict:
    """The gauges that this cell's per-layer readers want sampled."""
    out = {}
    for m in cells.layer_metrics_for(workload):
        mod = cells.load_module("readers", m["reader"])
        if hasattr(mod, "gauges"):
            out.update(mod.gauges(m))
    return out


def window(served: Served, seconds: float, rate=None) -> dict:
    """Start the load generator, watch the window, collect what it saw."""
    cell = served.cell
    wl = cell.workload
    t = wl["traffic"]
    cmd = [sys.executable, os.path.join(cells.BENCH_DIR, "loadgen", "main.py"),
           "--url", served.url, "--workload-file", cell.workload_file,
           "--vocab", str(cell.config["vocab_size"]),
           "--seed", str(cell.seed), "--seconds", str(seconds)]
    if rate is not None:
        cmd += ["--rate", str(rate)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    try:
        t0 = json.loads(proc.stdout.readline())["t0"]
        w0 = t0 + t["ramp_s"]
        w1 = w0 + seconds
        sleep_until(w0)
        count = lambda: cell.compiles.snapshot()["compilations"]
        compiled = -count()
        before = counters.snapshot()
        sampler = counters.Sampler(sampled_gauges(wl)).start() \
            if cell.trace else None
        reduced = None
        if cell.trace:
            sleep_until(w0 + t["trace_offset_s"])
            log_dir = cell.trace_dir
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # the traced stretch is left out of the count of compilations:
            # on the TPU starting the profiler compiles one program of its
            # own (both traced runs of PR 23 counted exactly 1, the
            # untraced ones 0); the program's own counter of compile-cache
            # misses covers the whole window all the same
            compiled += count()
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    time.sleep(t["trace_seconds"])
            finally:
                jax.profiler.stop_trace()
            compiled -= count()
        sleep_until(w1)
        after = counters.snapshot()
        queued = served.batcher.queuedRows()
        compiled += count()
        samples = sampler.stop() if sampler else {}
        out, _ = proc.communicate(timeout=t["drain_s"] + t["timeout_s"] + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"the load generator exited {proc.returncode}")
    if cell.trace:
        path = tracelib.newest_xplane(log_dir)
        reduced = tracelib.reduce_trace(tracelib.Trace(path),
                                        wl["chips"])
        shutil.rmtree(log_dir, ignore_errors=True)
    results = json.loads(out.strip().splitlines()[-1])["results"]
    return {"t0": t0, "w0": w0 - t0, "w1": w1 - t0, "results": results,
            "before": before, "after": after, "samples": samples,
            "compilations": compiled, "queued_at_end": queued,
            "trace": reduced, "seconds": seconds}


def complete(r: dict, req: dict, vocab: int) -> bool:
    return (r["status"] == 200 and r["done"] and not r["error"]
            and len(r["tokens"]) == req["max_new"]
            and all(0 <= tok < vocab for tok in r["tokens"]))


def check_served(cell, served: Served, sched: dict, finished: list) -> list:
    """Once the window has closed and the program's state is freed: the
    requests the window finished (all of them, or ``check_requests`` drawn
    from the seed with the longest among them, where there are more) go
    through the reference, once over each prompt with its served tokens;
    the numbers compared are the widest and the mean gap by which a
    served token's reference logit lies below the reference's best."""
    cfg, ref = cell.config, cell.reference
    by_id = {q["id"]: q for q in sched["requests"]}
    k = cell.workload["traffic"]["check_requests"]
    picked = sorted(finished, key=lambda r: r["id"])
    if len(picked) > k:
        longest = max(picked, key=lambda r: len(by_id[r["id"]]["tokens"])
                      + len(r["tokens"]))
        rest = [r for r in picked if r is not longest]
        picked = [longest] + random.Random(cell.seed).sample(rest, k - 1)
    gaps, ctl = [], []
    t0 = time.monotonic()
    for r in picked:
        g = ref.served_gaps(cfg, served.weights, by_id[r["id"]]["tokens"],
                            r["tokens"], control=cell.control)
        gaps.extend(g["served"])
        ctl.extend(g.get("control", []))
    device.say(f"reference: {len(picked)} requests, {len(gaps)} served "
               f"tokens compared in {time.monotonic() - t0:.1f} s "
               "(not in setup_s)")
    if ctl:
        device.say(f"control: served_gap_max = {max(ctl):.6g}")
        device.say(f"control: served_gap_mean = {sum(ctl) / len(ctl):.6g}")
    lim = cfg["limits"]
    return [{"name": "served_gap_max", "value": max(gaps),
             "limit": lim["served_gap_max"]},
            {"name": "served_gap_mean", "value": sum(gaps) / len(gaps),
             "limit": lim["served_gap_mean"]}]


def run(cell, measure) -> dict:
    """One run of a serving cell.  ``measure(sched, win, vocab)`` returns
    ``(measurements, attempted, failed, finished results, lines)`` from
    what the load generator saw."""
    cfg, wl = cell.config, cell.workload
    t = wl["traffic"]
    vocab = cfg["vocab_size"]
    served = Served(cell)
    sched = schedule.build(t, vocab, cell.seed, cell.seconds)
    win = window(served, cell.seconds)
    setup_s = win["t0"] + win["w0"] - cell.t_start
    held = served.stop()
    peak = device.memory_peak_bytes(jax.devices()[:wl["chips"]])
    served.free()
    measurements, attempted, failed, finished, lines = measure(
        sched, win, vocab)
    for line in lines:
        device.say(line)
    measurements["setup_s"] = setup_s
    compared = check_served(cell, served, sched, finished) if finished \
        else [{"name": "finished_requests", "value": float("inf"),
               "limit": 0}]
    misses = counters.scalar(
        win["after"], "dl4j_tpu_serving_compile_cache_misses_total") or 0.0
    misses -= counters.scalar(
        win["before"], "dl4j_tpu_serving_compile_cache_misses_total") or 0.0
    compared += [
        {"name": "failed_requests", "value": failed, "limit": 0},
        {"name": "compilations_in_window", "value": win["compilations"],
         "limit": 0},
        {"name": "compile_cache_misses_in_window", "value": misses,
         "limit": 0},
        {"name": "kv_pages_held_at_end", "value": held, "limit": 0}]
    return {"measurements": measurements, "attempted": attempted,
            "failed": failed, "compared": compared,
            "memory_peak_bytes": peak,
            "window": {"seconds": win["seconds"], "before": win["before"],
                       "after": win["after"], "samples": win["samples"],
                       "queued_at_end": win["queued_at_end"]},
            "trace": win["trace"]}
