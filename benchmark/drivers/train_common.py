"""What the two training drivers share: seeded weights and ring, the
first steps read for ``correct``, warm-up, the fenced window, the traced
window, and the reference's steps after the program's state is freed."""
from __future__ import annotations

import gc
import json
import math
import shutil
import time

import jax
import jax.numpy as jnp

from harness import counters, device, trace as tracelib


def _norms(ref, tree):
    return [float(v) for v in jax.jit(ref.leaf_norms)(tree)]


def over_chips(weights, batches, chips: int):
    """For the reference of a cell on several chips: the batches' rows
    spread over the chips and the weights on each, so that the plain
    float32 steps (whose BatchNorm needs the whole batch) hold a chip's
    share of the activations on each chip, as the program does."""
    if chips == 1:
        return weights, batches
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(jax.devices()[:chips], ("rows",))
    rows, whole = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
    return (jax.device_put(weights, whole),
            [tuple(jax.device_put(a, rows) for a in b) for b in batches])


def run(cell, make_step) -> dict:
    """One run of a training cell.  ``make_step(cell, net)`` returns the
    window's own call: ``step(dataset)`` runs one fused step."""
    cfg, wl, ref, fam = cell.config, cell.workload, cell.reference, cell.family
    t = wl["traffic"]
    chips = wl["chips"]
    key = device.seed_key(cell.seed)
    weights = ref.make_weights(cfg, jax.random.fold_in(key, 1))
    batches = ref.make_batches(cfg, jax.random.fold_in(key, 2), t["ring"],
                               t["batch"])
    net = fam.build(cfg, weights)
    step = make_step(cell, net)
    ring = [fam.dataset(x, y) for x, y in batches]
    fence = lambda: jax.block_until_ready(net.params_)

    # the first steps, through the window's own call and feed
    n_cmp = t["compare_steps"]
    got = {"losses": []}
    for i in range(n_cmp):
        step(ring[i % len(ring)])
        got["losses"].append(float(net.score()))
        if i == 0:
            first = fam.first_gradient(net, cfg, weights)
            got["grad_norms"] = _norms(ref, first)
            got["head_grad"] = jnp.copy(ref.head_leaf(first))
            got["batch_stats"] = jax.tree.map(
                jnp.copy, fam.batch_stats(net, cfg, weights))
            del first
    moved = jax.tree.map(jnp.subtract, fam.parameters(net, weights), weights)
    got["delta_norms"] = _norms(ref, moved)
    del moved
    for ds in ring[n_cmp:]:
        step(ds)
    fence()
    t0 = time.monotonic()
    for i in range(t["calibrate_steps"]):
        step(ring[i % len(ring)])
    fence()
    step_s = (time.monotonic() - t0) / t["calibrate_steps"]
    n = max(1, round(cell.seconds / step_s))
    device.say(f"set-up: first losses {got['losses']}, calibrated step "
               f"{step_s * 1e3:.2f} ms, window of {n} steps")

    setup_s = time.monotonic() - cell.t_start
    compiled0 = cell.compiles.snapshot()["compilations"]
    before = counters.snapshot()
    t0 = time.monotonic()
    for i in range(n):
        step(ring[i % len(ring)])
    fence()
    elapsed = time.monotonic() - t0
    after = counters.snapshot()
    compiled = cell.compiles.snapshot()["compilations"] - compiled0
    last = float(net.score())
    items = n * t["batch"]

    reduced = None
    if cell.trace:
        n_tr = max(2, round(t["trace_seconds"] / step_s))
        log_dir = cell.trace_dir
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                for i in range(n_tr):
                    with jax.profiler.TraceAnnotation("bench.enqueue_step"):
                        step(ring[i % len(ring)])
                with jax.profiler.TraceAnnotation("bench.wait_for_device"):
                    fence()
        finally:
            jax.profiler.stop_trace()
        path = tracelib.newest_xplane(log_dir)
        reduced = tracelib.reduce_trace(tracelib.Trace(path), chips)
        shutil.rmtree(log_dir, ignore_errors=True)

    peak = device.memory_peak_bytes(jax.devices()[:chips])
    device.say("memory: " + json.dumps(jax.devices()[0].memory_stats()))
    # the program's state goes before the reference's steps are made
    del net, step, ring
    gc.collect()
    t0 = time.monotonic()
    weights, batches = over_chips(weights, batches, chips)
    want = ref.train_steps(
        cfg, weights, [batches[i % len(batches)] for i in range(n_cmp)])
    ref_s = time.monotonic() - t0
    numbers = ref.compare(got, want)
    device.say("seen only: " + json.dumps(numbers.pop("seen_only")))
    if cell.control:
        ctl = ref.train_steps(
            cfg, weights, [batches[i % len(batches)] for i in range(n_cmp)],
            quant=True)
        ctl = ref.compare(ctl, want)
        device.say("control seen only: " + json.dumps(ctl.pop("seen_only")))
        for k, v in ctl.items():
            device.say(f"control: {k} = {v:.6g}")
    compared = [{"name": k, "value": v, "limit": cfg["limits"][k]}
                for k, v in numbers.items()]
    compared.append({"name": "compilations_in_window", "value": compiled,
                     "limit": 0})
    compared.append({"name": "last_loss_over_first",
                     "value": last / got["losses"][0]
                     if math.isfinite(last) else math.inf, "limit": 1.0})
    device.say(f"reference: losses {want['losses']} in {ref_s:.1f} s "
               "(not in setup_s)")
    return {"measurements": {"items_per_s": items / elapsed,
                             "setup_s": setup_s},
            "attempted": n, "failed": 0 if math.isfinite(last) else n,
            "compared": compared, "memory_peak_bytes": peak,
            "window": {"steps": n, "seconds": elapsed, "items": items,
                       "step_s_calibrated": step_s, "reference_s": ref_s,
                       "before": before, "after": after},
            "trace": reduced}
