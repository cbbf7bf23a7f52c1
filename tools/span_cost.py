#!/usr/bin/env python
"""What the tracing seam costs on this host, in microseconds — by hand:

    python tools/span_cost.py            # on the chip's host: chiprun -- python tools/span_cost.py

Times ``telemetry.tracer().span(...)`` in a tight loop with NO profiler
session (the state every production process is in: the span's
``jax.profiler.TraceAnnotation`` is then a no-op in the runtime), bare
and with a histogram observation, then one decode-loop iteration's worth
of spans (``serving.loop.iteration`` around ``serving.loop.admit`` and
``serving.decode.step`` with its six phases, seven of the nine observed
into ``dl4j_tpu_serving_loop_phase_seconds``),
and the same inside a ``jax.profiler`` session; then what the decode loop's
drain clock (``ContinuousBatcher._starved`` / ``_fed``, ISSUE 36) adds to a
``_dispatch``-shaped iteration (the ``serving.loop.dispatch`` span with its
observation around one assignment): while the device holds work (one
clock read and one ``is_ready()`` that says no: every steady step), and
when every dispatch finds the device idle and books a stretch (the worst
case: the histogram's observation on top).  One JSON line.
"""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.telemetry import (SERVING_LOOP_PHASES, Tracer,
                                          serving_metrics)

N = 20000
N_DISPATCH = 10000


def per_call_us(fn, n=N) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def drain_clock_cost(tr, observe) -> dict:
    """Microseconds a ``_dispatch``-shaped iteration, without the drain
    clock and with it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.remote import ContinuousBatcher
    cb = ContinuousBatcher.__new__(ContinuousBatcher)
    cb.name, cb._queuedRows = "m", 0
    cb._busyAt, cb._drainedAt, cb._idleCause = 0.0, None, "loop"
    done = jax.block_until_ready(jnp.zeros((4, 1), jnp.int32))

    def without():
        with tr.span("serving.loop.dispatch", observe=observe):
            cb._given = done

    def with_it(given):
        def iteration():
            with tr.span("serving.loop.dispatch", observe=observe):
                cb._fed(cb._starved(), given)
        return iteration

    out = {"dispatch_shaped_us": per_call_us(without, N_DISPATCH)}
    # something the device is still busy with while the loop is timed
    # (small matmuls: one thread of the CPU's, so that the loop timed
    # here keeps a core of its own; a chip needs a hundred times as many
    # to stay busy for the seconds this takes)
    turns = 300_000 if jax.devices()[0].platform == "cpu" else 30_000_000
    long = jax.jit(lambda a: jax.lax.fori_loop(
        0, turns, lambda _i, x: jnp.tanh(x @ x), a))
    busy = long(jnp.full((64, 64), 0.01, jnp.float32))
    # the CPU "device" shares the host's cores with the loop timed here,
    # so the pair to compare is the one measured beside that work
    out["dispatch_shaped_device_busy_us"] = per_call_us(without, N_DISPATCH)
    cb._given = busy
    out["with_drain_clock_device_busy_us"] = per_call_us(
        with_it(busy), N_DISPATCH)
    out["device_stayed_busy"] = not busy.is_ready()
    jax.block_until_ready(busy)
    cb._given = done
    out["with_drain_clock_device_idle_us"] = per_call_us(
        with_it(done), N_DISPATCH)
    return out


def main() -> int:
    tr = Tracer(maxEvents=1000)
    hist = serving_metrics().loop_phase_seconds
    observers = {p: (lambda dt, p=p: hist().observe(dt, model="m", phase=p))
                 for p in SERVING_LOOP_PHASES}

    def bare():
        with tr.span("serving.loop.grow"):
            pass

    def observed():
        with tr.span("serving.loop.grow", observe=observers["grow"]):
            pass

    def iteration():
        with tr.span("serving.loop.iteration"):
            with tr.span("serving.loop.admit", observe=observers["admit"]):
                pass
            with tr.span("serving.decode.step", replica="m") as args:
                for p in SERVING_LOOP_PHASES[2:]:
                    with tr.span("serving.loop." + p, observe=observers[p]):
                        pass
                args["active"] = 4

    out = {"span_us": per_call_us(bare),
           "span_observed_us": per_call_us(observed),
           "loop_iteration_us": per_call_us(iteration, N // 8),
           "spans_per_iteration": len(SERVING_LOOP_PHASES) + 1}
    import jax
    log_dir = tempfile.mkdtemp(prefix="span_cost_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        out["span_us_in_session"] = per_call_us(bare, 2000)
        out["loop_iteration_us_in_session"] = per_call_us(iteration, 500)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(log_dir, ignore_errors=True)
    out.update(drain_clock_cost(tr, observers["dispatch"]))
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
