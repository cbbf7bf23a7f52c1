#!/usr/bin/env python
"""What the tracing seam costs on this host, in microseconds — by hand:

    python tools/span_cost.py            # on the chip's host: chiprun -- python tools/span_cost.py

Times ``telemetry.tracer().span(...)`` in a tight loop with NO profiler
session (the state every production process is in: the span's
``jax.profiler.TraceAnnotation`` is then a no-op in the runtime), bare,
with one histogram observation (a loop phase as it was before ISSUE 50)
and as the decode loop enters a phase now (``ContinuousBatcher._phase``:
the thread's CPU clock read beside the wall clock, two observations),
then one decode-loop iteration's worth of spans
(``serving.loop.iteration`` around ``serving.loop.admit`` and
``serving.decode.step`` with its six phases, seven of the nine phases of
the loop),
and the same inside a ``jax.profiler`` session; then what the decode loop's
drain clock (``ContinuousBatcher._starved`` / ``_fed``, ISSUE 36) adds to a
``_dispatch``-shaped iteration (the ``serving.loop.dispatch`` span with its
observation around one assignment): while the device holds work (one
clock read and one ``is_ready()`` that says no: every steady step), and
when every dispatch finds the device idle and books a stretch (the worst
case: the histogram's observation on top).  One JSON line.
"""
import functools
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.remote import scheduler
from deeplearning4j_tpu.telemetry import (SERVING_LOOP_PHASES, Tracer,
                                          serving_metrics, set_tracer)

N = 20000
N_DISPATCH = 10000


def per_call_us(fn, n=N) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def loop_thread():
    """As much of a batcher as a phase and the drain clock touch."""
    cb = scheduler.ContinuousBatcher.__new__(scheduler.ContinuousBatcher)
    cb.name, cb._queuedRows = "m", 0
    cb._busyAt, cb._drainedAt, cb._idleCause = 0.0, None, "loop"
    cb._clock, cb._phaseHeld = scheduler._LoopClock(), {}
    cb._phaseObservers = {p: functools.partial(cb._observePhase, p)
                          for p in SERVING_LOOP_PHASES}
    return cb


def drain_clock_cost(cb) -> dict:
    """Microseconds a ``_dispatch``-shaped iteration, without the drain
    clock and with it."""
    import jax
    import jax.numpy as jnp

    done = jax.block_until_ready(jnp.zeros((4, 1), jnp.int32))

    def without():
        with cb._phase("dispatch"):
            cb._given = done

    def with_it(given):
        def iteration():
            with cb._phase("dispatch"):
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
    prev = set_tracer(tr)       # the loop's phases go through tracer()
    hist = serving_metrics().loop_phase_seconds
    cb = loop_thread()

    def bare():
        with tr.span("serving.loop.grow"):
            pass

    def observed_wall_only():
        with tr.span("serving.loop.grow", observe=lambda dt: hist().observe(
                dt, model="m", phase="grow")):
            pass

    def phase():
        with cb._phase("grow"):
            pass

    def iteration():
        with tr.span("serving.loop.iteration"):
            with cb._phase("admit"):
                pass
            with tr.span("serving.decode.step", replica="m") as args:
                for p in SERVING_LOOP_PHASES[2:]:
                    with cb._phase(p):
                        pass
                args["active"] = 4

    # an iteration that reads the CPU clock, and one that does not: the
    # loop reads it in one of ``cpu_clock_every`` (what a read costs here)
    out = {"span_us": per_call_us(bare),
           "span_observed_wall_only_us": per_call_us(observed_wall_only),
           "cpu_clock_read_us": scheduler._cpu_clock_read_seconds() * 1e6,
           "cpu_clock_every": cb._clock.every}
    for cb._clock.on, key in ((True, ""), (False, "_clock_unread")):
        out["span_observed" + key + "_us"] = per_call_us(phase)
        out["loop_iteration" + key + "_us"] = per_call_us(iteration, N // 8)
    out["loop_iteration_mean_us"] = (
        out["loop_iteration_us"] + (cb._clock.every - 1)
        * out["loop_iteration_clock_unread_us"]) / cb._clock.every
    cb._clock.on = True
    out["spans_per_iteration"] = len(SERVING_LOOP_PHASES) + 1
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
    out.update(drain_clock_cost(cb))
    out["platform"] = jax.devices()[0].platform
    set_tracer(prev)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
