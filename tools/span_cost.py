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
and the same inside a ``jax.profiler`` session.  One JSON line.
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


def per_call_us(fn, n=N) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


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
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
