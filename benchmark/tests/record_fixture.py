"""Records the small ``.xplane.pb`` the trace tests read (run once on the
chip, PR 23; kept so that the fixture can be made again):

    chiprun -- python benchmark/tests/record_fixture.py

Two tiny named programs, a gap between them and a host annotation, a few
milliseconds in all; written to ``chiprun_out/fixture.xplane.pb``.
"""
import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp


def main():
    x = jnp.ones((512, 512), jnp.bfloat16)

    def step(x):
        return jnp.tanh(x @ x)

    def other(x):
        return x + 1

    step_j, other_j = jax.jit(step), jax.jit(other)
    jax.block_until_ready((step_j(x), other_j(x)))
    log = "chiprun_out/fixture_trace"
    shutil.rmtree(log, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.enqueue_step"):
                y = step_j(x)
        jax.block_until_ready(y)
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.002)
        jax.block_until_ready(other_j(x))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(log + "/plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(path, "chiprun_out/fixture.xplane.pb")
    shutil.rmtree(log, ignore_errors=True)
    print(os.path.getsize("chiprun_out/fixture.xplane.pb"), "bytes")


if __name__ == "__main__":
    main()
