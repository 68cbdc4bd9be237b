#!/usr/bin/env python3
"""Record the small trace the reduction's test reads
(benchmarks/tests/data/small.xplane.pb): three calls of one named program
of two matmuls and a tanh, on the chip, with the Python tracer off."""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out):
    @jax.jit
    def fixture_step(x, w):
        return jnp.tanh(x @ w) @ w

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    fixture_step(x, w).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_clock_sync"):
        pass
    for _ in range(3):
        fixture_step(x, w).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(path, out)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
