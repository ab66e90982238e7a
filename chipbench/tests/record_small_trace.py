"""Records the small trace that `test_trace_reduce.py` reads. Run by hand on
the chip: three calls of a small jitted program with host sleeps between
them, so that busy time, idle gaps and per-name sums are known by design."""

import os
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/small_trace"


@jax.jit
def small_program(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) * 0.5
    return x


x = jnp.ones((1024, 1024), jnp.float32) * 0.01
small_program(x).block_until_ready()
jax.profiler.start_trace(out)
for _ in range(3):
    with jax.profiler.TraceAnnotation("chipbench.flush"):
        y = small_program(x)
        y.block_until_ready()
    time.sleep(0.02)
jax.profiler.stop_trace()
for root, _dirs, files in os.walk(out):
    for f in files:
        print(os.path.join(root, f), os.path.getsize(os.path.join(root, f)))
