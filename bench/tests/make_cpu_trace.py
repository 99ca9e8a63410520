"""Record ``data/cpu_trace.xplane.pb``, the small CPU trace that
``test_trace_reduce.py`` reads: three calls of a jitted matmul inside a
``bench.window`` span, each call in a ``bench.call`` span with a
``bench.ledger`` span of host work before it.

    JAX_PLATFORMS=cpu python3 bench/tests/make_cpu_trace.py
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "cpu_trace.xplane.pb")


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    with jax.profiler.TraceAnnotation("bench.ledger"):
                        time.sleep(0.002)
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, OUT)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
