"""Record the small profiler trace ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py OUT_DIR     # on the chip

A ``bench.window`` span around five steps; each step is a jitted matmul
under ``bench.ce_step`` followed by 5 ms of host work under
``bench.stamp``, so the device is idle in the stamp spans.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.ce_step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.stamp"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
