"""Record the small chip trace that ``test_trace_reduce.py`` reads.

  python3 benchmarks/chip/tests/record_trace.py <out_dir>

On a TPU: inside a ``window`` span, 3 runs of a matmul program
(``jit_mm``) under ``drain`` spans, each followed by a 50 ms sleep under
a ``wait_arrival`` span, then 5 runs of an elementwise program
(``jit_scale``) under one ``admit`` span. Writes the ``.xplane.pb`` under
``out_dir``.
"""
import sys
import time

import jax
import jax.numpy as jnp

MM_RUNS, SCALE_RUNS, SLEEP_S = 3, 5, 0.05


def mm(a, b):
    return a @ b


def scale(x):
    return x * 2.0 + 1.0


def main(out_dir: str):
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU found")
    a = jnp.ones((4096, 4096), jnp.bfloat16)
    x = jnp.ones((8192, 8192), jnp.float32)
    f_mm, f_scale = jax.jit(mm), jax.jit(scale)
    f_mm(a, a).block_until_ready()
    f_scale(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(MM_RUNS):
            with jax.profiler.TraceAnnotation("drain"):
                f_mm(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("wait_arrival"):
                time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation("admit"):
            for _ in range(SCALE_RUNS):
                x = f_scale(x)
            x.block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
