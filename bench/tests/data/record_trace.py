"""Record the small trace that bench/tests/test_bench_trace.py reads: a
`bench.window` annotation holding a `bench.device` matmul and a 10 ms
host sleep annotated `bench.host`, profiled as bench/harness.py profiles
a window.

    python3 bench/tests/data/record_trace.py <out_dir>

writes `<out_dir>/plugins/profile/<time>/<host>.xplane.pb`; run it on the
device whose trace the test should hold.
"""
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.harness import _profile_options  # noqa: E402


def main(out_dir):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir, profiler_options=_profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.device"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
