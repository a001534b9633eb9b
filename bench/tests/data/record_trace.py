"""Records the small profiler trace that ``test_bench_xplane.py`` reads:
a jitted matmul run under the harness's span names and profiler options,
on whatever device JAX finds.

    python3 bench/tests/data/record_trace.py <out.xplane.pb>
"""
import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))]
from bench.harness import common  # noqa: E402


def main(out: str) -> None:
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        common.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.decode"):
                    f(x).block_until_ready()
                jnp.zeros(8).block_until_ready()
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(src[0], out)
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
