"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out_dir>

Run on one chip. It traces a 2048x2048 bf16 matmul program run three times
inside ``bench:decode`` spans, then 20 ms of host sleep inside a
``bench:store`` span, twice, all inside ``bench:round`` and ``bench:window``
spans; the ``.xplane.pb`` lands under ``<out_dir>/plugins/profile/``.
"""
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileOptions, TraceAnnotation


def main(out_dir: str) -> None:
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with TraceAnnotation("bench:window"):
        for _ in range(2):
            with TraceAnnotation("bench:round"):
                with TraceAnnotation("bench:decode"):
                    for _ in range(3):
                        f(x).block_until_ready()
                with TraceAnnotation("bench:store"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
