"""Record the small trace that ``selftest.py`` checks ``trace_reduce`` on.

Three calls of one small jitted program, 50 ms of sleep between them, under
the benchmark's own annotations. Run on the chip:

    python3 benchmark/record_trace.py <out.xplane.pb>
"""
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 1)[0])
import trace_reduce  # noqa: E402


def main(out: str) -> None:
    @jax.jit
    def tiny_step(x):
        return (x @ x).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    tiny_step(x).block_until_ready()
    d = tempfile.mkdtemp(dir=".")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:collect"):
                tiny_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:between_queries"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(d), out)
    shutil.rmtree(d)
    print(jax.devices()[0].device_kind, trace_reduce.reduce_trace(out))


if __name__ == "__main__":
    main(sys.argv[1])
