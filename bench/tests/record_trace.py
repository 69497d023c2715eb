"""Record the small CPU trace that ``test_trace_reduce.py`` reads.

    JAX_PLATFORMS=cpu python3 bench/tests/record_trace.py

Writes ``fixtures/spans.xplane.pb``: a ``bench_window`` span holding three
``loader_wait`` spans of ~20 ms and three ``dispatch`` spans of ~10 ms, and
one ``epoch_reorder`` span outside the window.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("epoch_reorder"):
        time.sleep(0.005)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("loader_wait"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("dispatch"):
                time.sleep(0.01)
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "fixtures", "spans.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
