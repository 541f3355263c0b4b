"""Record the small trace that ``bench/tests/test_trace_reduce.py`` reads.

    python bench/tools/record_trace.py bench/tests/data/small.xplane.pb

Three steps, each a host pause inside a ``bench.materialize`` span (the
device waits) and then a jitted chain of matrix products inside a
``bench.step`` span, all inside one ``bench.window`` span.  Run it on the
chip; the file it writes is committed.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

PAUSE_S = 0.05
STEPS = 3


def main() -> None:
    out = sys.argv[1]

    @jax.jit
    def work(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    work(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.materialize", step=k):
                time.sleep(PAUSE_S)
            with jax.profiler.TraceAnnotation("bench.step", step=k):
                x = work(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes, platform "
          f"{jax.devices()[0].platform}")


if __name__ == "__main__":
    main()
