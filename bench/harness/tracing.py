"""The profiler around a traced run's window, and the reduction of its trace."""
from __future__ import annotations

import glob
import os
import shutil
import tempfile

import jax

from bench import trace_reduce

# A traced run's window: long enough for several steps of the slowest cell,
# short enough that the trace stays small.
TRACE_SECONDS = 10.0


class Tracer:
    def __init__(self, on: bool):
        self.on, self.result, self.dir = on, None, None

    def __enter__(self) -> "Tracer":
        if self.on:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc) -> None:
        if not self.on:
            return
        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                  recursive=True)
                if not files:
                    raise RuntimeError("the profiler wrote no trace")
                self.result = trace_reduce.reduce_file(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
