"""The training driver's module before it took a cell's chips and mesh:
re-exports ``to_desc`` from ``bench.harness.train`` for
``tests/test_tracing.py``, which imports it from here.  Delete it once
that test imports ``bench.harness.train``."""
from bench.harness.train import to_desc  # noqa: F401
