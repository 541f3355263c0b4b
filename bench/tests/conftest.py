import sys
from pathlib import Path

import jax

_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# CPU test programs stay out of the checkout's persistent compile cache,
# which the chip runs of the benchmark use.
jax.config.update("jax_enable_compilation_cache", False)
