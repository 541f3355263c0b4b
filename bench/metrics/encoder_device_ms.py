"""Mean device milliseconds a step in the encoder (``dflop.encoder``: forward,
backward and recomputation), from the traced window's scope reduction
(``bench/trace_reduce.py``)."""
from bench.trace_reduce import module_time


def read(run: dict):
    got = module_time(run.get("trace"), "encoder")
    return None if got is None else 1e3 * got[0] / got[1]
