"""Mean device milliseconds a step in attention (``dflop.attention``, encoder
and LLM together: a cut across modules), from the traced window's scope
reduction (``bench/trace_reduce.py``)."""
from bench.trace_reduce import module_time


def read(run: dict):
    got = module_time(run.get("trace"), "attention")
    return None if got is None else 1e3 * got[0] / got[1]
