"""The scheduler's error on the LLM: |sum over the window's steps of its
predicted LLM time (``ScheduleOutput.l_dur``, head included) / the device
time of the LLM and head in the traced window - 1| (percent)."""
from bench.trace_reduce import module_time


def read(run: dict):
    got = module_time(run.get("trace"), "llm", "head")
    if got is None:
        return None
    pred = sum(s["pred_llm_s"] for s in run["window"]["steps"])
    return 100.0 * abs(pred / got[0] - 1.0)
