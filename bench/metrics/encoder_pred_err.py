"""The scheduler's error on the encoder: |sum over the window's steps of
its predicted encoder time (``ScheduleOutput.e_dur``) / the encoder's
device time in the traced window - 1| (percent)."""
from bench.trace_reduce import module_time


def read(run: dict):
    got = module_time(run.get("trace"), "encoder")
    if got is None:
        return None
    pred = sum(s["pred_enc_s"] for s in run["window"]["steps"])
    return 100.0 * abs(pred / got[0] - 1.0)
