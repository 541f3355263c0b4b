"""The scheduler's error: |sum of predicted step makespans / sum of measured
step times - 1| over the window (percent)."""


def read(run: dict):
    steps = run["window"]["steps"]
    pred = sum(s["pred_s"] for s in steps)
    meas = sum(s["step_s"] for s in steps)
    return 100.0 * abs(pred / meas - 1.0)
