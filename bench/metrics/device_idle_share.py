"""Share of the traced window in which no operation ran on the device,
averaged over the chips (percent)."""


def read(run: dict):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
