"""Padding slots over all slots, encoder patches and text positions, of
every batch fed in the window (percent)."""


def read(run: dict):
    steps = run["window"]["steps"]
    slots = sum(s["media_slots"] + s["text_slots"] for s in steps)
    real = sum(s["media_real"] + s["text_real"] for s in steps)
    return 100.0 * (slots - real) / slots
