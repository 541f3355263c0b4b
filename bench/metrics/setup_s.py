"""Seconds from the start of the run to the first timed step: start-up,
weights, compile (from the cache after a checkout's first run) and the
driven steps."""


def read(run: dict):
    return run["setup_s"]
