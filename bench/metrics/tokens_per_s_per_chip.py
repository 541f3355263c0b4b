"""LLM tokens trained per second per chip over the whole window.

An item's tokens are its media items (up to the row's cap) times the
connector's tokens per item, plus its text tokens (up to the cap), counted
from the items the traffic drew; padding never counts."""


def read(run: dict):
    w = run["window"]
    return sum(s["tokens"] for s in w["steps"]) / w["window_s"] / run["chips"]
