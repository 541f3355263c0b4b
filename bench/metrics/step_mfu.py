"""The whole step's share of the chips' peak bf16 rate: required FLOPs of
the window's steps (bench/flops.py) over window time, chips and peak
(percent)."""


def read(run: dict):
    w = run["window"]
    flops = sum(s["flops"] for s in w["steps"])
    return 100.0 * flops / (w["window_s"] * run["chips"]
                            * run["peak"]["bf16_flops_per_s"])
