"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its limit in ``bench/limits/<cell>.json``:

* ``loss_gap``: the largest gap, in nats, between the program's loss and the
  reference's over the driven steps;
* ``grad_gap``: by the worst leaf, the gap between the norm of the first
  step's gradient as the optimizer got it (clipped; the program's is read
  from AdamW's first moment) and the reference's;
* ``update_gap``: by the worst leaf, the gap between the norms of the
  weights' change over the driven steps.

A leaf's gap is ``|norm(program) - norm(reference)|`` over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off alone
under Adam and are left out of ``update_gap``.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
QUIET = 1e-3


def worst_leaf(got, want, keep=None) -> tuple[float, int]:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    idx = np.arange(len(want)) if keep is None else np.flatnonzero(keep)
    if len(idx) == 0:
        return math.nan, -1
    denom = np.maximum(want[idx], np.median(want[idx]))
    gaps = np.abs(got[idx] - want[idx]) / denom
    j = int(np.argmax(gaps))
    return float(gaps[j]), int(idx[j])


def readings(prog: dict, refr: dict) -> dict:
    """Each compared number, with the leaf that gave it."""
    g = np.asarray(refr["grad_norms"], np.float64)
    keep = g >= QUIET * np.median(g)
    grad, gl = worst_leaf(prog["grad_norms"], refr["grad_norms"])
    upd, ul = worst_leaf(prog["update_norms"], refr["update_norms"], keep)
    loss = max(abs(a - b) for a, b in zip(prog["losses"], refr["losses"]))
    return {"loss_gap": float(loss), "grad_gap": grad, "update_gap": upd,
            "grad_leaf": gl, "update_leaf": ul,
            "quiet_leaves": int((~keep).sum())}


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in the order of NUMBERS.  A
    number whose limit is null has no reading that separates a fault from
    sound runs and is not compared (see PERF.md)."""
    checks, ok = {}, True
    for name in NUMBERS:
        v, lim = numbers[name], limits[name]["limit"]
        if lim is None:
            continue
        ok &= math.isfinite(v) and v <= lim
        checks[name] = {"value": v, "limit": lim}
    return bool(ok), checks
