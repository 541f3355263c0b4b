"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device numbers.

    python bench/trace_reduce.py <file.xplane.pb>

* the traced window: the host span named ``bench.window``;
* busy time of each device: the union of its op intervals ("XLA Ops" line)
  inside the window; idle is the rest of the window;
* the device ops that took most time, summed by instruction over the
  devices (loops and calls left out: their time is their body's);
* collective time and the part of it during which no other op runs on that
  device (exposed);
* idle gaps, each labelled with the innermost ``bench.`` host span that
  holds its midpoint: what the host was doing while the device waited;
* host span totals by name.

Host and device events share the profiler's clock.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

WINDOW = "bench.window"
PREFIX = "bench."
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all", re.I)
# control flow that holds other ops: its time is theirs
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s]")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[2,8192]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[2,8192]``: the instruction and its result type."""
    lhs, _, rhs = text.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}".strip()


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _minus(iv, cut) -> list[tuple[float, float]]:
    """Parts of the (unioned) intervals ``iv`` not covered by ``cut``."""
    out, j = [], 0
    cut = _union(cut)
    for a, b in iv:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def read_events(path: str) -> tuple[list, dict]:
    """(host spans [(name, start_s, end_s)], {device: [(name, s, e)]})."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            if ops:
                devices[plane.name] = ops
    return spans, devices


def reduce(spans: list, devices: dict, top: int = 10) -> dict:
    wins = [(a, b) for n, a, b in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = wins[0]
    window_s = w1 - w0
    inner = [(n, a, b) for n, a, b in spans if n != WINDOW and a < w1 and b > w0]
    by_op: dict = defaultdict(float)
    busy, coll, exposed, gaps = [], [], [], []
    for ops in devices.values():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if a < w1 and b > w0]
        for n, a, b in clipped:
            if not CONTAINER.match(n):
                by_op[op_name(n)] += b - a
        u = _union([(a, b) for _, a, b in clipped])
        busy.append(_length(u))
        is_coll = [bool(COLLECTIVE.search(n.partition(" = ")[0]))
                   for n, _, _ in clipped]
        c = _union([(a, b) for (_, a, b), k in zip(clipped, is_coll) if k])
        other = [(a, b) for (_, a, b), k in zip(clipped, is_coll) if not k]
        coll.append(_length(c))
        exposed.append(_length(_minus(c, other)))
        gaps += _minus([(w0, w1)], u)
    n_dev = max(len(devices), 1)
    label = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        holding = [(e - s, n) for n, s, e in inner if s <= mid <= e]
        label.append([min(holding)[1] if holding else "none", b - a])
    host: dict = defaultdict(lambda: [0, 0.0])
    for n, a, b in inner:
        host[n][0] += 1
        host[n][1] += b - a
    return {
        "window_s": window_s,
        "devices": len(devices),
        "busy_s": sum(busy) / n_dev,
        "collective_s": sum(coll) / n_dev,
        "collective_exposed_s": sum(exposed) / n_dev,
        "device_ops": sorted(([n, t / n_dev] for n, t in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": label,
        "host_spans": {n: {"count": c, "s": t} for n, (c, t) in host.items()},
    }


def reduce_file(path: str, top: int = 10) -> dict:
    return reduce(*read_events(path), top=top)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
