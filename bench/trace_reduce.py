"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device numbers.

    python bench/trace_reduce.py <file.xplane.pb> [<step.hlo.txt>]

* the traced window: the host span named ``bench.window``;
* busy time of each device: the union of its op intervals ("XLA Ops" line)
  inside the window; idle is the rest of the window;
* the device ops that took most time, summed by instruction over the
  devices (loops and calls left out: their time is their body's);
* collective time and the part of it during which no other op runs on that
  device (exposed);
* idle gaps, each labelled with the innermost ``bench.`` or ``dflop.`` host
  span that holds its midpoint on the thread that holds ``bench.window``
  (the program's background threads never label the training loop's gaps):
  what the host was doing while the device waited;
* host span totals by name: the benchmark's ``bench.`` spans and the
  program's ``dflop.`` spans (``repro.common.trace``), on every thread;
* given the compiled step's optimized HLO (``compiled.as_text()``), the
  device time of the step by the program's module, phase and attention
  (``scopes``).

The device trace's op events carry the instruction (``%fusion.1211 = ...``)
but not the JAX name scope it came from.  The HLO keeps that scope in each
instruction's ``metadata={op_name=...}``, so instruction -> scope, read from
the same executable the trace ran, attributes every op of the step:

* module: the innermost ``dflop.<module>`` scope of the op's name stack
  (encoder, connector, llm, head, grad_accum, optimizer); none is
  ``unscoped`` (loop control, the scan's slicing, XLA's own copies);
* phase: ``recompute`` under ``rematted_computation`` (``jax.checkpoint``),
  else ``backward`` under ``transpose(``, else ``forward``;
* attention: ``dflop.attention`` anywhere in the name stack, a cut across
  encoder and LLM.

Only ops that run inside the step's executable (the "XLA Modules" line
names it after its ``HloModule``) are attributed; the device time of ops of
other executables in the window is ``other_modules``.

Host and device events share the profiler's clock.  Times are seconds per
device inside the window.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.window"
PREFIXES = ("bench.", "dflop.")
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all", re.I)
# control flow that holds other ops: its time is theirs
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s]")
MODULES = ("encoder", "connector", "llm", "head", "grad_accum", "optimizer")
PHASES = ("forward", "backward", "recompute")
_MODULE = re.compile(r"dflop\.(" + "|".join(MODULES) + r")\b")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[2,8192]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[2,8192]``: the instruction and its result type."""
    lhs, _, rhs = text.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}".strip()


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


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


# --------------------------------------------------------------------------- #
# the compiled step's HLO: instruction -> the program's scope
# --------------------------------------------------------------------------- #
def scope_of(op_name: str) -> tuple[str | None, str, bool]:
    """(module or None, phase, attention) of one name stack."""
    mods = _MODULE.findall(op_name)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return (mods[-1] if mods else None), phase, "dflop.attention" in op_name


def hlo_scopes(text: str) -> tuple[str, dict]:
    """(the HloModule's name, {instruction: (module, phase, attention)}).

    An instruction with no ``op_name`` of its own (a fusion XLA built from
    ops it inserted) takes the one of its fused computation's root, else
    of the first instruction there that has one."""
    name = text.split(None, 2)[1].rstrip(",") if text.startswith(
        "HloModule") else ""
    own, calls, comp_names = {}, {}, defaultdict(list)
    comp = None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            comp = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        instr = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            own[instr] = op.group(1)
            if line.lstrip().startswith("ROOT "):
                comp_names[comp].insert(0, op.group(1))
            else:
                comp_names[comp].append(op.group(1))
        else:
            c = _CALLS.search(line)
            if c:
                calls[instr] = c.group(1)
    for instr, called in calls.items():
        if comp_names.get(called):
            own[instr] = comp_names[called][0]
    return name, {i: scope_of(o) for i, o in own.items()}


# --------------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------------- #
def read_events(path: str) -> tuple[list, dict, dict]:
    """(host spans [(name, start_s, end_s, thread)],
    {device: ops [(name, s, e)]}, {device: executables [(name, s, e)]}).

    A thread is ``(plane, line index)``; spans are those named ``bench.``
    or ``dflop.``."""
    from jax.profiler import ProfileData

    def sec(ev):
        return ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9

    pd = ProfileData.from_file(path)
    spans, devices, modules = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.name, *sec(ev), (plane.name, i)))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = devices.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    evs = modules.setdefault(plane.name, [])
                else:
                    continue
                evs += [(ev.name, *sec(ev)) for ev in line.events]
    return spans, {d: o for d, o in devices.items() if o}, modules


def window_of(spans: list) -> tuple[float, float, object]:
    """(start, end, thread) of the first ``bench.window`` span."""
    for n, a, b, t in spans:
        if n == WINDOW:
            return a, b, t
    raise ValueError(f"no {WINDOW} span in the trace")


def scopes(spans: list, devices: dict, modules: dict, step_module: str,
           table: dict) -> dict:
    """Seconds per device in the window: per module and phase, attention
    by phase, ``unscoped``, ``other_modules``, and ``step_module_s``, the
    step executable's own time on the "XLA Modules" line."""
    w0, w1, _ = window_of(spans)
    per = {m: dict.fromkeys(PHASES, 0.0) for m in MODULES + ("attention",)}
    unscoped = other = step_s = 0.0
    for dev, ops in devices.items():
        runs = sorted((max(a, w0), min(b, w1))
                      for n, a, b in modules.get(dev, ())
                      if n.partition("(")[0] == step_module
                      and a < w1 and b > w0)
        step_s += sum(b - a for a, b in runs)
        starts = [a for a, _ in runs]
        for n, a, b in ops:
            if b <= w0 or a >= w1 or CONTAINER.match(n):
                continue
            a, b = max(a, w0), min(b, w1)
            j = bisect.bisect_right(starts, (a + b) / 2) - 1
            if j < 0 or (a + b) / 2 > runs[j][1]:
                other += b - a
                continue
            mod, phase, attn = table.get(instruction(n), (None, "forward",
                                                          False))
            if mod is None:
                unscoped += b - a
            else:
                per[mod][phase] += b - a
            if attn:
                per["attention"][phase] += b - a
    n_dev = max(len(devices), 1)
    out = {m: {p: t / n_dev for p, t in ph.items()} for m, ph in per.items()}
    out.update(unscoped=unscoped / n_dev, other_modules=other / n_dev,
               step_module_s=step_s / n_dev)
    return out


def idle_gaps(spans: list, devices: dict, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the devices in the window, each
    labelled with the innermost span on the window's thread that holds its
    midpoint (``none`` if no span does)."""
    w0, w1, thread = window_of(spans)
    mine = [(n, a, b) for n, a, b, t in spans
            if t == thread and n != WINDOW and a < w1 and b > w0]
    gaps = []
    for ops in devices.values():
        busy = _union([(max(a, w0), min(b, w1))
                       for _, a, b in ops if a < w1 and b > w0])
        gaps += _minus([(w0, w1)], busy)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        holding = [(e - s, n) for n, s, e in mine if s <= mid <= e]
        out.append([min(holding)[1] if holding else "none", b - a])
    return out


def host_spans(spans: list) -> dict:
    """Count and seconds of each ``bench.`` and ``dflop.`` span name inside
    the window, on every thread."""
    w0, w1, _ = window_of(spans)
    host: dict = defaultdict(lambda: [0, 0.0])
    for n, a, b, _ in spans:
        if n != WINDOW and a < w1 and b > w0:
            host[n][0] += 1
            host[n][1] += b - a
    return {n: {"count": c, "s": t} for n, (c, t) in host.items()}


def module_time(result: dict | None, *modules: str):
    """(device seconds of the program's scopes ``modules``, all phases, in
    the window; the window's ``bench.step`` spans) from a reduction with
    ``scopes``; None without scopes, a step or any time in those scopes."""
    if not result or "scopes" not in result:
        return None
    steps = result["host_spans"].get("bench.step", {}).get("count", 0)
    total = sum(sum(result["scopes"][m].values()) for m in modules)
    return (total, steps) if steps and total else None


def reduce(spans: list, devices: dict, modules: dict | None = None,
           hlo_text: str | None = None, top: int = 10) -> dict:
    """The window's device numbers; with the step's HLO also ``scopes``."""
    w0, w1, _ = window_of(spans)
    by_op: dict = defaultdict(float)
    busy, coll, exposed = [], [], []
    for ops in devices.values():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if a < w1 and b > w0]
        for n, a, b in clipped:
            if not CONTAINER.match(n):
                by_op[op_name(n)] += b - a
        busy.append(_length(_union([(a, b) for _, a, b in clipped])))
        is_coll = [bool(COLLECTIVE.search(n.partition(" = ")[0]))
                   for n, _, _ in clipped]
        c = _union([(a, b) for (_, a, b), k in zip(clipped, is_coll) if k])
        other = [(a, b) for (_, a, b), k in zip(clipped, is_coll) if not k]
        coll.append(_length(c))
        exposed.append(_length(_minus(c, other)))
    n_dev = max(len(devices), 1)
    out = {
        "window_s": w1 - w0,
        "devices": len(devices),
        "busy_s": sum(busy) / n_dev,
        "collective_s": sum(coll) / n_dev,
        "collective_exposed_s": sum(exposed) / n_dev,
        "device_ops": sorted(([n, t / n_dev] for n, t in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": idle_gaps(spans, devices, top),
        "host_spans": host_spans(spans),
    }
    if hlo_text is not None:
        name, table = hlo_scopes(hlo_text)
        out["scopes"] = scopes(spans, devices, modules or {}, name, table)
    return out


def reduce_file(path: str, hlo_text: str | None = None,
                top: int = 10) -> dict:
    return reduce(*read_events(path), hlo_text, top=top)


def main() -> None:
    hlo = Path(sys.argv[2]).read_text() if len(sys.argv) > 2 else None
    print(json.dumps(reduce_file(sys.argv[1], hlo), indent=1))


if __name__ == "__main__":
    main()
