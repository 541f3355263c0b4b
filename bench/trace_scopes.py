"""Device time of a traced train step by the program's module, phase and
attention, and host spans on the window's thread.

    python bench/trace_scopes.py <file.xplane.pb> <step.hlo.txt>

The device trace's op events carry the instruction (``%fusion.1211 = ...``)
but not the JAX name scope it came from.  The compiled step's optimized HLO
(``compiled.as_text()``, the second argument) keeps that scope in each
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
other executables in the window is ``other_modules``.  Times are seconds
per device inside the ``bench.window`` span, loops and calls left out as in
``bench/trace_reduce.py`` (their time is their body's).

Host spans are the benchmark's ``bench.`` spans and the program's
``dflop.`` spans (``repro.common.trace``); an idle gap of the device is
labelled with the innermost of them that holds its midpoint on the thread
that holds ``bench.window``, so the program's background threads never
label the training loop's gaps.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict

from bench import trace_reduce

MODULES = ("encoder", "connector", "llm", "head", "grad_accum", "optimizer")
PHASES = ("forward", "backward", "recompute")
PREFIXES = ("bench.", "dflop.")
_MODULE = re.compile(r"dflop\.(" + "|".join(MODULES) + r")\b")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


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


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


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
        if n == trace_reduce.WINDOW:
            return a, b, t
    raise ValueError(f"no {trace_reduce.WINDOW} span in the trace")


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
            if b <= w0 or a >= w1 or trace_reduce.CONTAINER.match(n):
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
            if t == thread and n != trace_reduce.WINDOW and a < w1 and b > w0]
    gaps = []
    for ops in devices.values():
        busy = trace_reduce._union([(max(a, w0), min(b, w1))
                                    for _, a, b in ops if a < w1 and b > w0])
        gaps += trace_reduce._minus([(w0, w1)], busy)
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
        if n != trace_reduce.WINDOW and a < w1 and b > w0:
            host[n][0] += 1
            host[n][1] += b - a
    return {n: {"count": c, "s": t} for n, (c, t) in host.items()}


def reduce(spans: list, devices: dict, modules: dict, hlo_text: str,
           top: int = 10) -> dict:
    """``trace_reduce.reduce``'s numbers with ``scopes`` added, and its
    ``idle_gaps`` and ``host_spans`` read as this module reads them."""
    base = trace_reduce.reduce([(n, a, b) for n, a, b, _ in spans
                                if n.startswith(trace_reduce.PREFIX)],
                               devices, top=top)
    name, table = hlo_scopes(hlo_text)
    return {**base, "scopes": scopes(spans, devices, modules, name, table),
            "idle_gaps": idle_gaps(spans, devices, top),
            "host_spans": host_spans(spans)}


def reduce_file(path: str, hlo_text: str, top: int = 10) -> dict:
    return reduce(*read_events(path), hlo_text, top=top)


if __name__ == "__main__":
    with open(sys.argv[2]) as f:
        print(json.dumps(reduce_file(sys.argv[1], f.read()), indent=1))
