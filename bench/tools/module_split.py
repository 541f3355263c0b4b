"""Where a cell's step spends its device time, by module, phase and
attention, and what tracing costs; in one process on the chip.

    python bench/tools/module_split.py --workload ivl2.mixed --seed 7 \
        --seconds 10 --out split

Set-up and driven steps as ``bench/run.py``; then two windows of
``--seconds`` each on the same compiled step: one untraced, one under the
profiler.  The traced window is reduced by ``bench/trace_scopes.py``
against the step's optimized HLO.  Prints one JSON line: the steps a
second of both windows, ``scopes``, and per step the device milliseconds
of encoder, LLM, head, optimizer and attention with the scheduler's error
on encoder and LLM (its ``e_dur`` against the encoder's device time, its
``l_dur`` against LLM and head's).  ``--out`` keeps the ``.xplane.pb``,
the HLO and the line.  No reference and no check: ``bench/run.py`` does
those.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def module_metrics(result: dict, steps: list) -> dict:
    """Per-step device ms of each module and the scheduler's errors, from a
    ``trace_scopes.reduce`` result and the traced window's step records
    (each with ``pred_enc_s`` and ``pred_llm_s``)."""
    sc = result["scopes"]
    n = result["host_spans"].get("bench.step", {}).get("count", 0)
    if not n:
        return {}

    def total(*mods):
        return sum(sum(sc[m].values()) for m in mods)

    out = {f"{m}_device_ms": 1e3 * total(m) / n
           for m in ("encoder", "llm", "head", "optimizer", "attention")}
    pred_enc = sum(s["pred_enc_s"] for s in steps)
    pred_llm = sum(s["pred_llm_s"] for s in steps)
    if total("encoder"):
        out["encoder_pred_err"] = 100.0 * abs(pred_enc / total("encoder") - 1)
    if total("llm", "head"):
        out["llm_pred_err"] = 100.0 * abs(pred_llm / total("llm", "head") - 1)
    return out


def split(run, seconds: float, first_step: int, out_dir: Path | None,
          log) -> dict:
    """The two windows on a set-up, driven ``TrainRun``."""
    import jax

    from bench import trace_scopes

    preds = []
    schedule = run.ctl.schedule

    def recording_schedule(items):
        out = schedule(items)
        preds.append((float(out.e_dur.sum()), float(out.l_dur.sum())))
        return out

    run.ctl.schedule = recording_schedule
    plain = run.window(seconds, first_step)
    k = first_step + len(plain["steps"])
    n_plain = len(preds)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            traced = run.window(seconds, k)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        hlo = run.step.as_text()
        if out_dir is not None:
            out_dir.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[0], out_dir / "window.xplane.pb")
            (out_dir / "step.hlo.txt").write_text(hlo)
        result = trace_scopes.reduce_file(files[0], hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    steps = [{**s, "pred_enc_s": e, "pred_llm_s": l}
             for s, (e, l) in zip(traced["steps"], preds[n_plain:])]
    line = {
        "untraced": {"steps": len(plain["steps"]),
                     "window_s": plain["window_s"],
                     "steps_per_s": len(plain["steps"]) / plain["window_s"]},
        "traced": {"steps": len(traced["steps"]),
                   "window_s": traced["window_s"],
                   "steps_per_s": len(traced["steps"]) / traced["window_s"]},
        "metrics": module_metrics(result, steps),
        "scopes": result["scopes"],
        "device": {"busy_s": result["busy_s"],
                   "window_s": result["window_s"]},
        "idle_gaps": result["idle_gaps"],
        "host_spans": result["host_spans"],
        "device_ops": result["device_ops"],
    }
    for w in ("untraced", "traced"):
        log(f"[{w}] {line[w]['steps']} steps in {line[w]['window_s']:.3f} s")
    if out_dir is not None:
        (out_dir / "split.json").write_text(json.dumps(line, indent=1))
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)

    import jax

    from bench.harness.train_1chip import DRIVEN_STEPS, TrainRun
    from repro.common import compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("module_split: needs a TPU")
    compile_cache.enable()
    _, _, cfg, traffic, _ = bench_run.load_cell(ROOT, args.workload)
    run = TrainRun(cfg, traffic, args.seed)
    run.setup()
    run.drive(bench_run.log)
    line = split(run, args.seconds, DRIVEN_STEPS, args.out, bench_run.log)
    run.free()
    print(json.dumps({"workload": args.workload, "seed": args.seed, **line}),
          flush=True)


if __name__ == "__main__":
    main()
