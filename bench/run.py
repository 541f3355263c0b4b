"""Benchmark of DFLOP's training path on TPU chips, driven by BENCHMARK.json.

    python bench/run.py --workload ivl2.mixed --seed 7 --seconds 40 --trace 0

One run of one cell (a configuration under a traffic mix) in one process.
Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, its configuration file and its traffic mix
(``bench/traffic/<traffic>.json``); the limits of its check are in
``bench/limits/<cell>.json``; each metric is read by
``bench/metrics/<metric>.py``; the configuration's ``driver`` names the
module under ``bench/harness/`` that runs it, its ``reference`` the plain
reference under ``bench/reference/`` that the run is checked against, and
its ``mesh``, where it has one, how the cell's chips are laid out.

With ``--trace 0`` the last line of stdout is the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The numbers compared for ``correct`` are printed last on stderr and
last in that line.  With no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402
from pathlib import Path                                    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# JAX's persistent compilation cache, at one fixed place inside the checkout
# (gitignored): the program's compile_cache.enable() takes it from here.
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"bench: {msg}")
    raise SystemExit(2)


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict, dict]:
    """(BENCHMARK.json, cell, configuration file, traffic mix, limits)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    return bench, cell, cfg, traffic, limits


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports, in BENCHMARK.json's order."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(entries: list[dict], record: dict) -> dict:
    out = {}
    for m in entries:
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(
            record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(root: Path, workload: str, *, seed: int, seconds: float,
            trace: bool, require_chip: bool = True) -> dict:
    """One run of ``workload``; returns the result line."""
    bench, cell, cfg, traffic, limits = load_cell(root, workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            fail(f"needs a TPU; JAX found {dev.platform!r}")
        if len(devices) < cell["chips"]:
            fail(f"{workload} needs {cell['chips']} chips, found "
                 f"{len(devices)}")
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
        if dev.device_kind not in peaks["devices"]:
            fail(f"no peaks for device kind {dev.device_kind!r}")
        peak = peaks["devices"][dev.device_kind]
    else:
        peak = {"bf16_flops_per_s": float("nan")}
    from repro.common import compile_cache
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    driver = importlib.import_module(f"bench.harness.{cfg['driver']}")
    res = driver.run_cell(cell, cfg, traffic, limits, seed=seed,
                          seconds=seconds, trace=trace, t_start=T_START,
                          log=log)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": read_metrics(metrics_of(bench, cell["name"], trace),
                                    {**res, "peak": peak}),
            "device": device}
    if trace:
        tr = res["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        log(f"[trace] devices {tr['devices']}, busy_s {tr['busy_s']!r}, "
            f"collective_s {tr['collective_s']!r}, collective_exposed_s "
            f"{tr['collective_exposed_s']!r} (each a device's mean)")
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    log(f"[check] correct {res['correct']}, failed steps {res['failed']}")
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        fail(f"the program is not in this checkout ({src / 'repro'})")
    sys.path[0:1] = [str(ROOT), str(src)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's own log files go under TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    line = execute(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
