"""Readings that set a training cell's limits, in one process on the chip.

    python bench/tools/calibrate.py --workload ivl2.mixed --seeds 12 \
        --faults 3 --out readings.jsonl

For each seed: the program's driven steps against the reference that the
cell's configuration names (the lower readings).  For the first
``--faults`` seeds also the control, the reference computed with float8
products put in the program's place, and the fault "half the batch left
out", planted in the reference (the upper readings).  A step that returns
its state unchanged reads 1 on ``update_gap`` and ``grad_gap`` by
construction and needs no run.  One JSON line per seed; no measured window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def half_batches(batches):
    """Each microbatch's first half of the rows: the mean over the rest."""
    return [[mb[: max(1, len(mb) // 2)] for mb in step] for step in batches]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=10_000_000_019)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)

    import jax
    from bench.harness import check
    from bench.harness.train_1chip import TrainRun, reference_of
    from repro.common import compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    compile_cache.enable()
    _, _, cfg, traffic, _ = bench_run.load_cell(ROOT, args.workload)
    ref = reference_of(cfg)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    refs = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        tr = TrainRun(cfg, traffic, seed)
        tr.setup()
        prog = tr.drive(lambda m: print(m, file=sys.stderr, flush=True))
        tr.free()
        t1 = time.perf_counter()
        for p in ("highest", "fp8"):
            if p not in refs:
                refs[p] = ref.Reference(tr.m, tr.opt_cfg, p)
        batches = tr.ref_batches()
        base = refs["highest"].run(tr.key, batches, tr.init)
        t2 = time.perf_counter()
        rec = {"seed": seed, "program": check.readings(prog, base),
               "losses": prog["losses"], "ref_losses": base["losses"],
               "setup_and_driven_s": t1 - t0, "reference_s": t2 - t1}
        if i < args.faults:
            ctl = refs["fp8"].run(tr.key, batches, tr.init)
            rec["control"] = check.readings(ctl, base)
            half = refs["highest"].run(tr.key, half_batches(batches), tr.init)
            rec["half_batch"] = check.readings(half, base)
        rec["leaf_names"] = tr.leaf_names() if i == 0 else None
        print(json.dumps({k: v for k, v in rec.items() if k != "leaf_names"}),
              flush=True)
        with out.open("a") as f:
            f.write(json.dumps(rec) + "\n")
        del tr, batches


if __name__ == "__main__":
    main()
