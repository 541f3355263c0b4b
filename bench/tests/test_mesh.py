"""A cell's chips and mesh as data: the tiny cell on a (data=2, model=2)
mesh of four forced CPU devices, through ``bench/run.py``.

XLA fixes the device count when JAX starts, and the rest of the suite keeps
one device, so the four-device run is a subprocess.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.tests import tiny

MESH = {"data": 2, "model": 2}

SCRIPT = r'''
import json, sys
from pathlib import Path

import jax
import numpy as np

jax.config.update("jax_enable_compilation_cache", False)

from bench import run as bench_run
from bench.harness import check, train
from bench.tests import tiny

root, mesh = Path(sys.argv[1]), json.loads(sys.argv[2])
chips = mesh["data"] * mesh["model"]
name = tiny.write_checkout(root, config={**tiny.CONFIG, "mesh": mesh},
                           chips=chips)
runs, fed = [], {}
real_setup, real_drive, real_feed = (train.TrainRun.setup,
                                     train.TrainRun.drive,
                                     train.TrainRun.feed)


def setup(self):
    real_setup(self)
    runs.append(self)


def feed(self, k):
    out = real_feed(self, k)
    if k < train.DRIVEN_STEPS:
        fed[k] = out[1:]
    return out


def drive(self, log):
    self.readings = real_drive(self, log)
    return self.readings


def layout(tree):
    """(devices each leaf spans, bytes on the first device / all bytes)."""
    leaves = jax.tree.leaves(tree)
    spans = sorted({len(x.sharding.device_set) for x in leaves})
    dev0 = jax.devices()[0]
    here = sum(s.data.nbytes for x in leaves for s in x.addressable_shards
               if s.device == dev0)
    return spans, here / sum(x.nbytes for x in leaves)


train.TrainRun.setup, train.TrainRun.drive, train.TrainRun.feed = (
    setup, drive, feed)
state = {}
real_free = train.TrainRun.free


def free(self):
    if self.mesh_shape:
        state.update(params=layout(self.params), m=layout(self.opt["m"]),
                     v=layout(self.opt["v"]))
    real_free(self)


train.TrainRun.free = free
line = bench_run.execute(root, name, seed=2 ** 35 + 3, seconds=0.5,
                         trace=False, require_chip=False)
run = runs[0]
prog = run.readings

# the same three batches through the program on one device
one = train.TrainRun(tiny.CONFIG, tiny.TRAFFIC, run.seed, 1)
one.setup()
one.feed = lambda k: (jax.device_put(fed[k][0], one.dev), *fed[k])
base = one.drive(lambda m: None)
one.free()
print(json.dumps({"line": line, "state": state,
                  "gaps": check.readings(prog, base),
                  "losses": prog["losses"], "one_chip": base["losses"],
                  "devices": len(run.devices)}))
'''


def test_tiny_cell_on_a_2x2_mesh(tmp_path):
    """Through ``bench/run.py`` on four forced devices the tiny cell on a
    (data=2, model=2) mesh reads ``correct``; the program's weights and
    optimizer state are split over the four devices; its driven steps read
    as the one-device program's on the same batches, within the tiny
    cell's limits."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.REPO),
                                           str(tiny.REPO / "src")]))
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path),
                        json.dumps(MESH)], capture_output=True, text=True,
                       env=env, timeout=600, cwd=tiny.REPO)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-8000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["device"]["count"] == 4
    assert out["devices"] == 4
    for part in ("params", "m", "v"):
        spans, share = out["state"][part]
        assert spans == [4], (part, spans)
        # replicated state would hold all of it on every device
        assert share < 0.5, (part, share)
    assert out["state"]["m"][1] < out["state"]["params"][1]   # ZeRO
    for number in ("loss_gap", "grad_gap", "update_gap"):
        assert out["gaps"][number] <= tiny.LIMITS[number]["limit"], (
            number, out["gaps"], out["losses"], out["one_chip"])


@pytest.mark.parametrize("chips,mesh", [(1, MESH), (4, None),
                                        (4, {"data": 4, "model": 2})])
def test_mesh_not_the_cells_chips_stops_the_run(tmp_path, chips, mesh):
    """A ``mesh`` that does not multiply out to the cell's ``chips`` (a
    file without one is one chip) stops the run before set-up and names
    both numbers."""
    config = {**tiny.CONFIG, "mesh": mesh} if mesh else tiny.CONFIG
    name = tiny.write_checkout(tmp_path, config=config, chips=chips)
    product = mesh["data"] * mesh["model"] if mesh else 1
    with pytest.raises(ValueError, match=f"makes {product} chips, but the "
                                         f"cell asks for {chips}"):
        bench_run.execute(tmp_path, name, seed=5, seconds=0.5, trace=False,
                          require_chip=False)
