"""BENCHMARK.json and the files it names; a cell added by data alone."""
from __future__ import annotations

import collections
import importlib
import json
import re

import pytest

from bench import run as bench_run
from bench.harness.traffic import Traffic
from bench.tests import tiny

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_resolves_to_a_file():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        cfg = json.loads((REPO / configs[cell["config"]]["file"]).read_text())
        assert (REPO / "bench/harness" / f"{cfg['driver']}.py").is_file()
        assert (REPO / "bench/traffic" / f"{cell['traffic']}.json").is_file()
        limits = json.loads(
            (REPO / "bench/limits" / f"{cell['name']}.json").read_text())
        assert all(limits[n]["limit"] > 0
                   for n in ("loss_gap", "grad_gap", "update_gap"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)


def test_configs_list_what_they_cut():
    """Each key in ``reduced`` is changed in the file, and no other size
    differs from the program's configuration, but for the keys the file
    lists under ``program_departs``, which hold the published value."""
    import dataclasses
    from repro.configs import get_config
    arch = {"internvl2-2b-1chip": "internvl2-2b",
            "qwen2-audio-7b-1chip": "qwen2-audio-7b"}
    entries = {e["file"]: e for e in BENCH["configs"]}
    for path in sorted((REPO / "bench/configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        entry = entries.get(str(path.relative_to(REPO)), cfg)
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        pub = dataclasses.asdict(get_config(arch[cfg["name"]]).desc)
        for part in ("encoder", "llm"):
            for key, value in pub[part].items():
                if key == "name":
                    continue
                here = cfg["model"][part][key]
                if f"{part}.{key}" in cfg["reduced"]:
                    assert here < value
                else:
                    assert here == (list(value) if isinstance(value, tuple)
                                    else value), (cfg["name"], part, key)
        departs = cfg.get("program_departs", {})
        assert cfg["model"]["stub"] == pub["stub"]
        for key in ("connector_hidden", "tokens_per_item_out"):
            if key in departs:
                assert departs[key]["program"] == pub[key]
                assert cfg["model"][key] == departs[key]["published"]
            else:
                assert cfg["model"][key] == pub[key], (cfg["name"], key)


def test_traffic_orders_one_pool_per_seed():
    """Every seed trains the same pool, in its own order, and each batch of
    the pool holds near the same trained tokens."""
    from bench.harness.traffic import POOL_STEPS
    spec = json.loads((REPO / "bench/traffic/mixed.json").read_text())
    a, b = Traffic(spec, 2 ** 40 + 1, 256), Traffic(spec, 7, 256)

    def sizes(t, first):
        return collections.Counter(
            (it.n_media, it.text_len) for k in range(first, first + POOL_STEPS)
            for it in t.step_items(k))

    assert sizes(a, 0) == sizes(b, 0) == sizes(a, POOL_STEPS)
    assert [it.text_len for it in a.step_items(0)] != \
        [it.text_len for it in b.step_items(0)]
    assert a.step_items(3) == Traffic(spec, 2 ** 40 + 1, 256).step_items(3)
    load = [sum(a.tokens(*a.pool[i][:2], 256) for i in batch)
            for batch in a.batches]
    assert max(load) / min(load) < 1.02


@pytest.mark.parametrize("mix", ["mixed", "audio", "image"])
def test_traffic_draws_as_the_program_samples(mix):
    """The pool is the draw the program's ``MixedDataset.sample`` makes
    from the same seed."""
    import numpy as np
    from bench.harness.traffic import POOL_SEED, sample
    from repro.data.synthetic import MixedDataset
    spec = json.loads((REPO / f"bench/traffic/{mix}.json").read_text())
    got = sample(spec, 300, np.random.default_rng(POOL_SEED))
    want = MixedDataset(dict(spec["mixture"]), seed=POOL_SEED).sample(300)
    assert got == [(d.n_media_items, d.text_len, d.modality) for d in want]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_defined_by_data_alone(tmp_path, trace):
    """A new configuration, traffic mix and cell are files and entries
    only; the harness finds them by name and reports the metrics that
    BENCHMARK.json lists for the cell."""
    name = tiny.write_checkout(tmp_path)
    line = bench_run.execute(tmp_path, name, seed=99, seconds=0.5,
                             trace=trace, require_chip=False)
    group = BENCH["per_layer" if trace else "end_to_end"]
    expect = {m["name"] for m in group}
    if trace:
        # the CPU has no device plane and no peak: those readers say nothing
        expect -= {"device_idle_share"}
        assert "breakdown" in line and line["device"]["window_s"] > 0
    got = {k for k, v in line["metrics"].items() if v["value"] == v["value"]}
    assert got | {"step_mfu"} >= expect
    assert line["correct"] and list(line)[-1] == "checks"
