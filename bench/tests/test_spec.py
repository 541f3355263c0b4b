"""BENCHMARK.json and the files it names; a cell added by data alone."""
from __future__ import annotations

import collections
import dataclasses
import importlib
import json
import re
import sys

import pytest

import bench.reference
from bench import run as bench_run
from bench.harness.train import REFERENCE_API, reference_of, to_desc
from bench.harness.traffic import Traffic
from bench.tests import tiny

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CONFIG_FILES = sorted((REPO / "bench/configs").glob("*.json"))


def test_every_name_resolves_to_a_file():
    """Each cell's configuration, driver, reference (with the API the
    harness calls), registered architecture, traffic and limits; each
    metric's reader.  A configuration's ``mesh``, where present, multiplies
    out to the cell's ``chips``; without one the cell is one chip."""
    from repro.configs import get_config
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        cfg = json.loads((REPO / configs[cell["config"]]["file"]).read_text())
        assert (REPO / "bench/harness" / f"{cfg['driver']}.py").is_file()
        mesh = cfg.get("mesh", {"data": 1, "model": 1})
        assert mesh["data"] * mesh["model"] == cell["chips"], cell["name"]
        ref = reference_of(cfg)
        assert all(callable(getattr(ref, n)) for n in REFERENCE_API)
        assert get_config(cfg["arch"]).desc.name == cfg["arch"]
        assert (REPO / "bench/traffic" / f"{cell['traffic']}.json").is_file()
        limits = json.loads(
            (REPO / "bench/limits" / f"{cell['name']}.json").read_text())
        assert all(limits[n]["limit"] > 0
                   for n in ("loss_gap", "grad_gap", "update_gap"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)


def _fields(d: dict, at: str = ""):
    """(dotted key, value) of every field of an ``asdict`` tree."""
    for key, value in d.items():
        if isinstance(value, dict):
            yield from _fields(value, f"{at}{key}.")
        else:
            yield f"{at}{key}", value


def test_configs_list_what_they_cut():
    """Each key in ``reduced`` is cut in the file, and no other field of
    the registered architecture's ``MLLMConfig`` (``arch``) differs from the
    program's configuration as the file builds it, but for the keys the
    file lists under ``program_departs``, which hold the published value.
    ``name`` is the file's own."""
    from repro.configs import get_config
    entries = {e["file"]: e for e in BENCH["configs"]}
    assert CONFIG_FILES
    for path in CONFIG_FILES:
        cfg = json.loads(path.read_text())
        entry = entries.get(str(path.relative_to(REPO)), cfg)
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        pub = dict(_fields(dataclasses.asdict(get_config(cfg["arch"]).desc)))
        here = dict(_fields(dataclasses.asdict(to_desc(cfg["model"]))))
        departs = cfg.get("program_departs", {})
        assert set(here) == set(pub)
        for key, value in pub.items():
            if key == "name":
                continue
            if key in cfg["reduced"]:
                assert here[key] < value, key
            elif key in departs:
                assert departs[key]["program"] == value
                assert here[key] == departs[key]["published"], key
            else:
                assert here[key] == value, (cfg["name"], key)


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_to_desc_is_the_registered_config_cut(path):
    """The file's ``model`` builds the registered description with the
    ``reduced`` keys replaced, and nothing else."""
    from repro.configs import get_config
    cfg = json.loads(path.read_text())
    want = dataclasses.replace(get_config(cfg["arch"]).desc,
                               name=cfg["model"]["name"])
    for key, cut in cfg["reduced"].items():
        part, field = key.split(".")
        want = dataclasses.replace(want, **{part: dataclasses.replace(
            getattr(want, part), **{field: cut["here"]})})
    assert to_desc(cfg["model"]) == want


@pytest.mark.parametrize("where", ["", "llm", "stub"])
def test_unknown_model_key_stops_the_run(tmp_path, where):
    """A ``model`` key that no field of the program's config has stops the
    run before set-up, and names the key."""
    model = json.loads(json.dumps(tiny.MODEL))
    (model[where] if where else model)["kv_lora_rank"] = 512
    name = tiny.write_checkout(tmp_path, config={**tiny.CONFIG,
                                                 "model": model})
    with pytest.raises(ValueError, match="kv_lora_rank"):
        bench_run.execute(tmp_path, name, seed=5, seconds=0.5, trace=False,
                          require_chip=False)


def test_a_missing_field_takes_its_default():
    """A field the file leaves out is the dataclass default, so a field
    that the program adds with today's behaviour as its default leaves
    every configuration file valid unchanged."""
    model = json.loads(json.dumps(tiny.MODEL))
    del model["tokens_per_item_out"], model["llm"]["scan_layers"]
    desc = to_desc(model)
    assert desc.tokens_per_item_out == 0 and desc.llm.scan_layers is True


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


TINY_REFERENCE = '''"""The MLLM reference under a name of its own, with its own
FLOP count."""
from bench.reference.mllm import *  # noqa: F401,F403
from bench.reference.mllm import step_flops as mllm_step_flops

CALLS = []


def step_flops(m, n_rows, t_media, t_text):
    CALLS.append((n_rows, t_media, t_text))
    return mllm_step_flops(m, n_rows, t_media, t_text)
'''


@pytest.mark.parametrize("trace", [False, True])
def test_cell_defined_by_data_alone(tmp_path, monkeypatch, trace):
    """A new configuration, under a name no other test uses, with a
    reference of its own, a traffic mix and a cell are files and entries
    only; the harness finds them by name, counts the step's FLOPs with that
    reference, and reports the metrics that BENCHMARK.json lists for the
    cell."""
    name = tiny.write_checkout(tmp_path, config={
        **tiny.CONFIG, "name": "tiny-fresh-1chip", "reference": "tiny_fresh"})
    refs = tmp_path / "bench" / "reference"
    refs.mkdir()
    (refs / "tiny_fresh.py").write_text(TINY_REFERENCE)
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(refs)])
    # imported afresh from this checkout, and forgotten after the test
    monkeypatch.setitem(sys.modules, "bench.reference.tiny_fresh", None)
    del sys.modules["bench.reference.tiny_fresh"]
    line = bench_run.execute(tmp_path, name, seed=99, seconds=0.5,
                             trace=trace, require_chip=False)
    tr = tiny.TRAFFIC
    assert sys.modules["bench.reference.tiny_fresh"].CALLS == [(
        tr["microbatches"] * tr["rows_per_microbatch"],
        tr["media_cap"] * tiny.MODEL["stub"]["n_tokens"], tr["text_cap"])]
    group = BENCH["per_layer" if trace else "end_to_end"]
    expect = {m["name"] for m in group}
    if trace:
        # the CPU has no device plane and no peak: the readers of the
        # device trace say nothing
        expect -= {m["name"] for m in group if m["source"] == "device_trace"}
        assert "breakdown" in line and line["device"]["window_s"] > 0
    got = {k for k, v in line["metrics"].items() if v["value"] == v["value"]}
    assert got | {"step_mfu"} >= expect
    assert line["correct"] and list(line)[-1] == "checks"
