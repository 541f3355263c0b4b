"""``bench/trace_reduce.py`` on hand-made events and on a trace recorded on
a TPU v5e by ``bench/tools/record_trace.py`` (three steps, each a 50 ms host
pause in ``bench.materialize`` and then a chain of matrix products), and the
readers of the per-module device metrics on a hand-made reduction."""
from __future__ import annotations

import importlib
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"
# what the reduction read from SMALL before the scope reduction joined it
SMALL_REDUCED = Path(__file__).parent / "data" / "small.reduced.json"
MAIN = ("/host:CPU", 0)


def test_hand_made_events():
    spans = [("bench.window", 0.0, 10.0, MAIN),
             ("bench.materialize", 1.0, 4.0, MAIN),
             ("bench.step", 4.0, 10.0, MAIN)]
    devices = {
        "/device:TPU:0": [("fusion.1", 0.0, 1.0), ("all-gather.2", 4.0, 6.0),
                          ("fusion.3", 5.0, 7.0), ("fusion.4", 7.5, 12.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 1.0), ("all-reduce.9", 4.0, 5.0)],
    }
    r = tr.reduce(spans, devices)
    assert r["window_s"] == 10.0 and r["devices"] == 2
    # device 0 busy [0,1] [4,7] [7.5,10] = 6.5; device 1 [0,1] [4,5] = 2
    assert r["busy_s"] == pytest.approx(4.25)
    # collectives 2 and 1; exposed: [4,5] on device 0 and [4,5] on device 1
    assert r["collective_s"] == pytest.approx(1.5)
    assert r["collective_exposed_s"] == pytest.approx(1.0)
    # the longest gap is device 1's [5,10], inside bench.step
    assert r["idle_gaps"][0] == ["bench.step", pytest.approx(5.0)]
    assert ["bench.materialize", pytest.approx(3.0)] in r["idle_gaps"]
    assert r["device_ops"][0] == ["fusion.4", pytest.approx(1.25)]
    assert r["host_spans"]["bench.step"] == {"count": 1, "s": 6.0}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([("bench.step", 0.0, 1.0, MAIN)], {})


def test_recorded_tpu_trace():
    r = tr.reduce_file(str(SMALL))
    assert r["devices"] == 1
    spans = r["host_spans"]
    assert spans["bench.step"]["count"] == 3
    assert spans["bench.materialize"]["count"] == 3
    assert spans["bench.materialize"]["s"] >= 3 * 0.05
    assert 0.0 < r["busy_s"] < r["window_s"]
    # the device waits through each host pause, and the reduction says so
    gap, length = r["idle_gaps"][0]
    assert gap == "bench.materialize" and length >= 0.045
    idle = r["window_s"] - r["busy_s"]
    assert idle >= spans["bench.materialize"]["s"] * 0.9
    assert r["device_ops"] and r["collective_s"] == 0.0


def test_recorded_trace_reads_as_before():
    """Idle share and breakdown of the recorded trace are those of the
    reduction before it took in the program's scopes and spans."""
    r = tr.reduce_file(str(SMALL))
    want = json.loads(SMALL_REDUCED.read_text())
    assert (r["busy_s"], r["window_s"]) == (want["busy_s"], want["window_s"])
    assert r["device_ops"] == want["device_ops"]
    assert r["idle_gaps"] == want["idle_gaps"]
    assert "scopes" not in r


def _reduction(steps: int = 4) -> dict:
    """A reduction with ``scopes`` as the traced window of ``steps`` steps
    gives it: seconds per device by module and phase."""
    def ph(f, b, r=0.0):
        return {"forward": f, "backward": b, "recompute": r}
    return {"host_spans": {"bench.step": {"count": steps, "s": 2.0}},
            "scopes": {"encoder": ph(0.4, 0.6, 0.2), "connector": ph(0, 0),
                       "llm": ph(0.1, 0.2, 0.1), "head": ph(0.05, 0.15),
                       "grad_accum": ph(0.02, 0), "optimizer": ph(0.08, 0),
                       "attention": ph(0.3, 0.3, 0.2),
                       "unscoped": 0.1, "other_modules": 0.0,
                       "step_module_s": 2.0}}


@pytest.mark.parametrize("metric,want", [
    ("encoder_device_ms", 300.0), ("llm_device_ms", 100.0),
    ("head_device_ms", 50.0), ("optimizer_device_ms", 20.0),
    ("attention_device_ms", 200.0),
    # predicted 0.6 s against 1.2 s of encoder, 0.8 s against 0.6 s of
    # LLM and head
    ("encoder_pred_err", 50.0), ("llm_pred_err", 100.0 / 3)])
def test_module_readers(metric, want):
    run = {"trace": _reduction(),
           "window": {"steps": [{"pred_enc_s": 0.15, "pred_llm_s": 0.2}] * 4}}
    read = importlib.import_module(f"bench.metrics.{metric}").read
    assert read(run) == pytest.approx(want)
    # nothing to read: no trace, no scope reduction, no step, no time
    assert read({**run, "trace": None}) is None
    bare = {k: v for k, v in run["trace"].items() if k != "scopes"}
    assert read({**run, "trace": bare}) is None
    assert read({**run, "trace": _reduction(steps=0)}) is None
    empty = _reduction()
    empty["scopes"] = {m: dict.fromkeys(tr.PHASES, 0.0)
                       for m in tr.MODULES + ("attention",)}
    assert read({**run, "trace": empty}) is None
