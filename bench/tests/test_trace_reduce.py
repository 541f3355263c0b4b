"""``bench/trace_reduce.py`` on hand-made events and on a trace recorded on
a TPU v5e by ``bench/tools/record_trace.py`` (three steps, each a 50 ms host
pause in ``bench.materialize`` and then a chain of matrix products)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce as tr

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def test_hand_made_events():
    spans = [("bench.window", 0.0, 10.0), ("bench.materialize", 1.0, 4.0),
             ("bench.step", 4.0, 10.0)]
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
        tr.reduce([("bench.step", 0.0, 1.0)], {})


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
