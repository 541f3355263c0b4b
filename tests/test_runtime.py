"""repro.runtime: telemetry, drift detection, and continuous re-planning.

Covers the tentpole control loop end-to-end plus the async scheduler path
(submit/collect) it builds on.
"""
import json
import time

import numpy as np
import pytest

from repro.common.types import ModelConfig
from repro.core.engine import DFLOPEngine
from repro.core.optimizer.space import (ClusterSpec, ModuleParallelism,
                                        ParallelismPlan)
from repro.core.profiling.data_profiler import ShapeDistribution
from repro.data.synthetic import MixedDataset
from repro.runtime import (DriftDetector, OnlineCalibrator, PageHinkley,
                           RuntimeMetrics, TraceRecorder, ks_distance)

TPM = 64

ENC = ModelConfig(name="e", family="vlm-enc", n_layers=4, d_model=256,
                  n_heads=4, n_kv_heads=4, d_ff=1024, vocab_size=0,
                  causal=False, use_rope=False, input_embed_dim=64,
                  has_lm_head=False)
LLM = ModelConfig(name="l", family="dense", n_layers=8, d_model=512,
                  n_heads=8, n_kv_heads=8, d_ff=2048, vocab_size=8192)


def _engine(mixture="single_image", n_chips=32):
    ds = MixedDataset(mixture, seed=0, tokens_per_media_item=TPM)
    eng = DFLOPEngine(llm_cfg=LLM, enc_cfg=ENC, e_seq_len=64,
                      cluster=ClusterSpec(n_chips=n_chips, chips_per_node=8,
                                          mem_bytes=80e9),
                      tokens_per_media_item=TPM)
    eng.profile(ds, n_samples=512)
    eng.dataset = ds
    return eng


# --------------------------------------------------------------------- #
# trace
# --------------------------------------------------------------------- #
def test_trace_spans_and_chrome_export(tmp_path):
    tr = TraceRecorder(process_name="test")
    tr.name_thread(0, "main")
    with tr.span("outer", cat="step", batch=3):
        with tr.span("inner", cat="scheduler"):
            pass
    tr.instant("marker", args={"k": 1})
    tr.counter("imbalance", 0.25)
    tr.complete("simulated", ts_us=10.0, dur_us=5.0, tid=2)
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())          # valid JSON round-trip
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    assert by_name["outer"]["args"] == {"batch": 3}
    assert by_name["marker"]["ph"] == "i"
    assert by_name["imbalance"]["ph"] == "C"
    assert by_name["imbalance"]["args"]["value"] == 0.25
    assert by_name["simulated"] == {"ph": "X", "name": "simulated",
                                    "cat": "runtime", "ts": 10.0, "pid": 1,
                                    "tid": 2, "dur": 5.0}
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)


def test_trace_disabled_records_nothing():
    tr = TraceRecorder(enabled=False)
    with tr.span("x"):
        pass
    tr.counter("y", 1.0)
    assert len(tr) == 0


def test_trace_bounded_buffer_counts_drops():
    tr = TraceRecorder(max_events=2)
    for _ in range(5):
        tr.instant("e")
    assert len(tr) == 2
    assert tr.dropped == 3
    assert tr.to_chrome()["otherData"]["dropped_events"] == 3


# --------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------- #
def test_record_step_distinguishes_unmeasured_from_idle_busy():
    """An explicitly-passed busy_s=0.0 is a fully idle step (bubble 1.0);
    busy_s=None means 'not measured' and defaults to the non-idle
    remainder — the two must not be conflated."""
    m = RuntimeMetrics()
    m.record_step(2.0, idle_s=2.0, busy_s=0.0)        # fully idle step
    assert m.bubble_fraction.last() == 1.0
    m.record_step(2.0, idle_s=0.5)                    # busy not measured
    assert abs(m.bubble_fraction.last() - 0.25) < 1e-9
    m.record_step(2.0, idle_s=0.0)                    # nothing measured
    assert m.bubble_fraction.last() == 0.0


def test_metrics_rolling_and_snapshot():
    m = RuntimeMetrics(window=4)
    for i in range(8):
        m.record_prediction("llm", 1.0, 1.0 + 0.1 * i)
    # window keeps only the last 4 errors: 0.4..0.7
    assert abs(m.pred_error["llm"].mean() - 0.55) < 1e-9
    assert m.pred_error["llm"].count == 8
    m.record_step(2.0, idle_s=0.5, busy_s=1.5, stage_busy=np.array([1.0, 2.0]))
    snap = m.snapshot()
    assert abs(snap["bubble_fraction_mean"] - 0.25) < 1e-9
    assert snap["stage_utilization"] == {0: 0.5, 1: 1.0}
    assert snap["n_steps"] == 1


# --------------------------------------------------------------------- #
# calibration
# --------------------------------------------------------------------- #
def test_calibrator_converges_to_observed_ratio():
    cal = OnlineCalibrator(alpha=0.5, min_obs=2)
    for _ in range(12):
        cal.observe("llm", 1000.0, 4, predicted=1.0, actual=1.5)
    assert abs(cal.correct("llm", 1000.0, 4, 2.0) - 3.0) < 1e-3
    # other (module, bucket, tp) cells untouched
    assert cal.correct("llm", 1000.0, 8, 2.0) == 2.0
    assert cal.correct("encoder", 1000.0, 4, 2.0) == 2.0
    assert cal.residual("llm") > 0.4


def test_calibrator_tracks_regime_change_faster_than_lifetime_mean():
    cal = OnlineCalibrator(alpha=0.25, min_obs=2)
    for _ in range(50):
        cal.observe("llm", 512.0, 1, 1.0, 2.0)    # old regime: 2x slower
    for _ in range(20):
        cal.observe("llm", 512.0, 1, 1.0, 1.0)    # new regime: on-model
    # EWMA forgets the old regime; a lifetime mean would still be ~1.7x
    assert cal.correct("llm", 512.0, 1, 1.0) < 1.1


def test_calibrator_deadband_and_immature_cells():
    cal = OnlineCalibrator(min_obs=3, deadband=0.05)
    cal.observe("llm", 100.0, 1, 1.0, 3.0)
    assert cal.correct("llm", 100.0, 1, 1.0) == 1.0     # n < min_obs
    for _ in range(5):
        cal.observe("llm", 200.0, 1, 1.0, 1.01)
    assert cal.correct("llm", 200.0, 1, 1.0) == 1.0     # inside deadband


# --------------------------------------------------------------------- #
# drift
# --------------------------------------------------------------------- #
def test_page_hinkley_fires_on_mean_shift_not_on_noise():
    rng = np.random.default_rng(0)
    ph = PageHinkley(delta=0.01, threshold=0.5, burn_in=30)
    fired = [ph.update(x) for x in 0.05 + 0.01 * rng.standard_normal(300)]
    assert not any(fired)
    fired = [ph.update(x) for x in 0.5 + 0.01 * rng.standard_normal(100)]
    assert any(fired)


def test_ks_distance_known_values():
    a = np.array([1.0, 2.0, 3.0, 4.0])
    assert ks_distance(a, a) == 0.0
    assert ks_distance(np.zeros(100), np.ones(100)) == 1.0
    rng = np.random.default_rng(0)
    same = ks_distance(rng.normal(0, 1, 500), rng.normal(0, 1, 500))
    shifted = ks_distance(rng.normal(0, 1, 500), rng.normal(2, 1, 500))
    assert same < 0.15 < 0.5 < shifted


def test_drift_detector_fires_on_shape_shift_and_rebases():
    det = DriftDetector(window=128, ks_threshold=0.2, check_every=16,
                        cooldown=64)
    pre = MixedDataset("single_image", seed=0, tokens_per_media_item=TPM)
    post = MixedDataset("video", seed=1, tokens_per_media_item=TPM)
    from repro.core.profiling.data_profiler import DataProfiler
    det.set_reference(DataProfiler(TPM).profile(pre.sample(512)))
    for _ in range(8):
        assert det.observe_items(pre.sample(32), TPM) is None
    ev = None
    for _ in range(16):
        ev = ev or det.observe_items(post.sample(32), TPM)
    assert ev is not None and ev.kind == "shape-ks"
    assert ev.statistic > 0.2
    # after rebasing on the new regime the detector is quiet again
    for _ in range(8):
        det.observe_items(post.sample(32), TPM)
    det.rebase()
    for _ in range(16):
        assert det.observe_items(post.sample(32), TPM) is None


def test_drift_window_distribution_reflects_recent_items():
    det = DriftDetector(window=64)
    ds = MixedDataset("video", seed=0, tokens_per_media_item=TPM)
    det.observe_items(ds.sample(64), TPM)
    dist = det.window_distribution()
    assert len(dist) == 64
    assert dist.mean()[0] >= 8.0          # video items have 8-32 media


# --------------------------------------------------------------------- #
# async scheduler path (submit/collect)
# --------------------------------------------------------------------- #
def test_submit_collect_matches_synchronous_schedule():
    eng = _engine()
    eng.plan(32)
    sched = eng.scheduler(adaptive=False, ilp_time_limit_s=0.05)
    items = eng.dataset.sample(32)
    sync = sched.schedule(items)
    sched.submit(items)
    assert sched.has_pending
    asyn = sched.collect()
    assert not sched.has_pending
    assert asyn.groups == sync.groups
    np.testing.assert_allclose(asyn.cmax, sync.cmax)
    np.testing.assert_allclose(asyn.e_dur, sync.e_dur)


def test_double_submit_raises_and_collect_without_submit_is_none():
    eng = _engine()
    eng.plan(32)
    sched = eng.scheduler(adaptive=False, ilp_time_limit_s=0.05)
    assert sched.collect() is None
    items = eng.dataset.sample(16)
    sched.submit(items)
    with pytest.raises(RuntimeError, match="pending"):
        sched.submit(items)
    assert sched.collect() is not None
    assert sched.collect() is None


def test_observe_does_not_compound_adaptive_and_calibration():
    """Both correctors fed the same raw (predicted, actual) pair would each
    learn ratio r and compound to r² at prediction time; the calibrator must
    observe the residual left after adaptive correction instead."""
    eng = _engine()
    eng.plan(32)
    sched = eng.scheduler(adaptive=True, ilp_time_limit_s=0.05)
    sched.calibration = OnlineCalibrator(min_obs=2)
    for _ in range(20):
        sched.observe("llm", 1000.0, 1.0, 1.5)   # persistent 1.5x deviation
    d = sched.adaptive.correct("llm", 1000.0, 1.0)
    d = sched.calibration.correct("llm", 1000.0, sched.plan.llm.tp, d)
    assert 1.4 < d < 1.65                        # ~r, not r² (2.25)


def test_plan_hot_swap_takes_effect_next_schedule():
    eng = _engine()
    eng.plan(32)
    sched = eng.scheduler(adaptive=False, ilp_time_limit_s=0.05)
    items = eng.dataset.sample(32)
    old = sched.plan
    out1 = sched.schedule(items)
    assert len(out1.groups) == old.n_mb * old.llm.dp
    new_plan = ParallelismPlan(llm=ModuleParallelism(1, 1, 2),
                               encoder=ModuleParallelism(1, 1, 2), n_mb=2)
    sched.set_plan(new_plan)
    out2 = sched.schedule(items)
    assert len(out2.groups) == 4          # n_mb * llm.dp of the new plan
    assert sched.n_buckets == 4


# --------------------------------------------------------------------- #
# controller end-to-end
# --------------------------------------------------------------------- #
def test_controller_detects_drift_replans_and_improves_cmax(tmp_path):
    eng = _engine("single_image")
    eng.plan(64)
    drift = DriftDetector(window=128, ks_threshold=0.2, check_every=32,
                          cooldown=64)
    ctl = eng.runtime(64, adaptive=False, drift=drift,
                      ilp_time_limit_s=0.05)
    stale_plan = ctl.plan
    pre = MixedDataset("single_image", seed=0, tokens_per_media_item=TPM)
    post = MixedDataset("video", seed=1, tokens_per_media_item=TPM)
    for _ in range(4):
        ctl.schedule(pre.sample(64))
    assert ctl.metrics.n_drift_events == 0
    for i in range(12):
        ctl.schedule(post.sample(64))
        if ctl.metrics.n_replans:
            break
        ctl.drain(timeout=60.0)
    assert ctl.metrics.n_drift_events >= 1
    assert ctl.metrics.n_replans >= 1
    assert len(ctl.replans) >= 1
    rec = ctl.replans[0]
    assert rec.swapped
    assert rec.trigger.kind == "shape-ks"
    # post-replan predicted makespan beats the stale plan's on the drifted
    # distribution (per-batch throughput recovery at the paper's scale is
    # asserted by test_fig16_throughput_recovery below)
    assert rec.new_makespan < rec.stale_makespan
    assert ctl.plan.as_tuple() != stale_plan.as_tuple()
    # the swap takes effect: scheduling now uses the new plan's buckets
    out = ctl.schedule(post.sample(64))
    assert out.plan.as_tuple() == ctl.plan.as_tuple()
    # exported trace is valid Chrome-trace JSON with the swap marker
    path = ctl.export_trace(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    names = {e["name"] for e in doc["traceEvents"]}
    assert "plan-swap" in names
    assert "replan-search" in names
    assert "schedule" in names
    ctl.close()


def test_controller_no_replan_when_disabled():
    eng = _engine("single_image")
    eng.plan(64)
    ctl = eng.runtime(64, adaptive=False, auto_replan=False,
                      ilp_time_limit_s=0.05,
                      drift=DriftDetector(window=128, check_every=32,
                                          cooldown=64))
    post = MixedDataset("video", seed=1, tokens_per_media_item=TPM)
    plan0 = ctl.plan
    for _ in range(8):
        ctl.schedule(post.sample(64))
    ctl.drain(timeout=10.0)
    assert ctl.metrics.n_drift_events >= 1       # drift is still observed
    assert ctl.metrics.n_replans == 0            # but no search is launched
    assert ctl.plan is plan0
    ctl.close()


def test_observe_step_without_idle_records_no_bubble():
    """A step whose idle time nobody measured leaves the bubble fraction
    unmeasured (None), in the metrics and in the trace; a measured 0.0
    still records 0.0."""
    eng = _engine("single_image")
    eng.plan(32)
    ctl = eng.runtime(32, adaptive=False, auto_replan=False,
                      ilp_time_limit_s=0.05)
    out = ctl.schedule(eng.dataset.sample(32))
    ctl.observe_step(out, 0.5)
    snap = ctl.metrics.snapshot()
    assert snap["n_steps"] == 1 and snap["step_time_mean_s"] == 0.5
    assert snap["bubble_fraction_mean"] is None
    assert "bubble_fraction" not in {e[1] for e in ctl.trace._events}
    ctl.observe_step(out, 0.5, idle_s=0.0)
    assert ctl.metrics.snapshot()["bubble_fraction_mean"] == 0.0
    assert "bubble_fraction" in {e[1] for e in ctl.trace._events}
    ctl.close()


def test_controller_observe_feeds_calibration_and_adaptive():
    eng = _engine("single_image")
    eng.plan(32)
    ctl = eng.runtime(32, adaptive=True, auto_replan=False,
                      ilp_time_limit_s=0.05)
    assert ctl.scheduler.calibration is ctl.calibration
    for _ in range(20):
        ctl.observe("llm", 1000.0, predicted=1.0, actual=1.4)
    # combined adaptive+calibration correction converges to the observed
    # ratio (calibration only holds the post-adaptive residual)
    d = ctl.scheduler.adaptive.correct("llm", 1000.0, 1.0)
    d = ctl.calibration.correct("llm", 1000.0, ctl.plan.llm.tp, d)
    assert 1.3 < d < 1.5
    assert ctl.metrics.pred_error["llm"].mean() > 0.3
    ctl.close()


@pytest.mark.slow
def test_fig16_throughput_recovery():
    """Acceptance demo at the paper's scale: after the injected mid-run
    shift the controller detects drift, re-plans in the background, and the
    hot-swapped plan's predicted pipeline makespan beats the stale plan's.
    Also checks the exported Chrome trace is valid JSON."""
    from benchmarks.fig16_replan import TRACE_PATH, run as fig16_run

    rows = fig16_run(gbs=64, n_pre=4, n_post=18)
    summary = rows[-1]
    assert summary["phase"] == "summary"
    assert summary["n_drift_events"] >= 1
    assert summary["n_replans"] >= 1
    assert summary["swap_iter"] >= 0           # swapped mid-run, not at drain
    assert summary["plan_after"] != summary["plan_before"]
    assert summary["recovery_ratio"] > 1.2
    doc = json.loads(open(TRACE_PATH).read())
    assert {e["name"] for e in doc["traceEvents"]} >= {"schedule",
                                                       "replan-search",
                                                       "plan-swap"}


@pytest.mark.slow
def test_fig16_physical_swap_recovery_net_of_reshard():
    """Physical-swap variant of the fig16 acceptance demo: the hot-swap
    pays a *measured* reshard cost and still recovers — the summary
    reports the ratio net of that cost."""
    from benchmarks.fig16_replan import TRACE_PATH_PHYSICAL, run as fig16_run

    rows = fig16_run(gbs=64, n_pre=4, n_post=18, physical=True)
    summary = rows[-1]
    assert summary["phase"] == "summary"
    assert summary["n_replans"] >= 1
    assert summary["n_physical_swaps"] >= 1
    assert summary["reshard_s_total"] > 0.0
    assert summary["reshard_bytes_moved"] > 0
    # net recovery still clears the bar, and by construction sits at or
    # below the gross ratio
    assert summary["recovery_ratio_net"] > 1.2
    assert summary["recovery_ratio_net"] <= summary["recovery_ratio"]
    doc = json.loads(open(TRACE_PATH_PHYSICAL).read())
    names = {e["name"] for e in doc["traceEvents"]}
    assert "reshard" in names and "plan-swap" in names


def test_controller_pipelined_submit_collect():
    eng = _engine("single_image")
    eng.plan(32)
    ctl = eng.runtime(32, adaptive=False, auto_replan=False,
                      ilp_time_limit_s=0.05)
    ds = eng.dataset
    ctl.submit(ds.sample(32))
    out = ctl.collect()
    assert out is not None
    assert ctl.metrics.n_schedules == 1
    assert ctl.collect() is None
    ctl.close()


def _trace_stream(ctl):
    """Comparable trace view: (ph, name, cat, args) without timestamps."""
    return [(ph, name, cat, args)
            for ph, name, cat, ts, dur, tid, args in ctl.trace._events]


def test_submit_collect_telemetry_parity_with_sync_path():
    """The async path must emit the same trace spans/counters, feed the
    same metrics, and advance the drift window at the same points as
    schedule() — batch for batch."""
    eng = _engine("single_image")
    eng.plan(32)
    ds = eng.dataset
    batches = [ds.sample(32) for _ in range(4)]
    ctl_sync = eng.runtime(32, adaptive=False, auto_replan=False,
                           calibrate=False, ilp_time_limit_s=0.05)
    ctl_async = eng.runtime(32, adaptive=False, auto_replan=False,
                            calibrate=False, ilp_time_limit_s=0.05)
    for items in batches:
        ctl_sync.schedule(items)
    for items in batches:
        ctl_async.submit(items)
        # drift must NOT run ahead of the metrics stream: the submitted
        # batch enters the window only once its ScheduleOutput is collected
        n_before = len(ctl_async.drift._win_seq)
        out = ctl_async.collect()
        assert out is not None
        assert len(ctl_async.drift._win_seq) == n_before + 32

    assert ctl_async.batch_idx == ctl_sync.batch_idx == 4
    assert _trace_stream(ctl_async) == _trace_stream(ctl_sync)
    for name in ("imbalance", "pred_cmax_s", "sched_elapsed_s"):
        s, a = getattr(ctl_sync.metrics, name), getattr(ctl_async.metrics, name)
        assert a.count == s.count == 4
        if name != "sched_elapsed_s":          # elapsed is wall time
            np.testing.assert_allclose(list(a._buf), list(s._buf))
    assert (list(ctl_async.drift._win_seq) == list(ctl_sync.drift._win_seq))
    ctl_sync.close()
    ctl_async.close()
