"""repro.runtime — telemetry & continuous re-planning.

Turns the one-shot profile → plan → schedule façade into a closed control
loop (the paper's "continuously profiles runtime behavior" claim):

  trace       — span recorder (``repro.common.trace``, re-exported): Chrome
                export, and ``dflop.`` spans in the JAX profiler's trace
  metrics     — rolling bubble-fraction / utilization / imbalance counters
  calibration — online per-(module, shape-bucket, tp) EWMA residual model
  drift       — Page–Hinkley + KS drift detection over shapes & residuals
  controller  — RuntimeController: background re-plan + plan hot-swap

Entry point: ``DFLOPEngine.runtime(gbs)`` returns a wired controller.
"""
from repro.common.trace import TraceRecorder
from repro.runtime.calibration import OnlineCalibrator, shape_bucket
from repro.runtime.controller import (
    RecoveryRecord,
    ReplanRecord,
    RuntimeController,
)
from repro.runtime.drift import (
    DriftDetector,
    DriftEvent,
    PageHinkley,
    ks_distance,
)
from repro.runtime.metrics import RollingStat, RuntimeMetrics

__all__ = [
    "DriftDetector",
    "DriftEvent",
    "OnlineCalibrator",
    "PageHinkley",
    "RecoveryRecord",
    "ReplanRecord",
    "RollingStat",
    "RuntimeController",
    "RuntimeMetrics",
    "TraceRecorder",
    "ks_distance",
    "shape_bucket",
]
