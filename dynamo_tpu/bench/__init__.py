"""Benchmark-integrity subsystem: calibration guardrails + regression gate.

- `harness` — slope-timed measurement helpers, calibration probes, and
  the guardrails that mark a bench run invalid (a probe reading above
  1.1x the datasheet value is physically impossible — noise, not
  performance) and suppress `vs_baseline` so a broken run can never
  poison cross-round comparisons.
- `gate` — machine-readable regression gate: compares a new BENCH JSON
  against a baseline and fails on regressions beyond a threshold.
"""

from dynamo_tpu.bench.gate import GateResult, compare, load_bench_json
from dynamo_tpu.bench.harness import (
    CalibrationVerdict,
    Probe,
    SlopeEstimate,
    evaluate_calibration,
    guard_result,
    measure_slope,
    trimmed_median,
)

__all__ = [
    "CalibrationVerdict",
    "GateResult",
    "Probe",
    "SlopeEstimate",
    "compare",
    "evaluate_calibration",
    "guard_result",
    "load_bench_json",
    "measure_slope",
    "trimmed_median",
]
