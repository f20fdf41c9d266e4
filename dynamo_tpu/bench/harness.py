"""Measurement harness: slope timing, calibration probes, run guardrails.

Why this exists (VERDICT r5 weak #2): `bench.py` printed a measured
"peak" of 465.6 TFLOP/s on a 197 TFLOP/s v5e and kept going — the
headline number halved that round and nothing flagged the run.  The
failure mode: a pause lands inside one timing window, a slope estimate
collapses, and a physically impossible figure propagates into the
round's JSON.  The harness centralises the defenses:

- `measure_slope` — per-call cost from the slope between two run
  lengths (cancels the fixed per-run cost), repeated N times
  and aggregated with a trimmed median so one poisoned window cannot
  define the number.  Cold (compile) time is kept separate from warm
  samples.
- `Probe` / `evaluate_calibration` — a measured value above
  `CALIBRATION_TOLERANCE` (1.1x) of the datasheet nominal is impossible,
  so the run is INVALID, not merely noisy; wide spread between repeat
  samples (> `SPREAD_LIMIT`) marks the run NOISY.
- `guard_result` — stamps `calibration_ok` / `run_health` into the
  output JSON and suppresses `vs_baseline` on invalid runs, so the
  regression gate (`dynamo_tpu/bench/gate.py`) can reject them
  mechanically.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# A measured probe can legitimately exceed the datasheet a little
# (clock boost, favorable rounding in the byte/FLOP count) — 10%.
# Beyond that the measurement is broken, not the hardware fast.
CALIBRATION_TOLERANCE = 1.1
# max/min ratio between repeat samples of one probe above which the
# run is too unsteady to trust.
SPREAD_LIMIT = 2.0

HEALTH_OK = "ok"
HEALTH_NOISY = "noisy"
HEALTH_INVALID = "invalid"


def trimmed_median(samples: Sequence[float]) -> float:
    """Median with outlier trimming: for 4+ samples the min and max are
    dropped first (a pause shows up as one extreme sample), then
    the median of the rest is taken.  3 or fewer → plain median."""
    if not samples:
        raise ValueError("no samples")
    vs = sorted(samples)
    if len(vs) >= 4:
        vs = vs[1:-1]
    n = len(vs)
    mid = n // 2
    if n % 2:
        return vs[mid]
    return 0.5 * (vs[mid - 1] + vs[mid])


@dataclass(frozen=True)
class SlopeEstimate:
    """Per-call cost from repeated two-point slope measurements."""

    per_call_s: float            # trimmed-median slope
    samples: Tuple[float, ...]   # every individual slope (seconds/call)
    cold_s: float = 0.0          # first-run (compile/warmup) wall time

    @property
    def spread(self) -> float:
        """max/min across samples — 1.0 is perfectly quiet."""
        if len(self.samples) < 2:
            return 1.0
        lo = min(self.samples)
        return max(self.samples) / lo if lo > 0 else float("inf")


def measure_slope(run: Callable[[int], float], n1: int, n2: int,
                  repeats: int = 3, cold_s: float = 0.0) -> SlopeEstimate:
    """Slope-timed per-call cost: `run(m)` executes m chained calls and
    returns its wall time; per-call cost is (t2-t1)/(n2-n1), which
    cancels the fixed per-run tax (dispatch, the final fetch).
    Repeated `repeats` times; aggregate is the trimmed median."""
    if n2 <= n1:
        raise ValueError(f"need n2 > n1, got {n1}, {n2}")
    samples: List[float] = []
    for _ in range(repeats):
        t1, t2 = run(n1), run(n2)
        samples.append(max((t2 - t1) / (n2 - n1), 1e-9))
    return SlopeEstimate(per_call_s=trimmed_median(samples),
                         samples=tuple(samples), cold_s=cold_s)


def sequential_block_tables(batch: int, width: int):
    """The canonical decode micro-bench page layout: row i owns pages
    [1 + i*width, 1 + (i+1)*width), page 0 reserved as the null block.
    ONE definition (used by bench/sharded_decode.py and
    tools/profile_decode.py) so the allocator's page-numbering
    convention cannot silently skew one tool's measurements when the
    other is updated.  Returns int32 numpy; callers device-put it."""
    import numpy as np

    bt = np.zeros((batch, width), np.int32)
    for i in range(batch):
        bt[i] = np.arange(1 + i * width, 1 + (i + 1) * width)
    return bt


def timed(fn: Callable[[], object]) -> Tuple[object, float]:
    """(result, wall seconds) — for cold/compile phases kept separate
    from warm slope samples."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Calibration probes


@dataclass(frozen=True)
class Probe:
    """One calibration measurement against a datasheet nominal.

    `nominal=None` means no datasheet value applies (e.g. CPU fallback
    runs) — the impossibility check is skipped but spread still counts.
    """

    name: str
    measured: float
    nominal: Optional[float] = None
    samples: Tuple[float, ...] = ()
    unit: str = ""

    @property
    def ratio(self) -> Optional[float]:
        if not self.nominal:
            return None
        return self.measured / self.nominal

    @property
    def impossible(self) -> bool:
        """Measured exceeds what the silicon can do — the measurement is
        broken (a pause inflated a slope), never a real speedup."""
        r = self.ratio
        return r is not None and r > CALIBRATION_TOLERANCE

    @property
    def spread(self) -> float:
        if len(self.samples) < 2:
            return 1.0
        lo = min(self.samples)
        return max(self.samples) / lo if lo > 0 else float("inf")


@dataclass(frozen=True)
class CalibrationVerdict:
    calibration_ok: bool
    run_health: str          # "ok" | "noisy" | "invalid"
    reasons: Tuple[str, ...] = ()

    def to_dict(self) -> dict:
        return {"calibration_ok": self.calibration_ok,
                "run_health": self.run_health,
                "reasons": list(self.reasons)}


def evaluate_calibration(probes: Sequence[Probe],
                         tolerance: float = CALIBRATION_TOLERANCE,
                         spread_limit: float = SPREAD_LIMIT,
                         ) -> CalibrationVerdict:
    """Fold probes into one verdict.

    invalid — any probe reads above `tolerance` x nominal (physically
    impossible; the run's numbers cannot be trusted at all);
    noisy — all probes plausible but at least one has repeat-sample
    spread above `spread_limit` (numbers usable, error bars wide);
    ok — otherwise.
    """
    reasons: List[str] = []
    invalid = False
    noisy = False
    for p in probes:
        r = p.ratio
        if r is not None and r > tolerance:
            invalid = True
            reasons.append(
                f"{p.name}: measured {p.measured:.3g}{p.unit} is "
                f"{r:.2f}x the nominal {p.nominal:.3g}{p.unit} "
                f"(> {tolerance:.2f}x — physically impossible)")
        if p.spread > spread_limit:
            noisy = True
            reasons.append(
                f"{p.name}: repeat samples spread {p.spread:.2f}x "
                f"(> {spread_limit:.1f}x — run too unsteady)")
    health = (HEALTH_INVALID if invalid
              else HEALTH_NOISY if noisy else HEALTH_OK)
    return CalibrationVerdict(calibration_ok=not invalid,
                              run_health=health,
                              reasons=tuple(reasons))


def guard_result(result: Dict, verdict: CalibrationVerdict) -> Dict:
    """Stamp the verdict into a bench-output dict.  On an invalid run
    `vs_baseline` is suppressed (set to None) — a number derived from a
    broken calibration must never enter cross-round comparison — and
    `run_valid` goes false so `gate.compare` rejects the run outright."""
    out = dict(result)
    out["calibration_ok"] = verdict.calibration_ok
    out["run_health"] = verdict.run_health
    if verdict.reasons:
        out["calibration_reasons"] = list(verdict.reasons)
    out["run_valid"] = verdict.calibration_ok
    if not verdict.calibration_ok and "vs_baseline" in out:
        out["vs_baseline"] = None
    return out
