"""Regression gate: new BENCH JSON vs baseline, machine-readable verdict.

The cross-round failure the gate closes (VERDICT r5 #1): the canonical
serving number halved between rounds and the only detector was a human
reading two JSON files.  `compare` takes the new run and a baseline —
`BASELINE.json`, the previous round's `BENCH_rNN.json` (both the bare
bench output and the driver's `{"parsed": ...}` wrapper are accepted) —
and fails when any gated metric regresses beyond the threshold, or when
the new run carries `calibration_ok: false` / `run_valid: false` (an
invalid run is an automatic gate failure: it must be re-run, not
compared).

An INVALID BASELINE is different: its numbers are garbage, so
comparison is skipped with a warning instead of failing the new run for
the old run's sins.

CLI entry point: `tools/bench_gate.py` (exits nonzero on failure).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEFAULT_THRESHOLD = 0.2  # fractional regression that fails the gate


@dataclass(frozen=True)
class MetricSpec:
    """One gated metric.  `higher_is_better=False` flips the direction
    (latencies regress upward)."""

    key: str
    higher_is_better: bool = True


# The round-over-round health of the serving stack, in the order a human
# would triage them: raw decode ceiling, the full serving path, prefill,
# per-token latency, decode-under-prefill interference.
DEFAULT_METRICS: Tuple[MetricSpec, ...] = (
    MetricSpec("value"),
    MetricSpec("serving_tok_s"),
    MetricSpec("prefill_tok_s"),
    MetricSpec("itl_ms", higher_is_better=False),
)


@dataclass(frozen=True)
class FloorSpec:
    """Absolute bound for a metric (dot-path into the BENCH doc).
    Unlike the relative regression checks, floors hold even when the
    baseline itself already regressed — the r5 failure mode was exactly
    a bad number becoming next round's baseline.  `minimum` gates
    from below; `maximum` gates from above (ratios that must SHRINK,
    e.g. quantized-KV traffic vs bf16)."""

    key: str
    minimum: Optional[float] = None
    maximum: Optional[float] = None


# Enforced only on TPU runs (CPU bench output has neither a roofline nor
# real interference numbers).  Floors absent from a run are SKIPPED, not
# failed — feature sections (kv_quant / spec_decode) appear once bench.py
# runs them, and from then on can never silently regress below floor.
#
# Rationale per floor:
# - mbu >= 0.75 / interference >= 0.80 — ISSUE 2: decode must stay near
#   its bandwidth roofline and keep >= 80% throughput under mixed
#   prefill.
# - kv_quant.traffic_ratio <= 0.55 — ISSUE 6(a): int8 KV + scales must
#   genuinely halve decode KV bytes.  The honest ratio at serving
#   geometry (head_dim 64) is (F + 4*Hkv) / (2*F) = 0.531; 0.55 leaves
#   margin for layout padding while still failing any accounting bug
#   that forgets the scales (which alone would push a naive "0.5" claim
#   to ~0.53) or ships f16 scales per element (~1.0).
# - spec_decode.acceptance_rate >= 0.6 — ISSUE 6(b): on the repetitive
#   data_generator-shaped workload (decode_wall.repetitive_prompt) the
#   n-gram drafter must accept most drafts; measured 0.92 on the CPU
#   tiny model, so 0.6 catches drafter/verify regressions (e.g. the
#   truncated-continuation bug this PR fixed measured 0.26) without
#   flaking on model noise.
# - spec_decode.modeled_decode_speedup >= 1.3 — the sweep-count model
#   (baseline sweeps / spec sweeps / 1.1 verify surcharge) must clear
#   1.3x on the acceptance-friendly workload, the gate behind the
#   combined >= 1.5x tok/s/chip target for the next TPU round.
# - prefix_fleet.remote_hit_rate >= 0.2 — ISSUE 7: on the synthetic
#   shared-prefix workload (bench/prefix_fleet.py: 8 roots over a busy
#   6-worker modeled fleet) the router must spill popular prefixes AND
#   hand out remote-prefix hints for them; measures ~0.34, so 0.2
#   catches a broken donor policy (hints never attached, dead-donor
#   leakage filtering everything out) without flaking on routing noise.
# - prefill_plane.packed_vs_padded_tok_s_ratio >= 1.2 — ISSUE 10: on the
#   ragged prompt set the packed ragged plane (flat token axis + Pallas
#   flash-prefill over the pool) must beat the padded-bucket plane by
#   >= 1.2x warm.  The padded plane's waste on that workload is padding
#   (ragged lengths into [rows, chunk] buckets) plus the dense gather_kv
#   materialisation, so parity-or-worse means the packed plane regressed
#   to the gather path or the kernel lost its streaming advantage.  The
#   bench ZEROES the ratio when `token_parity` fails, so this floor also
#   trips on a fast-but-wrong kernel, and the existing interference
#   floor (>= 0.80) keeps holding with the measured-cost controller.
# - sharded_decode.tok_s_per_chip_ratio >= 0.8 — ISSUE 9: a tp2 engine's
#   fused decode window must deliver >= 80% of the meshless tok/s PER
#   CHIP (tp2 trades one all-reduce per layer for halved weight/KV
#   streaming, so the honest ratio sits near 0.9 on ICI-linked chips);
#   below 0.8 means the fast decode plane regressed to the gather path
#   or the sharded fused step broke.  Only present when the round ran on
#   >= 2 chips (single-chip rigs skip the modes and the floor).
# - transfer.device_vs_host_ratio >= 2.0 — ISSUE 13: the device-direct
#   KV plane (descriptor probe → batched device pull → ack; blocks never
#   touch the host) must beat the host-staged msgpack wire by >= 2x at
#   serving block geometry.  The host path pays extract-to-numpy,
#   msgpack framing, TCP, and inject-from-numpy per block — on ICI-linked
#   chips the device pull's only real cost is the fabric copy, so the
#   honest ratio sits well above 2; parity-or-worse means the plane
#   regressed to host staging under the covers (or double-copies on
#   inject, the pre-ISSUE-13 sharded bug).  The bench ZEROES the ratio
#   when byte parity fails, so this floor also trips on a
#   fast-but-corrupting plane.
# - moe_decode.grouped_vs_dense >= 1.5 — ISSUE 17: the grouped expert
#   kernel (sort-by-expert + ragged grouped GEMM streaming only ACTIVE
#   experts' weights) must beat the dense all-experts path by >= 1.5x at
#   decode shape.  The theoretical edge is E/k (4x at the 8-expert top-2
#   bench geometry — dense streams and multiplies every expert's weights
#   per token, grouped only the selected ones), so 1.5 leaves room for
#   the sort/scatter overhead while still failing a kernel that fell
#   back to dense-ish streaming.  The bench ZEROES the ratio when token
#   parity vs the moe_dense oracle fails, so this floor also trips on a
#   fast-but-wrong kernel.  Absent (skipped, not passed) on dense-model
#   rounds or grouped-ineligible geometries.
# - ring_plane.kernel_vs_xla >= 1.15 — ISSUE 19: the Pallas flash ring
#   (double-buffered next-hop RDMA issued BEFORE the local block's
#   online-softmax fold; per-hop s/p intermediates never leave VMEM)
#   must beat the XLA ppermute ring by >= 1.15x at sp prefill shape.
#   The XLA path's overlap is scheduler-dependent and its per-hop
#   intermediates round-trip HBM, so parity-or-worse means the kernel
#   silently fell back (or the RDMA stopped overlapping compute).  The
#   bench ZEROES the ratio when numeric parity vs the XLA ring fails,
#   so this floor also trips on a fast-but-wrong kernel.  Absent
#   (skipped, not passed) when the round's geometry is
#   ring_geometry_ok-ineligible or the rig has < 2 chips.
# - device_truth.modeled_vs_measured_kv <= 1.25 — ISSUE 20: the drift
#   auditor's kv_decode ratio folds the engine's MODELED per-chip KV
#   decode bytes against XLA's own bytes-accessed cost analysis for the
#   compiled decode programs.  Modeled KV traffic is a strict component
#   of what the program actually touches (XLA's total adds weights and
#   activations on top), so an honest ratio sits WELL below 1 — measured
#   ~0.14 on the CPU tiny model, higher but still sub-1 at serving
#   geometry where KV dominates.  A ratio above 1.25 means the
#   analytical model claims more bytes than the device moves: exactly
#   the PR-16 int8 bug class (modeled bytes double-counting scales /
#   missing a quantization factor) that made "halved KV traffic" claims
#   uncheckable.  One-sided on purpose: under-claim is expected, only
#   over-claim is a lie the capacity planner would act on.
# - sharded_decode.pp_fused_vs_single >= 1.2 — ISSUE 12: the all-in-one
#   pp stage program (schedule + fused argmax, [B] tokens out) must beat
#   the unfused loop it replaced (schedule dispatch returning [B, V] f32
#   logits + a separate argmax dispatch + host feedback) by >= 1.2x per
#   step.  The unfused loop pays an extra eager dispatch AND a
#   full-vocab f32 device->host-visible output per token — on real
#   dispatch-latency-bound serving that overhead is the r5 cliff, so
#   parity-or-worse means the fused program silently fell back or the
#   schedule regressed.  Only present when the round measured pp2.
TPU_FLOORS: Tuple[FloorSpec, ...] = (
    FloorSpec("mbu", minimum=0.75),
    FloorSpec("mixed_prefill_decode.interference_ratio", minimum=0.80),
    FloorSpec("kv_quant.traffic_ratio", maximum=0.55),
    FloorSpec("spec_decode.acceptance_rate", minimum=0.6),
    FloorSpec("spec_decode.modeled_decode_speedup", minimum=1.3),
    FloorSpec("prefix_fleet.remote_hit_rate", minimum=0.2),
    FloorSpec("sharded_decode.tok_s_per_chip_ratio", minimum=0.8),
    FloorSpec("sharded_decode.pp_fused_vs_single", minimum=1.2),
    FloorSpec("ring_plane.kernel_vs_xla", minimum=1.15),
    FloorSpec("moe_decode.grouped_vs_dense", minimum=1.5),
    FloorSpec("prefill_plane.packed_vs_padded_tok_s_ratio", minimum=1.2),
    FloorSpec("transfer.device_vs_host_ratio", minimum=2.0),
    FloorSpec("device_truth.modeled_vs_measured_kv", maximum=1.25),
)


def _lookup(doc: Dict, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def is_tpu_run(doc: Dict) -> bool:
    return "tpu" in str(doc.get("device", "")).lower()


def load_bench_json(path: str) -> Dict:
    """Load a bench artifact, unwrapping the driver's BENCH_rNN wrapper
    (`{"n": ..., "parsed": {...}}`) down to the bare metric dict."""
    with open(path) as f:
        doc = json.load(f)
    return unwrap(doc)


def unwrap(doc: Dict) -> Dict:
    if isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc


def _is_invalid(doc: Dict) -> bool:
    return (doc.get("calibration_ok") is False
            or doc.get("run_valid") is False)


@dataclass
class GateResult:
    ok: bool
    regressions: List[Dict] = field(default_factory=list)
    improvements: List[Dict] = field(default_factory=list)
    floor_failures: List[Dict] = field(default_factory=list)
    skipped: List[str] = field(default_factory=list)
    warnings: List[str] = field(default_factory=list)
    new_invalid: bool = False
    baseline_invalid: bool = False

    def to_dict(self) -> dict:
        return {
            "gate": "pass" if self.ok else "fail",
            "new_invalid": self.new_invalid,
            "baseline_invalid": self.baseline_invalid,
            "regressions": self.regressions,
            "improvements": self.improvements,
            "floor_failures": self.floor_failures,
            "skipped": self.skipped,
            "warnings": self.warnings,
        }


def _check_floors(new: Dict, res: GateResult,
                  floors: Sequence[FloorSpec]) -> None:
    """Absolute floors on the new run (TPU runs only): a metric below
    its floor fails the gate regardless of what the baseline says."""
    if not is_tpu_run(new):
        return
    for spec in floors:
        v = _lookup(new, spec.key)
        if not isinstance(v, (int, float)):
            res.skipped.append(f"floor:{spec.key}")
            continue
        if spec.minimum is not None and v < spec.minimum:
            res.floor_failures.append({
                "metric": spec.key, "floor": spec.minimum, "new": v})
            res.ok = False
        if spec.maximum is not None and v > spec.maximum:
            res.floor_failures.append({
                "metric": spec.key, "ceiling": spec.maximum, "new": v})
            res.ok = False
    _check_compose_matrix(new, res)


def _check_compose_matrix(new: Dict, res: GateResult) -> None:
    """ISSUE 12: the sharded_decode.compose_matrix summary must carry NO
    "rejected" cell — a combo the capability table says composes but
    whose builder raised during measurement.  "ok", "declared: ..." and
    "skipped: ..." statuses are fine; a rejected cell fails the gate
    outright (it is a broken composition, not a slow one)."""
    cm = _lookup(new, "sharded_decode.compose_matrix")
    if not isinstance(cm, dict):
        return
    for cell, info in cm.items():
        status = info.get("status") if isinstance(info, dict) else info
        if isinstance(status, str) and status.startswith("rejected"):
            res.floor_failures.append({
                "metric": f"sharded_decode.compose_matrix.{cell}",
                "status": status})
            res.ok = False


def compare(new: Dict, baseline: Dict,
            threshold: float = DEFAULT_THRESHOLD,
            metrics: Sequence[MetricSpec] = DEFAULT_METRICS,
            floors: Sequence[FloorSpec] = TPU_FLOORS) -> GateResult:
    """Gate `new` against `baseline`.  Fails (ok=False) when the new run
    is invalid, any gated metric regresses more than `threshold`
    (fractional: 0.2 = a 20% drop in a higher-is-better metric), or a
    TPU run sits below an absolute floor (MBU, interference_ratio)."""
    new = unwrap(new)
    baseline = unwrap(baseline)
    res = GateResult(ok=True)

    if _is_invalid(new):
        res.new_invalid = True
        res.ok = False
        res.warnings.append(
            "new run is invalid (calibration guardrails tripped: "
            f"run_health={new.get('run_health')!r}) — re-run it; "
            "an invalid run is never comparable")
        return res
    _check_floors(new, res, floors)
    if _is_invalid(baseline):
        res.baseline_invalid = True
        res.warnings.append(
            "baseline run is invalid — comparison skipped (pick an "
            "earlier valid round as baseline)")
        return res

    for spec in metrics:
        old_v = baseline.get(spec.key)
        new_v = new.get(spec.key)
        if not isinstance(old_v, (int, float)) or not isinstance(
                new_v, (int, float)):
            res.skipped.append(spec.key)
            continue
        if old_v == 0:
            res.skipped.append(spec.key)
            continue
        if spec.higher_is_better:
            change = (new_v - old_v) / old_v       # negative = regression
            regressed = change < -threshold
        else:
            change = (new_v - old_v) / old_v       # positive = regression
            regressed = change > threshold
        entry = {
            "metric": spec.key,
            "baseline": old_v,
            "new": new_v,
            "change": round(change, 4),
            "higher_is_better": spec.higher_is_better,
        }
        if regressed:
            res.regressions.append(entry)
        elif (spec.higher_is_better and change > threshold) or (
                not spec.higher_is_better and change < -threshold):
            res.improvements.append(entry)
    if res.regressions:
        res.ok = False
    if new.get("run_health") == "noisy":
        res.warnings.append(
            "new run is noisy: regressions may be measurement "
            "spread; re-run before acting on them")
    return res


def gate_files(new_path: str, baseline_path: str,
               threshold: float = DEFAULT_THRESHOLD) -> GateResult:
    return compare(load_bench_json(new_path),
                   load_bench_json(baseline_path), threshold)
