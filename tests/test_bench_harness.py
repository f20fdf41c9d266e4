"""Benchmark-integrity subsystem: calibration guardrails (the fabricated
465-TFLOP/s probe VERDICT r5 printed must be REJECTED), slope
aggregation, the regression gate, and the tier-1 bench_gate smoke."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.bench import gate, harness  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# harness: slope estimation


def test_trimmed_median():
    assert harness.trimmed_median([3.0]) == 3.0
    assert harness.trimmed_median([1.0, 9.0, 2.0]) == 2.0
    # 4+ samples: min and max dropped BEFORE the median — one
    # pause cannot drag the aggregate.
    assert harness.trimmed_median([1.0, 2.0, 3.0, 100.0]) == 2.5
    assert harness.trimmed_median([0.001, 2.0, 2.1, 2.2, 100.0]) == 2.1
    with pytest.raises(ValueError):
        harness.trimmed_median([])


def test_measure_slope_cancels_fixed_cost():
    # run(m) = fixed 10ms tax + 2ms/call: the slope must recover 2ms.
    est = harness.measure_slope(lambda m: 0.010 + 0.002 * m, 4, 20)
    assert est.per_call_s == pytest.approx(0.002)
    assert len(est.samples) == 3
    assert est.spread == pytest.approx(1.0)
    with pytest.raises(ValueError):
        harness.measure_slope(lambda m: 0.0, 5, 5)


def test_measure_slope_survives_one_poisoned_window():
    # Second repeat hits a pause: its short run is inflated,
    # making that slope collapse toward zero (the r5 failure shape).
    calls = {"n": 0}

    def run(m):
        calls["n"] += 1
        if calls["n"] == 3:          # t1 of repeat 2 inflated 50x
            return 0.010 + 0.002 * m + 0.5
        return 0.010 + 0.002 * m

    est = harness.measure_slope(run, 4, 20)
    assert est.per_call_s == pytest.approx(0.002)   # median unharmed
    assert est.spread > 2.0                          # ...but flagged


# ---------------------------------------------------------------------------
# harness: calibration guardrails


def _v5e_flops_probe(measured_tflops, samples=()):
    return harness.Probe(
        name="peak_flops", measured=measured_tflops * 1e12,
        nominal=197e12,
        samples=tuple(s * 1e12 for s in samples), unit=" FLOP/s")


def test_fabricated_465_tflops_probe_rejected():
    """The exact r5 artifact: 465.6 TFLOP/s 'measured' on a 197 TFLOP/s
    v5e must mark the run invalid and suppress vs_baseline."""
    verdict = harness.evaluate_calibration(
        [_v5e_flops_probe(465.6, samples=(455.0, 465.6, 470.2))])
    assert not verdict.calibration_ok
    assert verdict.run_health == "invalid"
    assert "physically impossible" in verdict.reasons[0]

    out = harness.guard_result(
        {"value": 10301.56, "vs_baseline": 0.466, "serving_tok_s": 4803.5},
        verdict)
    assert out["calibration_ok"] is False
    assert out["run_health"] == "invalid"
    assert out["vs_baseline"] is None        # suppressed, not printed
    assert out["run_valid"] is False
    assert out["value"] == 10301.56          # raw numbers stay visible


def test_plausible_probe_passes_and_spread_flags_noise():
    ok = harness.evaluate_calibration(
        [_v5e_flops_probe(184.0, samples=(180.0, 184.0, 190.0))])
    assert ok.calibration_ok and ok.run_health == "ok"

    # Within the datasheet but wildly spread: valid yet NOISY.
    noisy = harness.evaluate_calibration(
        [_v5e_flops_probe(150.0, samples=(50.0, 150.0, 180.0))])
    assert noisy.calibration_ok
    assert noisy.run_health == "noisy"

    out = harness.guard_result({"vs_baseline": 0.9}, noisy)
    assert out["vs_baseline"] == 0.9         # kept: run is usable
    assert out["run_health"] == "noisy"

    # 10% over datasheet is tolerated (clock boost / rounding)...
    assert harness.evaluate_calibration(
        [_v5e_flops_probe(210.0)]).calibration_ok
    # ...11% over is not.
    assert not harness.evaluate_calibration(
        [_v5e_flops_probe(219.0)]).calibration_ok
    # No nominal (CPU fallback): impossibility check skipped.
    free = harness.Probe("peak_flops", 1e15, nominal=None)
    assert harness.evaluate_calibration([free]).calibration_ok


# ---------------------------------------------------------------------------
# regression gate


GOOD = {"value": 10000.0, "serving_tok_s": 8000.0, "prefill_tok_s": 11000.0,
        "itl_ms": 6.5, "calibration_ok": True, "run_health": "ok"}


def test_gate_fails_on_20pct_throughput_drop():
    dropped = dict(GOOD, serving_tok_s=8000.0 * 0.79)   # >20% drop
    res = gate.compare(dropped, GOOD)
    assert not res.ok
    assert res.regressions[0]["metric"] == "serving_tok_s"
    assert res.regressions[0]["change"] == pytest.approx(-0.21)

    barely = dict(GOOD, serving_tok_s=8000.0 * 0.85)    # within threshold
    assert gate.compare(barely, GOOD).ok


def test_gate_latency_direction_and_improvements():
    slow = dict(GOOD, itl_ms=6.5 * 1.3)                 # latency REGRESSES up
    res = gate.compare(slow, GOOD)
    assert not res.ok and res.regressions[0]["metric"] == "itl_ms"

    better = dict(GOOD, serving_tok_s=8000.0 * 1.4, itl_ms=4.0)
    res = gate.compare(better, GOOD)
    assert res.ok
    assert {e["metric"] for e in res.improvements} == \
        {"serving_tok_s", "itl_ms"}


def test_gate_rejects_invalid_new_run_and_skips_invalid_baseline():
    invalid = dict(GOOD, calibration_ok=False, run_health="invalid")
    res = gate.compare(invalid, GOOD)
    assert not res.ok and res.new_invalid

    # Invalid BASELINE: comparison meaningless — skip with warning, the
    # new run is not punished for the old run's broken calibration.
    res = gate.compare(GOOD, invalid)
    assert res.ok and res.baseline_invalid and res.warnings


def test_gate_unwraps_bench_round_files(tmp_path):
    """BENCH_rNN.json driver wrapper ({"parsed": ...}) and the bare
    bench output must both gate."""
    wrapped_old = {"n": 4, "parsed": GOOD}
    new = dict(GOOD, serving_tok_s=8000.0 * 0.5)
    res = gate.compare(new, wrapped_old)
    assert not res.ok
    # Missing metrics are skipped, not crashed on.
    res = gate.compare({"serving_tok_s": 8000.0, "calibration_ok": True},
                       GOOD)
    assert res.ok and "value" in res.skipped

    # A round file as the driver wrote it loads and unwraps from disk.
    path = tmp_path / "BENCH_r99.json"
    path.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 0,
        "parsed": dict(GOOD, metric="decode_throughput_llama-3-1b")}))
    loaded = gate.load_bench_json(str(path))
    assert loaded["metric"].startswith("decode_throughput")
    assert loaded["serving_tok_s"] == GOOD["serving_tok_s"]


@pytest.mark.slow
def test_bench_gate_smoke_cli():
    """CPU-only synthesize → analyze → mocker replay → gate, in a
    subprocess exactly as CI invokes it (slow: spawns a process and
    replays a 40-request trace)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["smoke"] == "pass"
    assert out["hit_rate_within_5pts"] is True
    assert out["regression_fails"] is True
    assert out["invalid_run_fails"] is True
    assert out["low_mbu_fails"] is True
    assert out["interference_fails"] is True
    assert out["sharded_floor_fails"] is True
    assert out["sharded_decode_section_ok"] is True
    assert out["slow_prefill_plane_fails"] is True
    assert out["prefill_plane_token_parity"] is True
    assert out["slow_device_transfer_fails"] is True
    assert out["transfer_byte_parity"] is True
    assert out["transfer_device_plane_used"] is True


def test_gate_tpu_floors():
    """Absolute floors (MBU, interference) fail a TPU run even when its
    baseline already regressed there — and never apply off-TPU."""
    tpu = dict(GOOD, device="TPU v5 lite0", mbu=0.82,
               mixed_prefill_decode={"interference_ratio": 0.88})
    assert gate.compare(tpu, tpu).ok

    low = dict(tpu, mbu=0.6)
    res = gate.compare(low, low)  # baseline equally low: floors still fail
    assert not res.ok
    assert res.floor_failures and res.floor_failures[0]["metric"] == "mbu"

    interfered = dict(tpu, mixed_prefill_decode={"interference_ratio": 0.7})
    res = gate.compare(interfered, tpu)
    assert not res.ok and res.floor_failures

    # ISSUE 9: a sharded engine whose per-chip throughput collapsed vs
    # meshless fails the floor; a single-chip round (no ratio) skips it.
    slow_sharded = dict(tpu, sharded_decode={"tok_s_per_chip_ratio": 0.5})
    res = gate.compare(slow_sharded, slow_sharded)
    assert not res.ok and any(
        f["metric"] == "sharded_decode.tok_s_per_chip_ratio"
        for f in res.floor_failures)
    ok_sharded = dict(tpu, sharded_decode={"tok_s_per_chip_ratio": 0.91})
    assert gate.compare(ok_sharded, ok_sharded).ok
    single_chip = dict(tpu, sharded_decode={"tp2": {"skipped": "1 chip"}})
    res = gate.compare(single_chip, single_chip)
    assert res.ok
    assert "floor:sharded_decode.tok_s_per_chip_ratio" in res.skipped

    # CPU artifacts carry no roofline: floors are skipped, not failed.
    cpu = dict(GOOD, device="TFRT_CPU_0", mbu=0.01)
    assert gate.compare(cpu, cpu).ok


def test_bench_gate_cli_compares_files(tmp_path):
    new = tmp_path / "new.json"
    base = tmp_path / "base.json"
    base.write_text(json.dumps(GOOD))
    new.write_text(json.dumps(dict(GOOD, serving_tok_s=8000.0 * 0.7)))
    from tools.bench_gate import main

    assert main([str(new), "--baseline", str(base)]) == 1
    new.write_text(json.dumps(GOOD))
    assert main([str(new), "--baseline", str(base)]) == 0
