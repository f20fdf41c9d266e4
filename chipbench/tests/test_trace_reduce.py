"""The trace reduction: exact arithmetic on a hand-made capture, and
invariants on the recorded TPU capture in `testdata/`."""

import json
import os

import pytest

from chipbench import trace_reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")

MS = 1e6  # ns
ROLES = {"decode": {"jit_run": 8, "jit_fused": 1}, "prefill": {"jit_step": 1}}


def _hand_made():
    """One chip, 100 ms: two decode windows, a prefill and a single decode
    step, with gaps."""
    return {
        "/device:TPU:0": {
            "XLA Modules": [["jit_run(11)", 10 * MS, 20 * MS],
                            ["jit_step(12)", 40 * MS, 10 * MS],
                            ["jit_run(11)", 60 * MS, 20 * MS],
                            ["jit_fused(13)", 80 * MS, 0.0]],
            "XLA Ops": [["%while.6 = (s32[]) while(...)", 10 * MS, 20 * MS],
                        ["%fusion.1 = bf16[2,4096] fusion(%p0)", 10 * MS, 8 * MS],
                        ["paged_decode_attention.3", 20 * MS, 10 * MS],
                        ["fusion.9", 40 * MS, 4 * MS],
                        ["paged_prefill_attention.2", 44 * MS, 6 * MS],
                        ["fusion.1", 60 * MS, 8 * MS],
                        ["paged_decode_attention.3", 70 * MS, 10 * MS]]},
        "/host:CPU": {
            "python": [["step", 0.0, 9 * MS], ["_sync_one_window", 30 * MS, 10 * MS],
                       ["idle_wait", 80 * MS, 20 * MS]]},
    }


def test_hand_made_capture_reduces_exactly():
    out = trace_reduce.reduce(
        _hand_made(),
        roles=ROLES,
        kernels={"attn_decode": "paged_decode_attention",
                 "attn_prefill": "paged_prefill_attention"})
    assert out["devices"] == 1
    # The window is the span of the chip's own events: 10 .. 80 ms.
    assert out["window_s"] == pytest.approx(0.070)
    # Ops: the enclosing while 20 (covers 8+10 and the 2 ms between them)
    # + 4+6 + 8+10 = 48 ms busy; the while is in no table of operations.
    assert out["busy_s"] == pytest.approx(0.048)
    assert out["idle_share"] == pytest.approx(22 / 70)
    assert not any("while" in k for k, _ in out["device_ops"])
    assert out["programs"]["jit_run"] == {"seconds": pytest.approx(0.040),
                                          "calls": 2}
    # Two windows of eight steps and one single step: 17 steps in 3 calls.
    assert out["roles"]["decode"]["calls"] == 3
    assert out["roles"]["decode"]["steps"] == 17
    assert out["roles"]["decode"]["seconds"] == pytest.approx(0.040)
    assert out["roles"]["prefill"]["steps"] == 1
    assert out["roles"]["prefill"]["seconds"] == pytest.approx(0.010)
    assert out["kernels_s"]["attn_decode"] == pytest.approx(0.020)
    assert out["kernels_s"]["attn_prefill"] == pytest.approx(0.006)
    ops = dict(out["device_ops"])
    assert ops["jit_run/paged_decode_attention"] == pytest.approx(0.020)
    assert ops["jit_step/fusion"] == pytest.approx(0.004)
    # Gaps between programs: 30-40 and 50-60 ms.
    assert out["idle_gap_total_s"] == pytest.approx(0.020)
    gaps = dict(out["idle_gaps"])
    assert gaps["jit_run->jit_step | host: _sync_one_window"] == \
        pytest.approx(0.010)
    assert gaps["jit_step->jit_run"] == pytest.approx(0.010)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_reduce.reduce({"/host:CPU": {"python": [["x", 0.0, 1.0]]}})


def test_two_chips_average():
    ev = _hand_made()
    ev["/device:TPU:1"] = {"XLA Ops": [["fusion.1", 0.0, 100 * MS]]}
    out = trace_reduce.reduce(ev)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((0.048 + 0.100) / 2)


def _recorded():
    found = sorted(f for f in os.listdir(TESTDATA)
                   if f.endswith(".events.json"))
    return [os.path.join(TESTDATA, f) for f in found]


@pytest.mark.parametrize("path", _recorded(),
                         ids=[os.path.basename(p) for p in _recorded()])
def test_recorded_tpu_capture(path):
    with open(path) as f:
        events = json.load(f)
    out = trace_reduce.reduce(
        events,
        roles=ROLES,
        kernels={"attn_decode": "paged_decode_attention",
                 "attn_prefill": "paged_prefill_attention"})
    assert out["devices"] >= 1
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 <= out["idle_share"] < 1
    assert out["roles"]["decode"]["steps"] >= out["roles"]["decode"]["calls"] > 0
    assert out["kernels_s"]["attn_decode"] > 0
    assert out["busy_s"] + out["idle_gap_total_s"] <= out["window_s"] * 1.001 \
        or out["idle_gap_total_s"] <= out["window_s"]
    assert sum(s for _n, s in out["device_ops"]) <= out["busy_s"] * 1.001
