"""The layer metrics of the hybrid block with recurrent state, on recorded
counters: each reader's arithmetic by hand over a hand-made page, and None
(never an exception) on a run with none of the new series, without a capture,
with a page lost, and on another configuration's file: what the parent of the
PR that added them, and every accepted cell, hand it."""

import json
import os
import types

import pytest

from chipbench import manifest_form, run, state_block

CELL = "falcon-h1-34b.chat-short"
NAMES = ["ssm_decode_step_mfu_share", "ssm_prefill_mfu_share",
         "ssm_state_update_roofline_share", "ssm_chunk_scan_roofline_share",
         "ssm_state_update_kernel_share", "ssm_state_bytes_per_seq",
         "ssm_slots_used_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
ROW_STEPS = "dynamo_worker_ssm_decode_row_steps_total"
SCANNED = "dynamo_worker_ssm_prefill_tokens_total"
IN_CAPTURE = "dynamo_worker_ssm_capture_%s_total"


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name="falcon-h1-34b-instruct-d6"):
    entry = {c["name"]: c for c in _bench()["configs"]}[name]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        return json.load(f)


def _page(windows, singles, prefills, tokens, kv, pairs, row_steps, scanned,
          used, in_capture=(0, 0, 0, 0)):
    return {**dict(zip((IN_CAPTURE % what for what in (
                "decode_row_steps", "decode_steps", "prefill_tokens",
                "prefill_calls")), in_capture)),
            "dynamo_worker_engine_window_dispatches": windows,
            "dynamo_worker_engine_single_step_dispatches": singles,
            "dynamo_worker_engine_prefill_dispatches": prefills,
            "dynamo_worker_engine_prefill_tokens_dispatched": tokens,
            "dynamo_worker_engine_kv_read_bytes_modeled": kv,
            "dynamo_worker_prefill_attn_pairs_total": pairs,
            ROW_STEPS: row_steps, SCANNED: scanned,
            "dynamo_worker_ssm_prefill_segments_total": 3 * prefills,
            'dynamo_ssm_state_slots{state="used"}': used,
            'dynamo_ssm_state_slots{state="capacity"}': 64,
            "dynamo_ssm_state_bytes_per_slot": 25350144}


def _ctx(config=None, series=True, trace=True):
    # The capture's scrapes: 10 windows of 8 + 4 single steps = 84 decode
    # steps over 40 live rows, 5 prefill chunks of 400 tokens at 1.5 M pairs.
    # Of them the engine dispatched 5 windows and 2 single steps (42 steps,
    # at 40 rows too) and 2 chunks while the capture ran.
    zero = _page(0, 0, 0, 0, 0, 0, 0, 0, 32)
    cap = _page(10, 4, 5, 2000, 4 * 10 ** 9, 1.5e6, 84 * 40, 2000, 48,
                in_capture=(42 * 40, 42, 800, 2))
    pages = {"window_start": zero, "window_end": cap,
             "capture_start": zero, "capture_end": cap}
    if not series:
        pages = {k: {kk: vv for kk, vv in v.items() if "ssm" not in kk}
                 for k, v in pages.items()}
    scrapes = {k: {"worker": v, "frontend": {}} for k, v in pages.items()}

    def delta(source, key, scope="window"):
        a = (scrapes.get(f"{scope}_start") or {}).get(source)
        b = (scrapes.get(f"{scope}_end") or {}).get(source)
        if not a or not b or key not in a or key not in b:
            return None
        return b[key] - a[key]

    held = {"busy_s": 2.0,
            "kernels_s": {"attn_decode": 0.1, "attn_prefill": 0.02,
                          "ssm_update": 0.4, "ssm_scan": 0.03},
            "roles": {"decode": {"calls": 14, "seconds": 1.3, "steps": 84},
                      "prefill": {"calls": 5, "seconds": 0.12, "steps": 5}}}
    return types.SimpleNamespace(
        scrapes=scrapes, delta=delta, trace=held if trace else None,
        config=config or _config(), peaks=PEAKS)


def _read(name, ctx):
    return run.load_reader("layer_metrics", name).read(ctx)


def test_the_block_by_its_keys():
    hf = _config()
    assert state_block.mixer_matmul_params(hf) == 5120 * 9248 + 4096 * 5120
    assert state_block.attn_params(hf) == 31_457_280
    assert state_block.layer_matmul_params(hf) \
        == 31_457_280 + 68_321_280 + 330_301_440
    # The issue's 7.84 GB a step: six layers and the head.
    assert state_block.weight_bytes_per_step(hf) \
        == pytest.approx(7.836e9, rel=1e-3)
    assert state_block.scan_state_bytes(hf) == 4_194_304
    assert state_block.state_bytes_per_seq(hf) == 25_165_824 + 184_320
    assert state_block.pair_operations(hf) == 4 * 20 * 128
    assert state_block.scan_operations_per_token(hf) \
        == 2 * 128 * 256 * 2 + 2 * 128 * 4096 + 4 * 4096 * 256


def test_the_new_metrics_by_hand():
    ctx = _ctx()
    hf = ctx.config
    rows = 84 * 40
    need = 84 * state_block.weight_bytes_per_step(hf) \
        + rows * 2 * 25350144 + 4e9
    assert _read("ssm_decode_step_mfu_share", ctx) \
        == pytest.approx(100 * need / (1.3 * 819e9))
    ops = 6 * (2000 * 2 * state_block.layer_matmul_params(hf)
               + 1.5e6 * 10240 + 2000 * 5_373_952)
    assert _read("ssm_prefill_mfu_share", ctx) \
        == pytest.approx(100 * ops / (0.12 * 197e12))
    assert _read("ssm_state_update_roofline_share", ctx) \
        == pytest.approx(100 * rows * 6 * 2 * 4194304 / 819e9 / 0.4)
    assert _read("ssm_chunk_scan_roofline_share", ctx) \
        == pytest.approx(100 * 2000 * 6 * 5_373_952 / 197e12 / 0.03)
    assert _read("ssm_state_update_kernel_share", ctx) \
        == pytest.approx(100 * 0.4 / 2.0)
    assert _read("ssm_state_bytes_per_seq", ctx) == 25350144
    assert _read("ssm_slots_used_share", ctx) \
        == pytest.approx(100 * (32 + 48 + 32 + 48) / 4 / 64)
    for name in NAMES:
        value = _read(name, ctx)
        assert value is not None and value > 0, name
    # No share of a peak over 100 on numbers a chip could give.
    for name in NAMES[:4]:
        assert _read(name, ctx) < 100, name


def test_the_trace_holds_another_count_of_steps_than_the_counters():
    """The counters' edges and the capture's are not the same instants: the
    rows a step and the tokens a call come from the calls dispatched inside
    the capture and are laid over the steps and calls the trace holds."""
    ctx = _ctx()
    ctx.trace["roles"]["decode"]["steps"] = 42           # half of 84
    hf = ctx.config
    need = 42 * state_block.weight_bytes_per_step(hf) \
        + 42 * 40 * 2 * 25350144 + 0.5 * 4e9
    assert _read("ssm_decode_step_mfu_share", ctx) \
        == pytest.approx(100 * need / (1.3 * 819e9))
    assert _read("ssm_state_update_roofline_share", ctx) \
        == pytest.approx(100 * 42 * 40 * 6 * 2 * 4194304 / 819e9 / 0.4)


def test_rows_that_rise_after_the_trace_do_not_reach_its_shares():
    """What refused the second hand-in: the capture's second scrape comes
    when the profile is collected, here after a drain at three times the
    rows, and a share read off those scrapes would pass 100 %."""
    ctx = _ctx()
    quiet_rows = _read("ssm_state_update_roofline_share", ctx)
    end = ctx.scrapes["capture_end"]["worker"]
    end[ROW_STEPS] *= 3
    end[SCANNED] *= 3
    end["dynamo_worker_engine_prefill_tokens_dispatched"] *= 3
    assert _read("ssm_state_update_roofline_share", ctx) == quiet_rows
    assert _read("ssm_chunk_scan_roofline_share", ctx) \
        == pytest.approx(100 * 2000 * 6 * 5_373_952 / 197e12 / 0.03)
    # And with none dispatched inside the capture there is nothing to read.
    for what in ("decode_steps", "prefill_calls"):
        end[IN_CAPTURE % what] = 0
    for name in NAMES[:4]:
        assert _read(name, ctx) is None, name


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_returns_none_and_never_raises(name):
    """A program without the series (this PR's parent), a run without a
    capture, a lost page, a label the trace does not hold, and every other
    configuration's file."""
    trace_free = ("ssm_state_bytes_per_seq", "ssm_slots_used_share")
    kernel_only = name == "ssm_state_update_kernel_share"   # the trace alone
    if kernel_only:
        # The parent has no such kernel: the label's regex meets nothing.
        parent = _ctx(series=False)
        parent.trace["kernels_s"]["ssm_update"] = 0.0
        assert _read(name, parent) is None
    else:
        assert _read(name, _ctx(series=False)) is None
    if name not in trace_free:
        assert _read(name, _ctx(trace=False)) is None
    lost = _ctx()
    for at in lost.scrapes:
        lost.scrapes[at]["worker"] = None
    assert kernel_only or _read(name, lost) is None
    bare = _ctx()
    bare.scrapes.clear()
    assert kernel_only or _read(name, bare) is None
    unlabelled = _ctx()
    unlabelled.trace["kernels_s"] = {"attn_decode": 0.1}
    if "update" in name or "scan" in name:
        assert _read(name, unlabelled) is None
    for other in _bench()["configs"]:
        if other["name"] == "falcon-h1-34b-instruct-d6":
            continue
        # Its program has no such series and its file no such key.
        theirs = _ctx(_config(other["name"]), series=False)
        theirs.trace["kernels_s"].pop("ssm_update")
        theirs.trace["kernels_s"].pop("ssm_scan")
        assert _read(name, theirs) is None
        got = _read(name, _ctx(_config(other["name"])))
        assert got is None or name in trace_free or kernel_only
    no_peaks = _ctx()
    no_peaks.peaks = None
    if name.endswith("_share") and name not in trace_free \
            and "kernel_share" not in name:
        assert _read(name, no_peaks) is None


def test_the_manifest_lists_the_seven_for_their_cell_alone():
    bench = _bench()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == NAMES
    for name in NAMES:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "itl_ms.mean"
        mod = run.load_reader("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == tuple(
            entries[name][k] for k in ("layer", "unit", "source", "moves"))
    for name in ("program_store_hit_share", "req_cohort_wait_ms.mean",
                 "cohort_joins_at_chunk_share"):
        assert entries[name]["workloads"][-1] == CELL
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert sorted(listed) == sorted(NAMES + [
        "program_store_hit_share", "req_cohort_wait_ms.mean",
        "cohort_joins_at_chunk_share"])
    assert manifest_form.problems(bench, run.ROOT) == []
    for kind in ("configs", "workloads"):
        for entry in bench[kind]:
            assert 0 < len(entry["why"]) <= 200, entry["name"]
    for entry in bench["configs"]:
        assert len(entry["source"]) <= 200
    for entry in bench["per_layer"]:
        assert 0 < len(entry["layer"]) <= 200
