"""The pattern block's bytes and operations by its configuration's keys and
its pattern, its layer metrics by hand, and what each reader does
where what it reads is absent: None, and never a raise (a reader that raised
would end a traced run of whatever cell it is read in)."""

import json
import os
import types

import pytest

from chipbench import manifest_form, pattern_block, run

CONFIG = "nemotron-3-super-120b-a12b-d11-ep4"
CELL = "nemotron-3-super.reasoning"
NAMES = ["pattern_decode_step_mfu_share", "pattern_prefill_mfu_share",
         "moe_local_experts_touched_share",
         "moe_local_rows_per_touched_expert", "moe_local_assignments_share",
         "pattern_state_update_roofline_share",
         "moe_local_expert_roofline_share", "moe_local_expert_kernel_share"]
SHARED = ["program_store_hit_share", "req_cohort_wait_ms.mean",
          "cohort_joins_at_chunk_share", "ssm_slots_used_share",
          "ssm_state_bytes_per_seq", "ssm_state_update_kernel_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MOE = "dynamo_worker_moe_%s_total"
CAP = "dynamo_worker_moe_capture_%s_%s_total"
SSM_CAP = "dynamo_worker_ssm_capture_%s_total"


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name=CONFIG):
    entry = {c["name"]: c for c in _bench()["configs"]}[name]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        return json.load(f)


def _page(scale):
    """A worker page after `scale` x: 10 windows of 8 + 4 single steps (84
    decode steps over 40 live rows: 420 expert layer forwards that touched
    110 held experts each with 40 x 22 / 4 = 220 held assignments) and 5
    prefill chunks of 400 tokens (25 layer forwards, all 128 touched, 2,200
    held assignments each); half of each dispatched inside the capture."""
    dec_fw, pre_fw = 84 * 5, 5 * 5
    page = {
        "dynamo_worker_engine_window_dispatches": 10,
        "dynamo_worker_engine_single_step_dispatches": 4,
        "dynamo_worker_engine_prefill_dispatches": 5,
        "dynamo_worker_engine_kv_read_bytes_modeled": 4e8,
        "dynamo_worker_prefill_attn_pairs_total": 1.5e6,
        MOE % "experts_touched": dec_fw * 110 + pre_fw * 128,
        MOE % "layer_forwards": dec_fw + pre_fw,
        MOE % "local_assignments": dec_fw * 220 + pre_fw * 2200,
        MOE % "routed_assignments": 4 * (dec_fw * 220 + pre_fw * 2200),
        CAP % ("decode", "experts_touched"): dec_fw // 2 * 110,
        CAP % ("decode", "local_assignments"): dec_fw // 2 * 220,
        CAP % ("decode", "layer_forwards"): dec_fw // 2,
        CAP % ("prefill", "experts_touched"): 10 * 128,
        CAP % ("prefill", "local_assignments"): 10 * 2200,
        CAP % ("prefill", "layer_forwards"): 10,
        SSM_CAP % "decode_row_steps": 42 * 40,
        SSM_CAP % "decode_steps": 42,
        SSM_CAP % "prefill_tokens": 800, SSM_CAP % "prefill_calls": 2}
    return {k: v * scale for k, v in page.items()}


def _ctx(config=None, series=True, trace=True):
    pages = {"window_start": _page(0), "window_end": _page(1),
             "capture_start": _page(0), "capture_end": _page(1)}
    if not series:        # the parent's program: no such series
        pages = {k: {kk: vv for kk, vv in v.items()
                     if "moe_capture" not in kk and "local_" not in kk
                     and "routed_" not in kk and "ssm" not in kk}
                 for k, v in pages.items()}
    scrapes = {k: {"worker": v, "frontend": {}} for k, v in pages.items()}

    def delta(source, key, scope="window"):
        a = (scrapes.get(f"{scope}_start") or {}).get(source)
        b = (scrapes.get(f"{scope}_end") or {}).get(source)
        if not a or not b or key not in a or key not in b:
            return None
        return b[key] - a[key]

    held = {"busy_s": 2.0,
            "kernels_s": {"attn_decode": 0.05, "ssm_update": 0.35,
                          "ssm_scan": 0.03, "moe_local": 0.9},
            "roles": {"decode": {"calls": 14, "seconds": 1.4, "steps": 84},
                      "prefill": {"calls": 5, "seconds": 0.1, "steps": 5}}}
    return types.SimpleNamespace(
        scrapes=scrapes, delta=delta, trace=held if trace else None,
        config=config or _config(), peaks=PEAKS)


def _read(name, ctx):
    return run.load_reader("layer_metrics", name).read(ctx)


def test_the_block_by_its_keys_and_its_pattern():
    hf = _config()
    assert pattern_block.kinds(hf) == {"M": 5, "*": 1, "E": 5}
    assert pattern_block.d_ssm(hf) == 8192
    assert pattern_block.conv_dim(hf) == 10240
    # ISSUE 47's arithmetic: a mixer 109.64 M, attention 35.66 M, an expert
    # layer outside its routed experts 54.53 M, an expert 5.505 M.
    assert pattern_block.mixer_matmul_params(hf) \
        == 4096 * 18560 + 8192 * 4096
    assert pattern_block.mixer_bytes(hf) == pytest.approx(2 * 109.64e6,
                                                          rel=1e-3)
    assert pattern_block.attn_matmul_params(hf) == 35_651_584
    assert pattern_block.expert_layer_matmul_params(hf) \
        == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert pattern_block.expert_params(hf) == 5_505_024
    assert pattern_block.expert_bytes(hf) == 11_010_048
    assert pattern_block.weight_bytes_every_row(hf) \
        == pytest.approx(1.98e9, rel=2e-3)
    assert pattern_block.scan_state_bytes(hf) == 4_194_304
    assert pattern_block.state_bytes_per_seq(hf) == 21_278_720
    assert pattern_block.pair_operations(hf) == 4 * 32 * 128
    assert pattern_block.held(hf) == {"first": 0, "count": 128, "of": 512}
    # The whole of what is held: 4.648 B parameters, 9.30 GB.
    held = (pattern_block.weight_bytes_every_row(hf)
            + 5 * 128 * pattern_block.expert_bytes(hf)
            + hf["vocab_size"] * hf["hidden_size"] * 2)
    assert held == pytest.approx(9.30e9, rel=2e-3)


def test_the_new_metrics_by_hand():
    ctx = _ctx()
    hf = ctx.config
    step = (pattern_block.weight_bytes_every_row(hf) + 5 * 110 * 11_010_048
            + 40 * 2 * 21_278_720)
    assert _read(NAMES[0], ctx) == pytest.approx(
        100 * (84 * step + 4e8) / (1.4 * 819e9))
    nbytes = 5 * (pattern_block.weight_bytes_every_row(hf)
                  + 5 * 128 * 11_010_048)
    ops = 5 * (400 * (pattern_block.token_matmul_operations(hf)
                      + 5 * pattern_block.scan_operations_per_token(hf))
               + 5 * 2200 * 2 * 5_505_024) + 1.5e6 * 16384
    assert nbytes / 819e9 > ops / 197e12      # a chunk of 400: bytes bind
    assert _read(NAMES[1], ctx) == pytest.approx(
        100 * nbytes / 819e9 / 0.1)
    # The grouped kernel's two shares have no manifest entry while the cell
    # runs the dense expert path; their arithmetic stays held here.
    dec = 84 * 5 * (110 * 11_010_048 + 2 * 220 * 1024 * 2) / 819e9
    pre = 5 * 5 * (128 * 11_010_048 + 2 * 2200 * 1024 * 2) / 819e9
    assert pattern_block.local_expert_roofline_share(ctx) == pytest.approx(
        100 * (dec + pre) / 0.9)
    assert pattern_block.local_expert_kernel_share(ctx) == pytest.approx(
        100 * 0.9 / 2.0)
    touched = 420 * 110 + 25 * 128
    local = 420 * 220 + 25 * 2200
    assert _read(NAMES[2], ctx) == pytest.approx(
        100 * touched / (445 * 128))
    assert _read(NAMES[3], ctx) == pytest.approx(local / touched)
    assert _read(NAMES[4], ctx) == pytest.approx(25.0)
    assert _read(NAMES[5], ctx) == pytest.approx(
        100 * 84 * 40 * 5 * 2 * 4_194_304 / 819e9 / 0.35)
    for name in NAMES:
        value = _read(name, ctx)
        assert value is not None and value > 0, name
    for name in NAMES:                      # no share of a peak over 100
        if name.endswith(("mfu_share", "roofline_share")):
            assert _read(name, ctx) < 100, name


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_returns_none_and_never_raises(name):
    """On the parent's program (no such series), without a capture, with a
    scrape lost or none, without the kernel's label, without peaks, on every
    other configuration's file."""
    traced = name.endswith(("mfu_share", "roofline_share", "kernel_share"))
    parent = _ctx(series=False)
    parent.trace["kernels_s"].pop("moe_local")   # nor such a kernel
    assert _read(name, parent) is None
    if traced:
        assert _read(name, _ctx(trace=False)) is None
    lost = _ctx()
    for at in lost.scrapes:
        lost.scrapes[at]["worker"] = None
    assert _read(name, lost) is None or name.endswith("kernel_share")
    bare = _ctx()
    bare.scrapes.clear()
    assert _read(name, bare) is None or name.endswith("kernel_share")
    unlabelled = _ctx()
    unlabelled.trace["kernels_s"] = {"attn_decode": 0.1}
    if name.endswith(("roofline_share", "kernel_share")):
        assert _read(name, unlabelled) is None
    no_peaks = _ctx()
    no_peaks.peaks = None
    if traced and not name.endswith("kernel_share"):
        assert _read(name, no_peaks) is None
    for other in _bench()["configs"]:
        if other["name"] == CONFIG:
            continue
        theirs = _ctx(_config(other["name"]), series=False)
        theirs.trace["kernels_s"].pop("moe_local")
        assert pattern_block.local_expert_roofline_share(theirs) is None
        assert _read(name, theirs) is None
        _read(name, _ctx(_config(other["name"])))      # must not raise


def test_the_manifest_holds_the_entries_by_name_and_lists_by_membership():
    bench = _bench()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert CELL in entries[name]["workloads"]
        assert entries[name]["moves"] == "itl_ms.mean"
        mod = run.load_reader("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == tuple(
            entries[name][k] for k in ("layer", "unit", "source", "moves"))
    for name in SHARED:
        assert CELL in entries[name]["workloads"]
    # PR 44's four shares multiply by `num_hidden_layers` and would misread
    # a pattern: this cell is in none of them.
    for name in ("ssm_decode_step_mfu_share", "ssm_prefill_mfu_share",
                 "ssm_state_update_roofline_share",
                 "ssm_chunk_scan_roofline_share"):
        assert CELL not in entries[name]["workloads"]
    assert manifest_form.problems(bench, run.ROOT) == []
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert 0 < len(cell["why"]) <= 200 and cell["chips"] == 1
    with open(os.path.join(run.HERE, "cells", CELL + ".json")) as f:
        params = json.load(f)
    assert params["rate_rps"] == pytest.approx(0.8 * params["knee_rps"])
