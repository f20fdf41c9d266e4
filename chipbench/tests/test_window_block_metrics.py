"""The parallel window block's bytes and operations by its configuration's
keys, its layer metrics by hand on synthetic scrapes and a synthetic trace,
and what each reader does where what it reads is absent: None, and never a
raise (a reader that raised would end a traced run of whatever cell it is read
in)."""

import json
import os
import types

import pytest

from chipbench import manifest_form, run, window_block

CONFIG = "command-a-plus-05-2026-d4-ep8"
CELL = "command-a-plus.mixed-length"
NAMES = ["window_decode_step_mfu_share", "window_prefill_mfu_share",
         "window_attn_decode_roofline_share",
         "window_attn_prefill_roofline_share", "attn_rows_read_share",
         "window_pages_held_share", "moe_local_expert_roofline_share.gated"]
SHARED = ["program_store_hit_share", "req_cohort_wait_ms.mean",
          "cohort_joins_at_chunk_share", "moe_local_experts_touched_share",
          "moe_local_rows_per_touched_expert", "moe_local_assignments_share",
          "moe_local_expert_kernel_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
PAIRS = 'dynamo_worker_attn_%s_total{at="%s",kind="%s"}'
CALLS = 'dynamo_worker_attn_capture_calls_total{at="%s"}'
CAP = "dynamo_worker_moe_capture_%s_%s_total"
POOL = 'dynamo_kv_window_pool_blocks{state="%s"}'


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name=CONFIG):
    entry = {c["name"]: c for c in _bench()["configs"]}[name]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        return json.load(f)


def _page(scale):
    """A worker page after `scale` x.  Inside the capture: 40 decode steps of
    2 rows at contexts about 6,000 (a window layer reads 4,096 of them: 2 x
    (3 x 4,096 + 6,000) pair-layers a step), each of whose 4 expert layers
    touched 2 held experts with 2 held assignments; 4 prefill chunks of 512
    tokens behind 5,000 (pair-layers a chunk: 3 x 512 x 4,096 window, 512 x
    5,256 full), all 16 held experts touched, 512 held assignments a layer.
    Over the window, four times that."""
    dec = 2 * (3 * 4096 + 6000)
    dec_un = 2 * 4 * 6000
    pre_w, pre_f, pre_un = 3 * 512 * 4096, 512 * 5256, 4 * 512 * 5256
    page = {
        PAIRS % ("capture_pairs", "decode", "window"): 40 * 2 * 3 * 4096,
        PAIRS % ("capture_pairs", "decode", "full"): 40 * 2 * 6000,
        PAIRS % ("capture_pairs", "decode", "queries"): 40 * 2,
        PAIRS % ("capture_pairs", "prefill", "window"): 4 * pre_w,
        PAIRS % ("capture_pairs", "prefill", "full"): 4 * pre_f,
        PAIRS % ("capture_pairs", "prefill", "queries"): 4 * 512,
        CALLS % "decode": 40, CALLS % "prefill": 4,
        PAIRS % ("pairs", "decode", "window"): 160 * 2 * 3 * 4096,
        PAIRS % ("pairs", "decode", "full"): 160 * 2 * 6000,
        PAIRS % ("pairs", "decode", "unwindowed"): 160 * dec_un,
        PAIRS % ("pairs", "prefill", "window"): 16 * pre_w,
        PAIRS % ("pairs", "prefill", "full"): 16 * pre_f,
        PAIRS % ("pairs", "prefill", "unwindowed"): 16 * pre_un,
        CAP % ("decode", "experts_touched"): 160 * 2,
        CAP % ("decode", "local_assignments"): 160 * 2,
        CAP % ("decode", "layer_forwards"): 160,
        CAP % ("prefill", "experts_touched"): 16 * 16,
        CAP % ("prefill", "local_assignments"): 16 * 512,
        CAP % ("prefill", "layer_forwards"): 16}
    assert dec == 2 * 3 * 4096 + 2 * 6000
    return {k: v * scale for k, v in page.items()}


def _ctx(config=None, series=True, trace=True):
    pages = {"window_start": _page(0), "window_end": _page(1),
             "capture_start": _page(0), "capture_end": _page(1)}
    for at, (used, full) in zip(pages, ((60, 100), (90, 150), (60, 150),
                                        (90, 100))):
        pages[at][POOL % "used"], pages[at][POOL % "full_used"] = used, full
    if not series:        # the parent's program: no such series
        pages = {k: {kk: vv for kk, vv in v.items()
                     if "attn_" not in kk and "moe_capture" not in kk
                     and "window_pool" not in kk}
                 for k, v in pages.items()}
    scrapes = {k: {"worker": v, "frontend": {}} for k, v in pages.items()}

    def delta(source, key, scope="window"):
        a = (scrapes.get(f"{scope}_start") or {}).get(source)
        b = (scrapes.get(f"{scope}_end") or {}).get(source)
        if not a or not b or key not in a or key not in b:
            return None
        return b[key] - a[key]

    held = {"busy_s": 2.0,
            "kernels_s": {"attn_decode": 0.004, "window_attn_decode": 0.008,
                          "attn_prefill": 0.05, "window_attn_prefill": 0.1,
                          "moe_local": 0.4},
            "roles": {"decode": {"calls": 5, "seconds": 0.25, "steps": 40},
                      "prefill": {"calls": 4, "seconds": 0.8, "steps": 4}}}
    return types.SimpleNamespace(
        scrapes=scrapes, delta=delta, trace=held if trace else None,
        config=config or _config(), peaks=PEAKS)


def _read(name, ctx):
    return run.load_reader("layer_metrics", name).read(ctx)


def test_the_block_by_its_keys():
    hf = _config()
    assert window_block.kinds(hf) == {"window": 3, "full": 1}
    assert window_block.held(hf) == {"first": 0, "count": 16, "of": 128}
    # ISSUE 53's arithmetic: attention 142.6 M, one expert 50.33 M (100.7
    # MB), a layer outside its routed experts 344.5 M.
    assert window_block.attn_matmul_params(hf) == 142_606_336
    assert window_block.expert_params(hf) == 50_331_648
    assert window_block.expert_bytes(hf) == 100_663_296
    assert window_block.layer_matmul_params(hf) == pytest.approx(
        344.5e6, rel=1e-3)
    assert window_block.weight_bytes_every_row(hf) == pytest.approx(
        3.02e9, rel=2e-3)
    assert window_block.kv_row_bytes(hf) == 4096
    assert window_block.pair_operations(hf) == 4 * 128 * 128
    # The whole of what is held: 4.733 B parameters, 9.47 GB.
    held = (window_block.weight_bytes_every_row(hf)
            + 4 * 16 * window_block.expert_bytes(hf))
    assert held == pytest.approx(9.47e9, rel=2e-3)


def test_the_new_metrics_by_hand():
    ctx = _ctx()
    hf = ctx.config
    pairs = 2 * (3 * 4096 + 6000)
    step = (window_block.weight_bytes_every_row(hf) + 4 * 2 * 100_663_296
            + pairs * 4096)
    assert _read(NAMES[0], ctx) == pytest.approx(
        100 * 40 * step / (0.25 * 819e9))
    chunk_pairs = 3 * 512 * 4096 + 512 * 5256
    ops = 4 * (512 * window_block.token_matmul_operations(hf)
               + 4 * 512 * 2 * 50_331_648 + chunk_pairs * 65536)
    nbytes = 4 * (window_block.weight_bytes_every_row(hf)
                  + 4 * 16 * 100_663_296)
    # A chunk of 512 behind 5,000: its bytes (all 9.47 GB held) and its
    # operations need 11.6 and 11.2 ms of the chip: the bytes bind, barely.
    assert nbytes / 819e9 > ops / 197e12 > 0.9 * nbytes / 819e9
    assert _read(NAMES[1], ctx) == pytest.approx(100 * nbytes / 819e9 / 0.8)
    assert _read(NAMES[2], ctx) == pytest.approx(
        100 * 40 * pairs * 4096 / 819e9 / 0.012)
    assert _read(NAMES[3], ctx) == pytest.approx(
        100 * 4 * chunk_pairs * 65536 / 197e12 / 0.15)
    read = 160 * pairs + 16 * chunk_pairs
    total = 160 * 2 * 4 * 6000 + 16 * 4 * 512 * 5256
    assert _read(NAMES[4], ctx) == pytest.approx(100 * read / total)
    assert _read(NAMES[5], ctx) == pytest.approx(
        (60 + 60 + 40 + 90) / 4)
    dec = 40 * 4 * (2 * 100_663_296 + 2 * 2 * 4096 * 2) / 819e9
    pre = 4 * 4 * max((16 * 100_663_296 + 2 * 512 * 4096 * 2) / 819e9,
                      512 * 2 * 50_331_648 / 197e12)
    assert _read(NAMES[6], ctx) == pytest.approx(100 * (dec + pre) / 0.4)
    for name in NAMES:
        value = _read(name, ctx)
        assert value is not None and 0 < value < 100, name


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_returns_none_and_never_raises(name):
    """On the parent's program (no such series), without a capture, with a
    scrape lost or none, without the kernels' labels, without peaks, on
    every other configuration's file."""
    traced = name.endswith(("mfu_share", "roofline_share",
                            "roofline_share.gated"))
    parent = _ctx(series=False)
    assert _read(name, parent) is None
    if traced:
        assert _read(name, _ctx(trace=False)) is None
        unlabelled = _ctx()
        unlabelled.trace["kernels_s"] = {}
        if "roofline" in name:
            assert _read(name, unlabelled) is None
        no_peaks = _ctx()
        no_peaks.peaks = None
        assert _read(name, no_peaks) is None
    lost = _ctx()
    for at in lost.scrapes:
        lost.scrapes[at]["worker"] = None
    assert _read(name, lost) is None
    bare = _ctx()
    bare.scrapes.clear()
    assert _read(name, bare) is None
    for other in _bench()["configs"]:
        if other["name"] == CONFIG:
            continue
        assert _read(name, _ctx(_config(other["name"]), series=False)) \
            is None
        _read(name, _ctx(_config(other["name"])))      # must not raise


def test_the_manifest_holds_the_entries_by_name_and_lists_by_membership():
    bench = _bench()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == [CELL]
        mod = run.load_reader("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == tuple(
            entries[name][k] for k in ("layer", "unit", "source", "moves"))
    for name in SHARED:
        assert CELL in entries[name]["workloads"]
    # The two-matrix reader counts the latent form's bytes: not this cell's.
    assert CELL not in entries["moe_local_expert_roofline_share"]["workloads"]
    assert manifest_form.problems(bench, run.ROOT) == []
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert 0 < len(cell["why"]) <= 200 and cell["chips"] == 1
    with open(os.path.join(run.HERE, "cells", CELL + ".json")) as f:
        params = json.load(f)
    assert params["rate_rps"] == pytest.approx(0.8 * params["knee_rps"])


def test_the_shared_local_expert_readers_read_this_configuration():
    """The four `moe_local_*` entries the cell appends itself to read the
    configuration's `routed_experts_held.count` and nothing of a pattern."""
    ctx = _ctx()
    moe = "dynamo_worker_moe_%s_total"
    for at, n in (("window_start", 0), ("window_end", 1)):
        ctx.scrapes[at]["worker"].update({
            moe % "experts_touched": n * 1000, moe % "layer_forwards": n * 200,
            moe % "local_assignments": n * 2500,
            moe % "routed_assignments": n * 20000})
    assert _read("moe_local_experts_touched_share", ctx) == pytest.approx(
        100 * 1000 / (200 * 16))
    assert _read("moe_local_rows_per_touched_expert", ctx) == pytest.approx(
        2.5)
    assert _read("moe_local_assignments_share", ctx) == pytest.approx(12.5)
    assert _read("moe_local_expert_kernel_share", ctx) == pytest.approx(20.0)
