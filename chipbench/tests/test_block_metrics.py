"""The layer metrics of the block-diffusion cell on recorded counters: each
reader on a synthetic run whose arithmetic can be done by hand, and None on a
program that has none of the series (the parent of the PR that added them),
so that its result line just leaves the metric out."""

import json
import os
import types

import pytest

from chipbench import run

FWD = 'dynamo_worker_diffusion_forwards_total{kind="%s"}'
NEW = ("denoise_forwards_per_block", "block_tokens_per_forward",
       "commit_forward_share", "moe_experts_touched_share",
       "moe_rows_per_touched_expert", "moe_expert_kernel_share",
       "moe_expert_roofline_share", "block_step_hbm_share")
HF = {"hidden_size": 2048, "moe_intermediate_size": 768, "head_dim": 128,
      "num_attention_heads": 32, "num_key_value_heads": 4,
      "num_hidden_layers": 7, "num_experts": 128, "vocab_size": 151936}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _page(denoise, commit, rows, toks, touched, touched_blk, assign, layers,
          kv):
    return {FWD % "denoise": denoise, FWD % "commit": commit,
            "dynamo_worker_diffusion_row_forwards_total": rows,
            "dynamo_worker_engine_decode_tokens_emitted": toks,
            "dynamo_worker_moe_experts_touched_total": touched,
            "dynamo_worker_diffusion_experts_touched_total": touched_blk,
            "dynamo_worker_moe_assignments_total": assign,
            "dynamo_worker_moe_layer_forwards_total": layers,
            "dynamo_worker_engine_kv_read_bytes_modeled": kv}


def _ctx(with_series=True):
    zero = _page(0, 0, 0, 0, 0, 0, 0, 0, 0)
    # The window: 100 block calls, 390 denoising forwards, 3 live rows.
    win = _page(390, 100, 1470, 1160, 180000, 171500, 720000, 3570, 10 ** 9)
    # The capture: 10 calls of 5 forwards and 2 prefill chunks.
    cap = _page(40, 10, 150, 120, 18200, 17500, 80000, 364, 10 ** 8)
    pages = {"window_start": zero, "window_end": win,
             "capture_start": zero, "capture_end": cap}
    if not with_series:
        pages = {k: {"dynamo_worker_engine_decode_tokens_emitted": 5}
                 for k in pages}
    scrapes = {k: {"worker": v, "frontend": {}} for k, v in pages.items()}

    def delta(source, key, scope="window"):
        a = scrapes[f"{scope}_start"][source]
        b = scrapes[f"{scope}_end"][source]
        if key not in a or key not in b:
            return None
        return b[key] - a[key]

    trace = {"busy_s": 2.0, "kernels_s": {"moe_expert": 0.4},
             "roles": {"decode": {"calls": 10, "seconds": 0.5, "steps": 50},
                       "prefill": {"calls": 2, "seconds": 0.03,
                                   "steps": 2}}}
    return types.SimpleNamespace(scrapes=scrapes, delta=delta, trace=trace,
                                 config=HF, peaks=PEAKS)


def _read(name, ctx):
    return run.load_reader("layer_metrics", name).read(ctx)


def test_the_counter_metrics_by_hand():
    ctx = _ctx()
    assert _read("denoise_forwards_per_block", ctx) == pytest.approx(3.9)
    assert _read("block_tokens_per_forward", ctx) \
        == pytest.approx(1160 / 1470)
    assert _read("commit_forward_share", ctx) \
        == pytest.approx(100 * 100 / 490)
    assert _read("moe_experts_touched_share", ctx) \
        == pytest.approx(100 * 180000 / (3570 * 128))
    assert _read("moe_rows_per_touched_expert", ctx) == pytest.approx(4.0)
    assert _read("moe_expert_kernel_share", ctx) == pytest.approx(20.0)


def test_the_expert_kernel_roofline_by_hand():
    """The capture's counters saw 364 expert layers = 52 forwards; the trace
    holds 10 calls x 5 forwards + 2 chunks = 52: scale 1."""
    ctx = _ctx()
    expert = 3 * 2048 * 768 * 2
    need = 18200 * expert + 80000 * 2 * 2048 * 2
    ops = 80000 * 6 * 2048 * 768
    assert need / 819e9 > ops / 197e12        # bandwidth binds
    assert _read("moe_expert_roofline_share", ctx) \
        == pytest.approx(100 * need / 819e9 / 0.4)
    # Twice the calls in the trace than between the scrapes: twice the need.
    ctx.trace["roles"]["decode"]["calls"] = 20
    ctx.trace["roles"]["prefill"]["calls"] = 4
    assert _read("moe_expert_roofline_share", ctx) \
        == pytest.approx(200 * need / 819e9 / 0.4)


def test_the_block_step_bandwidth_share_by_hand():
    ctx = _ctx()
    attn = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
    layer = attn + 2048 * 128 + 2 * 2048 + 2 * 128
    dense = (7 * layer + 2048 + 2048 * 151936) * 2
    assert 37e6 < attn * 2 < 38e6             # "38 MB of attention a layer"
    need = 50 * dense + 17500 * 3 * 2048 * 768 * 2 + 10 ** 8
    got = _read("block_step_hbm_share", ctx)
    assert got == pytest.approx(100 * need / (0.5 * 819e9))
    assert 0 < got < 105


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none_not_an_error(name):
    """On the parent's program (no such series) and on an untraced run."""
    ctx = _ctx(with_series=False)
    ctx.trace["kernels_s"] = {}       # nor does its trace hold the kernel
    assert _read(name, ctx) is None
    ctx = _ctx()
    ctx.trace = None
    if name.startswith(("moe_expert_", "block_step")):
        assert _read(name, ctx) is None


def test_the_new_metrics_list_the_cell_and_nothing_else_changed():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["sdar-30b-a3b.block-gen"]
        assert by_name[name]["moves"] == "itl_ms.mean"
    cell = [w for w in bench["workloads"]
            if w["name"] == "sdar-30b-a3b.block-gen"][0]
    assert cell["chips"] == 1 and cell["traffic"] == "block-gen"
    assert "sdar-30b-a3b.block-gen" not in by_name["decode_hbm_share"][
        "workloads"]
