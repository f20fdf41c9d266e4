"""What the latent-attention configuration brings, at tiny widths on the CPU:
`references/mla_shared_routed_moe.py` against the program (prefill in chunks,
then decode, through the paged latent cache); `comparisons/
causal_logits_long_routed.py` refusing a lower precision than stated (latent
rows through 8 bits) and a recording with a hole in it; the block's bytes and
operations by hand; and each new layer metric on a synthetic run, None where
there is nothing to read (the parent's program)."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import check, latent_block, pieces, run

CELL = "glm-4.7-flash.long-context"
NEW = ("latent_decode_hbm_share", "latent_prefill_mfu_share",
       "latent_attn_decode_roofline_share",
       "latent_attn_prefill_roofline_share", "latent_cache_bytes_per_token",
       "moe_experts_touched_share.causal", "moe_expert_roofline_share.causal",
       "moe_rows_per_touched_expert.causal", "moe_expert_kernel_share.causal")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LENGTHS = (5, 17, 40)


def _config():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        return bench, cell, json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(hf at rehearsal widths, float32 config, params, reference)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama, loader

    hf = _config()[2]
    hf.update(hf["cpu_rehearsal"])
    cfg = loader.config_from_hf(hf, "t").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(2))
    return hf, cfg, params, pieces.load("references", hf["reference"])


def _engine(cfg, **kw):
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    return EngineCore(EngineConfig(
        model=cfg, num_blocks=64, enable_prefix_cache=False, decode_window=8,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=8,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32)), **kw))


def test_reference_matches_the_program_through_the_latent_cache(tiny):
    """Prefill in chunks of 16, then eight single decode steps, through the
    paged latent cache (gather path, absorbed read) against the reference's
    materialised full forward: every position's logits."""
    import jax

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    hf, cfg, params, ref = tiny
    n = 45
    tokens = np.random.default_rng(1).integers(1, 500, size=n).astype(np.int32)
    want = np.asarray(ref.forward(hf, params, tokens.tolist()))
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, num_blocks=8, block_size=16))
    step = jax.jit(llama.make_forward_step(cfg, 16, with_expert_load=True))
    pages = np.array([[2, 5, 3]], np.int32)
    edges = [0, 16, 32, 37] + list(range(38, n + 1))
    for lo, hi in zip(edges, edges[1:]):
        logits, cache, _ = step(
            params, cache, tokens[None, lo:hi],
            np.arange(lo, hi, dtype=np.int32)[None],
            np.array([hi], np.int32), pages, None)
        np.testing.assert_allclose(np.asarray(logits[0]), want[lo:hi],
                                   atol=3e-5)
    only = np.asarray(ref.forward(hf, params, tokens.tolist(),
                                  positions=[4, n - 1]))
    np.testing.assert_allclose(only, want[[4, n - 1]], atol=1e-6)


def test_the_blocks_of_the_reference_change_nothing(tiny, monkeypatch):
    """Query blocks, token blocks, expert chunks and vocabulary slices are
    how it fits, not what it computes."""
    hf, _cfg, params, ref = tiny
    tokens = np.random.default_rng(2).integers(1, 500, size=37).tolist()
    whole = np.asarray(ref.forward(hf, params, tokens))
    for name, value in (("QUERY_BLOCK", 8), ("TOKEN_BLOCK", 16),
                        ("EXPERT_CHUNK", 3), ("VOCAB_CHUNK", 200)):
        monkeypatch.setattr(ref, name, value)
    np.testing.assert_allclose(np.asarray(ref.forward(hf, params, tokens)),
                               whole, atol=2e-5)


def _over(out):
    return {i["name"] for i in out["limits"] if i["value"] > i["limit"]}


def test_latent_rows_through_eight_bits_fail_the_limits(tiny, monkeypatch):
    """The nearest precision below the stated one for what this
    configuration adds: each latent row rounded through float8_e4m3 as it is
    written to the cache.  Refused by both logits limits."""
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    hf, cfg, _params, _ref = tiny
    assert check.run_check(_engine(cfg), hf, 11, LENGTHS)["ok"] is True
    project = llama._latent_project

    def rounded(cfg, p_attn, x, positions):
        q_abs, rows = project(cfg, p_attn, x, positions)
        return q_abs, rows.astype(jnp.float8_e4m3fn).astype(rows.dtype)

    monkeypatch.setattr(llama, "_latent_project", rounded)
    out = check.run_check(_engine(cfg), hf, 11, LENGTHS)
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    assert {"max_abs_logit_diff", "max_body_logit_diff"} <= _over(out), \
        out["limits"]


@pytest.mark.parametrize("fault", ["bias_left_out", "chosen_by_weight"])
def test_a_router_that_chooses_wrongly_fails_the_shortfall(tiny, monkeypatch,
                                                           fault):
    """The reference takes the engine's expert choices, so the logits limits
    cannot see a router that chooses consistently wrongly; the shortfall of
    each choice under the reference's own k-th best `s + b` does.  Two such
    routers: the correction bias left out (choice by `s`), and the choice
    made by the renormalised gate instead of `s + b`."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import moe

    hf, cfg, _params, _ref = tiny
    sound = check.run_check(_engine(cfg), hf, 11, LENGTHS)
    read = {i["name"]: i["value"] for i in sound["limits"]}
    assert sound["ok"] is True and read["max_choice_shortfall"] < 1e-4
    topk = moe.router_topk

    def wrong(c, p, x):
        if fault == "bias_left_out":
            return topk(c, dict(p, router_bias=jnp.zeros_like(
                p["router_bias"])), x)
        scores = jax.nn.sigmoid(jnp.dot(x, p["router"]).astype(jnp.float32))
        _, idx = jax.lax.top_k(scores - p["router_bias"],
                               c.num_experts_per_token)
        return idx, topk(c, p, x)[1]

    monkeypatch.setattr(moe, "router_topk", wrong)
    out = check.run_check(_engine(cfg), hf, 11, LENGTHS)
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    assert "max_choice_shortfall" in _over(out), out["limits"]


def test_the_shortfall_by_hand():
    """Scores + bias 0.9 0.7 0.6 0.2, k = 2: the second best is 0.7.  A row
    given experts (0, 2) falls 0.1 short, (1, 0) nothing, a row of -1
    chooses here and falls nothing short."""
    import jax.numpy as jnp

    ref = pieces.load("references", "mla_shared_routed_moe")
    h = jnp.ones((1, 1), jnp.float32)
    logit = lambda p: float(np.log(p / (1 - p)))
    router = jnp.asarray([[logit(0.8), logit(0.5), logit(0.6), logit(0.3)]],
                         jnp.float32)
    bias = jnp.asarray([0.1, 0.2, 0.0, -0.1], jnp.float32)
    for chosen, want in (([[0, 2]], 0.1), ([[1, 0]], 0.0), ([[-1, -1]], 0.0),
                         ([[3, 0]], 0.5)):
        _w, short = ref._route(h, router, bias,
                               jnp.asarray(chosen, jnp.int32), top_k=2,
                               factor=1.0)
        assert float(short) == pytest.approx(want, abs=1e-6)


def test_a_recording_with_a_hole_is_a_failure_not_a_skip(tiny):
    """The reference may not choose experts for a position the engine
    decoded: a decode call left out of the recording fails the prompts it
    fed."""
    hf, cfg, _params, _ref = tiny
    core = _engine(cfg)
    record = core._record_decode
    core._record_decode = lambda *a, **kw: None
    try:
        out = check.run_check(core, hf, 11, LENGTHS)
    finally:
        core._record_decode = record
    assert out["ok"] is False and out["compared"] == 0
    assert any("no expert choices" in p for p in out["problems"])


def test_the_blocks_bytes_and_operations_by_hand():
    hf = _config()[2]
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert latent_block.attn_params(hf) == attn + 768 + 512
    assert attn == pytest.approx(21.76e6, rel=1e-3)
    expert = 3 * 2048 * 1536
    assert latent_block.expert_bytes(hf) == 2 * expert == 18874368
    dense = (8 * (attn + 768 + 512 + 2 * 2048) + 3 * 2048 * 10240
             + 7 * (2048 * 64 + expert) + 2048 + 2048 * 154880)
    assert latent_block.dense_bytes_per_step(hf) == 2 * dense + 7 * 64 * 4
    assert 2 * dense == pytest.approx(1.242e9, rel=2e-3)
    assert latent_block.row_bytes(hf) == 1280        # 640 values stored
    assert latent_block.pair_operations(hf) == 2 * 20 * 1088
    # 1.136 GFLOP a token through every layer's matrices.
    macs = 8 * attn + 3 * 2048 * 10240 + 7 * (2048 * 64 + 5 * expert)
    assert latent_block.token_matmul_operations(hf) == 2 * macs
    assert 2 * macs == pytest.approx(1.136e9, rel=2e-3)


def _page(windows, singles, prefills, tokens, kv, touched, dec_touched,
          assigned, layers, pairs):
    return {"dynamo_worker_engine_window_dispatches": windows,
            "dynamo_worker_engine_single_step_dispatches": singles,
            "dynamo_worker_engine_prefill_dispatches": prefills,
            "dynamo_worker_engine_prefill_tokens_dispatched": tokens,
            "dynamo_worker_engine_kv_read_bytes_modeled": kv,
            "dynamo_worker_moe_experts_touched_total": touched,
            "dynamo_worker_moe_decode_experts_touched_total": dec_touched,
            "dynamo_worker_moe_assignments_total": assigned,
            "dynamo_worker_moe_layer_forwards_total": layers,
            "dynamo_worker_prefill_attn_pairs_total": pairs,
            'dynamo_kv_bytes_per_block{pool="device"}': 64 * 10240}


def _ctx(with_series=True):
    zero = _page(0, 0, 0, 0, 0, 0, 0, 0, 0, 0)
    # The capture's scrapes: 10 windows of 8 + 4 single steps = 84 decode
    # steps, 5 prefill chunks of 400 tokens at 1.5 M pairs; 89 forwards x 7
    # expert layers.
    cap = _page(10, 4, 5, 2000, 8 * 10 ** 9, 30000, 20000, 21000, 623,
                1.5e6)
    pages = {"window_start": zero, "window_end": cap,
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

    trace = {"busy_s": 2.0,
             "kernels_s": {"attn_decode": 0.2, "attn_prefill": 0.05,
                           "moe_expert": 0.8},
             "roles": {"decode": {"calls": 14, "seconds": 0.9, "steps": 84},
                       "prefill": {"calls": 5, "seconds": 0.12, "steps": 5}}}
    return types.SimpleNamespace(scrapes=scrapes, delta=delta, trace=trace,
                                 config=_config()[2], peaks=PEAKS)


def _read(name, ctx):
    return run.load_reader("layer_metrics", name).read(ctx)


def test_the_new_metrics_by_hand():
    ctx = _ctx()
    hf = ctx.config
    dense = latent_block.dense_bytes_per_step(hf)
    need = 84 * dense + 20000 * 18874368 + 8e9
    assert _read("latent_decode_hbm_share", ctx) \
        == pytest.approx(100 * need / (0.9 * 819e9))
    ops = 2000 * latent_block.token_matmul_operations(hf) \
        + 1.5e6 * 43520 * 8
    assert _read("latent_prefill_mfu_share", ctx) \
        == pytest.approx(100 * ops / (0.12 * 197e12))
    # Decode attention: bytes bind.
    assert 8e9 / 819e9 > 8e9 / 1280 * 43520 / 197e12
    assert _read("latent_attn_decode_roofline_share", ctx) \
        == pytest.approx(100 * 8e9 / 819e9 / 0.2)
    # Prefill attention: operations bind.
    assert _read("latent_attn_prefill_roofline_share", ctx) \
        == pytest.approx(100 * 1.5e6 * 43520 * 8 / 197e12 / 0.05)
    assert _read("latent_cache_bytes_per_token", ctx) == 10240
    assert _read("moe_experts_touched_share.causal", ctx) \
        == pytest.approx(100 * 30000 / (623 * 64))
    assert _read("moe_rows_per_touched_expert.causal", ctx) \
        == pytest.approx(0.7)
    assert _read("moe_expert_kernel_share.causal", ctx) == pytest.approx(40.0)
    need = 30000 * 18874368 + 21000 * 2 * 2048 * 2
    assert _read("moe_expert_roofline_share.causal", ctx) \
        == pytest.approx(100 * need / 819e9 / 0.8)
    for name in NEW:
        if "share" in name:
            assert 0 < _read(name, ctx) < 105, name
    # Twice the steps and calls in the trace than between the scrapes:
    # twice the tallies' part of the need.
    ctx.trace["roles"]["decode"].update(calls=28, steps=168)
    assert _read("latent_attn_decode_roofline_share", ctx) \
        == pytest.approx(200 * 8e9 / 819e9 / 0.2)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none_not_an_error(name):
    """On the parent's program (no such series, no such kernels) and on an
    untraced run."""
    ctx = _ctx(with_series=False)
    ctx.trace["kernels_s"] = {}
    assert _read(name, ctx) is None
    ctx = _ctx()
    ctx.trace = None
    if name.startswith("latent_") and "bytes" not in name \
            or "kernel" in name or "roofline" in name:
        assert _read(name, ctx) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    bench, cell, hf = _config()
    assert cell["chips"] == 1 and cell["traffic"] == "long-context"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "itl_ms.mean"
    assert CELL in by_name["program_store_hit_share"]["workloads"]
    with open(os.path.join(run.HERE, "cells", CELL + ".json")) as f:
        params = json.load(f)
    assert params["rate_rps"] == pytest.approx(0.8 * params["knee_rps"])
    mix = run.load_cell(CELL)[4]
    assert mix["input_tokens"] == {"median": 6144, "sigma": 0.6,
                                   "min": 1024, "max": 12288}
    flags = hf["engine_flags"]
    assert int(flags[flags.index("--max-context") + 1]) \
        == mix["input_tokens"]["max"] + mix["output_tokens"]["max"]
    assert sorted(hf["reduced"]) == ["num_hidden_layers",
                                     "num_nextn_predict_layers"]


def test_the_new_entries_keep_the_manifests_form():
    """What the driver refuses before any run (PR 36's first hand-in: a
    configuration's `why` of 202 characters)."""
    import re
    bench, cell, _ = _config()
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
    metrics = [m for m in bench["per_layer"] if m["name"] in NEW]
    lines = [entry["why"], entry["source"], cell["why"]] \
        + [m["layer"] for m in metrics]
    for text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    names = [entry["name"], cell["name"], cell["traffic"]] \
        + entry["reduced"] + [m["name"] for m in metrics]
    for text in names:
        assert name.fullmatch(text), text
    for m in metrics:
        assert unit.fullmatch(m["unit"]), m["unit"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) <= 64 * 1024
