"""Layers of different kinds by a pattern (the `nemotron_h` block: a Mamba-2
mixer, attention without a position term, or latent experts, each alone in
its layer) and the chip's share of an expert layer, at tiny widths on the CPU
with seeded weights: the cache's leaves by layer kind and its byte counts at
the benchmark configuration's keys, the state's life under the pattern, the
share in `moe_grouped` against the dense oracle, the two-matrix grouped
kernel against the plain form, the counters, the loader, and what is refused
by name.  The programs of models whose layers are all alike keep their
signatures and their cache."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import (
    STATE_NO_TRANSFER, EngineConfig, EngineCore)
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import (
    TINY, TINY_H1, TINY_MLA, TINY_MOE, TINY_PATTERN)
from dynamo_tpu.ops import moe as moe_ops
from dynamo_tpu.ops.pallas import moe_grouped as kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(
    ROOT, "chipbench/configs/nemotron-3-super-120b-a12b-d11-ep4.json")
BS = 8


def _engine(cfg=TINY_PATTERN, max_seqs=4, window=4, blocks=64, **kw):
    return EngineCore(EngineConfig(
        model=cfg, num_blocks=blocks, decode_window=window,
        scheduler=SchedulerConfig(block_size=BS, max_seqs=max_seqs,
                                  max_prefill_chunk=16,
                                  prefill_buckets=(8, 16)), **kw))


def _generate(core, prompts, max_tokens=11):
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
    out = {f"r{i}": [] for i in range(len(prompts))}
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
    return [out[f"r{i}"] for i in range(len(prompts))]


def _prompts(*lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def alone():
    """Each of three prompts served alone on the padded plane, one token a
    step: what every other way of serving them must give."""
    prompts = _prompts(5, 19, 40)
    return prompts, [_generate(_engine(window=1), [p])[0] for p in prompts]


def test_cache_leaves_follow_the_layer_kinds():
    """Pages for the "*" layers only, state for the "M" layers only, nothing
    for an "E" layer; the byte counts follow the kinds."""
    cfg = TINY_PATTERN                                   # "ME*ME"
    assert cfg.attention_layers == (2,) and cfg.state_layers == (0, 3)
    assert cfg.num_moe_layers == 2 and cfg.layer_is_moe(1)
    cc = kvc.KvCacheConfig.for_model(cfg, 16, BS, state_slots=4)
    cache = kvc.init_cache(cc)
    assert {k: len(v) for k, v in cache.items()} == {
        "k": 1, "v": 1, "ssm": 2, "conv": 2}
    assert cache["ssm"][0].shape == (5, 4, 16, 8)
    assert cc.bytes_per_block == BS * 2 * 4 * 16 * 4      # one layer, f32
    assert cc.state_bytes_per_slot == 2 * (4 * 4 * 16 * 8 + 3 * 96 * 4)
    assert cc.block_wire_shape == (2, 1, BS, 4 * 16)
    # Models whose layers are all alike: a leaf a layer, as before.
    both = kvc.KvCacheConfig.for_model(TINY_H1, 16, BS, state_slots=4)
    assert both.num_layers == both.num_state_layers == 2
    assert kvc.KvCacheConfig.for_model(TINY, 16, BS).num_state_layers == 0


def test_byte_counts_at_the_benchmark_configurations_keys():
    with open(CONFIG) as f:
        hf = json.load(f)
    cfg = loader.config_from_hf(hf, "nemotron")
    cfg.validate()
    assert cfg.layer_pattern == "MEMEMEM*EME"
    assert (len(cfg.state_layers), len(cfg.attention_layers),
            cfg.num_moe_layers) == (5, 1, 5)
    assert cfg.experts_held == (0, 128) and cfg.num_experts == 512
    assert cfg.num_experts_per_token == 22 and not cfg.use_rope
    assert (cfg.moe_latent_size, cfg.expert_size, cfg.shared_size) == (
        1024, 2688, 5376)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_proj_size) == (
        128, 64, 128, 8, 18560)
    flags = dict(zip(hf["engine_flags"][::2], hf["engine_flags"][1::2]))
    block = int(flags["--block-size"])
    cc = kvc.KvCacheConfig.for_model(cfg, int(flags["--num-blocks"]), block,
                                     state_slots=64)
    # dynamo_kv_bytes_per_block: 1,024 B a token (65,536 a block of 64).
    assert cc.bytes_per_block == 1024 * block == 262_144
    assert cc.num_slots * 1024 == pytest.approx(0.34e9, rel=0.02)
    assert cc.state_bytes_per_slot == 21_278_720
    assert cfg.param_count() == pytest.approx(4.648e9, rel=1e-3)


def test_programs_of_models_without_a_pattern_keep_their_signatures():
    want = {
        "window": ["params", "cache", "last_tokens", "positions0",
                   "seq_lens0", "block_tables", "temp", "top_k", "top_p",
                   "base_key_data", "key_offsets", "state_slots",
                   "window_tables"],
        "packed": ["params", "cache", "tokens", "positions", "seg_ids",
                   "block_tables", "q_starts", "q_lens", "seq_lens",
                   "sample_positions", "state_slots", "window_tables"],
        "step": ["params", "cache", "tokens", "positions", "seq_lens",
                 "block_tables", "sample_positions", "input_embeds",
                 "embed_mask", "finish", "state_slots",
                 "window_tables"]}
    for cfg in (TINY, TINY_MLA, TINY_H1, TINY_PATTERN):
        got = {
            "window": llama.make_decode_window(cfg, BS, 4),
            "packed": llama.make_packed_prefill_step(cfg, BS),
            "step": llama.make_forward_step(cfg, BS)}
        for name, fn in got.items():
            assert list(inspect.signature(fn).parameters) == want[name], (
                cfg.name, name)
    # What the engine hands them: a state argument for a model with state
    # layers and for no other, and the cache each had.
    assert set(_engine(TINY, enable_prefix_cache=False).cache) == {"k", "v"}
    assert set(_engine(TINY_MLA).cache) == {"kv"}
    assert _engine(TINY)._state_args(4) == ()
    assert len(_engine(TINY_H1)._state_args(4)) == 1
    for cfg in (TINY, TINY_MLA, TINY_H1, TINY_MOE):
        assert not cfg.has_pattern and cfg.experts_held is None
        assert cfg.attention_layers == tuple(range(cfg.num_layers))


@pytest.mark.parametrize("cfg,given,want", [
    (TINY_PATTERN, None, (1, 2, 4, 8, 16, 24, 32, 40, 48, 56, 64)),
    (TINY_PATTERN, (1, 2, 4, 8), (1, 2, 4, 8)),
    (TINY_PATTERN, (4, 32), (4, 16, 24, 32)),
    (TINY_H1, None, (1, 2, 4, 8, 16, 32, 64)),
    (TINY, None, (1, 2, 4, 8, 16, 32, 64))],
    ids=["pattern", "pattern-short-ladder", "pattern-sparse-ladder",
         "state-every-layer", "dense"])
def test_a_pattern_decodes_on_a_row_ladder_of_eights(cfg, given, want):
    """A padding row of the pattern block costs what a live one does, so its
    engine adds a decode bucket every 8 rows from 16 up to the ladder's top;
    a ladder that ends under 16 and every other model keep what they had."""
    kw = {} if given is None else dict(decode_buckets=given)
    sched = EngineCore(EngineConfig(
        model=cfg, num_blocks=64, decode_window=4,
        enable_prefix_cache=not cfg.has_ssm,
        scheduler=SchedulerConfig(block_size=BS, max_seqs=4,
                                  max_prefill_chunk=16,
                                  prefill_buckets=(8, 16), **kw))
    ).scheduler.config
    assert sched.decode_buckets == want
    if given is None and cfg.has_pattern:
        assert [sched.bucket_for_decode(n) for n in (16, 17, 25, 57)] == [
            16, 24, 32, 64]


def test_seventeen_rows_on_the_24_row_bucket_equal_each_alone():
    """17 sequences decode together in windows of the 24-row bucket (7
    padding rows on the scratch slot) and read what each reads alone in one
    slot, reused 17 times."""
    prompts = _prompts(*range(3, 20), seed=11)
    one = _engine(max_seqs=1, window=4)
    want = [_generate(one, [p], max_tokens=7)[0] for p in prompts]
    core = _engine(max_seqs=24, window=4, blocks=128)
    assert _generate(core, prompts, max_tokens=7) == want
    buckets = {key[2] for key in core.counters._seen_shapes
               if key[0] == "window"}
    assert 24 in buckets, buckets


def test_a_reused_slot_starts_from_zero_under_the_pattern(alone):
    """One slot: the second sequence takes it when the first (ended on a stop
    token inside a window) leaves; its first chunk starts from zero state in
    both state layers all the same."""
    prompts, want = alone
    core = _engine(max_seqs=1, window=4, window_pipeline_depth=4)
    first = want[2]
    core.add_request("a", prompts[2], SamplingParams(
        max_tokens=64, stop_token_ids=[first[5]]))
    core.add_request("b", prompts[1], SamplingParams(max_tokens=11))
    out = {"a": [], "b": []}
    slots = set()
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
        slots |= {r.slot for r in core.scheduler.running}
    assert slots == {0}
    assert out["a"] == first[:6]
    assert out["b"] == want[1]


def test_the_counters_tell_routed_from_local_and_count_layers_by_kind():
    core = _engine()
    _generate(core, _prompts(5, 19))
    core.snapshot_expert_load()
    c = core.counters
    assert c.model_layers == {"ssm": 2, "attention": 1, "moe": 2}
    assert c.ssm_state_bytes_per_slot == core.cache_cfg.state_bytes_per_slot
    assert c.moe_assignments == int(core.expert_load.sum()) > 0
    first, count = TINY_PATTERN.experts_held
    assert c.moe_local_assignments == int(
        core.expert_load[first:first + count].sum())
    assert 0 < c.moe_local_assignments < c.moe_assignments
    # Touched counts held experts: at most `count` a layer forward.
    assert 0 < c.moe_experts_touched <= count * c.moe_layer_forwards
    lines = "\n".join(c.block_metrics_lines())
    for series in ("dynamo_worker_moe_routed_assignments_total",
                   "dynamo_worker_moe_local_assignments_total",
                   "dynamo_worker_moe_capture_decode_layer_forwards_total",
                   'dynamo_model_layers{kind="ssm"} 2',
                   'dynamo_model_layers{kind="attention"} 1',
                   'dynamo_model_layers{kind="moe"} 2',
                   "dynamo_ssm_state_bytes_per_slot"):
        assert series in lines, series
    assert all(v == 0 for held in c.moe_capture.values()
               for v in held.values())       # nothing ran inside a capture
    # A model that holds all its experts has none of the new series.
    whole = _engine(TINY_MOE)
    _generate(whole, _prompts(5))
    whole.snapshot_expert_load()
    assert "local_assignments" not in "\n".join(
        whole.counters.block_metrics_lines())
    assert whole._capture_tally is None


def test_capture_tallies_count_what_is_dispatched_inside_a_capture():
    core = _engine()
    core.counters.trace_phases = True
    try:
        _generate(core, _prompts(5, 19))
    finally:
        core.counters.trace_phases = False
    core.snapshot_expert_load()
    c = core.counters
    dec, pre = c.moe_capture["decode"], c.moe_capture["prefill"]
    assert dec["layer_forwards"] == c.moe_decode_layer_forwards > 0
    assert dec["experts_touched"] == c.moe_decode_experts_touched
    assert pre["layer_forwards"] \
        == c.moe_layer_forwards - c.moe_decode_layer_forwards > 0
    assert dec["local_assignments"] + pre["local_assignments"] \
        == c.moe_local_assignments
    before = dict(dec)
    _generate(core, _prompts(7))             # outside a capture: no move
    core.snapshot_expert_load()
    assert c.moe_capture["decode"] == before


def _share_case(seed=0, n=37):
    cfg = TINY_PATTERN.replace(dtype=jnp.float32)
    moe = llama.init_params(cfg, jax.random.key(seed))["layers"][1]["moe"]
    routed = {k: v for k, v in moe.items()
              if k not in ("shared", "latent_in", "latent_out")}
    x = jax.random.normal(jax.random.key(seed + 1), (1, n, 64), jnp.float32)
    return cfg, routed, x, x @ moe["latent_in"]


def test_a_share_in_the_grouped_path_equals_the_dense_oracle():
    """`moe_grouped` told (4, 4) of 16: routes over all 16, packs the held
    experts' assignments only (about a quarter), and returns what the dense
    oracle given the same share returns; the load is over all 16."""
    cfg, routed, x, u = _share_case()
    want, load_d = moe_ops.moe_dense(cfg, routed, x, x_expert=u)
    for rows in (None, 8, 16):
        got, load_g = moe_ops.moe_grouped(cfg, routed, x, x_expert=u,
                                          block_rows=rows, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(load_g), np.asarray(load_d))
    assert got.shape == (1, 37, 32)                  # the latent width
    assert int(load_d[:-1].sum()) == 37 * 6 and int(load_d[-1]) == 0
    local = int(load_d[4:8].sum())
    assert 0 < local < 37 * 6
    # An engine that held every expert would add the other shares' parts.
    assert float(jnp.abs(want).max()) > 0.05
    # The tile is sized for the quarter that lands here, the buffer for all.
    assert kernel.grouped_block_rows(64 * 22, 512, 128) == 8
    assert kernel.grouped_block_rows(512 * 22, 512, 128) == 64
    assert kernel.grouped_block_rows(256 * 8, 128, 128) \
        == kernel.auto_block_rows(256 * 8, 128)


@pytest.mark.parametrize("share", [True, False])
def test_a_share_packs_and_unpacks_by_gather(share):
    """The share path lays its buffer out and finds its way back by gather
    alone: the scatters of the all-held form stalled a v5e at 22 experts a
    token x 32 rows (PERF.md section 6, PR 47).  The all-held form keeps
    its two, as the accepted programs run them."""
    cfg, routed, x, u = _share_case()
    if not share:
        cfg = cfg.replace(experts_held=None)
        routed = dict(routed, **{k: jnp.concatenate([routed[k]] * 4)
                                 for k in ("w_up", "w_down")})
    text = jax.jit(lambda p, x, u: moe_ops.moe_grouped(
        cfg, p, x, x_expert=u, interpret=True)).lower(routed, x, u).as_text()
    assert (text.count("stablehlo.scatter") == 0) == share


def test_the_two_matrix_kernel_equals_the_plain_form():
    rng = np.random.default_rng(0)
    E, H, F, bm = 4, 128, 256, 8
    x = jnp.asarray(rng.normal(size=(5 * bm, H)), jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(E, H, F)) * H ** -0.5, jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(E, F, H)) * F ** -0.5, jnp.float32)
    tile_expert = jnp.asarray([0, 0, 2, 3, 3], jnp.int32)
    live = jnp.asarray([4], jnp.int32)
    for block_f in (None, 128):
        got = kernel.grouped_expert_ffn_relu2(
            x, tile_expert, w_up, w_down, live_tiles=live, block_rows=bm,
            block_f=block_f, interpret=True)
        for t in range(4):                           # the fifth is skipped
            rows = x[t * bm:(t + 1) * bm]
            e = int(tile_expert[t])
            want = jnp.square(jax.nn.relu(rows @ w_up[e])) @ w_down[e]
            np.testing.assert_allclose(
                np.asarray(got[t * bm:(t + 1) * bm]), np.asarray(want),
                atol=2e-5)
    assert kernel.auto_block_f(1024, 2688, 2, matrices=2) == 2688
    assert kernel.auto_block_f(2048, 768, 2) == 768   # the gated form's rule


def test_config_from_hf_maps_the_nemotron_h_keys():
    with open(CONFIG) as f:
        hf = json.load(f)
    whole = dict(hf, n_routed_experts=512)
    whole.pop("routed_experts_held")
    cfg = loader.config_from_hf(whole, "whole")
    assert cfg.experts_held is None and cfg.experts_local == (0, 512)
    assert cfg.rms_norm_eps == 1e-5 and cfg.activation == "relu2"
    assert cfg.router_scoring == "sigmoid"
    assert cfg.routed_scaling_factor == 5.0 and cfg.mamba_conv_bias


@pytest.mark.parametrize("change,message", [
    (dict(hybrid_override_pattern="MEMEMEM*EMX"), "unknown layer kind"),
    (dict(hybrid_override_pattern="MEMEMEM*EM-"), "plain MLP alone"),
    (dict(hybrid_override_pattern="MEMEMEM*EM"), "names 10 layers"),
    (dict(hybrid_override_pattern="MEMEMEMEEME"), "attention layer"),
    (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
    (dict(n_group=2), "group-limited routing"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(routed_experts_held={"first": 0, "count": 64, "of": 512}),
     "routed_experts_held.count"),
    (dict(routed_experts_held={"first": 448, "count": 128, "of": 512}),
     "no range of the model's 512"),
    (dict(model_type="nemotron_x"), "is not mapped"),
], ids=["unknown-kind", "mlp-kind", "short-pattern", "no-attention", "mtp",
        "groups", "activation", "bias", "held-count", "held-range",
        "unmapped-type"])
def test_loader_refuses_what_it_does_not_map(change, message):
    with open(CONFIG) as f:
        hf = dict(json.load(f), **change)
    with pytest.raises(ValueError, match=message):
        loader.config_from_hf(hf, "x").validate()


@pytest.mark.parametrize("build,message", [
    (lambda: _engine(mesh=object()), "serves meshless"),
    (lambda: _engine(kv_quant="int8"), "no int8 KV form"),
    (lambda: _engine(speculative_tokens=2), "speculative decoding"),
    (lambda: _engine(host_blocks=8), "no tier offload"),
    (lambda: _engine(disk_blocks=8), "no tier offload"),
    (lambda: _engine().export_blocks([1]), "disaggregated transfer"),
    (lambda: _engine().import_blocks({}), "tier offload"),
    (lambda: TINY_PATTERN.replace(
        diffusion_block_length=4, denoising_steps=4,
        mask_token_id=255).validate(), "block diffusion|block-diffusion"),
    (lambda: llama.make_forward_step(TINY_PATTERN, BS, mesh=object()),
     "serves meshless"),
    (lambda: llama._moe_block(
        TINY_PATTERN, {"latent_in": 0, "latent_out": 0}, None, "dense",
        object()), "latent experts have no sharded form"),
    (lambda: TINY.replace(activation="relu2").validate(), "ungated MLP"),
    (lambda: TINY.replace(moe_latent_size=32).validate(),
     "needs a model with experts"),
    (lambda: TINY_MOE.replace(experts_held=(6, 4)).validate(),
     "no range of the model's 8"),
], ids=["mesh", "int8", "speculative", "host-tier", "disk-tier", "export",
        "import", "block-diffusion", "mesh-step", "mesh-latent-experts",
        "relu2-without-pattern", "latent-without-experts", "held-range"])
def test_each_refused_combination_raises_by_name(build, message):
    with pytest.raises(ValueError, match=message):
        build()
    assert "disaggregated transfer" in STATE_NO_TRANSFER


def test_a_prefill_or_decode_role_is_refused_for_the_pattern_block():
    """The worker refuses `--role prefill|decode` for any model with state
    layers by the transfer plane's own words; a pattern model has them."""
    assert TINY_PATTERN.has_ssm
    from dynamo_tpu.worker import main as worker_main

    src = inspect.getsource(worker_main)
    assert "cfg.has_ssm" in src and "STATE_NO_TRANSFER" in src
