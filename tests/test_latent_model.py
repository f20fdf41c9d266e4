"""The latent-attention block (MLA) with a shared expert beside sigmoid-routed
experts behind a leading dense layer, at tiny widths on the CPU with seeded
weights: the paged latent cache read in the absorbed form by prefill and
decode, against the materialised published form (the benchmark's reference,
which imports nothing of the program); the kernels in interpret mode against
the gather path; the router, the shared expert and the leading dense layer;
the latent block through the block ops, the prefix cache and the loader; and
every combination the latent form is refused."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import TINY, TINY_MLA, TINY_MOE
from dynamo_tpu.ops import moe as moe_ops
from dynamo_tpu.ops.pallas.latent_attention import (
    latent_decode_attention, latent_geometry_ok, latent_prefill_attention)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF = {"model_type": "glm4_moe_lite", "hidden_size": 64,
      "intermediate_size": 128, "moe_intermediate_size": 32,
      "num_attention_heads": 8, "num_key_value_heads": 8,
      "q_lora_rank": 32, "kv_lora_rank": 48, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
      "num_hidden_layers": 3, "n_routed_experts": 8, "n_shared_experts": 1,
      "num_experts_per_tok": 2, "first_k_dense_replace": 1,
      "routed_scaling_factor": 1.8, "norm_topk_prob": True,
      "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
      "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "rope_scaling": None,
      "partial_rotary_factor": 1, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
BS = 8


@pytest.fixture(scope="module")
def reference():
    from chipbench import pieces

    return pieces.load("references", "mla_shared_routed_moe")


@pytest.fixture(scope="module")
def tiny():
    """(cfg, params): float32, norm weights moved off 1 so that they count."""
    cfg = loader.config_from_hf(HF, "tiny-mla").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(5))
    k = iter(jax.random.split(jax.random.key(6), 64))

    def jitter(w):
        return w + 0.2 * jax.random.normal(next(k), w.shape, w.dtype)

    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm"):
            layer[name] = jitter(layer[name])
        for name in ("q_a_norm", "kv_a_norm"):
            layer["attn"][name] = jitter(layer["attn"][name])
    return cfg, params


def _cache(cfg, blocks=32):
    return kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, num_blocks=blocks, block_size=BS))


def test_config_from_hf_maps_the_block():
    cfg = loader.config_from_hf(HF, "t")
    assert cfg == TINY_MLA.replace(
        name="t", dtype=cfg.dtype, rope_theta=10000.0, max_context=512)
    assert cfg.is_latent and cfg.head_dim == 24 and cfg.latent_dim == 56
    assert cfg.latent_row == 128 and cfg.kv_feature_dim == 128
    assert cfg.num_moe_layers == 2 and not cfg.layer_is_moe(0)
    with open(os.path.join(
            ROOT, "chipbench/configs/glm-4.7-flash-d8.json")) as f:
        real = loader.config_from_hf(json.load(f), "glm")
    # The issue's count: one dense layer, 7 expert layers, the vocabulary.
    assert real.param_count() == pytest.approx(5.166e9, rel=1e-3)
    assert (real.latent_dim, real.latent_row, real.num_moe_layers) \
        == (576, 640, 7)
    assert real.router_scoring == "sigmoid" \
        and real.routed_scaling_factor == 1.8 and real.n_shared_experts == 1


@pytest.mark.parametrize("bad,message", [
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"n_group": 2}, "group-limited"),
    ({"topk_method": "greedy"}, "topk_method"),
])
def test_config_from_hf_refuses_what_is_not_implemented(bad, message):
    with pytest.raises(ValueError, match=message):
        loader.config_from_hf(dict(HF, **bad), "t")


def test_leading_dense_layer_and_expert_layers_have_their_params(tiny):
    cfg, params = tiny
    first, second = params["layers"][0], params["layers"][1]
    assert "mlp" in first and "moe" not in first
    assert first["mlp"]["w_gate"].shape == (64, 128)     # the dense width
    assert "moe" in second and "mlp" not in second
    moe = second["moe"]
    assert moe["w_gate"].shape == (8, 64, 32)
    assert moe["shared"]["w_down"].shape == (32, 64)
    assert moe["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(moe["router_bias"]).max()) > 0.01   # seeded, not 0
    assert set(second["attn"]) == {"wq_a", "q_a_norm", "wq_b", "wkv_a",
                                   "kv_a_norm", "wkv_b", "wo"}


@pytest.mark.parametrize("n", [7, 21, 40])
def test_whole_forward_matches_the_reference(tiny, reference, n):
    """One chunk through the unified step on an empty latent cache (gather
    path, absorbed read) against the reference's materialised forward,
    every position, and under the program's own expert choices too."""
    cfg, params = tiny
    tokens = np.random.default_rng(n).integers(1, 250, size=n)
    step = llama.make_forward_step(cfg, BS, with_expert_load=True,
                                   moe_aux=True)
    pages = np.arange(1, 9, dtype=np.int32)[None]
    logits, cache, aux = step(
        params, _cache(cfg), tokens[None].astype(np.int32),
        np.arange(n, dtype=np.int32)[None], np.array([n], np.int32), pages,
        None)
    assert set(cache) == {"kv"} and cache["kv"][0].shape == (32 * BS, 128)
    ref = np.asarray(reference.forward(HF, params, tokens.tolist()))
    np.testing.assert_allclose(np.asarray(logits[0]), ref, atol=2e-5)
    assert aux["routing"].shape == (2, n, 2)       # expert layers only
    fed = np.asarray(reference.forward(HF, params, tokens.tolist(),
                                       choices=np.asarray(aux["routing"])))
    np.testing.assert_allclose(fed, ref, atol=2e-5)


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["gather", "kernels"])
def test_prefill_then_decode_through_the_latent_cache(tiny, reference,
                                                      pallas):
    """Chunked prefill (two chunks) then single decode steps through the
    paged latent cache, logits not tokens, against the reference's full
    forward; with `pallas` the decode step streams the rows through the
    latent decode kernel (interpret mode) and the prefill is the packed
    step over the latent prefill kernel."""
    cfg, params = tiny
    n, first = 29, 16
    tokens = np.random.default_rng(3).integers(1, 250, size=n).astype(np.int32)
    ref = np.asarray(reference.forward(HF, params, tokens.tolist()))
    pages = np.array([[3, 9, 4, 7]], np.int32)
    cache = _cache(cfg)
    step = jax.jit(llama.make_forward_step(
        cfg, BS, use_pallas_decode=pallas, with_expert_load=True))
    if pallas:
        packed = jax.jit(llama.make_packed_prefill_step(cfg, BS))
        for lo, hi in ((0, first), (first, 24)):
            m = hi - lo
            t = np.zeros((16,), np.int32)
            p = np.full((16,), 10 ** 6, np.int32)
            t[:m], p[:m] = tokens[lo:hi], np.arange(lo, hi)
            bts = np.zeros((2, 4), np.int32)
            bts[0] = pages[0]
            z = np.zeros((2,), np.int32)
            logits, cache, _ = packed(
                params, cache, t, p, np.zeros((16,), np.int32), bts, z,
                np.array([m, 0], np.int32), np.array([hi, 0], np.int32),
                np.array([m - 1, 0], np.int32))
            np.testing.assert_allclose(np.asarray(logits[0]), ref[hi - 1],
                                       atol=3e-5)
    else:
        for lo, hi in ((0, first), (first, 24)):
            logits, cache, _ = step(
                params, cache, tokens[None, lo:hi],
                np.arange(lo, hi, dtype=np.int32)[None],
                np.array([hi], np.int32), pages, None)
            np.testing.assert_allclose(np.asarray(logits[0]), ref[lo:hi],
                                       atol=3e-5)
    for t in range(24, n):
        logits, cache, _ = step(
            params, cache, tokens[None, t:t + 1],
            np.array([[t]], np.int32), np.array([t + 1], np.int32), pages,
            np.zeros((1,), np.int32))
        np.testing.assert_allclose(np.asarray(logits[0]), ref[t], atol=3e-5)


def test_absorbed_read_equals_materialised_read(tiny, reference):
    """One layer's attention alone: the program's absorbed read over the
    rows it stores against the reference's keys and values, in float32."""
    cfg, params = tiny
    layer = params["layers"][1]
    n = 19
    x = jax.random.normal(jax.random.key(9), (1, n, 64), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    h = llama.rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    slots = jnp.arange(8, 8 + n, dtype=jnp.int32)
    mixers = llama.chunk_mixers(cfg, BS, pos, jnp.array([n], jnp.int32),
                                None, slots, slots[None], pos)
    bufs, q_abs = mixers.attn_write(
        layer["attn"], h, {"kv": jnp.zeros((64, 128), jnp.float32)})
    out, kv = mixers.attn_read(layer["attn"], bufs, q_abs), bufs["kv"]
    with jax.default_matmul_precision("highest"):
        want = reference.attention_layer(HF, layer, x[0]) - x[0]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=2e-5)
    # What was stored: [c_kv | k_rope | zeros], nothing beyond the chunk.
    stored = np.asarray(kv)
    assert np.abs(stored[8:8 + n, :56]).min() > 0
    assert not stored[8:8 + n, 56:].any() and not stored[:8].any()


def _pool(rng, slots, row, used):
    kv = np.zeros((slots, row), np.float32)
    kv[:, :used] = rng.normal(size=(slots, used))
    return jnp.asarray(kv)


def _gather_attention(q, kv, bts, positions, seq_lens, v_width, scale):
    """The gather path: `ops.attention.paged_attention` over gathered rows."""
    from dynamo_tpu.ops.attention import paged_attention

    B, P = bts.shape
    ctx_pos = jnp.broadcast_to(jnp.arange(P * BS, dtype=jnp.int32),
                               (B, P * BS))
    slots = kvc.slots_for_positions(bts, ctx_pos, BS)
    ctx = jnp.take(kv, slots, axis=0)[:, :, None]
    return paged_attention(q, ctx, ctx, positions, ctx_pos, seq_lens,
                           scale=scale)[..., :v_width]


@pytest.mark.parametrize("heads,pair", [(8, None), (20, 1), (5, 2)])
def test_decode_kernel_matches_the_gather_path(heads, pair):
    rng = np.random.default_rng(heads)
    row, v = 128, 48
    kv = _pool(rng, 48 * BS, row, 56)
    seq_lens = jnp.array([37, 1, 0, 64, 20], jnp.int32)
    bts = jnp.asarray(rng.permutation(np.arange(1, 48))[:5 * 8]
                      .reshape(5, 8).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(5, heads, row)), jnp.float32)
    got = latent_decode_attention(q, kv, bts, seq_lens, block_size=BS,
                                  scale=0.2, v_width=v, interpret=True,
                                  pair=pair)
    want = _gather_attention(q[:, None], kv, bts, (seq_lens - 1)[:, None],
                             seq_lens, v, 0.2)[:, 0]
    live = np.asarray(seq_lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    assert got.shape == (5, heads, v)


@pytest.mark.parametrize("heads,group,q_tile", [(8, None, None), (20, 4, 16),
                                                (6, 3, 8)])
def test_prefill_kernel_matches_the_gather_path(heads, group, q_tile):
    """Ragged segments, one of them behind cached context, one empty."""
    rng = np.random.default_rng(heads)
    row, v, T = 128, 48, 64
    kv = _pool(rng, 40 * BS, row, 56)
    q_lens = np.array([19, 0, 30, 5], np.int32)
    q_starts = np.array([0, 24, 24, 56], np.int32)
    seq_lens = np.array([19, 0, 61, 5], np.int32)       # seg 2: 31 cached
    bts = rng.permutation(np.arange(1, 40))[:32].reshape(4, 8).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, heads, row)), jnp.float32)
    got = np.asarray(latent_prefill_attention(
        q, kv, jnp.asarray(bts), jnp.asarray(seq_lens), jnp.asarray(q_starts),
        jnp.asarray(q_lens), block_size=BS, scale=0.2, v_width=v,
        interpret=True, q_tile=q_tile, head_group=group))
    owned = np.zeros((T,), bool)
    for r in range(4):
        n, s0 = int(q_lens[r]), int(q_starts[r])
        if not n:
            continue
        pos = np.arange(seq_lens[r] - n, seq_lens[r], dtype=np.int32)[None]
        want = _gather_attention(
            q[None, s0:s0 + n], kv, jnp.asarray(bts[r:r + 1]),
            jnp.asarray(pos), jnp.asarray(seq_lens[r:r + 1]), v, 0.2)[0]
        np.testing.assert_allclose(got[s0:s0 + n], np.asarray(want),
                                   atol=2e-5)
        owned[s0:s0 + n] = True
    assert not got[~owned].any()          # rows no segment owns come back 0


def test_kernels_refuse_geometry_the_chip_cannot_take():
    assert latent_geometry_ok(640, 512, 64)
    assert not latent_geometry_ok(576, 512, 64)
    assert not latent_geometry_ok(640, 500, 64)
    q = jnp.zeros((2, 4, 96), jnp.float32)
    with pytest.raises(ValueError, match="row % 128"):
        latent_decode_attention(q, jnp.zeros((64, 96)), jnp.zeros((2, 2),
                                jnp.int32), jnp.zeros((2,), jnp.int32),
                                block_size=8, scale=1.0, v_width=48)
    with pytest.raises(ValueError, match="query width"):
        latent_decode_attention(q, jnp.zeros((64, 128)), jnp.zeros(
            (2, 2), jnp.int32), jnp.zeros((2,), jnp.int32), block_size=8,
            scale=1.0, v_width=48, interpret=True)


def test_sigmoid_router_chooses_by_biased_score_and_weighs_by_score():
    """Four experts, two a token: the bias moves the choice and never the
    weight; the weights are the chosen scores renormalised, times 1.8."""
    cfg = TINY_MLA.replace(num_experts=4)
    x = jnp.eye(4, dtype=jnp.float32)[:1]                     # one token
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    p = {"router": jnp.zeros((4, 4), jnp.float32).at[0].set(logits[0]),
         "router_bias": jnp.array([0.0, 0.0, 0.0, 0.9], jnp.float32)}
    idx, gates = moe_ops.router_topk(cfg, p, x)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    # s = .88 .73 .5 .27; s + b = .88 .73 .5 1.17: experts 3 and 0.
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    want = {0: 1.8 * s[0] / (s[0] + s[3]), 3: 1.8 * s[3] / (s[0] + s[3])}
    for e, g in zip(np.asarray(idx[0]), np.asarray(gates[0])):
        assert g == pytest.approx(want[int(e)], rel=1e-6)
    p0 = dict(p, router_bias=jnp.zeros((4,), jnp.float32))
    assert sorted(np.asarray(moe_ops.router_topk(cfg, p0, x)[0][0])
                  .tolist()) == [0, 1]


@pytest.mark.parametrize("mode", ["dense", "grouped"])
def test_expert_layer_matches_the_reference(tiny, reference, mode):
    """One expert layer, shared expert and all, by both of the program's
    meshless expert paths against the reference's, own choices and fed."""
    cfg, params = tiny
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.key(4), (2, 9, 64), jnp.float32)
    h = llama.rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    out, stats = llama._moe_block(cfg, layer["moe"], h, mode, None)
    assert int(stats[:-1].sum()) == 2 * 9 * 2 and int(stats[-1]) == 0
    with jax.default_matmul_precision("highest"):
        want = reference.moe_layer(HF, layer, x.reshape(18, 64))
    np.testing.assert_allclose(np.asarray(out).reshape(18, 64),
                               np.asarray(want), atol=2e-5)
    # Without the shared expert the result differs: it is not a no-op.
    bare, _ = llama._moe_block(
        cfg, {k: v for k, v in layer["moe"].items() if k != "shared"}, h,
        mode, None)
    assert float(jnp.abs(out - bare).max()) > 1e-2


def test_latent_block_extract_inject_round_trip(tiny):
    cfg, _ = tiny
    cc = kvc.KvCacheConfig.for_model(cfg, num_blocks=8, block_size=BS)
    assert cc.block_wire_shape == (1, 3, BS, 128)
    assert cc.bytes_per_context_token == 3 * 128 * 4      # float32 rows
    assert cc.bytes_per_block == BS * 3 * 128 * 4
    cache = kvc.init_cache(cc)
    cache = {"kv": [layer.at[3 * BS:4 * BS].set(
        jax.random.normal(jax.random.key(i), (BS, 128)))
        for i, layer in enumerate(cache["kv"])]}
    extract, inject = kvc.make_block_ops(BS)
    block = extract(cache, jnp.int32(3))
    assert block.shape == cc.block_wire_shape
    np.testing.assert_array_equal(np.asarray(block[0, 1]),
                                  np.asarray(cache["kv"][1][3 * BS:4 * BS]))
    moved = inject(kvc.init_cache(cc), jnp.int32(5), block)
    for src, dst in zip(cache["kv"], moved["kv"]):
        np.testing.assert_array_equal(np.asarray(dst[5 * BS:6 * BS]),
                                      np.asarray(src[3 * BS:4 * BS]))
        assert not np.asarray(dst[:5 * BS]).any()


def _engine(cfg=TINY_MLA, **kw):
    kw.setdefault("scheduler", SchedulerConfig(block_size=BS))
    return EngineCore(EngineConfig(model=cfg, num_blocks=64, decode_window=4,
                                   **kw))


def _generate(core, prompts, max_tokens=9):
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
    out = {f"r{i}": [] for i in range(len(prompts))}
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
    return out


@pytest.mark.parametrize("planes", ["gather", "kernels"])
def test_engine_serves_the_block_and_counts_its_expert_layers(reference,
                                                              planes):
    """The normal path, both planes: every greedy token is the reference's
    best under the engine's own expert choices (float32: no flips), the
    expert tallies are right from prefill, windows and single steps (expert
    layers, not all layers; distinct experts from decode too), and the
    windows' reports ride the windows' own reads."""
    on = planes == "kernels"
    core = _engine(packed_prefill=on, use_pallas_decode=on)
    assert set(core.cache) == {"kv"}
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 19, 40)]
    toks = _generate(core, prompts)
    hf = dict(HF, rope_theta=TINY_MLA.rope_theta)
    for i, p in enumerate(prompts):
        got = toks[f"r{i}"]
        assert len(got) == 9
        ref = np.asarray(reference.forward(hf, core.params, p + got[:-1]))
        for j, tok in enumerate(got):
            row = ref[len(p) - 1 + j]
            assert row.max() - row[tok] < 1e-4
    c = core.counters
    core.snapshot_expert_load()
    # 1 prefill call, 2 windows of 4 steps: 2 expert layers each forward.
    assert c.window_dispatches == 2 and c.prefill_dispatches == 1
    assert c.moe_layer_forwards == 2 * (1 + 2 * 4)
    assert c.moe_decode_layer_forwards == 2 * 2 * 4
    assert 0 < c.moe_decode_experts_touched <= 8 * c.moe_decode_layer_forwards
    assert c.moe_decode_experts_touched < c.moe_experts_touched
    assert int(core.expert_load.sum()) == c.moe_assignments
    assert c.prefill_attn_pairs == sum(n * (n + 1) // 2 for n in (5, 19, 40))
    # Latent rows at their stored width: 3 layers x 128 values x 4 bytes.
    assert core.cache_cfg.bytes_per_context_token == 1536
    assert c.kv_read_bytes_modeled % 1536 == 0 and c.kv_read_bytes_modeled
    # One sync a window, and the first tokens': the expert report adds none.
    assert c.window_syncs == 2 and c.host_syncs == 3


def test_prefix_cache_hit_on_a_latent_block(reference):
    core = _engine()
    prompt = np.random.default_rng(8).integers(1, 256, size=40).tolist()
    first = _generate(core, [prompt], 4)["r0"]
    assert core.scheduler.prefix_hit_tokens == 0
    again = _generate(core, [prompt], 4)["r0"]
    assert core.scheduler.prefix_hit_tokens >= 4 * BS     # whole blocks
    assert again == first
    # A host tier holds a latent block like any other.
    block = core._extract_block(1)
    assert tuple(block.shape) == core.cache_cfg.block_wire_shape
    core._validate_block(np.asarray(block))


def test_refused_combinations_name_the_latent_form():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "sp", "ep", "tp"))
    with pytest.raises(ValueError, match=r"latent attention \(MLA\) serves "
                                         "meshless"):
        _engine(mesh=mesh)
    sp = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2, 1, 1),
              ("dp", "pp", "sp", "ep", "tp"))
    with pytest.raises(ValueError, match="ring/sequence-parallel"):
        _engine(mesh=sp)
    with pytest.raises(ValueError, match="ring/sequence-parallel"):
        llama.make_forward_step(TINY_MLA, BS, mesh=sp, sp_ring=True)
    with pytest.raises(ValueError, match=r"latent \(MLA\) cache has no int8"):
        _engine(kv_quant="int8")
    with pytest.raises(ValueError, match="block-diffusion"):
        TINY_MLA.replace(diffusion_block_length=4, denoising_steps=4,
                         mask_token_id=255).validate()
    with pytest.raises(ValueError, match="num_kv_heads == num_heads"):
        TINY_MLA.replace(num_kv_heads=4).validate()
    with pytest.raises(ValueError, match="q_lora_rank"):
        TINY_MLA.replace(q_lora_rank=0).validate()
    with pytest.raises(ValueError, match="head norms"):
        TINY_MLA.replace(qk_norm=True).validate()
    # The expert layer's new parts without the latent cache, under a mesh.
    routed = TINY_MOE.replace(n_shared_experts=1, router_scoring="sigmoid")
    with pytest.raises(ValueError, match="shared expert, a sigmoid router"):
        _engine(cfg=routed, mesh=mesh)
    with pytest.raises(ValueError, match="need a model with experts"):
        TINY.replace(n_shared_experts=1).validate()
    with pytest.raises(ValueError, match="first_k_dense"):
        TINY_MOE.replace(first_k_dense=2).validate()


def test_a_saved_checkpoint_loads_by_the_published_tensor_names(tiny,
                                                                tmp_path):
    """No checkpoint of the family is in the sandbox: a tiny one is saved
    under the DeepSeek-V3 family's tensor names (the configuration's
    `assumed`), with a multi-token-prediction block behind the last layer
    that must not be read, and loaded back to the same logits."""
    from safetensors.numpy import save_file

    cfg, params = tiny
    t = {}

    def lin(name, w):
        t[name] = np.ascontiguousarray(np.asarray(w, np.float32).T)

    def vec(name, w):
        t[name] = np.asarray(w, np.float32)

    vec("model.embed_tokens.weight", params["embed"])
    vec("model.norm.weight", params["final_norm"])
    lin("lm_head.weight", params["lm_head"])
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        a = layer["attn"]
        for ours, theirs in (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"),
                             ("wkv_a", "kv_a_proj_with_mqa"),
                             ("wkv_b", "kv_b_proj"), ("wo", "o_proj")):
            lin(p + f"self_attn.{theirs}.weight", a[ours])
        vec(p + "self_attn.q_a_layernorm.weight", a["q_a_norm"])
        vec(p + "self_attn.kv_a_layernorm.weight", a["kv_a_norm"])
        vec(p + "input_layernorm.weight", layer["attn_norm"])
        vec(p + "post_attention_layernorm.weight", layer["mlp_norm"])
        if "mlp" in layer:
            for k in ("gate", "up", "down"):
                lin(p + f"mlp.{k}_proj.weight", layer["mlp"][f"w_{k}"])
            continue
        m = layer["moe"]
        lin(p + "mlp.gate.weight", m["router"])
        vec(p + "mlp.gate.e_score_correction_bias", m["router_bias"])
        for k in ("gate", "up", "down"):
            lin(p + f"mlp.shared_experts.{k}_proj.weight",
                m["shared"][f"w_{k}"])
            for e in range(cfg.num_experts):
                lin(p + f"mlp.experts.{e}.{k}_proj.weight", m[f"w_{k}"][e])
    vec("model.layers.3.eh_proj.weight", np.ones((4, 4)))    # the MTP block
    save_file(t, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump(dict(HF, num_nextn_predict_layers=1), f)
    got_cfg, got = loader.load_params(str(tmp_path), dtype=jnp.float32)
    assert got_cfg.is_latent and got_cfg.num_layers == 3
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert got["layers"][1]["moe"]["router_bias"].dtype == jnp.float32


def test_worker_max_context_sets_the_block_tables_width():
    """`--max-context` is how a deployment serves sequences past the
    default 8,192 tokens (128 pages): the worker turns it into the block
    tables' width, and a request that could outgrow it is refused at
    admission, one that fits is admitted."""
    import asyncio

    from dynamo_tpu.engine.scheduler import Request
    from dynamo_tpu.worker import main as worker

    base = ["--control-plane", "127.0.0.1:1", "--model", "tiny-mla"]
    assert worker.parse_args(base).max_context == 8192
    args = worker.parse_args(base + ["--num-blocks", "64", "--block-size",
                                     "8", "--max-context", "100"])

    async def build():
        _client, _metrics, shutdown, _card, engine = \
            await worker.build_engine(args, None)
        try:
            sched = engine.core.scheduler
            assert sched.config.max_pages_per_seq == 13
            reasons = {}
            for rid, n in (("fits", 90), ("too-long", 100)):
                req = Request(rid, list(range(1, n + 1)),
                              SamplingParams(max_tokens=10))
                sched.add_request(req)
                reasons[rid] = req.finish_reason
            return reasons
        finally:
            await shutdown()

    out = asyncio.run(build())
    assert out["fits"] is None and out["too-long"] is not None
