"""Block-diffusion serving (SDAR family) at test size on the CPU: the mask
in both attention planes, a block's queries on the decode kernel, the
unmasking rules, the scheduler's block edges, a whole generation through
EngineCore against a host-side sampler over the plain reference, and block
length 1 being the causal engine."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams, diffusion_unmask
from dynamo_tpu.engine.scheduler import (
    BlockAllocator, Request, RequestState, Scheduler, SchedulerConfig)
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models import loader
from dynamo_tpu.ops.attention import paged_attention

HF = {"model_type": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
      "moe_intermediate_size": 32, "num_attention_heads": 8,
      "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
      "num_hidden_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "diffusion_block_length": 4, "denoising_steps": 4,
      "mask_token_id": 255}


def _core(hf=HF, **kw):
    cfg = loader.config_from_hf(hf, "t").replace(dtype=jnp.float32)
    sched = SchedulerConfig(max_seqs=8, block_size=16, max_pages_per_seq=8,
                            max_prefill_chunk=32,
                            decode_buckets=(1, 2, 4, 8),
                            prefill_buckets=(16, 32))
    return EngineCore(EngineConfig(model=cfg, num_blocks=64,
                                   scheduler=sched, **kw))


def _generate(core, prompts, max_tokens, **sampling):
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", list(p),
                         SamplingParams(max_tokens=max_tokens, **sampling))
    out = {f"r{i}": [] for i in range(len(prompts))}
    order = []
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
            order.extend((d.request_id, t) for t in d.token_ids)
    return out, order


def _reference_generate(hf, params, prompt, max_tokens):
    """The published sampler over the plain reference: one sequence, no
    cache, the whole block-causal forward for every denoising step."""
    from chipbench import pieces

    ref = pieces.load("references", "sdar_moe_block_diffusion")
    B, mask = hf["diffusion_block_length"], hf["mask_token_id"]
    per_step = max(1, B // hf["denoising_steps"])
    dynamic = hf.get("remasking") == "low_confidence_dynamic"
    seq, forwards = list(prompt), 0
    while len(seq) - len(prompt) < max_tokens:
        c = len(seq) // B * B
        block = seq[c:] + [mask] * (B - (len(seq) - c))
        while mask in block:
            logits = np.array(ref.forward(hf, params, seq[:c] + block,
                                          positions=range(c, c + B)))
            forwards += 1
            logits[:, mask] = -np.inf
            x0 = logits.argmax(-1)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            conf = p[np.arange(B), x0] / p.sum(-1)
            conf[[t != mask for t in block]] = -np.inf
            take = per_step
            if dynamic and (conf > hf["confidence_threshold"]).sum() >= take:
                take = int((conf > hf["confidence_threshold"]).sum())
            for i in np.argsort(-conf, kind="stable")[:take]:
                if block[i] == mask:
                    block[i] = int(x0[i])
        forwards += 1                                   # the commit
        seq = seq[:c] + block
    return seq[len(prompt): len(prompt) + max_tokens], forwards


# -- configuration and loader ------------------------------------------------

def test_sdar_config_maps_and_counts_its_parameters():
    hf = dict(HF, hidden_size=2048, intermediate_size=6144,
              moe_intermediate_size=768, num_attention_heads=32,
              num_key_value_heads=4, head_dim=128, vocab_size=151936,
              num_hidden_layers=48, num_experts=128, num_experts_per_tok=8,
              mask_token_id=151669)
    cfg = loader.config_from_hf(hf, "sdar")
    cfg.validate()
    assert (cfg.num_experts, cfg.expert_size, cfg.num_experts_per_token) \
        == (128, 768, 8)
    assert cfg.qk_norm and cfg.norm_topk_prob and cfg.is_diffusion
    assert cfg.unmask_per_step == 1
    assert cfg.param_count() == 30_532_122_624
    # The benchmark's cut: seven layers, 4.984 B parameters, 9.97 GB in bf16.
    assert cfg.replace(num_layers=7).param_count() == 4_984_176_384
    # Stated values override the family's defaults; a causal model has none.
    assert loader.config_from_hf(
        dict(hf, diffusion_block_length=8, denoising_steps=2,
             remasking="low_confidence_dynamic"), "x").unmask_per_step == 4
    assert not loader.config_from_hf(
        {k: v for k, v in hf.items() if k != "model_type"
         and not k.startswith(("diffusion", "denois", "mask"))},
        "dense").is_diffusion
    with pytest.raises(ValueError, match="mask_token_id"):
        mcfg.TINY_SDAR.replace(mask_token_id=None).validate()


def test_init_params_have_head_norms_and_narrow_experts():
    from dynamo_tpu.models.llama import init_params

    cfg = mcfg.TINY_SDAR
    p = init_params(cfg, jax.random.key(0))
    attn, moe = p["layers"][0]["attn"], p["layers"][0]["moe"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (cfg.head_dim,)
    assert moe["w_gate"].shape == (8, 64, 32)
    assert moe["w_down"].shape == (8, 32, 64)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(p))
    assert n == cfg.param_count()


def test_load_params_reads_qwen3_moe_names(tmp_path):
    """A checkpoint in the Qwen3-MoE layout (mlp.gate, mlp.experts.E.*_proj,
    self_attn.{q,k}_norm) loads into the same pytree init_params builds."""
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.models.llama import init_params

    cfg = loader.config_from_hf(HF, "t").replace(dtype=jnp.float32)
    want = init_params(cfg, jax.random.key(1))
    t = {"model.embed_tokens.weight": want["embed"],
         "model.norm.weight": want["final_norm"],
         "lm_head.weight": want["lm_head"].T}
    for i, layer in enumerate(want["layers"]):
        p = f"model.layers.{i}."
        a, m = layer["attn"], layer["moe"]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            t[p + f"self_attn.{theirs}.weight"] = a[ours].T
        t[p + "self_attn.q_norm.weight"] = a["q_norm"] * 1.5
        t[p + "self_attn.k_norm.weight"] = a["k_norm"] * 0.5
        t[p + "input_layernorm.weight"] = layer["attn_norm"]
        t[p + "post_attention_layernorm.weight"] = layer["mlp_norm"]
        t[p + "mlp.gate.weight"] = m["router"].T
        for e in range(cfg.num_experts):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                t[p + f"mlp.experts.{e}.{theirs}.weight"] = m[ours][e].T
    save_file({k: np.ascontiguousarray(np.asarray(v)) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(
        dict(HF, torch_dtype="float32")))
    got_cfg, got = loader.load_params(str(tmp_path), dtype=jnp.float32)
    assert got_cfg.is_diffusion and got_cfg.qk_norm
    np.testing.assert_array_equal(
        got["layers"][1]["moe"]["w_up"], want["layers"][1]["moe"]["w_up"])
    np.testing.assert_allclose(got["layers"][0]["attn"]["q_norm"], 1.5)
    np.testing.assert_allclose(got["layers"][0]["attn"]["k_norm"], 0.5)
    np.testing.assert_array_equal(got["layers"][0]["moe"]["router"],
                                  want["layers"][0]["moe"]["router"])


# -- masks and kernels ---------------------------------------------------------

@pytest.mark.parametrize("B", [1, 4, 8])
def test_gather_mask_is_by_block(B):
    rng = np.random.default_rng(B)
    T = 16
    q = jnp.asarray(rng.normal(size=(1, T, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, T, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, T, 2, 8)), jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    out = paged_attention(q, k, v, pos, pos, jnp.asarray([T]), mask_block=B)
    # By hand: softmax over the keys of blocks <= the query's.
    kk, vv = np.repeat(k[0], 2, axis=1), np.repeat(v[0], 2, axis=1)
    s = np.einsum("qhd,khd->hqk", q[0], kk) * 8 ** -0.5
    blk = np.arange(T) // B
    s = np.where(blk[None, :] <= blk[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", p, vv)
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=1e-5)


@pytest.mark.parametrize("B", [1, 4, 8])
def test_packed_prefill_kernel_masks_by_block(B):
    """The Pallas packed-prefill kernel (interpret mode) against the gather
    path under the same block mask: two segments, one with a cached prefix."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.ops.pallas import paged_prefill_attention

    rng = np.random.default_rng(10 + B)
    bs, Hq, Hkv, D = 16, 4, 2, 8
    S = 8 * bs
    kc = jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.float32)
    bts = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    # Segment 0: 24 new tokens after 16 cached; segment 1: 16 from scratch.
    q_starts, q_lens = jnp.asarray([0, 24]), jnp.asarray([24, 16])
    seq_lens = jnp.asarray([40, 16])
    q = jnp.asarray(rng.normal(size=(40, Hq, D)), jnp.float32)
    out = paged_prefill_attention(
        q, kc, vc, bts, seq_lens, q_starts, q_lens, block_size=bs,
        interpret=True, q_tile=8, mask_block=B)
    for seg, (lo, n, start) in enumerate(((0, 24, 16), (24, 16, 0))):
        C = 4 * bs
        ctx = jnp.arange(C, dtype=jnp.int32)[None]
        slots = kvc.slots_for_positions(bts[seg][None], ctx, bs)
        k_ctx, v_ctx = kvc.gather_kv(kc, vc, slots, Hkv)
        want = paged_attention(
            q[lo: lo + n][None], k_ctx, v_ctx,
            (start + jnp.arange(n, dtype=jnp.int32))[None], ctx,
            seq_lens[seg][None], mask_block=B)
        np.testing.assert_allclose(np.asarray(out[lo: lo + n]),
                                   np.asarray(want[0]), atol=2e-5)


def test_a_blocks_queries_ride_the_decode_kernel():
    """T queries a row that all see [0, seq_len): the decode kernel with the
    queries on its head-group axis equals the gather path under the block
    mask (block = T, the block's own K/V already in the cache)."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.ops.pallas import paged_block_attention

    rng = np.random.default_rng(3)
    bs, Hq, Hkv, D, T = 16, 8, 4, 16, 4
    kc = jnp.asarray(rng.normal(size=(6 * bs, Hkv * D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(6 * bs, Hkv * D)), jnp.float32)
    bts = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    seq_lens = jnp.asarray([40, 20, 0], jnp.int32)       # row 2 is dead
    q = jnp.asarray(rng.normal(size=(3, T, Hq, D)), jnp.float32)
    out = paged_block_attention(q, kc, vc, bts, seq_lens, block_size=bs,
                                interpret=True)
    ctx = jnp.broadcast_to(jnp.arange(3 * bs, dtype=jnp.int32), (3, 3 * bs))
    k_ctx, v_ctx = kvc.gather_kv(
        kc, vc, kvc.slots_for_positions(bts, ctx, bs), Hkv)
    pos = seq_lens[:, None] - T + jnp.arange(T, dtype=jnp.int32)[None]
    want = paged_attention(q, k_ctx, v_ctx, pos, ctx, seq_lens, mask_block=T)
    np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(want[:2]),
                               atol=2e-5)


# -- the unmasking rules -------------------------------------------------------

def _unmask_case():
    # Confidences (softmax of the argmax) by position: row 0 -> .5 .9 .7 .6,
    # row 1 -> .95 .3 .92 .4 (position 0 of row 1 is already decided).
    p = np.asarray([[.5, .9, .7, .6], [.95, .3, .92, .4]])
    logits = np.log(np.stack([p, 1 - p], -1))
    x0 = jnp.zeros((2, 4), jnp.int32)
    masked = jnp.asarray([[1, 1, 1, 1], [0, 1, 1, 1]], bool)
    return jnp.asarray(logits, jnp.float32), x0, masked


def test_low_confidence_static_takes_the_most_confident_masked():
    logits, x0, masked = _unmask_case()
    conf, take = diffusion_unmask(logits, x0, masked, 1, 0.9, False)
    np.testing.assert_array_equal(take, [[0, 1, 0, 0], [0, 0, 1, 0]])
    assert conf[1, 0] == -jnp.inf and abs(float(conf[0, 1]) - 0.9) < 1e-6
    _, take2 = diffusion_unmask(logits, x0, masked, 2, 0.9, False)
    np.testing.assert_array_equal(take2, [[0, 1, 1, 0], [0, 0, 1, 1]])
    # Fewer masked than the quota: all that are left, never a decided one.
    _, rest = diffusion_unmask(logits, x0, jnp.asarray(
        [[0, 0, 0, 1], [0, 0, 0, 0]], bool), 2, 0.9, False)
    np.testing.assert_array_equal(rest, [[0, 0, 0, 1], [0, 0, 0, 0]])


def test_low_confidence_dynamic_takes_all_over_the_threshold_if_enough():
    logits, x0, masked = _unmask_case()
    # Over 0.55: row 0 has three (>= quota 2): all three; row 1 has one
    # masked over it (< quota 2): the static two.
    _, take = diffusion_unmask(logits, x0, masked, 2, 0.55, True)
    np.testing.assert_array_equal(take, [[0, 1, 1, 1], [0, 0, 1, 1]])
    # Nothing over 0.99: the static rule.
    _, none = diffusion_unmask(logits, x0, masked, 1, 0.99, True)
    np.testing.assert_array_equal(none, [[0, 1, 0, 0], [0, 0, 1, 0]])


# -- the scheduler's block edges -----------------------------------------------

def test_scheduler_prefills_whole_blocks_on_block_edges():
    cfg = SchedulerConfig(max_seqs=4, block_size=16, max_pages_per_seq=8,
                          max_prefill_chunk=32, max_batched_tokens=22,
                          decode_buckets=(1, 2, 4), prefill_buckets=(16, 32),
                          token_block=4)
    assert cfg.prefill_target(30) == 28 and cfg.prefill_target(3) == 0
    sched = Scheduler(cfg, BlockAllocator(32))
    long, short = (Request(f"r{i}", list(range(1, n + 1)),
                           SamplingParams(max_tokens=8))
                   for i, n in enumerate((30, 3)))
    sched.add_request(long)
    sched.add_request(short)
    plan = sched.plan()
    # A prompt shorter than a block goes straight to its first block; the
    # budget of 22 (21 left beside the decoding row) is cut to a block edge.
    assert short.state is RequestState.DECODE and short.prefilled == 0
    assert [(w.start, w.length) for w in plan.prefill.items] == [(0, 20)]
    assert plan.decode.requests == [short]
    assert cfg.decode_extent(short) == 4
    sched.prefill_done(plan.prefill.items[0])
    plan = sched.plan()
    assert [(w.start, w.length) for w in plan.prefill.items] == [(20, 8)]
    sched.prefill_done(plan.prefill.items[0])
    assert long.state is RequestState.DECODE and long.prefilled == 28
    assert cfg.decode_extent(long) == 32
    with pytest.raises(ValueError, match="token_block"):
        SchedulerConfig(block_size=16, token_block=3)
    # A causal scheduler is what it was.
    one = SchedulerConfig()
    assert one.prefill_target(30) == 30 and one.token_block == 1


# -- the engine ----------------------------------------------------------------

@pytest.mark.parametrize("B,steps,rule", [
    (4, 4, "low_confidence_static"), (8, 4, "low_confidence_static"),
    (4, 2, "low_confidence_dynamic"), (8, 8, "low_confidence_dynamic")])
def test_whole_generation_matches_the_reference_sampler(B, steps, rule):
    """Prompts with n % B zero and not, 11 tokens (no multiple of B) through
    the scheduler, the paged cache and the block program, against the
    published sampler over the plain float32 reference."""
    hf = dict(HF, diffusion_block_length=B, denoising_steps=steps,
              remasking=rule, confidence_threshold=0.0045)
    core = _core(hf)
    rng = np.random.default_rng(B * 10 + steps)
    prompts = [rng.integers(1, 250, size=n).tolist()
               for n in (3, 2 * B, 2 * B + 1, 37)]
    out, _ = _generate(core, prompts, 11)
    forwards = 0
    for i, p in enumerate(prompts):
        want, n = _reference_generate(hf, core.params, p, 11)
        assert out[f"r{i}"] == want, (i, len(p))
        forwards += n
    c = core.counters
    assert c.diffusion_denoise_forwards + c.diffusion_commit_forwards \
        <= forwards          # rows share forwards; none is run twice
    if rule == "low_confidence_dynamic":
        # The threshold was met somewhere: fewer forwards than static needs.
        assert c.diffusion_positions_unmasked \
            > c.diffusion_denoise_forwards * core.config.model.unmask_per_step


def test_block_length_1_is_the_causal_engine_token_for_token():
    """A model that states block length 1 takes the causal path: the same
    programs, the same tokens as one that states nothing."""
    stated = dict(HF, diffusion_block_length=1, denoising_steps=1,
                  model_type="qwen3_moe")
    plain = {k: v for k, v in stated.items()
             if not k.startswith(("diffusion", "denois", "mask", "remask"))}
    prompts = [list(range(1, n + 1)) for n in (5, 16, 23)]
    a, b = _core(stated), _core(plain)
    assert not a._diffusion and a.scheduler.config.token_block == 1
    assert a.config.model == b.config.model
    out_a, _ = _generate(a, prompts, 10)
    out_b, _ = _generate(b, prompts, 10)
    assert out_a == out_b and all(len(t) == 10 for t in out_a.values())
    assert a.counters.window_dispatches == b.counters.window_dispatches > 0
    assert a.counters.diffusion_commit_forwards == 0
    assert a.counters.block_metrics_lines()[:1] != [
        'dynamo_worker_diffusion_forwards_total{kind="denoise"} 0']


def test_stream_order_max_tokens_cut_and_counters():
    core = _core()
    prompts = [list(range(1, 7)), list(range(20, 36))]    # n % 4 = 2, 0
    out, order = _generate(core, prompts, 9)
    assert [len(out[r]) for r in ("r0", "r1")] == [9, 9]
    for rid in out:             # a request's tokens arrive in order
        assert [t for r, t in order if r == rid] == out[rid]
    assert 255 not in out["r0"] + out["r1"]       # never the mask
    c = core.counters
    # r0: blocks of 2 (tail of 2 known), 4, 3 (cut); r1: 4, 4, 1 (cut).
    assert c.diffusion_blocks_committed == 6
    assert c.window_dispatches == c.diffusion_commit_forwards == 3
    assert c.decode_tokens_emitted == 18
    assert c.host_syncs == c.window_syncs == 3
    assert c.diffusion_positions_unmasked == 2 + 4 + 4 + 4 + 4 + 4
    # Two expert layers a forward and the one prefill chunk, less the last
    # layer of each call's commit, which stops at its last K/V write.
    assert c.moe_layer_forwards == 2 * (
        c.diffusion_denoise_forwards + c.diffusion_commit_forwards + 1) \
        - c.diffusion_commit_forwards
    assert c.diffusion_scored_forwards == c.diffusion_denoise_forwards
    assert 0 < c.diffusion_experts_touched <= c.moe_experts_touched \
        <= 8 * c.moe_layer_forwards
    lines = "\n".join(c.block_metrics_lines())
    assert 'diffusion_forwards_total{kind="commit"} 3' in lines
    assert f"moe_assignments_total {c.moe_assignments}" in lines
    assert core.expert_load.sum() == c.moe_assignments
    assert c.phase_entries[-1] == 3                # dispatch_block
    # The stop token ends a request inside a block; its tail is dropped.
    first = out["r1"][1]
    core2 = _core()
    core2.add_request("s", prompts[1], SamplingParams(
        max_tokens=9, stop_token_ids=(first,)))
    got = []
    while core2.has_work:
        for d in core2.step():
            got.extend(d.token_ids)
    assert got == out["r1"][: out["r1"].index(first) + 1]


def test_prefix_cache_and_preemption_keep_the_tokens():
    """A second identical prompt reuses the sealed pages of the first (whole
    blocks only) and generates the same tokens; so does a pool so small that
    sequences are preempted and recomputed."""
    prompt = list(range(1, 40))                  # 39 = 2 pages + 7
    core = _core()
    first, _ = _generate(core, [prompt], 10)
    hits = core.scheduler.prefix_hit_tokens
    again, _ = _generate(core, [prompt], 10)
    assert again == first
    assert core.scheduler.prefix_hit_tokens - hits == 32
    cfg = loader.config_from_hf(HF, "t").replace(dtype=jnp.float32)
    tight = EngineCore(EngineConfig(
        model=cfg, num_blocks=9, enable_prefix_cache=False,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=16, max_pages_per_seq=8, watermark=0.0,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4),
            prefill_buckets=(16, 32))))
    prompts = [list(range(1 + i, 40 + i)) for i in range(3)]
    roomy, _ = _generate(_core(enable_prefix_cache=False), prompts, 26)
    squeezed, _ = _generate(tight, prompts, 26)
    assert squeezed == roomy
    assert sum(r.preempts for r in tight.scheduler.running) == 0


def test_sampled_blocks_are_seeded_and_never_the_mask():
    core = _core()
    prompt = list(range(1, 12))
    kw = dict(temperature=0.8, top_k=20, seed=7)
    a, _ = _generate(core, [prompt], 12, **kw)
    b, _ = _generate(core, [prompt], 12, **kw)
    c, _ = _generate(core, [prompt], 12, temperature=0.8, top_k=20, seed=8)
    assert a == b and a != c
    assert 255 not in a["r0"] + c["r0"]


def test_prewarm_prefill_unpacks_on_an_expert_block():
    """`--prewarm-prefill` on routed experts (packed step: three outputs,
    four for a block-diffusion model) compiles every shape and serves."""
    for hf in (HF, dict(HF, diffusion_block_length=1, model_type="qwen3_moe")):
        core = _core(hf, packed_prefill=True)
        assert core.prewarm_prefill() == len(core.packed_prefill_shape_set())
        out, _ = _generate(core, [list(range(1, 20))], 5)
        assert len(out["r0"]) == 5
        assert core.counters.packed_prefill_dispatches >= 1


def test_a_mesh_or_speculation_is_refused_pointedly():
    cfg = loader.config_from_hf(HF, "t").replace(dtype=jnp.float32)
    with pytest.raises(ValueError, match="block-diffusion"):
        EngineCore(EngineConfig(model=cfg, speculative_tokens=2))


# -- one block call in flight --------------------------------------------------

def _steps(core):
    """Step until no work is left; [(tokens by request, terminal reasons)]
    for each iteration, and every token in order."""
    per_step, out = [], {}
    while core.has_work:
        got, ended = {}, {}
        for d in core.step():
            got.setdefault(d.request_id, []).extend(d.token_ids)
            out.setdefault(d.request_id, []).extend(d.token_ids)
            if d.finished:
                ended[d.request_id] = d.finish_reason
        per_step.append((got, ended))
    return per_step, out


def test_a_block_call_is_dispatched_before_the_one_before_is_read():
    """Two or more blocks to go: every call but a sequence set's first is
    dispatched while its predecessor is unread, each call is read once, and
    a `max_tokens` end inside a block dispatches nothing more for that row
    (no row is ever dropped)."""
    core = _core()
    prompts = [list(range(1, 7)), list(range(20, 36)), list(range(3, 40))]
    out, _ = _generate(core, prompts, 25)
    assert all(len(t) == 25 for t in out.values())
    c = core.counters
    # r0 (tail of 2 known): 2 + 5 x 4 + 3; r1, r2: 6 x 4 + 1: 7 blocks each.
    assert c.diffusion_blocks_committed == 21 and c.diffusion_rows_dropped == 0
    assert c.block_calls_overlapped == c.window_dispatches - 1 > 0
    assert c.host_syncs == c.window_syncs == c.window_dispatches
    assert c.phase_entries[-1] == c.window_dispatches   # dispatch_block
    lines = "\n".join(c.block_metrics_lines())
    assert ("dynamo_worker_block_calls_overlapped_total "
            f"{c.block_calls_overlapped}") in lines
    assert "dynamo_worker_diffusion_rows_dropped_total 0" in lines
    # The same streams as the serial order of reads gives: one at a time.
    for i, p in enumerate(prompts):
        alone, _ = _generate(_core(), [p], 25)
        assert alone["r0"] == out[f"r{i}"]


def test_work_lasts_until_the_last_call_is_read_and_a_last_block_waits_for_nothing():
    core = _core()
    core.add_request("a", list(range(1, 9)), SamplingParams(max_tokens=10))
    per_step, out = _steps(core)
    # Prefill; block 0 dispatched (nothing to read); block 1 dispatched and
    # 0 read; block 2 (the last: 2 of its 4 tokens) dispatched, 1 read, and
    # 2 read at once in that same iteration.
    assert [sum(map(len, got.values())) for got, _ in per_step] == [0, 0, 4, 6]
    assert per_step[-1][1] == {"a": "length"} and len(out["a"]) == 10
    c = core.counters
    assert c.window_dispatches == c.host_syncs == 3
    assert c.block_calls_overlapped == 2
    # `has_work` while a call is unread, even with no request left: a row
    # that stops inside block N leaves block N + 1 in flight.
    first = out["a"][1]
    core.add_request("s", list(range(1, 9)), SamplingParams(
        max_tokens=10, stop_token_ids=(first,)))
    core.step(), core.step()                 # prefill, block 0 dispatched
    assert core._block_unread is not None and core.has_work
    while core.has_work:
        core.step()
    assert core._block_unread is None and not core._requests


def test_a_stop_inside_a_block_drops_the_block_dispatched_behind_it():
    """The row rides once more: its next block is computed and dropped: no
    token of it, its pages back in the pool, nothing past the stop sealed
    in the prefix cache, no part of it in a recording, one row counted."""
    prompt = list(range(1, 33))                      # 2 pages of 16
    free = _core().allocator.free_blocks
    plain, _ = _generate(_core(), [prompt], 40)
    stop = plain["r0"][17]                           # inside block 4
    n = plain["r0"].index(stop) + 1
    assert n > 8
    core = _core()
    core.block_record = record = []
    core.block_record_logits = False
    core.add_request("s", prompt, SamplingParams(
        max_tokens=40, stop_token_ids=(stop,)))
    core.add_request("t", list(range(40, 60)), SamplingParams(max_tokens=40))
    _, out = _steps(core)
    assert out["s"] == plain["r0"][:n]
    assert len(out["t"]) == 40
    c = core.counters
    assert c.diffusion_rows_dropped == 1
    blocks = -(-n // 4)
    per_rid = [e for e in record if not e.get("prefill")]
    assert sum("s" in e["rids"] for e in per_rid) == blocks
    # The call that held the dropped row recorded the other row alone.
    late = [e for e in per_rid if e["rids"] == ["t"]]
    assert late and all(e["fed"].shape[1] >= 1 and len(e["starts"]) == 1
                        for e in late)
    assert late[0]["routing"].shape[2] == late[0]["fed"].shape[1] * 4
    # Pages: all back (sealed ones stay matchable but free).
    assert core.allocator.free_blocks == free
    # A prompt that runs past the stop finds whole pages of what was
    # streamed and committed, never the dropped block's page.
    hits = core.scheduler.prefix_hit_tokens
    longer = prompt + out["s"] + plain["r0"][n: n + 20]
    _generate(core, [longer], 4)
    sealed = (len(prompt) + (n - 1) // 4 * 4) // 16 * 16
    assert core.scheduler.prefix_hit_tokens - hits <= sealed


def test_max_tokens_inside_a_block_dispatches_no_further_call_for_the_row():
    core = _core()
    core.add_request("short", list(range(1, 9)), SamplingParams(max_tokens=6))
    core.add_request("long", list(range(11, 19)),
                     SamplingParams(max_tokens=14))
    _, out = _steps(core)
    assert [len(out[r]) for r in ("short", "long")] == [6, 14]
    c = core.counters
    # short rides calls 1-2, long calls 1-4: six blocks, none dropped.
    assert c.window_dispatches == 4 and c.diffusion_blocks_committed == 6
    assert c.diffusion_rows_dropped == 0
    assert c.diffusion_row_forwards == 6 * 5


def test_cancel_with_a_call_unread_reads_it_first():
    core = _core()
    prompts = [list(range(1, 9)), list(range(11, 19))]
    want, _ = _generate(_core(), prompts, 16)
    free = core.allocator.free_blocks
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", p, SamplingParams(max_tokens=16))
    out = {"r0": [], "r1": []}
    for _ in range(3):                    # prefill, block 0, block 1 + read 0
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
    assert core._block_unread is not None and len(out["r0"]) == 4
    syncs = core.counters.host_syncs
    core.cancel("r0")
    # The unread block's tokens are read before the request goes, and
    # handed out by the next step, before its terminal delta.
    assert core._block_unread is None and core.has_work
    assert core.counters.host_syncs == syncs + 1
    reasons = {}
    while core.has_work:
        for d in core.step():
            assert not (d.token_ids and d.request_id in reasons)
            out[d.request_id].extend(d.token_ids)
            if d.finished:
                reasons[d.request_id] = d.finish_reason
    assert out["r0"] == want["r0"][:8] and reasons["r0"] == "cancelled"
    assert out["r1"] == want["r1"] and reasons["r1"] == "length"
    assert core.counters.diffusion_rows_dropped == 0
    assert core.allocator.free_blocks == free
    # Cancelling what is gone reads nothing (a served stream's close).
    core.cancel("r0")
    assert not core.has_work


def test_a_full_pool_drains_before_it_preempts_and_streams_are_unchanged():
    cfg = loader.config_from_hf(HF, "t").replace(dtype=jnp.float32)

    def engine(num_blocks):
        return EngineCore(EngineConfig(
            model=cfg, num_blocks=num_blocks, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=16, max_pages_per_seq=8,
                watermark=0.0, max_prefill_chunk=32,
                decode_buckets=(1, 2, 4), prefill_buckets=(16, 32))))

    prompts = [list(range(1 + i, 40 + i)) for i in range(3)]
    kw = dict(temperature=0.7, top_k=12, seed=11)
    roomy, _ = _generate(engine(64), prompts, 26, **kw)
    tight = engine(9)
    preempted = []
    real = tight.scheduler.preempt

    def preempt(req):
        # Host bookkeeping is exact when a sequence is preempted.
        assert tight._block_unread is None
        preempted.append(req.request_id)
        real(req)

    tight.scheduler.preempt = preempt
    squeezed, _ = _generate(tight, prompts, 26, **kw)
    assert preempted
    assert squeezed == roomy
    assert tight.counters.diffusion_rows_dropped == 0


def test_a_seeded_stream_depends_on_the_seed_and_the_index_alone():
    """Recorded on the parent of the change that reads one call behind (its
    sampler's offsets counted appended tokens; they now count dispatched
    ones): alone, and beside other rows."""
    kw = dict(temperature=0.8, top_k=20, seed=7)
    prompts = [list(range(1, 12)), list(range(30, 47)), list(range(5, 10))]
    want = {"r0": [102, 178, 35, 147, 18, 178, 147, 81, 127, 154, 154, 30],
            "r1": [34, 34, 119, 216, 168, 149, 215, 116, 202, 168, 43, 168],
            "r2": [19, 125, 114, 213, 35, 102, 139, 102, 251, 102, 33, 8]}
    alone, _ = _generate(_core(), prompts[:1], 12, **kw)
    assert alone["r0"] == want["r0"]
    beside, _ = _generate(_core(), prompts, 12, **kw)
    assert beside == want


def test_what_touches_the_cache_between_steps_reads_the_call_first():
    core = _core()
    want, _ = _generate(_core(), [list(range(1, 9))], 12)
    core.add_request("r0", list(range(1, 9)), SamplingParams(max_tokens=12))
    core.step(), core.step()
    assert core._block_unread is not None
    core.clear_prefix_cache()
    assert core._block_unread is None and core._block_held
    _, out = _steps(core)
    assert out["r0"] == want["r0"]
    assert core.counters.host_syncs == core.counters.window_dispatches == 3


# -- a commit stops at its last K/V write --------------------------------------

DENSE_HF = dict({k: v for k, v in HF.items()
                 if "expert" not in k and k != "norm_topk_prob"},
                model_type="sdar")

# What the parent's block program (every forward whole) returned for
# `_three_blocks`, live rows only, recorded before the commit was cut short.
# The grouped kernel and the dense expert path are byte-identical.
PARENT_BLOCKS = {
    "moe-greedy-static": [[[130, 99, 35, 99], [7, 9, 230, 200], [37, 61, 85, 98]], [[230, 107, 58, 107], [17, 61, 125, 145], [85, 60, 85, 85]], [[102, 102, 98, 98], [221, 221, 221, 72], [72, 46, 60, 71]]],
    "moe-greedy-dynamic": [[[35, 35, 35, 35], [7, 9, 230, 200], [98, 98, 98, 98]], [[121, 121, 160, 160], [17, 17, 125, 10], [205, 205, 205, 228]], [[177, 177, 121, 121], [37, 187, 187, 187], [61, 68, 68, 68]]],
    "moe-sampled-static": [[[242, 71, 99, 35], [7, 9, 230, 46], [97, 72, 98, 28]], [[148, 8, 8, 8], [178, 57, 14, 43], [72, 72, 199, 37]], [[148, 8, 229, 8], [106, 137, 8, 106], [60, 232, 217, 230]]],
    "moe-sampled-dynamic": [[[242, 71, 99, 35], [7, 9, 230, 46], [97, 72, 98, 28]], [[107, 203, 8, 8], [29, 57, 57, 14], [85, 72, 199, 37]], [[229, 8, 8, 19], [216, 156, 234, 178], [226, 217, 217, 230]]],
    "dense-greedy-static": [[[251, 8, 251, 35], [7, 9, 230, 35], [85, 98, 209, 209]], [[8, 8, 8, 8], [209, 230, 102, 209], [217, 43, 115, 249]], [[8, 8, 8, 8], [230, 230, 170, 68], [185, 103, 206, 249]]],
    "dense-greedy-dynamic": [[[251, 251, 251, 35], [7, 9, 230, 230], [209, 209, 209, 209]], [[8, 8, 8, 8], [165, 209, 209, 230], [73, 73, 73, 73]], [[8, 8, 8, 8], [230, 19, 230, 230], [43, 43, 43, 85]]],
    "dense-sampled-static": [[[57, 107, 64, 251], [7, 9, 35, 97], [189, 16, 173, 206]], [[156, 225, 156, 107], [230, 230, 228, 209], [185, 16, 209, 87]], [[187, 224, 251, 135], [230, 230, 251, 205], [189, 19, 209, 209]]],
    "dense-sampled-dynamic": [[[57, 107, 64, 251], [7, 9, 35, 97], [232, 16, 206, 206]], [[168, 251, 187, 107], [209, 230, 209, 209], [185, 16, 185, 8]], [[187, 156, 251, 251], [230, 230, 251, 205], [185, 178, 206, 115]]],
}  # noqa: E501
# Denoising forwards a call under the dynamic rule (threshold 0.027): the
# commit comes earlier, and the skip with it.
PARENT_DENOISE = {"moe-greedy-dynamic": [2, 2, 1],
                  "moe-sampled-dynamic": [4, 3, 3],
                  "dense-greedy-dynamic": [2, 1, 1],
                  "dense-sampled-dynamic": [4, 3, 4]}


def _block_programs(model, greedy, rule):
    """(cfg, params, an empty cache, build(record) -> the jitted block
    program) at toy widths: `grouped` through both kernels in interpret
    mode, `moe` and `dense` through the gather path."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    hf = dict(DENSE_HF if model == "dense" else HF, remasking=rule,
              confidence_threshold=0.027)
    cfg = loader.config_from_hf(hf, "t").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, num_blocks=16, block_size=16, dtype=jnp.float32))

    def build(record):
        return jax.jit(llama.make_block_step(
            cfg, 16, use_pallas_decode=model == "grouped",
            greedy_only=greedy,
            moe_mode="grouped" if model == "grouped" else "dense",
            record=record))

    return cfg, params, cache, build


def _block_args(cfg, starts, known):
    """The nine small arguments of a block call: row i's block starts at
    `starts[i]` (None = a dead row, as the engine pads) with `known[i]`
    its first tokens; sampling parameters and keys as a sampled call's."""
    R, B = len(starts), cfg.diffusion_block_length
    tokens = np.full((R, B), cfg.mask_token_id, np.int32)
    positions = np.zeros((R, B), np.int32)
    seq_lens = np.zeros((R,), np.int32)
    bts = np.zeros((R, 2), np.int32)
    for i, c in enumerate(starts):
        if c is None:
            tokens[i] = 0
            continue
        first = known.get(i, [])
        tokens[i, :len(first)] = first
        positions[i] = np.arange(c, c + B)
        seq_lens[i] = c + B
        bts[i] = [1 + 2 * i, 2 + 2 * i]
    keys = np.asarray(jax.random.key_data(
        jax.random.split(jax.random.key(5), R)))
    offsets = np.asarray([0 if c is None else c // B for c in starts],
                         np.int32)
    return tuple(jnp.asarray(a) for a in (
        tokens, positions, seq_lens, bts, np.full((R,), 0.8, np.float32),
        np.full((R,), 20, np.int32), np.ones((R,), np.float32), keys,
        offsets))


def _three_blocks(cfg, params, cache, fn):
    """Three block calls in a row over four rows: one from position 0, one
    from 8 whose first block opens with two known tokens, a dead one and
    one from 20.  Returns the outputs of each call."""
    outs = []
    for b in range(3):
        out = fn(params, cache, *_block_args(
            cfg, [4 * b, 8 + 4 * b, None, 20 + 4 * b],
            {1: [7, 9]} if b == 0 else {}))
        cache = out[0]
        outs.append(out)
    return outs


@pytest.mark.parametrize("rule", ["static", "dynamic"])
@pytest.mark.parametrize("sampler", ["greedy", "sampled"])
@pytest.mark.parametrize("model", ["grouped", "moe", "dense"])
def test_a_commit_stops_at_its_last_kv_write(model, sampler, rule):
    """The served block program beside its recording twin, whose commit
    runs whole: the same K and V in every layer bit for bit, the same
    tokens and tallies, the trail in its form with nothing routed in the
    commit's last layer, the expert counters less that layer, and the
    streams the parent's program gave."""
    cfg, params, cache, build = _block_programs(
        model, sampler == "greedy", "low_confidence_" + rule)
    served = _three_blocks(cfg, params, cache, build(False))
    twin = _three_blocks(cfg, params, cache, build(True))
    key = f"{'dense' if model == 'dense' else 'moe'}-{sampler}-{rule}"
    L, B, E = cfg.num_layers, cfg.diffusion_block_length, cfg.num_experts
    for b, (got, ref) in enumerate(zip(served, twin)):
        for name in ("k", "v"):
            for layer, (x, y) in enumerate(zip(got[0][name], ref[0][name])):
                assert np.array_equal(x, y), (b, name, layer)
        toks, stats = np.asarray(got[1]), np.asarray(got[2])
        assert np.array_equal(toks, ref[1])
        assert np.array_equal(stats[:2], np.asarray(ref[2])[:2])
        n = int(stats[0])                      # the commit's index
        assert n == PARENT_DENOISE.get(key, [4, 4, 4])[b]
        assert int(stats[2]) == n and int(ref[2][2]) == n + 1
        assert toks[[0, 1, 3]].tolist() == PARENT_BLOCKS[key][b]
        trail, whole = got[4], ref[4]
        assert "logits" not in trail and whole["logits"].shape == (
            5, 4, B, cfg.vocab_size)
        for name in ("fed", "masked"):
            assert trail[name].shape == (5, 4, B)
            assert np.array_equal(trail[name], whole[name])
        assert np.array_equal(trail["fed"][n], toks)
        assert not np.asarray(trail["masked"][n]).any()
        if model == "dense":
            assert "routing" not in trail
            assert int(got[3]["load"].sum()) == int(got[3]["touched"]) == 0
            continue
        k = cfg.num_experts_per_token
        routing, full = (np.array(t["routing"]) for t in (trail, whole))
        assert routing.shape == full.shape == (5, L, 4 * B, k)
        assert (routing[n, L - 1] == -1).all() and (full[n] >= 0).all()
        routing[n, L - 1] = full[n, L - 1]
        assert np.array_equal(routing, full)
        # What the twin's commit routed in its last layer, dead row and all.
        last = np.bincount(full[n, L - 1].ravel(), minlength=E)
        assert np.array_equal(np.asarray(got[3]["load"])[:E] + last,
                              np.asarray(ref[3]["load"])[:E])
        assert int(got[3]["touched"]) + int((last > 0).sum()) \
            == int(ref[3]["touched"])
        assert int(got[3]["load"].sum()) == (L * (n + 1) - 1) * 4 * B * k


@pytest.mark.parametrize("model", ["grouped", "dense"])
def test_a_call_with_all_rows_dead_scores_nothing(model):
    """The form the benchmark's warm-up calls the program in (eleven
    arguments, every block end 0): no denoising forward, one commit, and
    that one stops at its last K/V write."""
    cfg, params, cache, build = _block_programs(
        model, True, "low_confidence_static")
    args = _block_args(cfg, [None] * 4, {})
    new_cache, toks, stats, moe, trail = build(False)(params, cache, *args)
    assert np.asarray(stats).tolist() == [0, 0, 0]
    assert np.array_equal(toks, args[0])
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)
    if cfg.is_moe:
        # The first layer's experts saw the padding rows, the last none.
        assert int(moe["load"].sum()) == (cfg.num_layers - 1) \
            * 4 * 4 * cfg.num_experts_per_token
        assert (np.asarray(trail["routing"])[0, -1] == -1).all()
    assert np.asarray(build(True)(params, cache, *args)[2]).tolist() \
        == [0, 0, 1]


@pytest.mark.parametrize("with_logits", [False, True])
def test_the_counters_tell_what_a_call_ran(with_logits):
    """Beside the forwards by kind: the forwards that were scored, as the
    program counted them; the expert layers that ran, one less a call of
    the served program; and the modeled KV sweep, one layer's less a
    commit.  The program that hands out logits runs its commit whole."""
    core = _core()
    core.block_record = record = []
    core.block_record_logits = with_logits
    out, _ = _generate(core, [list(range(1, 7)), list(range(20, 36))], 9)
    assert [len(t) for t in out.values()] == [9, 9]
    c = core.counters
    L, B = 2, 4
    calls = [e for e in record if not e.get("prefill")]
    forwards = c.diffusion_denoise_forwards + c.diffusion_commit_forwards
    assert len(calls) == c.diffusion_commit_forwards == 3
    assert forwards == sum(e["forwards"] for e in calls)
    short = 0 if with_logits else len(calls)
    assert c.diffusion_scored_forwards == forwards - short
    assert ("dynamo_worker_diffusion_scored_forwards_total "
            f"{forwards - short}") in c.block_metrics_lines()
    prefill_layers = L * c.prefill_dispatches
    assert c.moe_layer_forwards == L * forwards - short + prefill_layers
    assert "dynamo_worker_moe_layer_forwards_total " \
        f"{c.moe_layer_forwards}" in c.block_metrics_lines()
    per_token = core.cache_cfg.bytes_per_context_token
    assert c.kv_read_bytes_modeled == sum(
        sum(start + B for start in e["starts"])
        * (L * e["forwards"] - (not with_logits)) * per_token // L
        for e in calls)
    for e in calls:            # the trail of a served call says so too
        last = e["routing"][e["forwards"] - 1, L - 1]
        assert (last == -1).all() != with_logits


@pytest.mark.parametrize("experts", [8, 64])
def test_the_counters_tell_the_packed_rows_a_call_walked(experts):
    """`moe_packed_rows`: the rows of the packed buffers the grouped kernel
    was handed, from the programs' static shapes alone: a block call's
    expert layer-forwards (one less than layers x forwards: the served
    commit) over its row bucket x 4 positions, and the prefill chunks' over
    their tokens.  At 64 experts of 2 a token a call of one or two rows
    has fewer assignments than experts and packs a tile an assignment."""
    from dynamo_tpu.ops.pallas.moe_grouped import auto_block_rows, packed_rows

    core = _core(dict(HF, num_experts=experts), moe_mode="grouped")
    dispatched, real = [], core.counters.note_dispatch

    def note(tag, *sig):
        dispatched.append((tag,) + sig)
        return real(tag, *sig)

    core.counters.note_dispatch = note
    core.block_record = record = []
    core.block_record_logits = False       # the served program
    out, _ = _generate(core, [list(range(1, 7)), list(range(20, 36))], 9)
    assert [len(t) for t in out.values()] == [9, 9]
    L, B, k = 2, 4, 2

    def rows(tokens):
        S = tokens * k
        return packed_rows(S, experts, auto_block_rows(S, experts))

    calls = [e for e in record if not e.get("prefill")]
    buckets = [sig[2] for tag, *sig in dispatched if tag == "block"]
    chunks = [sig[0] * (sig[1] if tag == "prefill" else 1)
              for tag, *sig in dispatched if tag.startswith("prefill")]
    assert len(buckets) == len(calls) == 3 and chunks
    want = sum((L * e["forwards"] - 1) * rows(b * B)
               for e, b in zip(calls, buckets)) \
        + sum(L * rows(t) for t in chunks)
    c = core.counters
    assert c.moe_packed_rows == want >= c.moe_assignments > 0
    if experts == 64:
        assert any(b * B * k < experts for b in buckets)
    assert f"dynamo_worker_moe_packed_rows_total {want}" \
        in c.block_metrics_lines()
