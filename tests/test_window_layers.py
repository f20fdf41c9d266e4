"""Window layers beside a full one (the `cohere2_moe` block: attention and
experts in parallel on one mean-subtracting norm, the interleaved-pair rotary
embedding on the window layers alone, averaged shared experts, a share of the
routed experts), at tiny widths on the CPU with seeded weights: the loader, the
program against the plain reference across the window's edge and a page
release, the window kernels against the gather path, the eight shares of an
expert layer against the uncut layer, the window group's allocator, and every
combination the window is refused."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    BlockAllocator, Request, RequestState, Scheduler, SchedulerConfig,
    window_cap_blocks)
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import (
    TINY, TINY_MLA, TINY_WINDOW, WINDOW_MESHLESS, WINDOW_NO_TRANSFER)
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas import (
    paged_decode_attention, paged_prefill_attention,
    paged_window_decode_attention, paged_window_prefill_attention)
from dynamo_tpu.ops.pallas.paged_prefill import head_groups

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF = {"model_type": "cohere2_moe", "hidden_size": 64, "intermediate_size": 32,
      "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
      "vocab_size": 256, "num_hidden_layers": 4, "layer_norm_eps": 1e-5,
      "rope_theta": 10000.0, "max_position_embeddings": 512,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
      "sliding_window": 24, "num_experts": 4, "num_experts_per_tok": 4,
      "num_shared_experts": 2,
      "routed_experts_held": {"first": 4, "count": 4, "of": 16},
      "tie_word_embeddings": True, "use_parallel_block": True,
      "norm_topk_prob": True, "position_embedding_type": "rope_gptj",
      "shared_expert_combination_strategy": "average",
      "expert_selection_fn": "sigmoid", "logit_scale": 1,
      "reference": "parallel_window_gqa_shared_routed_moe",
      "comparison": "causal_logits_window_routed"}
BS = 8


def _load(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("references", HF["reference"])


def _engine(cfg=TINY_WINDOW, max_seqs=4, window=4, blocks=64, **kw):
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


# ---------------------------------------------------------------------------
# The loader and the configuration


def test_config_from_hf_maps_the_block():
    cfg = loader.config_from_hf(HF, "t")
    assert cfg == TINY_WINDOW.replace(name="t", dtype=cfg.dtype)
    assert cfg.window_layers == (0, 1, 2) and cfg.window_of(3) is None
    assert [cfg.rope_of(i) for i in range(4)] == [True, True, True, False]
    with open(os.path.join(ROOT, "chipbench/configs/"
                           "command-a-plus-05-2026-d4-ep8.json")) as f:
        hf = json.load(f)
    real = loader.config_from_hf(hf, "c")
    real.validate()
    # The issue's count: 4 x (344.5 + 16 x 50.33) M and an eighth of the
    # tied vocabulary.
    assert real.param_count() == pytest.approx(4.733e9, rel=1e-4)
    assert real.experts_held == (0, 16) and real.num_experts == 128
    assert real.layer_windows == (4096, 4096, 4096, 0)
    flags = dict(zip(hf["engine_flags"][::2], hf["engine_flags"][1::2]))
    cap = window_cap_blocks(4096, 512, 256)
    assert cap == 19
    cc = kvc.KvCacheConfig.for_model(
        real, int(flags["--num-blocks"]), 256, window_blocks=513)
    # One full layer: 4,096 B a token, 1 MiB a block; three window layers:
    # 3 MiB a block.
    assert (cc.bytes_per_context_token, cc.bytes_per_block,
            cc.window_bytes_per_block) == (4096, 1 << 20, 3 << 20)
    assert cc.window_places == (0, 1, 2) and cc.full_layers == 1


@pytest.mark.parametrize("change,message", [
    ({"attention_bias": True}, "attention_bias"),
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"first_k_dense_replace": 1}, "prefix dense layers"),
    ({"use_parallel_block": False}, "use_parallel_block false"),
    ({"logit_scale": 0.25}, "logit_scale"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"shared_expert_combination_strategy": "sum"}, "combination_strategy"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"attention_sinks": True}, "sink terms"),
    ({"layer_types": ["sliding_attention"] * 3}, "names 3 layers"),
    ({"layer_types": ["chunked_attention"] * 4}, "unknown layer type"),
    ({"sliding_window": None}, "need sliding_window"),
    ({"routed_experts_held": {"first": 0, "count": 8, "of": 16}},
     "routed_experts_held.count"),
])
def test_loader_refuses_what_it_does_not_map(change, message):
    with pytest.raises(ValueError, match=message):
        loader.config_from_hf(dict(HF, **change), "t").validate()


def test_the_loader_keeps_its_refusal_of_a_window_under_a_pattern():
    with open(os.path.join(ROOT, "chipbench/configs/"
                           "nemotron-3-super-120b-a12b-d11-ep4.json")) as f:
        hf = dict(json.load(f), sliding_window=4096)
    with pytest.raises(ValueError, match="nemotron_h: sliding_window and "
                       "moe_shared_expert_overlap are not implemented"):
        loader.config_from_hf(hf, "n")


@pytest.mark.parametrize("build,message", [
    (lambda: _engine(kv_quant="int8"), "no int8 KV form"),
    (lambda: _engine(speculative_tokens=2), "speculative decoding"),
    (lambda: _engine(host_blocks=8), "tier offload"),
    (lambda: _engine(disk_blocks=8), "tier offload"),
    (lambda: _engine(remote_fetch_fn=lambda *a: None), "tier offload"),
    (lambda: _engine(mesh=object()), WINDOW_MESHLESS[:40]),
    (lambda: llama.make_forward_step(TINY_WINDOW, BS, mesh=object()),
     "serves meshless"),
    (lambda: llama.make_forward_step(TINY_WINDOW, BS, sp_ring=True),
     "ring/sequence-parallel"),
    (lambda: TINY_WINDOW.replace(
        diffusion_block_length=4, denoising_steps=4,
        mask_token_id=255).validate(), "block diffusion under a window"),
    (lambda: TINY_MLA.replace(
        layer_windows=(8, 8, 0)).validate(), "latent attention .* window"),
    (lambda: TINY_WINDOW.replace(
        layer_windows=(24, 16, 24, 0)).validate(), "different lengths"),
    (lambda: TINY_WINDOW.replace(
        layer_windows=(24, 24, 24, 24)).validate(), "all have a window"),
    (lambda: TINY_WINDOW.replace(layer_windows=(24, 0)).validate(),
     "names 2 layers"),
    (lambda: TINY.replace(parallel_block=True).validate(),
     "parallel block"),
    (lambda: TINY.replace(norm_kind="batch").validate(), "norm_kind"),
    (lambda: kvc.KvCacheConfig.for_model(
        TINY_WINDOW, 16, BS, kv_quant="int8", window_blocks=8),
     "no int8 KV form"),
    (lambda: _engine().export_blocks([1]), "disagg block transfer"),
    (lambda: _engine().export_blocks_device([1]), "drain migration"),
    (lambda: _engine().import_blocks({}), "tier offload"),
    (lambda: _engine().embed_tokens([[1, 2, 3]]),
     "embeddings are not wired"),
    (lambda: llama.make_forward_step(TINY_WINDOW, BS)(
        {"embed": jnp.zeros((4, 64))}, {},
        *(jnp.zeros((1, 1), jnp.int32),) * 2,
        jnp.ones((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32)),
     "window_tables"),
], ids=["int8", "speculative", "host-tier", "disk-tier", "remote-fetch",
        "mesh", "mesh-step", "ring", "block-diffusion", "latent",
        "two-windows", "no-full-layer", "list-length", "parallel-dense",
        "norm-kind", "int8-cache", "export", "export-device", "import",
        "embeddings", "no-window-tables"])
def test_each_refused_combination_raises_by_name(build, message):
    with pytest.raises((ValueError, AttributeError, TypeError),
                       match=message) as caught:
        build()
    assert caught.type is ValueError
    assert "drain migration" in WINDOW_NO_TRANSFER \
        and "disagg block transfer" in WINDOW_NO_TRANSFER


# ---------------------------------------------------------------------------
# The program against the plain reference


@pytest.fixture(scope="module")
def checked(reference):
    """The configuration's comparison at tiny widths, window 24 over blocks
    of 8 and chunks of 16, the kernels in interpret mode: prompts under the
    window, with a chunk that straddles its edge, and well past it (their
    decoded tokens cross block edges, so pages go back between windows);
    the sound engine and the two controls."""
    comparison = _load("comparisons", HF["comparison"])
    core = _engine(window=8, packed_prefill=True, use_pallas_decode=True,
                   moe_mode="grouped")
    return comparison.controls(core, HF, 7, reference,
                               lengths=(5, 17, 30, 40, 90)), comparison


def test_the_program_follows_the_reference_across_the_window_and_a_release(
        checked):
    out = checked[0]["None"]
    assert out["ok"], out["problems"]
    assert out["compared"] == 5
    # float32 both sides: rounding alone.
    assert {item["name"]: item["value"] < 1e-3 for item in out["limits"]} \
        == dict.fromkeys(("max_abs_logit_diff", "max_body_logit_diff",
                          "max_decode_margin", "max_choice_shortfall"), True)
    # 90 + 27 tokens behind a window of 24: a dozen blocks went back, some
    # of them under the decoded tokens (30 -> 56 and 40 -> 66 cross edges).
    assert out["window_blocks_released"] >= 12


@pytest.mark.parametrize("control", ["f8_weights", "no_window"])
def test_each_control_is_refused(checked, control):
    """A lower precision than the configuration states, and the reference
    run with no window, each fail at least one limit."""
    results, comparison = checked
    assert control in comparison.CONTROLS
    out = results[control]
    assert out["ok"] is False and out["compared"] == 5
    over = [i["name"] for i in out["limits"] if i["value"] > i["limit"]]
    assert over, out["limits"]
    if control == "no_window":      # a prompt inside the window cannot tell
        assert out["rows"][0]["logit_diff_max"] < 1e-3
        assert out["rows"][-1]["logit_diff_max"] > 0.5


def test_every_plane_serves_three_prompts_as_each_alone(reference):
    """Padded and packed prefill, windows and single steps, the gather path
    and the kernels: the tokens each prompt gives alone, which are the
    reference's own greedy choices; every page of both groups comes back."""
    prompts = _prompts(5, 19, 70)
    alone = [_generate(_engine(window=1, max_seqs=1), [p])[0]
             for p in prompts]
    params = _engine().params
    for p, out in zip(prompts, alone):
        seq = p + out
        best = np.asarray(jnp.argmax(reference.forward(HF, params, seq), -1))
        assert out == best[len(p) - 1:len(seq) - 1].tolist()
    for kw in (dict(window=4),
               dict(window=4, packed_prefill=True, use_pallas_decode=True),
               dict(window=4, packed_prefill=True, moe_mode="grouped")):
        core = _engine(**kw)
        assert _generate(core, prompts) == alone, kw
        assert core.scheduler.window_released > 0
        assert core.window_allocator.free_blocks \
            == core.cache_cfg.window_blocks - 1
        assert core.allocator.free_blocks == 63
        lines = core.counters.block_metrics_lines()
        assert 'dynamo_model_layers{kind="window"} 3' in lines
        assert 'dynamo_model_layers{kind="full"} 1' in lines
        assert any(line.startswith(
            "dynamo_kv_window_blocks_released_total ") for line in lines)
        pairs = core.counters.attn_pairs
        for at in ("decode", "prefill"):
            assert 0 < pairs[at]["window"] + pairs[at]["full"] \
                < pairs[at]["unwindowed"]


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        reference):
    """Eight chips each hold an eighth of the routed experts and all of the
    shared ones: their layers' outputs, the shared experts counted once, sum
    to the whole layer's, in the program and in the reference alike."""
    hf = dict(HF, num_experts=16, routed_experts_held=None)
    whole = loader.config_from_hf(hf, "w").replace(dtype=jnp.float32)
    params = llama.init_params(whole, jax.random.key(1))
    moe = params["layers"][0]["moe"]
    h = jax.random.normal(jax.random.key(2), (1, 13, 64), jnp.float32)
    want, _ = reference.ffn(hf, params["layers"][0], h[0])
    uncut, _ = llama._moe_block(whole, moe, h, "dense", None)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(want),
                               atol=2e-5)
    shared = llama._dense_mlp(moe["shared"], h) / whole.n_shared_experts
    total = 0
    for first in range(0, 16, 2):
        part = whole.replace(experts_held=(first, 2))
        held = dict(moe, **{k: moe[k][first:first + 2]
                            for k in ("w_gate", "w_up", "w_down")})
        for mode in ("dense", "grouped"):
            out, load = llama._moe_block(part, held, h, mode, None)
            ref_part, _ = reference.ffn(dict(hf, num_experts=2,
                                             routed_experts_held={
                "first": first, "count": 2, "of": 16}),
                {"moe": held}, h[0])
            np.testing.assert_allclose(np.asarray(out[0]),
                                       np.asarray(ref_part), atol=2e-5)
        assert int(load[:-1].sum()) == 13 * 4     # the router's whole width
        total = total + out - shared
    np.testing.assert_allclose(np.asarray((total + shared)[0]),
                               np.asarray(want), atol=5e-5)


# ---------------------------------------------------------------------------
# The kernels against the gather path


@pytest.mark.parametrize("heads,kv_heads,dim", [(8, 4, 16), (64, 4, 128)],
                         ids=["one-group", "two-head-groups"])
@pytest.mark.parametrize("window", [None, 5, 13, 20],
                         ids=["none", "under-a-tile", "not-a-block-multiple",
                              "over-two-blocks"])
def test_window_kernels_equal_the_gather_path(heads, kv_heads, dim, window):
    """Decode and packed prefill in interpret mode against gather + masked
    attention: table entries wholly behind the window are the null block
    (released), so a kernel that visited them would read junk."""
    rng = np.random.default_rng(0)
    P, B, feat = 8, 3, kv_heads * dim
    assert head_groups(heads, kv_heads, dim) == (2 if heads == 64 else 1)
    k = jnp.asarray(rng.standard_normal((64 * BS, feat)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((64 * BS, feat)), jnp.float32)
    seq = np.array([BS * P - 3, 5, BS * 3 + 1], np.int32)
    perm = rng.permutation(np.arange(1, 64))
    pos = jnp.broadcast_to(jnp.arange(P * BS), (B, P * BS))
    kw = {} if window is None else {"window": window}

    def tables(first_query):
        bt = perm[:B * P].reshape(B, P).astype(np.int32)
        if window:
            for b in range(B):
                bt[b, :max(first_query[b] - window + 1, 0) // BS] = 0
        return bt

    def gathered(bt, rows):
        slots = kvc.slots_for_positions(jnp.asarray(bt[rows]), pos[rows], BS)
        return kvc.gather_kv(k, v, slots, kv_heads)

    bt = tables(seq - 1)
    q = jnp.asarray(rng.standard_normal((B, heads, dim)), jnp.float32)
    fn = paged_decode_attention if window is None \
        else paged_window_decode_attention
    got = fn(q, k, v, jnp.asarray(bt), jnp.asarray(seq), block_size=BS,
             interpret=True, **kw)
    want = paged_attention(q[:, None], *gathered(bt, slice(None)),
                           jnp.asarray(seq - 1)[:, None], pos,
                           jnp.asarray(seq), **kw)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    q_lens = np.minimum(np.array([40, 5, 16], np.int32), seq)
    q_starts = np.array([0, 40, 48], np.int32)
    bt = tables(seq - q_lens)
    qq = jnp.asarray(rng.standard_normal((128, heads, dim)), jnp.float32)
    fn = paged_prefill_attention if window is None \
        else paged_window_prefill_attention
    got = fn(qq, k, v, jnp.asarray(bt), jnp.asarray(seq),
             jnp.asarray(q_starts), jnp.asarray(q_lens), block_size=BS,
             interpret=True, q_tile=16, **kw)
    for r in range(B):
        n, at = int(q_lens[r]), int(q_starts[r])
        want = paged_attention(
            qq[at:at + n][None], *gathered(bt, slice(r, r + 1)),
            jnp.arange(seq[r] - n, seq[r])[None], pos[:1],
            jnp.asarray(seq[r:r + 1]), **kw)[0]
        np.testing.assert_allclose(np.asarray(got[at:at + n]),
                                   np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# The window group's allocator


def _scheduler(window_blocks, blocks=64, max_seqs=4):
    cfg = SchedulerConfig(block_size=BS, max_seqs=max_seqs,
                          max_prefill_chunk=16, prefill_buckets=(8, 16))
    return Scheduler(cfg, BlockAllocator(blocks),
                     BlockAllocator(window_blocks), window=24)


def _request(rid, n):
    return Request(rid, list(range(1, n + 1)), SamplingParams(max_tokens=4))


def test_blocks_behind_the_window_go_back_and_are_never_named_again():
    sched = _scheduler(window_blocks=16)
    assert sched.window_cap == 6              # ceil((24 + 16) / 8) + 1
    req = _request("a", 100)
    sched.add_request(req)
    held = []
    while req.state is not RequestState.DECODE:
        plan = sched.plan()
        work = plan.prefill.items[0]
        live = [p for p in req.window_pages if p]
        held.append(len(live))
        # Every position the chunk's queries see has its page; the blocks
        # wholly behind the first query's window have none.
        first = max(work.start - 24 + 1, 0) // BS
        last = -(-(work.start + work.length) // BS)
        assert all(req.window_pages[first:last])
        assert not any(req.window_pages[:first])
        assert len(set(live)) == len(live)
        sched.prefill_done(work)
    assert max(held) <= sched.window_cap
    assert len(req.pages) == 13               # the full group keeps them all
    assert sched.window_released == 13 - len(
        [p for p in req.window_pages if p])
    before = sched.window_allocator.free_blocks
    sched.finish(req, None)
    assert sched.window_allocator.free_blocks == 15 > before
    assert sched.allocator.free_blocks == 63


def test_admission_counts_both_groups():
    """Two long prompts fit the full group's pool together; the window
    group's holds one's first blocks only, so the second waits until the
    first has let go of enough of them."""
    sched = _scheduler(window_blocks=10)
    a, b = _request("a", 100), _request("b", 100)
    sched.add_request(a)
    sched.add_request(b)
    sched.plan()
    assert a.state is RequestState.PREFILL and b.state is RequestState.WAITING
    assert sched.allocator.free_blocks >= 13      # the full group had room
    while a.state is not RequestState.DECODE:
        plan = sched.plan()
        sched.prefill_done(plan.prefill.items[0])
    sched.finish(a, None)
    sched.plan()
    assert b.state is RequestState.PREFILL


def test_a_recompute_preempted_sequence_resumes_to_the_same_tokens():
    """Preempted mid-decode (the pages of both groups given up), a sequence
    prefills prompt + generated from token 0 and goes on as if nothing had
    happened."""
    prompt = _prompts(5, 19, 70)[2]
    want = _generate(_engine(window=1, max_seqs=1), [prompt])[0]
    core = _engine(max_seqs=2, packed_prefill=True, use_pallas_decode=True)
    core.add_request("a", prompt, SamplingParams(max_tokens=11))
    got, preempted = [], False
    while core.has_work:
        for d in core.step():
            got.extend(d.token_ids)
        req = core._requests.get("a")
        if not preempted and req is not None and len(got) >= 5:
            core._drain_inflight([])
            got = list(req.prompt_tokens[len(prompt):]) \
                + list(req.output_tokens)
            core.scheduler.preempt(req)
            assert req.window_pages == [] and req.pages == []
            assert core.window_allocator.free_blocks \
                == core.cache_cfg.window_blocks - 1
            preempted = True
    assert preempted and got == want


def test_a_prefix_is_prefilled_whole_and_the_pool_is_sized_from_both_sides():
    """The engine gives a model with window layers the no-reuse block
    source, and its window group a pool of its own size: what every
    sequence can hold at most, but no more bytes than the full group has."""
    prompt = _prompts(40)[0]
    core = _engine()
    first = _generate(core, [prompt], max_tokens=3)[0]
    before = core.counters.prefill_tokens_dispatched
    assert _generate(core, [prompt], max_tokens=3)[0] == first
    assert core.counters.prefill_tokens_dispatched - before == 40
    assert not core._managed_cache
    # 4 sequences x 6 blocks = 24 against 63 // 3 = 21 blocks of three
    # window layers in the full group's bytes.
    assert core.cache_cfg.window_blocks == 1 + 21
    assert _engine(blocks=256).cache_cfg.window_blocks == 1 + 24
    k = core.cache["k"]
    assert [b.shape[0] for b in k] == [22 * BS] * 3 + [64 * BS]


def test_the_step_programs_keep_their_names_under_the_window_tables():
    """XLA names a program after its function, and a capture's reduction
    tells the decode window (`jit_run`) from the packed prefill chunk
    (`jit_step`) by that name: the wrapper that hands the window tables over
    by position keeps the name of what it wraps.  (On the chip the two once
    both read `jit_run`: prefill chunks were counted as eight decode steps
    each and a roofline share read 135 %.)"""
    core = _engine(packed_prefill=True, use_pallas_decode=True)
    i32 = jnp.zeros((4,), jnp.int32)
    f32 = jnp.zeros((4,), jnp.float32)
    bts = jnp.zeros((4, 2), jnp.int32)
    window = core._window_fn(True).lower(
        core.params, core.cache, i32, i32, i32, bts, f32, i32, f32,
        jnp.zeros((4, 2), jnp.uint32), i32, bts).as_text()
    t = jnp.zeros((16,), jnp.int32)
    r = jnp.zeros((8,), jnp.int32)
    rb = jnp.zeros((8, 2), jnp.int32)
    packed = core._packed_prefill_fn().lower(
        core.params, core.cache, t, t, t, rb, r, r, r, r, rb).as_text()
    assert "module @jit_run " in window and "module @jit_step " in packed
