"""int8 quantized KV plane (ISSUE 6a).

Quality pins: the quantized cache must change HBM bytes, not outputs —
greedy decode on the tiny model is TOKEN-EXACT between bf16/f32 and int8
KV (both the single-step and fused-window paths), the Pallas dequant
kernel matches the XLA gather-dequant path, and the bytes accounting the
block manager / dynamo_kv_pool_* metrics report includes the scales.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg

TINY = mcfg.get_config("tiny-test")
BS = 8


def small_engine(**kw) -> EngineCore:
    defaults = dict(
        model=TINY,
        num_blocks=64,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=BS, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16)),
    )
    defaults.update(kw)
    return EngineCore(EngineConfig(**defaults))


def run_to_completion(core, max_steps=500):
    outputs = {}
    for _ in range(max_steps):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if core.scheduler.num_active == 0 and not core._requests:
            break
    return outputs


# -- quantization primitives -------------------------------------------------


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (32, 64), jnp.float32)
    q, s = kvc.quantize_kv_rows(x, num_kv_heads=4)
    assert q.dtype == jnp.int8 and s.shape == (32, 4)
    deq = kvc.dequantize_rows(q.reshape(32, 4, 16), s,
                              jnp.float32).reshape(32, 64)
    rel = (np.max(np.abs(np.asarray(deq) - np.asarray(x)))
           / np.max(np.abs(np.asarray(x))))
    # Symmetric per-token-per-head int8: worst-case error is half a
    # quantization step of the head max, ~0.4% relative.
    assert rel < 0.01


def test_quantize_zero_rows_safe():
    """All-zero rows (padding, null block) must not divide by zero and
    must dequantize back to exactly zero."""
    x = jnp.zeros((4, 32), jnp.float32)
    q, s = kvc.quantize_kv_rows(x, num_kv_heads=2)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(s)))
    deq = kvc.dequantize_rows(q.reshape(4, 2, 16), s, jnp.float32)
    assert np.all(np.asarray(deq) == 0)


def test_write_gather_quant_matches_dequant():
    cfg = kvc.KvCacheConfig(num_blocks=4, block_size=BS, num_layers=1,
                            num_kv_heads=4, head_dim=16, kv_quant="int8")
    cache = kvc.init_cache(cfg)
    assert kvc.cache_is_quantized(cache)
    x = jax.random.normal(jax.random.key(1), (BS, cfg.feature_dim))
    slots = jnp.arange(BS, 2 * BS, dtype=jnp.int32)
    k2, v2, ks2, vs2 = kvc.write_kv_quant(
        cache["k"][0], cache["v"][0], cache["k_scale"][0],
        cache["v_scale"][0], slots, x, 2 * x)
    gk, gv = kvc.gather_kv_quant(k2, v2, ks2, vs2, slots[None, :], 4,
                                 out_dtype=jnp.float32)
    q, s = kvc.quantize_kv_rows(x, 4)
    want = kvc.dequantize_rows(q.reshape(BS, 4, 16), s, jnp.float32)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(want),
                               rtol=0, atol=0)


# -- bytes accounting (satellite: honest dynamo_kv_pool_* / HBM numbers) -----


def test_bytes_per_block_includes_scales():
    c16 = kvc.KvCacheConfig(num_blocks=4, block_size=64, num_layers=16,
                            num_kv_heads=8, head_dim=64)
    c8 = kvc.KvCacheConfig(num_blocks=4, block_size=64, num_layers=16,
                           num_kv_heads=8, head_dim=64, kv_quant="int8")
    F, H, L, bs = 512, 8, 16, 64
    assert c16.bytes_per_block == 2 * L * bs * F * 2
    # int8 pages + 4-byte f32 scale per (token, head) — NOT bare int8.
    assert c8.bytes_per_block == 2 * L * bs * (F + 4 * H)
    ratio = c8.bytes_per_block / c16.bytes_per_block
    assert ratio <= 0.55  # llama-3-1b's geometry: 0.531, scales included
    # And the wire shape advertises the packed layout.
    assert c8.block_wire_shape == (2, L, bs, F + 4 * H)
    assert c8.block_wire_dtype == jnp.int8


def test_kv_metrics_report_true_block_bytes():
    from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

    core = small_engine(kv_quant="int8")
    reg = MetricsRegistry()
    kvm = KvCacheMetrics(reg)
    kvm.observe_engine(core)
    got = kvm.kv_bytes_per_block.value(labels={"kv_quant": "int8"})
    assert got == core.cache_cfg.bytes_per_block
    assert "dynamo_kv_bytes_per_block" in reg.expose()


# -- kernel parity -----------------------------------------------------------


def test_pallas_quant_kernel_matches_gather_path():
    from dynamo_tpu.ops.attention import paged_attention
    from dynamo_tpu.ops.pallas import paged_decode_attention

    B, Hq, Hkv, D, bs, P = 3, 8, 4, 16, 8, 4
    F = Hkv * D
    S = (1 + B * P) * bs
    ks = jax.random.split(jax.random.key(2), 3)
    kraw = jax.random.normal(ks[0], (S, F), jnp.float32)
    vraw = jax.random.normal(ks[1], (S, F), jnp.float32)
    q = jax.random.normal(ks[2], (B, Hq, D), jnp.float32)
    bt = np.zeros((B, P), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * P, 1 + (i + 1) * P)
    bt = jnp.asarray(bt)
    sl = jnp.asarray([9, 25, 32], jnp.int32)

    kq, ksc = kvc.quantize_kv_rows(kraw, Hkv)
    vq, vsc = kvc.quantize_kv_rows(vraw, Hkv)
    out = paged_decode_attention(q, kq, vq, bt, sl, block_size=bs,
                                 interpret=True, k_scale=ksc, v_scale=vsc)

    ctx_pos = jnp.broadcast_to(jnp.arange(P * bs, dtype=jnp.int32),
                               (B, P * bs))
    cslots = kvc.slots_for_positions(bt, ctx_pos, bs)
    kc, vc = kvc.gather_kv_quant(kq, vq, ksc, vsc, cslots, Hkv,
                                 out_dtype=jnp.float32)
    ref = paged_attention(q[:, None], kc, vc, (sl - 1)[:, None], ctx_pos,
                          sl)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pallas_quant_requires_both_scales_and_int8():
    from dynamo_tpu.ops.pallas import paged_decode_attention

    q = jnp.zeros((1, 4, 16), jnp.float32)
    kc = jnp.zeros((16, 64), jnp.int8)
    s = jnp.ones((16, 4), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    sl = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="both k_scale and v_scale"):
        paged_decode_attention(q, kc, kc, bt, sl, block_size=8,
                               interpret=True, k_scale=s)
    with pytest.raises(ValueError, match="int8"):
        paged_decode_attention(q, kc.astype(jnp.float32),
                               kc.astype(jnp.float32), bt, sl,
                               block_size=8, interpret=True,
                               k_scale=s, v_scale=s)


def test_auto_pair_doubles_tile_for_int8():
    from dynamo_tpu.ops.pallas.paged_attention import auto_pair

    # Serving geometry: bf16 targets 256-token tiles, int8 512.
    assert auto_pair(64, 512, itemsize=2) == 4
    assert auto_pair(64, 512, itemsize=1) == 8


# -- engine quality pins -----------------------------------------------------


def test_greedy_decode_token_exact_bf16_vs_int8():
    """The quality pin: same prompt, greedy decode, token-for-token
    identical output across cache modes — on BOTH decode paths (fused
    single step and pipelined windows)."""
    prompt = list(range(1, 30))

    def outputs(**kw):
        core = small_engine(**kw)
        core.add_request("a", prompt, SamplingParams(max_tokens=12))
        return run_to_completion(core)

    want = outputs()
    assert outputs(kv_quant="int8") == want
    assert outputs(kv_quant="int8", decode_window=4,
                   window_pipeline_depth=2) == want
    assert len(want["a"]) == 12


def test_int8_engine_counts_fewer_effective_bytes():
    """The modeled effective-bytes-per-token series must reflect the
    quantized cache — same workload, strictly fewer bytes per token."""
    def run_mode(kv_quant):
        core = small_engine(kv_quant=kv_quant, decode_window=1)
        core.add_request("a", list(range(1, 30)),
                         SamplingParams(max_tokens=6))
        run_to_completion(core)
        return core.counters.effective_bytes_per_token

    b16 = run_mode("none")
    b8 = run_mode("int8")
    assert b8 > 0
    ratio = b8 / b16
    # tiny-test stores f32 (itemsize 4): int8+scales is 0.3125x.
    assert abs(ratio - (TINY.num_kv_heads * (TINY.head_dim + 4))
               / (TINY.num_kv_heads * TINY.head_dim * 4)) < 1e-6


def test_kv_quant_mesh_composition_gating():
    """ISSUE 12: int8 composes with EVERY mesh — the old pp/ring-SP
    rejections are gone (stacked scale buffers and the quantized ring
    exchange landed), construction succeeds and each layout's cache
    pytree carries its scale buffers; the capability table
    (parallel.sharding.plane_capability) is where any future impossible
    combo must be declared."""
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    sched = SchedulerConfig(
        max_seqs=8, block_size=BS, max_pages_per_seq=8,
        max_prefill_chunk=16,
        decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16))
    tp2 = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=TINY, num_blocks=64, mesh=tp2, kv_quant="int8",
        enable_prefix_cache=False, scheduler=sched))
    assert kvc.cache_is_quantized(core.cache)
    assert core.kv_shard_count == 2

    pp2 = make_mesh(MeshConfig(pp=2), jax.devices()[:2])
    core_pp = EngineCore(EngineConfig(
        model=TINY, num_blocks=64, kv_quant="int8", mesh=pp2,
        enable_prefix_cache=False, scheduler=sched))
    assert kvc.cache_is_quantized(core_pp.cache)
    assert core_pp.cache["k_scale"].shape[0] == TINY.num_layers  # stacked

    sp2 = make_mesh(MeshConfig(sp=2), jax.devices()[:2])
    core_sp = EngineCore(EngineConfig(
        model=TINY, num_blocks=64, kv_quant="int8", mesh=sp2,
        enable_prefix_cache=False, scheduler=sched))
    assert kvc.cache_is_quantized(core_sp.cache)
    assert core_sp._sp_step is not None

    with pytest.raises(ValueError, match="kv_quant"):
        kvc.KvCacheConfig(num_blocks=4, block_size=8, num_layers=1,
                          num_kv_heads=2, head_dim=16, kv_quant="fp8")


def test_quantized_tier_offload_onboard_roundtrip():
    """G1→G2 offload and G2→G1 onboard move the PACKED block (pages +
    scales atomically): evicted quantized prefixes stay warm and serve
    identical outputs after onboarding."""
    prompt = list(range(1, 25))  # 3 sealed blocks
    core = small_engine(kv_quant="int8", num_blocks=8, host_blocks=16)
    core.add_request("a", prompt, SamplingParams(max_tokens=4))
    out_a = run_to_completion(core)["a"]
    # Force G1 pressure: new request churns pages, evicting a's blocks.
    core.add_request("churn", list(range(100, 140)),
                     SamplingParams(max_tokens=4))
    run_to_completion(core)
    mgr = core.allocator.manager
    assert mgr.offloaded_blocks > 0
    core.add_request("a2", prompt, SamplingParams(max_tokens=4))
    out_a2 = run_to_completion(core)["a2"]
    assert out_a2 == out_a
    assert mgr.onboarded_blocks > 0
