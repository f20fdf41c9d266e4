"""Ring attention (sp axis) vs the causal-attention oracle.

SURVEY §2.5 + VERDICT r2 item 8: ring/blockwise SP prefill attention
over the previously-dead sp axis, parity-tested on the 8-device CPU
mesh and wired into a sharded prefill step.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params, make_forward_step
from dynamo_tpu.ops.attention import causal_attention
from dynamo_tpu.ops.ring_attention import ring_causal_attention
from jax import shard_map
from dynamo_tpu.parallel import (
    MeshConfig,
    cache_pspecs,
    make_mesh,
    make_sp_prefill_step,
    param_pspecs,
    shard_pytree,
)

CFG = mcfg.get_config("tiny-test")
BLOCK = 8


def _qkv(B, T, Hq, Hkv, D, key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    return q, k, v


def test_ring_single_shard_matches_causal():
    B, T, Hq, Hkv, D = 2, 16, 8, 4, 16
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    want = causal_attention(q, k, v)
    got = ring_causal_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_ring_sharded_matches_causal():
    """shard_map over sp=8: every K/V block must make the full circuit."""
    B, T, Hq, Hkv, D = 2, 64, 8, 4, 16
    q, k, v = _qkv(B, T, Hq, Hkv, D, key=1)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    want = causal_attention(q, k, v)

    mesh = make_mesh(MeshConfig(sp=8), jax.devices())
    spec4 = P(None, "sp", None, None)
    fn = shard_map(
        lambda qs, ks, vs, ps: ring_causal_attention(qs, ks, vs, ps,
                                                     axis_name="sp"),
        mesh=mesh,
        in_specs=(spec4, spec4, spec4, P(None, "sp")),
        out_specs=spec4, check_vma=False)
    got = fn(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_sp_prefill_step_matches_unsharded():
    """Full-prompt prefill over dp=2 x sp=2 x tp=2: logits AND the
    written KV cache must match the single-device step."""
    params = init_params(CFG, jax.random.key(0))
    batch, T = 4, 16
    tokens = jax.random.randint(jax.random.key(5), (batch, T), 0,
                                CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (batch, T))
    bt = np.zeros((batch, 8), np.int32)
    for i in range(batch):
        bt[i, :4] = np.arange(1 + 4 * i, 5 + 4 * i)
    seq_lens = jnp.full((batch,), T, jnp.int32)
    sample_pos = jnp.full((batch,), T - 1, jnp.int32)
    inputs = (tokens, positions, seq_lens, jnp.asarray(bt), sample_pos)

    def fresh_cache():
        return kvc.init_cache(kvc.KvCacheConfig.for_model(
            CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32))

    ref_step = make_forward_step(CFG, BLOCK)
    want, want_cache = ref_step(params, fresh_cache(), *inputs)

    mesh = make_mesh(MeshConfig(dp=2, sp=2, tp=2), jax.devices())
    sharded = shard_pytree(params, param_pspecs(CFG), mesh)
    cache = shard_pytree(fresh_cache(), cache_pspecs(CFG.num_layers), mesh)
    step = make_sp_prefill_step(CFG, BLOCK, mesh)
    got, got_cache = step(sharded, cache, *inputs)

    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=5e-4, atol=5e-4)
    # The sp-sharded chunk writes must land replica-consistent in the
    # paged cache (the decode continuation depends on it).
    np.testing.assert_allclose(
        np.asarray(want_cache["k"][0]), np.asarray(got_cache["k"][0]),
        rtol=5e-4, atol=5e-4)


def test_sp_prefill_then_decode_continues():
    """Prefill via the sp ring step, then decode one token with the
    regular step on the same cache — output equals a full unsharded run."""
    params = init_params(CFG, jax.random.key(0))
    batch, T = 2, 16
    tokens = jax.random.randint(jax.random.key(7), (batch, T), 0,
                                CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (batch, T))
    bt = np.zeros((batch, 8), np.int32)
    for i in range(batch):
        bt[i, :4] = np.arange(1 + 4 * i, 5 + 4 * i)
    bt = jnp.asarray(bt)

    def fresh_cache():
        return kvc.init_cache(kvc.KvCacheConfig.for_model(
            CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32))

    ref_step = make_forward_step(CFG, BLOCK)
    logits, ref_cache = ref_step(
        params, fresh_cache(), tokens, positions,
        jnp.full((batch,), T, jnp.int32), bt,
        jnp.full((batch,), T - 1, jnp.int32))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    want, _ = ref_step(
        params, ref_cache, nxt, jnp.full((batch, 1), T, jnp.int32),
        jnp.full((batch,), T + 1, jnp.int32), bt,
        jnp.zeros((batch,), jnp.int32))

    mesh = make_mesh(MeshConfig(dp=2, sp=4), jax.devices())
    sharded = shard_pytree(params, param_pspecs(CFG), mesh)
    cache = shard_pytree(fresh_cache(), cache_pspecs(CFG.num_layers), mesh)
    sp_step = make_sp_prefill_step(CFG, BLOCK, mesh)
    logits2, cache = sp_step(
        sharded, cache, tokens, positions,
        jnp.full((batch,), T, jnp.int32), bt,
        jnp.full((batch,), T - 1, jnp.int32))
    nxt2 = jnp.argmax(logits2, -1).astype(jnp.int32)[:, None]
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(nxt2))

    from dynamo_tpu.parallel import make_sharded_step

    dec_step = make_sharded_step(CFG, BLOCK, mesh)
    got, _ = dec_step(
        sharded, cache, nxt2, jnp.full((batch, 1), T, jnp.int32),
        jnp.full((batch,), T + 1, jnp.int32), bt,
        jnp.zeros((batch,), jnp.int32))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=5e-4, atol=5e-4)
