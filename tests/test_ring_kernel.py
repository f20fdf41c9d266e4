"""Pallas flash ring-attention kernel vs the XLA ring and causal oracle.

ISSUE 19: the kernel body — remote copies, barrier and ack semaphores
included — runs under Pallas's TPU interpreter on the CPU mesh, so
tier-1 pins its numerics — bf16-path and int8 scale folding, soft_cap,
fully-masked padding rows, degenerate sp=1 —
against `ring_causal_attention` (the XLA ppermute fallback, which stays
the oracle) and the meshless `causal_attention`.  Eligibility
(`ring_geometry_ok` / `ring_kernel_supported`) is tested as the ONE
predicate every dispatch site shares.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.ops.attention import causal_attention
from dynamo_tpu.ops.pallas.ring_attention import (
    ring_flash_attention,
    ring_geometry_ok,
    ring_kernel_supported,
)
from dynamo_tpu.ops.ring_attention import ring_causal_attention
from dynamo_tpu.parallel import MeshConfig, make_mesh
from jax import shard_map

B, T, Hq, Hkv, D = 2, 32, 4, 2, 32
SPEC4 = P("dp", "sp", "tp", None)
SPEC3 = P("dp", "sp", "tp")
SPEC2 = P("dp", "sp")


@pytest.fixture(scope="module")
def mesh():
    # sp=4 x tp=2 exercises multi-hop RDMA on a multi-axis mesh (the
    # LOGICAL-device-id flattening the kernel computes is nontrivial
    # exactly when another axis sits inside sp's stride).
    return make_mesh(MeshConfig(sp=4, tp=2))


def _qkv(key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    return q, k, v


def _run(mesh, fn, *args, specs):
    f = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=SPEC4,
                  check_vma=False)
    return np.asarray(jax.jit(f)(*args))


@pytest.mark.parametrize("soft_cap", [None, 30.0])
def test_kernel_matches_xla_ring_and_causal(mesh, soft_cap):
    q, k, v = _qkv()
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    specs = (SPEC4, SPEC4, SPEC4, SPEC2)
    got = _run(mesh, lambda qs, ks, vs, ps: ring_flash_attention(
        qs, ks, vs, ps, mesh=mesh, soft_cap=soft_cap, interpret=True),
        q, k, v, pos, specs=specs)
    want = _run(mesh, lambda qs, ks, vs, ps: ring_causal_attention(
        qs, ks, vs, ps, axis_name="sp", soft_cap=soft_cap),
        q, k, v, pos, specs=specs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # Meshless oracles: single-shard ring (soft_cap-aware) always, plain
    # causal_attention on the uncapped path.
    oracle = np.asarray(ring_causal_attention(q, k, v, pos,
                                              soft_cap=soft_cap))
    np.testing.assert_allclose(got, oracle, rtol=2e-5, atol=2e-5)
    if soft_cap is None:
        np.testing.assert_allclose(
            got, np.asarray(causal_attention(q, k, v)),
            rtol=2e-5, atol=2e-5)


def test_kernel_int8_matches_xla_ring(mesh):
    """int8 rows + per-token-per-head scales ride the ring; folding the
    scales into the scores must reproduce the XLA ring's
    dequantize_rows numerics."""
    q, k, v = _qkv(key=1)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    kq, ks = kvc.quantize_kv_rows(k.reshape(B * T, Hkv * D), Hkv)
    vq, vs = kvc.quantize_kv_rows(v.reshape(B * T, Hkv * D), Hkv)
    kq = kq.reshape(B, T, Hkv, D)
    vq = vq.reshape(B, T, Hkv, D)
    ks = ks.reshape(B, T, Hkv)
    vs = vs.reshape(B, T, Hkv)
    specs = (SPEC4, SPEC4, SPEC4, SPEC3, SPEC3, SPEC2)
    got = _run(mesh, lambda qs, kk, vv, ksc, vsc, ps: ring_flash_attention(
        qs, kk, vv, ps, mesh=mesh, soft_cap=30.0, k_scale=ksc,
        v_scale=vsc, interpret=True),
        q, kq, vq, ks, vs, pos, specs=specs)
    want = _run(mesh, lambda qs, kk, vv, ksc, vsc, ps: ring_causal_attention(
        qs, kk, vv, ps, axis_name="sp", soft_cap=30.0, k_scale=ksc,
        v_scale=vsc),
        q, kq, vq, ks, vs, pos, specs=specs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_kernel_padding_rows_match_xla_ring(mesh):
    """Fully-masked padding rows (position 0 tail after real tokens at
    higher positions) keep l == 0 on later shards; both implementations
    must produce the identical guarded junk-but-finite output."""
    q, k, v = _qkv(key=2)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    pos = pos.at[1, T - 5:].set(0)
    specs = (SPEC4, SPEC4, SPEC4, SPEC2)
    got = _run(mesh, lambda qs, ks, vs, ps: ring_flash_attention(
        qs, ks, vs, ps, mesh=mesh, interpret=True),
        q, k, v, pos, specs=specs)
    want = _run(mesh, lambda qs, ks, vs, ps: ring_causal_attention(
        qs, ks, vs, ps, axis_name="sp"),
        q, k, v, pos, specs=specs)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_kernel_sp1_degenerate():
    """sp=1: zero hops, the kernel is a plain flash fold of the local
    block and must still match the meshless oracle."""
    q, k, v = _qkv(key=3)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    mesh1 = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    specs = (P(None, None, "tp", None),) * 3 + (P(None, None),)
    f = shard_map(lambda qs, ks, vs, ps: ring_flash_attention(
        qs, ks, vs, ps, mesh=mesh1, interpret=True),
        mesh=mesh1, in_specs=specs,
        out_specs=P(None, None, "tp", None), check_vma=False)
    got = np.asarray(jax.jit(f)(q, k, v, pos))
    oracle = np.asarray(causal_attention(q, k, v))
    np.testing.assert_allclose(got, oracle, rtol=2e-5, atol=2e-5)


def test_geometry_gate_and_shared_predicate():
    # (feat, t_local, batch, q_heads, head_dim), all per shard.
    # Mosaic-legal: 128-lane feature width and chunk, VMEM in budget.
    assert ring_geometry_ok(128, 128, 1, 4, 64)
    assert ring_geometry_ok(512, 128, 2, 32, 64)      # llama-3-1b widths
    assert not ring_geometry_ok(64, 128, 1, 4, 64)    # lane-misaligned feat
    assert not ring_geometry_ok(128, 64, 1, 4, 64)    # positions off-lane
    assert not ring_geometry_ok(128, 0, 1, 4, 64)     # empty shard
    # The v5e compiler refused T_loc 256 at llama-3-1b widths (17.88 MB
    # of scoped VMEM against 16 MiB): the model must refuse it too.
    assert not ring_geometry_ok(512, 256, 1, 32, 64)
    # Compiled mode defers to the geometry gate; interpret mode runs any
    # shape (tier-1's whole point).
    assert ring_kernel_supported(128, 128, 1, 4, 64, interpret=False)
    assert not ring_kernel_supported(64, 128, 1, 4, 64, interpret=False)
    assert ring_kernel_supported(64, 8, 1, 4, 64, interpret=True)


def test_ineligible_geometry_raises_toward_xla_fallback(mesh):
    """Compiled-mode dispatch of a Mosaic-illegal shape must fail loudly
    at trace time and point at the XLA ring fallback — never lower a
    kernel that would die inside Mosaic."""
    q, k, v = _qkv(key=4)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    specs = (SPEC4, SPEC4, SPEC4, SPEC2)
    f = shard_map(lambda qs, ks, vs, ps: ring_flash_attention(
        qs, ks, vs, ps, mesh=mesh, interpret=False),
        mesh=mesh, in_specs=specs, out_specs=SPEC4, check_vma=False)
    with pytest.raises(ValueError, match="ring_attention.ring_causal"):
        jax.jit(f)(q, k, v, pos)
