"""Ahead-of-time compiles of the serving path's Pallas kernels for TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: a slice off
the (8, 128) tiling, a DMA of a memref whose minor dim is under 128
lanes, more scoped VMEM than a kernel may use.  The TPU compiler is
installed next to the CPU backend and compiles for a DESCRIBED device
(`jax.experimental.topologies`), so these tests keep every kernel of the
llama-3-1b serving path — and the ring kernel on a four-device mesh —
compiling at real widths, at no chip time.  Nothing runs: they say
nothing about results or speed.

The persistent compile cache stays off around them (an entry written
for a described device cannot be read back without a chip, and the next
run would warn and recompile).
"""

import os
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.ops.pallas import (
    paged_decode_attention,
    paged_prefill_attention,
    ring_flash_attention,
    ring_geometry_ok,
)

BLOCK = 64
SLOTS = 512 * BLOCK     # the default server's pool (--num-blocks 512)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _decode(topo, heads, kv_heads, head_dim, quant):
    """(fn, args): the top decode bucket (64 rows) over 8 pages."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    feat = kv_heads * head_dim
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    args = [sds((64, heads, head_dim), jnp.bfloat16),
            sds((SLOTS, feat), kv_dtype), sds((SLOTS, feat), kv_dtype),
            sds((64, 8), jnp.int32), sds((64,), jnp.int32)]
    if quant:
        args += [sds((SLOTS, kv_heads), jnp.float32)] * 2

    def fn(q, k, v, bt, sl, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_decode_attention(q, k, v, bt, sl, block_size=BLOCK,
                                      **kw)

    return fn, args


def _prefill(topo, tokens, quant):
    """(fn, args): one packed bucket, 8 segments, 8-page tables."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    seg = sds((8,), jnp.int32)
    args = [sds((tokens, 32, 64), jnp.bfloat16),
            sds((SLOTS, 512), kv_dtype), sds((SLOTS, 512), kv_dtype),
            sds((8, 8), jnp.int32), seg, seg, seg]
    if quant:
        args += [sds((SLOTS, 8), jnp.float32)] * 2

    def fn(q, k, v, bt, sl, qs, ql, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_prefill_attention(q, k, v, bt, sl, qs, ql,
                                       block_size=BLOCK, **kw)

    return fn, args


def _ring(topo, quant):
    """(fn, args): llama-3-1b widths, a 512-token prompt over sp=4 — the
    largest per-shard chunk (128) the VMEM model admits there."""
    B, T, Hq, Hkv, D = 1, 512, 32, 8, 64
    assert ring_geometry_ok(Hkv * D, T // 4, B, Hq, D)
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4, 1, 1),
                ("dp", "pp", "sp", "ep", "tp"))
    s4, s3, s2 = (P(None, "sp", None, None), P(None, "sp", None),
                  P(None, "sp"))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    args = [sds((B, T, Hq, D), jnp.bfloat16, s4),
            sds((B, T, Hkv, D), kv_dtype, s4),
            sds((B, T, Hkv, D), kv_dtype, s4),
            sds((B, T), jnp.int32, s2)]
    specs = (s4, s4, s4, s2)
    if quant:
        args += [sds((B, T, Hkv), jnp.float32, s3)] * 2
        specs += (s3, s3)

    def body(q, k, v, pos, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return ring_flash_attention(q, k, v, pos, mesh=mesh,
                                    interpret=False, **kw)

    return jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=s4,
                         check_vma=False), args


PROGRAMS = {
    # Hq 32 / Hkv 8 / D 64, block 64: llama-3-1b.
    "decode-1b-bf16": lambda t: _decode(t, 32, 8, 64, quant=False),
    "decode-1b-int8": lambda t: _decode(t, 32, 8, 64, quant=True),
    # llama-3-8b under tp4: each shard holds Hq 8 / Hkv 2 / D 128.
    "decode-8b-tp4-shard-bf16": lambda t: _decode(t, 8, 2, 128, quant=False),
    # The engine's two default packed buckets.
    "prefill-1b-bf16-128": lambda t: _prefill(t, 128, quant=False),
    "prefill-1b-bf16-512": lambda t: _prefill(t, 512, quant=False),
    "prefill-1b-int8-128": lambda t: _prefill(t, 128, quant=True),
    "prefill-1b-int8-512": lambda t: _prefill(t, 512, quant=True),
    "ring-sp4-bf16": lambda t: _ring(t, quant=False),
    "ring-sp4-int8": lambda t: _ring(t, quant=True),
}


@pytest.fixture(scope="module")
def compiled(topo):
    """Every program compiled once, side by side (the compiler releases
    the GIL; nine compiles of 0.3-4.5 s take as long as the slowest few).
    Maps name -> compiled text, or the exception the compiler raised."""

    def compile_one(name):
        fn, args = PROGRAMS[name](topo)
        try:
            return jax.jit(fn).lower(*args).compile().as_text()
        except Exception as e:  # handed to the test that owns `name`
            return e

    with ThreadPoolExecutor(max_workers=len(PROGRAMS)) as pool:
        return dict(zip(PROGRAMS, pool.map(compile_one, PROGRAMS)))


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_kernel_compiles_for_v5e(compiled, name):
    if isinstance(compiled[name], Exception):
        raise compiled[name]
    assert "tpu_custom_call" in compiled[name]
