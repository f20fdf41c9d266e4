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

from dynamo_tpu.ops.pallas.latent_attention import (
    latent_decode_attention,
    latent_prefill_attention,
)
from dynamo_tpu.ops.pallas.moe_grouped import packed_rows
from dynamo_tpu.ops.pallas import (
    grouped_expert_ffn,
    paged_block_attention,
    paged_decode_attention,
    paged_prefill_attention,
    paged_window_decode_attention,
    paged_window_prefill_attention,
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


# What the benchmark's configurations run their workers under (their `env`):
# 32 MB of scoped VMEM, for the packed prefill kernel's resident rows.
VMEM_32M = {"xla_tpu_scoped_vmem_limit_kib": "32768"}


def _window_attention(topo, tokens, window):
    """(fn, args): paged attention at Command A+'s geometry (128 query heads
    over 8 key heads of 128, blocks of 256, tables of 68): the decode kernel
    at `tokens` 0 (the top bucket's 64 rows), else a packed bucket of
    `tokens` whose queries ride four groups of 32 heads; with a window, the
    window forms."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    slots = 513 * 256
    pool = sds((slots, 1024), jnp.bfloat16)
    kw = {} if window is None else {"window": window}
    if not tokens:
        fn = paged_decode_attention if window is None \
            else paged_window_decode_attention
        args = [sds((64, 128, 128), jnp.bfloat16), pool, pool,
                sds((64, 68), jnp.int32), sds((64,), jnp.int32)]
        return (lambda q, k, v, bt, sl: fn(
            q, k, v, bt, sl, block_size=256, **kw)), args
    fn = paged_prefill_attention if window is None \
        else paged_window_prefill_attention
    seg = sds((8,), jnp.int32)
    args = [sds((tokens, 128, 128), jnp.bfloat16), pool, pool,
            sds((8, 68), jnp.int32), seg, seg, seg]
    return (lambda q, k, v, bt, sl, qs, ql: fn(
        q, k, v, bt, sl, qs, ql, block_size=256, **kw)), args


def _experts_gated_share(topo, rows, held=16, of=128, H=4096, F=4096):
    """(fn, args): the gated grouped kernel at Command A+'s experts (4096 ->
    4096 -> 4096, 16 of 128 held here), `rows` (token, expert) pairs the
    router chose over all 128 packed as `moe_grouped` packs the held ones."""
    from dynamo_tpu.ops.pallas.moe_grouped import grouped_block_rows

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    tile = grouped_block_rows(rows, of, held)
    padded = packed_rows(rows, held, tile)
    w = sds((held, H, F), jnp.bfloat16)
    args = [sds((padded, H), jnp.bfloat16), sds((padded // tile,), jnp.int32),
            w, w, sds((held, F, H), jnp.bfloat16), sds((1,), jnp.int32)]
    return (lambda x, te, wg, wu, wd, live: grouped_expert_ffn(
        x, te, wg, wu, wd, live_tiles=live, block_rows=tile)), args


def _block_decode(topo):
    """(fn, args): a block-diffusion step's attention, 4 queries a row on
    the decode kernel's head-group axis (Hq 32 / Hkv 4 / D 128: 128 rows)."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    args = [sds((64, 4, 32, 128), jnp.bfloat16),
            sds((SLOTS, 512), jnp.bfloat16), sds((SLOTS, 512), jnp.bfloat16),
            sds((64, 8), jnp.int32), sds((64,), jnp.int32)]
    return (lambda q, k, v, bt, sl: paged_block_attention(
        q, k, v, bt, sl, block_size=BLOCK)), args


def _block_prefill(topo, tokens):
    """(fn, args): the packed prefill under a block mask of 4."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    seg = sds((8,), jnp.int32)
    args = [sds((tokens, 32, 128), jnp.bfloat16),
            sds((SLOTS, 512), jnp.bfloat16), sds((SLOTS, 512), jnp.bfloat16),
            sds((8, 8), jnp.int32), seg, seg, seg]
    return (lambda q, k, v, bt, sl, qs, ql: paged_prefill_attention(
        q, k, v, bt, sl, qs, ql, block_size=BLOCK, mask_block=4)), args


def _experts(topo, rows, tile, E=128, F=768, quant=False):
    """(fn, args): the grouped expert FFN at `E` experts of 2048 x `F`
    (SDAR's 128 of 768; GLM-4.7-Flash's 64 of 1536, which ride two F
    blocks), `rows` (token, expert) pairs packed into `tile`-row tiles,
    in the buffer `moe_grouped` packs them into.  `quant`: int8 weights,
    their scale slivers on the fetch ring beside them (no cell runs it)."""
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    H = 2048
    padded = packed_rows(rows, E, tile)
    w = sds((E, H, F), jnp.int8 if quant else jnp.bfloat16)
    args = [sds((padded, H), jnp.bfloat16), sds((padded // tile,), jnp.int32),
            w, w, sds((E, F, H), w.dtype), sds((1,), jnp.int32)]
    if quant:
        args += [sds((E, F), jnp.float32)] * 2 + [sds((E, H), jnp.float32)]

    def fn(x, te, wg, wu, wd, live, *scales):
        kw = dict(zip(("w_gate_scale", "w_up_scale", "w_down_scale"), scales))
        return grouped_expert_ffn(x, te, wg, wu, wd, live_tiles=live,
                                  block_rows=tile, **kw)

    return fn, args


def _experts_relu2(topo, rows, held=128, of=512, H=1024, F=2688):
    """(fn, args): the two-matrix grouped kernel at Nemotron-3-Super's
    latent experts (1024 -> 2688 -> 1024, 128 of 512 held here), `rows`
    (token, expert) pairs the router chose over all 512 packed as
    `moe_grouped` packs the held ones: the tile `grouped_block_rows` picks
    for a quarter of them, the buffer for all of them landing here."""
    from dynamo_tpu.ops.pallas.moe_grouped import (
        grouped_block_rows, grouped_expert_ffn_relu2)

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    tile = grouped_block_rows(rows, of, held)
    padded = packed_rows(rows, held, tile)
    args = [sds((padded, H), jnp.bfloat16), sds((padded // tile,), jnp.int32),
            sds((held, H, F), jnp.bfloat16), sds((held, F, H), jnp.bfloat16),
            sds((1,), jnp.int32)]
    return (lambda x, te, wu, wd, live: grouped_expert_ffn_relu2(
        x, te, wu, wd, live_tiles=live, block_rows=tile)), args


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


# GLM-4.7-Flash: a latent row of 576 values stored at 640, value = its
# leading 512 columns, 20 query heads; the cell's pool and its top page bucket
# (13,312 tokens of context: 208 pages).
LATENT_SLOTS, LATENT_PAGES = 5200 * BLOCK, 208


def _latent_decode(topo, rows):
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    args = [sds((rows, 20, 640), jnp.bfloat16),
            sds((LATENT_SLOTS, 640), jnp.bfloat16),
            sds((rows, LATENT_PAGES), jnp.int32), sds((rows,), jnp.int32)]
    return (lambda q, kv, bt, sl: latent_decode_attention(
        q, kv, bt, sl, block_size=BLOCK, scale=1 / 16, v_width=512)), args


def _latent_prefill(topo, tokens):
    sds = _on(SingleDeviceSharding(topo.devices[0]))
    seg = sds((8,), jnp.int32)
    args = [sds((tokens, 20, 640), jnp.bfloat16),
            sds((LATENT_SLOTS, 640), jnp.bfloat16),
            sds((8, LATENT_PAGES), jnp.int32), seg, seg, seg]
    return (lambda q, kv, bt, sl, qs, ql: latent_prefill_attention(
        q, kv, bt, sl, qs, ql, block_size=BLOCK, scale=1 / 16,
        v_width=512)), args


def _state_update(topo, rows, H=32, P=128, N=256, G=2):
    """The decode step's in-place state update at the published Falcon-H1
    widths: 32 heads x 128 x 256 float32 a slot, 64 slots and the scratch
    (Nemotron-3-Super: 128 heads x 64 x 128, 8 groups)."""
    from dynamo_tpu.ops.pallas.ssm import state_update_kernel

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    f32 = jnp.float32
    args = [sds((65, H, P, N), f32), sds((rows,), jnp.int32),
            sds((rows, H, P), f32), sds((rows, H), f32), sds((H,), f32),
            sds((rows, G, N), f32), sds((rows, G, N), f32)]
    return state_update_kernel, args


def _chunk_scan(topo, tokens, H=32, P=128, N=256, G=2):
    """The prefill chunk's scan at the published Falcon-H1 widths: the scan
    chunks of `tokens` packed tokens in 8 segments."""
    from dynamo_tpu.ops.pallas.ssm import chunk_scan_kernel

    sds = _on(SingleDeviceSharding(topo.devices[0]))
    f32, i32 = jnp.float32, jnp.int32
    nc = tokens // 128 + 8
    args = [sds((nc, 128, H, P), f32), sds((nc, 128, H), f32),
            sds((H,), f32), sds((nc, 128, G, N), f32),
            sds((nc, 128, G, N), f32), sds((nc,), i32), sds((nc,), i32),
            sds((9, H, P, N), f32)]
    return chunk_scan_kernel, args


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
    # SDAR-30B-A3B: a block of 4 queries a row, the block mask in the
    # packed prefill, 128 experts of width 768 at decode and prefill rows.
    "block-decode-sdar": _block_decode,
    "block-prefill-sdar-512": lambda t: _block_prefill(t, 512),
    "experts-sdar-256x8": lambda t: _experts(t, 256, 8),
    "experts-sdar-4096x32": lambda t: _experts(t, 4096, 32),
    # What the cell's block call packs (17 rows x 4 positions x 8, rounded
    # up: tiles of 16), and the int8 form on the same ring (one F block at
    # either width: an int8 expert is half the bytes).
    "experts-sdar-1024x16": lambda t: _experts(t, 1024, 16),
    "experts-sdar-int8-1024x16": lambda t: _experts(t, 1024, 16, quant=True),
    "experts-glm-int8-8rows-tile8": lambda t: _experts(
        t, 8, 8, E=64, F=1536, quant=True),
    # GLM-4.7-Flash: the latent kernels at the top decode bucket and the two
    # packed buckets, at the compiler's default scoped VMEM.
    # Fewer assignments than experts (a decode step of one row; of one and
    # of two for GLM): 32, 4 and 8 tiles since PR 38, not 116, 56 and 57.
    "experts-sdar-32rows-tile8": lambda t: _experts(t, 32, 8),
    "experts-glm-4rows-tile8": lambda t: _experts(t, 4, 8, E=64, F=1536),
    "experts-glm-8rows-tile8": lambda t: _experts(t, 8, 8, E=64, F=1536),
    "experts-glm-2048rows-tile64": lambda t: _experts(t, 2048, 64, E=64,
                                                      F=1536),
    "latent-decode-glm-64": lambda t: _latent_decode(t, 64),
    "latent-prefill-glm-128": lambda t: _latent_prefill(t, 128),
    "latent-prefill-glm-512": lambda t: _latent_prefill(t, 512),
    # Falcon-H1: the state update at the top decode bucket, at one row and
    # at the bucket its cell decodes on (16 heads a block since PR 49).
    "state-update-h1-64": lambda t: _state_update(t, 64),
    "state-update-h1-1": lambda t: _state_update(t, 1),
    "state-update-h1-16": lambda t: _state_update(t, 16),
    "chunk-scan-h1-512": lambda t: _chunk_scan(t, 512),
    "chunk-scan-h1-128": lambda t: _chunk_scan(t, 128),
    # Nemotron-3-Super: 16 query heads a key head, the state kernels at 128
    # heads x 64 with state 128 and 8 groups, the two-matrix expert kernel
    # at a decode step of 64 rows and a prefill chunk of 512 tokens.
    "decode-nemotron-bf16": lambda t: _decode(t, 32, 2, 128, quant=False),
    "state-update-nemotron-64": lambda t: _state_update(
        t, 64, 128, 64, 128, 8),
    # One row, and the two buckets its cell decodes on: 64 heads a block
    # (2 MiB, four of them in VMEM under the kernel's own limit).
    "state-update-nemotron-1": lambda t: _state_update(t, 1, 128, 64, 128, 8),
    "state-update-nemotron-16": lambda t: _state_update(
        t, 16, 128, 64, 128, 8),
    "state-update-nemotron-24": lambda t: _state_update(
        t, 24, 128, 64, 128, 8),
    "chunk-scan-nemotron-512": lambda t: _chunk_scan(t, 512, 128, 64, 128, 8),
    "experts-relu2-nemotron-64rows": lambda t: _experts_relu2(t, 64 * 22),
    "experts-relu2-nemotron-512tokens": lambda t: _experts_relu2(
        t, 512 * 22),
    "experts-relu2-nemotron-16rows": lambda t: _experts_relu2(t, 16 * 22),
    # Command A+: both attention kernels, plain and with the window, at 128
    # query heads (the packed prefill's four head groups under the
    # configurations' 32 MB of scoped VMEM), the gated expert kernel at a
    # share of 16 of 128 experts of 4096 x 4096.
    "decode-command-a-full": lambda t: _window_attention(t, 0, None),
    "decode-command-a-window": lambda t: _window_attention(t, 0, 4096),
    "prefill-command-a-full-512": lambda t: _window_attention(t, 512, None),
    "prefill-command-a-window-512": lambda t: _window_attention(
        t, 512, 4096),
    "prefill-command-a-window-128": lambda t: _window_attention(
        t, 128, 4096),
    "experts-command-a-8rows": lambda t: _experts_gated_share(t, 8),
    "experts-command-a-512tokens": lambda t: _experts_gated_share(
        t, 512 * 8),
    "experts-command-a-32rows": lambda t: _experts_gated_share(t, 32 * 8),
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
        options = VMEM_32M if "command-a" in name else {}
        try:
            return jax.jit(fn).lower(*args).compile(
                compiler_options=options).as_text()
        except Exception as e:  # handed to the test that owns `name`
            return e

    with ThreadPoolExecutor(max_workers=len(PROGRAMS)) as pool:
        return dict(zip(PROGRAMS, pool.map(compile_one, PROGRAMS)))


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_kernel_compiles_for_v5e(compiled, name):
    if isinstance(compiled[name], Exception):
        raise compiled[name]
    assert "tpu_custom_call" in compiled[name]


def test_state_update_takes_the_live_count_as_a_value(topo):
    """A decode bucket has one state-update program whatever it holds: the
    lowered kernel call takes the order of the rows, their slots and the
    live count as operands ([16], [16] and [1] int32, reckoned on the device
    from the slots) before the decays, and the update's jit has no static
    argument but `interpret`, so nothing of a program is keyed on a count."""
    import inspect
    import re

    from dynamo_tpu.ops import ssm as ssm_ops

    fn, args = _state_update(topo, 16, 128, 64, 128, 8)
    text = jax.jit(fn).lower(*args).as_text()
    calls = [line for line in text.splitlines() if "@tpu_custom_call" in line]
    assert len(calls) == 1
    operands = re.search(r"\}\s*:\s*\(([^)]*)\)\s*->", calls[0]).group(1)
    assert operands.split(", ")[:4] == [
        "tensor<16xi32>", "tensor<16xi32>", "tensor<1xi32>",
        "tensor<2048xf32>"], operands
    assert "operand_index = 7" in calls[0]       # the leaf, stepped in place
    params = inspect.signature(ssm_ops.ssm_state_update).parameters
    assert list(params) == ["ssm", "slots", "x", "dt", "a", "b", "c",
                            "interpret"]


def test_block_program_names_its_kernels(topo, monkeypatch):
    """The block program's Pallas calls keep a name each in the compiled
    program, with locations as the compile cache wants them (no full
    tracebacks): a device trace tells the expert kernel from the decode
    attention kernel by it (chipbench's `moe_expert` and `attn_decode`
    labels).  Calls made directly in a `while_loop` body lose the name of
    the jit around them, and so do calls made directly in a `cond` branch;
    `make_block_step` calls its forward, and what of it a commit skips,
    through a jit of its own each for that.  The skip is one conditional."""
    import json
    import os
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama, loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/sdar-30b-a3b-chat-d7.json")) as f:
        hf = dict(json.load(f), num_hidden_layers=2)
    cfg = loader.config_from_hf(hf, "sdar")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    full = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        one = SingleDeviceSharding(topo.devices[0])
        on = lambda tree: jax.tree.map(                     # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        params = on(jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0))))
        cache = on(jax.eval_shape(lambda: kvc.init_cache(
            kvc.KvCacheConfig.for_model(cfg, num_blocks=64, block_size=64))))
        sds = _on(one)
        R, P = 8, 4
        i32, f32 = jnp.int32, jnp.float32
        text = jax.jit(
            llama.make_block_step(cfg, 64, use_pallas_decode=True,
                                  greedy_only=True, moe_mode="grouped"),
            donate_argnums=(1,)).lower(
            params, cache, sds((R, 4), i32), sds((R, 4), i32),
            sds((R,), i32), sds((R, P), i32), sds((R,), f32),
            sds((R,), i32), sds((R,), f32), sds((R, 2), jnp.uint32),
            sds((R,), i32)).compile().as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
    names = [re.sub(r"[.]\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    assert sorted(set(names)) == ["grouped_expert_ffn",
                                  "paged_decode_attention"], names
    assert len(names) == 2 * cfg.num_layers
    assert len(re.findall(r" conditional\(", text)) == 1


def test_latent_decode_window_names_its_kernels(topo, monkeypatch):
    """The causal decode window of the latent-attention block over routed
    experts keeps a name for each of its Pallas calls inside its loop: a
    device trace tells the latent decode kernel from the expert kernel by
    it (chipbench's `attn_decode` and `moe_expert` labels)."""
    import json
    import os
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama, loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/glm-4.7-flash-d8.json")) as f:
        hf = dict(json.load(f), num_hidden_layers=2)
    cfg = loader.config_from_hf(hf, "glm")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(                         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    cache = on(jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=64, block_size=64))))
    assert set(cache) == {"kv"} and cache["kv"][0].shape == (64 * 64, 640)
    sds = _on(one)
    R, P = 8, 4
    i32, f32 = jnp.int32, jnp.float32
    text = jax.jit(
        llama.make_decode_window(cfg, 64, 8, use_pallas_decode=True,
                                 greedy_only=True, moe_mode="grouped",
                                 with_expert_load=True, moe_aux=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((R,), i32), sds((R,), i32), sds((R,), i32),
        sds((R, P), i32), sds((R,), f32), sds((R,), i32), sds((R,), f32),
        sds((R, 2), jnp.uint32), sds((R,), i32)).compile().as_text()
    names = [re.sub(r"[.]\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    # Two latent decode calls (a layer each), one expert layer (named
    # after the pallas call or after the jit around it, as the
    # configuration's `moe_expert` label allows).
    assert names.count("latent_decode_attention") == 2, names
    assert len(names) == 3 and set(names) - {"latent_decode_attention"} \
        <= {"moe_grouped_ffn", "grouped_expert_ffn"}, names


def test_state_decode_window_names_its_kernels_and_steps_in_place(
        topo, monkeypatch):
    """The decode window of the hybrid block with recurrent state keeps a
    name for the state update inside its loop (chipbench's `ssm_update`
    label) beside the paged decode attention, and steps the `ssm` leaves
    where they lie: no copy of a whole leaf in the compiled window."""
    import json
    import os
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama, loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/falcon-h1-34b-instruct-d6.json")) as f:
        hf = dict(json.load(f), num_hidden_layers=2, vocab_size=4096)
    cfg = loader.config_from_hf(hf, "h1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(                         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    cache = on(jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=64, block_size=64,
                                    state_slots=64))))
    assert set(cache) == {"k", "v", "ssm", "conv"}
    assert cache["ssm"][0].shape == (65, 32, 128, 256)
    sds = _on(one)
    R, P = 8, 4
    i32, f32 = jnp.int32, jnp.float32
    text = jax.jit(
        llama.make_decode_window(cfg, 64, 8, use_pallas_decode=True,
                                 greedy_only=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((R,), i32), sds((R,), i32), sds((R,), i32),
        sds((R, P), i32), sds((R,), f32), sds((R,), i32), sds((R,), f32),
        sds((R, 2), jnp.uint32), sds((R,), i32),
        sds((R,), i32)).compile().as_text()
    names = [re.sub(r"[.]\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    assert names.count("ssm_state_update") == 2, names       # a layer each
    assert names.count("paged_decode_attention") == 2, names
    whole_leaf_copies = [line for line in text.splitlines()
                         if " copy(" in line and "f32[65,32,128,256]" in line]
    assert not whole_leaf_copies, whole_leaf_copies[:2]
    # The packed prefill chunk names its scan beside its attention.
    T, S = 512, 8
    seg = sds((S,), i32)
    text = jax.jit(
        llama.make_packed_prefill_step(cfg, 64), donate_argnums=(1,)).lower(
        params, cache, sds((T,), i32), sds((T,), i32), sds((T,), i32),
        sds((S, P), i32), seg, seg, seg, seg, seg).compile().as_text()
    names = [re.sub(r"[.]\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    assert names.count("ssm_chunk_scan") == 2, names
    assert names.count("paged_prefill_attention") == 2, names


def test_pattern_programs_name_a_kernel_for_each_layer_of_its_kind(
        topo, monkeypatch):
    """The decode window and the packed prefill chunk of the pattern block
    at the published widths (11 layers by `MEMEMEM*EME`, 128 of 512 experts
    held): a state kernel for each of the 5 "M" layers, one paged attention
    for the "*" layer, the two-matrix grouped kernel for each of the 5 "E"
    layers and the gated one for none."""
    import json
    import os
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama, loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/"
            "nemotron-3-super-120b-a12b-d11-ep4.json")) as f:
        hf = dict(json.load(f), vocab_size=4096)
    cfg = loader.config_from_hf(hf, "pattern")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(                         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    cache = on(jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=64, block_size=64,
                                    state_slots=64))))
    assert [len(cache[k]) for k in ("k", "v", "ssm", "conv")] == [1, 1, 5, 5]
    assert cache["ssm"][0].shape == (65, 128, 64, 128)
    sds = _on(one)
    R, P = 64, 4
    i32, f32 = jnp.int32, jnp.float32

    def kernels(text):
        return [re.sub(r"[.]\d+$", "", m) for m in re.findall(
            r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)]

    names = kernels(jax.jit(
        llama.make_decode_window(cfg, 64, 8, use_pallas_decode=True,
                                 greedy_only=True, moe_mode="grouped",
                                 with_expert_load=True, moe_aux=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((R,), i32), sds((R,), i32), sds((R,), i32),
        sds((R, P), i32), sds((R,), f32), sds((R,), i32), sds((R,), f32),
        sds((R, 2), jnp.uint32), sds((R,), i32),
        sds((R,), i32)).compile().as_text())
    assert names.count("ssm_state_update") == 5, names
    assert names.count("paged_decode_attention") == 1, names
    assert names.count("grouped_expert_ffn_relu2") == 5, names
    assert "grouped_expert_ffn" not in names, names
    T, S = 512, 8
    seg = sds((S,), i32)
    names = kernels(jax.jit(
        llama.make_packed_prefill_step(cfg, 64, moe_mode="grouped",
                                       moe_aux=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((T,), i32), sds((T,), i32), sds((T,), i32),
        sds((S, P), i32), seg, seg, seg, seg, seg).compile().as_text())
    assert names.count("ssm_chunk_scan") == 5, names
    assert names.count("paged_prefill_attention") == 1, names
    assert names.count("grouped_expert_ffn_relu2") == 5, names


def test_window_programs_name_a_kernel_for_each_layer_of_its_kind(
        topo, monkeypatch):
    """The decode window and the packed prefill chunk of the parallel window
    block at the published widths (4 layers: three window layers, then a
    full one; 16 of 128 experts held): the window form of the attention
    kernel, under its own name, for each of the 3 window layers, the plain
    one for the full layer, the gated grouped kernel for each of the 4
    layers' experts."""
    import json
    import os
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama, loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/"
            "command-a-plus-05-2026-d4-ep8.json")) as f:
        hf = dict(json.load(f), vocab_size=4096)
    cfg = loader.config_from_hf(hf, "window")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(                         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    cache = on(jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=96, block_size=256,
                                    window_blocks=33))))
    # Two pools under the same leaves: three window layers' buffers, then
    # the full layer's.
    assert [b.shape[0] // 256 for b in cache["k"]] == [33, 33, 33, 96]
    sds = _on(one)
    R, P = 64, 68
    i32, f32 = jnp.int32, jnp.float32

    def kernels(text):
        return [re.sub(r"[.]\d+$", "", m) for m in re.findall(
            r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)]

    names = kernels(jax.jit(
        llama.make_decode_window(cfg, 256, 8, use_pallas_decode=True,
                                 greedy_only=True, moe_mode="grouped",
                                 with_expert_load=True, moe_aux=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((R,), i32), sds((R,), i32), sds((R,), i32),
        sds((R, P), i32), sds((R,), f32), sds((R,), i32), sds((R,), f32),
        sds((R, 2), jnp.uint32), sds((R,), i32), None,
        sds((R, P), i32)).compile(compiler_options=VMEM_32M).as_text())
    assert names.count("paged_window_decode_attention") == 3, names
    assert names.count("paged_decode_attention") == 1, names
    assert names.count("grouped_expert_ffn") == 4, names
    T, S = 512, 8
    seg = sds((S,), i32)
    names = kernels(jax.jit(
        llama.make_packed_prefill_step(cfg, 256, moe_mode="grouped",
                                       moe_aux=True),
        donate_argnums=(1,)).lower(
        params, cache, sds((T,), i32), sds((T,), i32), sds((T,), i32),
        sds((S, P), i32), seg, seg, seg, seg, None,
        sds((S, P), i32)).compile(compiler_options=VMEM_32M).as_text())
    assert names.count("paged_window_prefill_attention") == 3, names
    assert names.count("paged_prefill_attention") == 1, names
    assert names.count("grouped_expert_ffn") == 4, names


def test_tp2_decode_window_holds_its_collectives_and_kernels(
        topo, monkeypatch):
    """The head-sharded decode window, as the engine builds it for a tp2
    mesh, compiled for two described chips at llama-3-1b widths (depth
    cut to 2): each shard's decode attention is the Pallas kernel under
    its name, one call a layer, and the partial sums of every attention
    and MLP block meet in an all-reduce.  A window that lost its kernel
    (the gather path) or its collectives (a replicated model) serves the
    same tokens on the CPU mesh and shows only here."""
    import dataclasses
    import re

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import config as mcfg, llama
    from dynamo_tpu.parallel import MeshConfig, make_mesh, sharding

    cfg = dataclasses.replace(mcfg.get_config("llama-3-1b"), num_layers=2)
    mesh = make_mesh(MeshConfig(tp=2), topo.devices[:2])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def on(tree, pspecs):
        return jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            tree, pspecs)

    params = on(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))),
        sharding.param_pspecs(cfg))
    cache = on(jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=64, block_size=BLOCK))),
        sharding.cache_pspecs(cfg.num_layers))
    sds = _on(NamedSharding(mesh, P()))
    B, pages = 8, 4
    i32, f32 = jnp.int32, jnp.float32
    rows = sds((B,), i32)
    text = sharding.make_sharded_window(
        cfg, BLOCK, mesh, 8, greedy_only=True,
        use_pallas_decode=True).lower(
        params, cache, rows, rows, rows, sds((B, pages), i32),
        sds((B,), f32), rows, sds((B,), f32), sds((B, 2), jnp.uint32),
        rows).compile().as_text()
    kernels = [re.sub(r"[.]\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    assert kernels == ["paged_decode_attention"] * cfg.num_layers, kernels
    n_reduce = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    assert n_reduce >= 2 * cfg.num_layers, n_reduce
