"""Pallas TPU paged-attention decode kernel.

The XLA fallback (ops/attention.py) materialises every sequence's context
K/V — [B, width*block_size, Hkv, D] in f32 — per layer per decode step.
Context-length bucketing bounds that width, but the gather still reads and
converts the full bucket for every sequence regardless of its own length.
This kernel streams exactly `ceil(seq_len/block_size)` KV pages per
sequence from HBM through VMEM with an online-softmax (flash-attention)
accumulator, so decode attention cost is per-sequence-length, and no
gathered context array ever exists in HBM.

Role in the reference: the engines it delegates to (vLLM) run paged
attention CUDA kernels; the one kernel the reference itself ships is the
block-copy scatter/gather (`lib/llm/src/kernels/block_copy.cu:41`).  This
is the TPU-native equivalent of that layer of the stack.

Layout strategy: Mosaic DMA wants 128-aligned trailing dims, and head_dim
is 64 on small Llamas — so the kernel sees the cache as 2D
`[S, F = Hkv * head_dim]` (the engine's native storage layout — see
kv_cache.init_cache) and GQA head selection is algebraic instead of
indexed: each query row h is masked into its KV head's column band, so
`qp @ k_tile.T` contracts to exactly the right per-head scores, and the
band of `probs @ v_tile` is head h's output.  Banding and band-extraction
happen INSIDE the kernel on VMEM-resident tiles (v3; earlier revisions
did them in XLA, costing an extra [B, Hq, F] materialisation per layer
per step).

The padded matmuls do Hkv x the minimal attention FLOPs, but decode
attention is HBM-bandwidth-bound, and bytes moved is what the kernel
minimises; the MXU eats the extra zeros nearly for free at these sizes.

Perf structure (v4):
- bf16 x bf16 MXU passes with f32 accumulation (f32 operands cost ~4x
  the passes for accuracy the f32 accumulator already provides);
- `pair` pages per tile, AUTO-SIZED per (feature width, block_size): one
  MXU pass over a 256-token tile costs barely more than over a 64-token
  page (the F-contraction dominates), and fewer, larger DMA bursts sit
  closer to the HBM streaming rate than many page-sized ones — so the
  tile grows toward `TARGET_TILE` tokens until the 3-slot double-buffer
  scratch would crowd VMEM (`_SCRATCH_BUDGET`), then halves.  r5 ran a
  fixed pair=2 (128-token tiles): at serving geometry (block 64,
  ctx 512) that is 4 loop iterations per sequence where 2 suffice, and
  per-iteration fixed costs (semaphore waits, control flow) were a
  visible slice of the 0.70-MBU gap;
- double-buffered tile DMA pipeline within a sequence, PLUS cross-program
  prefetch: a sequence's last-tile compute overlaps the first-tile fetch
  of the NEXT sequence (slot 2), so the 64 grid-program boundaries don't
  each drain the pipeline.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Auto `pair` sizing targets: tiles of ~256 tokens keep the MXU's
# F-contraction efficiency while cutting per-tile fixed costs, bounded so
# the K+V scratch (2 buffers x 3 slots x tile x F) leaves most of the
# ~16 MB VMEM for the compiler's own staging.  int8 tiles halve the
# scratch bytes per token, so the quantized kernel targets 2x the tile —
# same VMEM budget, half the per-tile fixed costs per byte moved.
TARGET_TILE = 256
TARGET_TILE_INT8 = 512
_SCRATCH_BUDGET = 4 * 1024 * 1024


def mosaic_geometry_ok(feat: int, block_size: int) -> bool:
    """THE Mosaic DMA-tiling eligibility rule for this kernel: the cache
    view's lane (feature) dim must be 128-aligned and the sublane
    (block) dim 8-aligned, or compilation dies deep in the DMA lowering.
    One predicate for every auto-selection site (the engine's auto rule
    for the decode kernel and for the packed prefill plane;
    tests/test_packed_prefill.py::
    test_explicit_packed_rejects_ineligible_tpu_geometry).  `feat` is the
    PER-SHARD feature
    width (F/tp under head-sharded tensor parallelism, full F under
    dp_attention's slot sharding)."""
    return feat % 128 == 0 and block_size % 8 == 0


def auto_pair(block_size: int, feat: int, itemsize: int = 2,
              target: Optional[int] = None) -> int:
    """Pages per DMA tile for a (block_size, feature-width) geometry:
    grow toward the target tile tokens (`TARGET_TILE`, doubled for int8
    caches whose bytes/token halve), halve while the two 3-slot
    double-buffer scratch arrays would exceed `_SCRATCH_BUDGET`."""
    if target is None:
        target = TARGET_TILE_INT8 if itemsize == 1 else TARGET_TILE
    pair = max(1, target // block_size)
    while pair > 1 and (2 * 3 * pair * block_size * feat * itemsize
                        > _SCRATCH_BUDGET):
        pair //= 2
    return pair


def scale_tiles(scale: jax.Array, block_tables: jax.Array,
                block_size: int, pair: int) -> jax.Array:
    """Per-sequence int8 scales in the kernels' tile order:
    `[S, Hkv]` pool scales + `[B, P]` tables -> `[B, n_tiles, Hkv, W]`
    f32 with W = pair * block_size tokens ON THE LANES.

    The pool's `[S, Hkv]` scale arrays cannot be DMA-sliced by a kernel:
    Mosaic refuses memref slices whose minor dim (Hkv) is under the
    128-lane tiling.  So XLA gathers each sequence's pages of scales
    (~4*Hkv/F of the K/V bytes) and hands them over lane-dense; the
    kernels multiply them into the `[*, W]` score / probability tiles.
    Table columns past P (tile padding) read the null block's scales,
    whose positions are masked."""
    S, Hkv = scale.shape
    B, P = block_tables.shape
    n_tiles = -(-P // pair)
    bt = jnp.pad(block_tables, ((0, 0), (0, n_tiles * pair - P)))
    pages = scale.reshape(S // block_size, block_size, Hkv)[bt]
    # [B, n_tiles, pair, bs, Hkv] -> [B, n_tiles, Hkv, pair * bs]
    pages = pages.reshape(B, n_tiles, pair * block_size, Hkv)
    return jnp.swapaxes(pages, 2, 3)


def scale_tile_operands(k_scale, v_scale, block_tables, block_size: int,
                        pair: int):
    """The int8 kernels' two extra operands: (in_specs, inputs) — one
    `[1, n_tiles, Hkv, W]` block of K and of V scales per grid program,
    pipelined into VMEM by Pallas."""
    tiles = [scale_tiles(s, block_tables, block_size, pair)
             for s in (k_scale, v_scale)]
    spec = pl.BlockSpec((1,) + tiles[0].shape[1:],
                        lambda i, *_: (i, 0, 0, 0))
    return [spec, spec], tiles


def _decode_kernel(block_size: int, pair: int, n_kv: int,
                   soft_cap: Optional[float], quant: bool,
                   window: Optional[int],
                   # refs
                   bt_ref, len_ref,          # scalar-prefetch (SMEM)
                   q_ref, k_hbm, v_hbm,      # q [1, Hq, D]; 2D cache views
                   *rest):
    if quant:
        # int8 cache: the pages stream as int8 (half the HBM bytes); this
        # sequence's per-token-per-head f32 scales arrive as a pipelined
        # VMEM block [1, n_tiles, Hkv, W] with TOKENS ON THE LANES (see
        # `scale_tiles`), and are folded into the scores / probabilities
        # instead of dequantizing the [W, F] tiles.
        ks_ref, vs_ref, o_ref, k_vmem, v_vmem, sem = rest
    else:
        o_ref, k_vmem, v_vmem, sem = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    seq_len = len_ref[b]
    n_pages = pl.cdiv(seq_len, block_size)
    n_iters = pl.cdiv(seq_len, block_size * pair)

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    F = n_kv * D
    G = Hq // n_kv
    W = block_size * pair

    # With a window the query (at position seq_len - 1) sees positions
    # [seq_len - window, seq_len): the loop starts at the tile that holds
    # the first of them and never visits one wholly behind it (whose pages
    # the sequence may have let go: their table entries are the null block).
    def first_tile(length):
        if window is None:
            return 0
        return jnp.maximum(length - window, 0) // W

    def tiles_of(length):
        n = pl.cdiv(length, W)
        return n if window is None else n - first_tile(length)

    t0 = first_tile(seq_len)

    # Band mask [Hq, F]: query row h owns columns [D*(h//G), D*(h//G+1)).
    row_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 0) // G
    col_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 1) // D
    band = row_head == col_head
    # qp [Hq, F]: q tiled across kv-head bands (lane concat — Mosaic has
    # no 3D broadcast reshape), off-band zeroed (bf16).
    q = q_ref[0]                                        # [Hq, D] pre-scaled
    qp = jnp.where(band, jnp.concatenate([q] * n_kv, axis=1),
                   jnp.zeros((Hq, F), q.dtype))

    row_kv = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 0) // G

    def per_q_head(scale_tile):
        # [Hkv, W] -> [Hq, W]: query row h takes its KV head's scale row
        # (static select chain — Mosaic has no sublane repeat).
        out = jnp.zeros((Hq, W), jnp.float32)
        for h in range(n_kv):
            out = jnp.where(row_kv == h, scale_tile[h:h + 1, :], out)
        return out

    m0 = jnp.full((Hq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((Hq, 1), jnp.float32)
    a0 = jnp.zeros((Hq, F), jnp.float32)

    def fetch(buf, hbm, slot, seq, t, j, kv):
        # page index p = t*pair + j for sequence row `seq`; clamp to that
        # row's last real page so a tail tile's extra DMA is a harmless
        # re-fetch (its positions are masked in compute).
        last = jnp.maximum(pl.cdiv(len_ref[seq], block_size) - 1, 0)
        p = jnp.minimum(t * pair + j, last)
        return pltpu.make_async_copy(
            hbm.at[pl.ds(bt_ref[seq, p] * block_size, block_size)],
            buf.at[slot, pl.ds(j * block_size, block_size)],
            sem.at[slot, j, kv])

    # (buffer, hbm array, semaphore lane) per DMA stream.
    streams = [(k_vmem, k_hbm, 0), (v_vmem, v_hbm, 1)]

    def start_tile(slot, seq, t):
        for j in range(pair):
            for buf, hbm, lane in streams:
                fetch(buf, hbm, slot, seq, t, j, lane).start()

    def wait_tile(slot, seq, t):
        for j in range(pair):
            for buf, hbm, lane in streams:
                fetch(buf, hbm, slot, seq, t, j, lane).wait()

    # Tile 0 lives in slot 2: the PREVIOUS program prefetched it during its
    # last tile's compute (see below) iff it had 2+ tiles itself (a
    # single-tile program is still READING slot 2 at its last tile — a
    # prefetch there would overwrite live data); otherwise fetch it now.
    # Slots 0/1 double-buffer tiles 1..n-1.
    prev_iters = tiles_of(len_ref[jnp.maximum(b - 1, 0)])
    prefetched = jnp.logical_and(b > 0, prev_iters > 1)

    @pl.when(jnp.logical_and(n_iters > 0, jnp.logical_not(prefetched)))
    def _():
        start_tile(2, b, t0)

    def slot_of(t):
        return jnp.where(t == t0, 2, jax.lax.rem(t, 2))

    def body(t, carry):
        m, l, acc = carry
        slot = slot_of(t)

        @pl.when(t + 1 < n_iters)
        def _():
            start_tile(jax.lax.rem(t + 1, 2), b, t + 1)

        # Last tile (and not tile 0 — slot 2 is still live there): overlap
        # the NEXT program's tile-0 fetch (slot 2) with this tile's
        # compute — kills the per-program pipeline drain.  The issue
        # condition must mirror `prefetched` above exactly: issued iff
        # this program has 2+ tiles and the next program has pages.
        @pl.when(jnp.logical_and(
            jnp.logical_and(t + 1 >= n_iters, t >= t0 + 1),
            jnp.logical_and(b + 1 < nb,
                            len_ref[jnp.minimum(b + 1, nb - 1)] > 0)))
        def _():
            nxt = jnp.minimum(b + 1, nb - 1)
            start_tile(2, nxt, 0 if window is None
                       else first_tile(len_ref[nxt]))

        wait_tile(slot, b, t)

        # int8 values are exact in bf16, so quantized tiles feed the MXU
        # as-is and the scales multiply the [Hq, W] products below.
        k = k_vmem[slot].astype(qp.dtype)             # [W, F]
        v = v_vmem[slot].astype(qp.dtype)
        # Zero bands in qp make this the per-KV-head score despite the
        # full-F contraction: [Hq, F] x [W, F] -> [Hq, W].
        s = jax.lax.dot_general(
            qp, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quant:
            s = s * per_q_head(ks_ref[0, t])
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        pos = t * W + jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        seen = pos < seq_len
        if window is not None:
            seen = jnp.logical_and(seen, pos >= seq_len - window)
        s = jnp.where(seen, s, -jnp.inf)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        if quant:
            probs = probs * per_q_head(vs_ref[0, t])
        # [Hq, W] x [W, F] -> [Hq, F]; band h carries head h's output.
        pv = jax.lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(t0, n_iters, body, (m0, l0, a0))
    # Padding rows (seq_len 0) skip the loop: l stays 0; guard the divide —
    # their output rows are discarded by the engine anyway.
    out = acc / jnp.maximum(l, 1e-30)
    # Band extraction on VMEM: head h's output is its own band of `out`;
    # zero the off-bands and fold the D-wide column groups (static slices
    # — Mosaic has no 3D reshape-reduce).
    outm = jnp.where(band, out, 0.0)
    out_d = outm[:, 0:D]
    for kk in range(1, n_kv):
        out_d = out_d + outm[:, kk * D:(kk + 1) * D]
    o_ref[0] = out_d.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "soft_cap", "interpret", "pair",
                     "window"))
def paged_decode_attention(
    q: jax.Array,             # [B, Hq, D] current (single) decode queries
    k_cache: jax.Array,       # [S, F = Hkv * D] one layer's flat-slot keys
    v_cache: jax.Array,       # [S, F]
    block_tables: jax.Array,  # [B, P] int32 page ids
    seq_lens: jax.Array,      # [B] int32 valid context length
    *,
    block_size: int,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    interpret: bool = False,
    pair: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [S, Hkv] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Decode-step attention over the paged cache; returns [B, Hq, D].

    The cache is the engine's native 2D layout [S, F] with F flat
    head-major (kv_cache.init_cache) — exactly the view the kernel's DMA
    wants, no relayout at the boundary.  Numerics match ops/attention.py's
    masked gather path for T=1 (the decode query at position seq_len-1
    sees exactly slots pos < seq_len): bf16 MXU passes with f32
    accumulation on both paths.

    Quantized variant: pass an int8 cache with `k_scale`/`v_scale`
    ([S, Hkv] f32, kv_cache.init_cache's `k_scale`/`v_scale` buffers).
    Pages stream HBM→VMEM as int8 and feed the MXU unscaled (int8 is
    exact in bf16); the scales reach the kernel through `scale_tiles`
    and multiply the f32 scores and probabilities, which equals
    dequantize-then-contract up to rounding.  The auto tile target
    doubles (auto_pair int8 path).

    `window` W: the query sees positions [seq_len - W, seq_len) alone; the
    kernel starts at the tile that holds the first of them, so table
    entries wholly behind the window are never read (they may be the null
    block).  A model's window layers call `paged_window_decode_attention`,
    the same program under a name of its own.
    """
    B, Hq, D = q.shape
    S, Fc = k_cache.shape
    Hkv = Fc // D
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if quant and k_cache.dtype != jnp.int8:
        raise ValueError(
            f"scales imply an int8 cache; got {k_cache.dtype}")
    if Fc % D or Hq % Hkv:
        raise ValueError(f"bad geometry: q {q.shape}, cache {k_cache.shape}")
    if not interpret and not mosaic_geometry_ok(Fc, block_size):
        # Mosaic DMA tiling: the cache's lane dim must be 128-aligned and
        # the sublane (block) dim 8-aligned, or compilation dies deep in
        # the DMA lowering.  Callers (engine auto-selection) should fall
        # back to the gather path for such geometries.
        raise ValueError(
            f"pallas paged decode needs F % 128 == 0 and block_size % 8 "
            f"== 0; got F={Fc}, block_size={block_size} (use the XLA "
            "gather path for this geometry)")
    F = Hkv * D
    if pair is None:
        # Clamp to the table width: a tile wider than the whole table
        # would only re-fetch the clamped last page.
        pair = min(auto_pair(block_size, F,
                             jnp.dtype(k_cache.dtype).itemsize),
                   block_tables.shape[1])
    if scale is None:
        scale = D ** -0.5

    # int8 caches must not drag q down to int8: their tiles are cast to
    # q's dtype in-kernel, so contract in q's dtype; bf16 caches keep the
    # cast-to-cache-dtype.
    q_scaled = (q.astype(jnp.float32) * scale).astype(
        q.dtype if quant else k_cache.dtype)

    kernel = functools.partial(_decode_kernel, block_size, pair, Hkv,
                               soft_cap, quant, window)
    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, bt, sl: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),   # K stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # V stays in HBM
    ]
    scratch = [
        pltpu.VMEM((3, pair * block_size, F), k_cache.dtype),
        pltpu.VMEM((3, pair * block_size, F), v_cache.dtype),
    ]
    inputs = [block_tables, seq_lens, q_scaled, k_cache, v_cache]
    if quant:
        specs, tiles = scale_tile_operands(k_scale, v_scale, block_tables,
                                           block_size, pair)
        in_specs += specs
        inputs += tiles
    scratch.append(pltpu.SemaphoreType.DMA((3, pair, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, bt, sl: (b, 0, 0)),
        scratch_shapes=scratch,
    )
    # The window form under a name of its own: a capture tells the window
    # layers' time from the full layers'.
    named = {} if window is None else {
        "name": "paged_window_decode_attention"}
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        **named,
    )(*inputs)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "soft_cap", "interpret", "pair",
                     "window"))
def paged_window_decode_attention(q, k_cache, v_cache, block_tables,
                                  seq_lens, *, window: int, **kw):
    """`paged_decode_attention` with a window, as a program of its own name
    (what a model's window layers call)."""
    return paged_decode_attention.__wrapped__(
        q, k_cache, v_cache, block_tables, seq_lens, window=window, **kw)


def paged_block_attention(
    q: jax.Array,             # [B, T, Hq, D]: T queries a row
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,      # [B]: every query of a row sees [0, seq_len)
    **kw,
) -> jax.Array:
    """Decode attention with T queries a row that all see the row's whole
    context `[0, seq_len)`: the denoising and commit forwards of a
    block-diffusion model, whose block of T positions sees the cache and
    itself in both directions (the block's own K/V are written to its slots
    before the call, `seq_len` = the block's end).  No per-query mask is
    needed, so the T queries ride the decode kernel's head-group axis: row
    (kv head g, query t, group member j) of a [B, Hkv * T * G, D] query is
    one more head of KV head g.  Returns [B, T, Hq, D]."""
    B, T, Hq, D = q.shape
    Hkv = k_cache.shape[1] // D
    G = Hq // Hkv
    qh = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    out = paged_decode_attention(
        qh.reshape(B, Hkv * T * G, D), k_cache, v_cache, block_tables,
        seq_lens, **kw)
    out = out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, Hq, D)
