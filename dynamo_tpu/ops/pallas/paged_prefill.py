"""Pallas TPU paged flash-prefill attention over the KV block pool.

Prefill attention used to be the one path that still materialised context:
`gather_kv` in models/llama.py pulled every cached slot of every row into
a dense `[R, C, Hkv, D]` buffer per layer per chunk — a full copy of up to
`max_pages_per_seq * block_size` tokens of K/V through HBM for each of R
rows, regardless of how much context each row really has.  This kernel is
the prefill sibling of `paged_attention.py`: K/V pages stream straight
from the pool's 2D `[S, F]` layer buffers through VMEM tiles with an
online-softmax accumulator, so no gathered context array ever exists and
the read cost is per-sequence-length.

Packed ragged layout (the engine's packed prefill plane): the query axis
is ONE flat `[T]` token axis holding several sequences' chunks
back-to-back ("segments"), described by per-segment
(q_start, q_len, seq_len, block-table row).  One compiled program then
serves any mix of chunk lengths — the engine stops padding `[R, T]`
buckets, and the prefill shape lattice collapses to the packed token
buckets × page buckets (the cold-prefill cliff shrinks with it).

Semantics per segment r (grid program r):

- its queries are packed rows [q_start[r], q_start[r] + q_len[r]) and
  carry absolute positions [seq_len[r] - q_len[r], seq_len[r]);
- each query attends to every pool slot of its own block table at
  positions `kv_pos < seq_len` AND `kv_pos <= q_pos` — so CACHED-PREFIX
  attention (chunked/residual prefill: prior context is resident pages)
  and in-chunk causal masking are the same position test.  The chunk's
  own K/V must be scattered into the pool before the kernel runs (the
  engine's standing write-then-attend discipline);
- segments never see each other: masking is by construction (each
  program reads only its own table's pages), not a soft segment-id
  compare.

Compute structure: per q tile (`q_tile` rows, default 128) the segment's
KV tiles stream once (double-buffered `pair`-page DMAs, the decode
kernel's fetch discipline); scores run as a static per-q-head loop of
`[TQ, D] x [D, W]` MXU passes — minimal FLOPs (no Hkv-fold banding: the
decode kernel's banding trick trades FLOPs for bytes, correct for
bandwidth-bound decode but wrong for compute-bound prefill; the cost
here is the D=64 contraction running the MXU at half fill, which the
docstring owns rather than hides).  Flash state (m, l, acc) lives
per-head as loop-carried VMEM values.

int8 variant: pass the pool's int8 buffers with their `[S, Hkv]` f32
scale siblings (PR 6 layout) — pages DMA as int8 and feed the MXU
unscaled; the scales arrive lane-dense through
`paged_attention.scale_tiles` and multiply the per-head score and
probability tiles (dequantize-then-contract up to rounding).

Window form (`window` W, a model's window layers): a query also sees no
key W or more positions behind it, and a query tile's key loop STARTS at the
first tile visible to the tile's first row, so tiles wholly behind the window
are never fetched (their table entries may be the null block: the sequence
let those pages go).  The pallas call then runs under a name of its own.

Head groups: queries wider than `MAX_RESIDENT_WIDTH` (Hq * D over 4096: four
times any shape the kernel was first sized for) are split into groups of
whole KV heads on an outer grid axis, each group's queries and output
resident as `[T, Hg * D]` and its K/V streamed as its own column band of the
pages, so what is resident a program is what it was at 32 heads of 128 and
no byte of K/V is read twice.  Decided from the shape alone (`head_groups`).

Eligibility is `mosaic_geometry_ok` — THE shared predicate with the
decode kernel (F % 128, block_size % 8), plus packed-axis alignment
(T % 8, segment starts % 8, handled by the engine's pack builder).
Ineligible geometries take the gather path (the padded-bucket plane);
`interpret=True` runs anywhere (CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.paged_attention import (
    TARGET_TILE, auto_pair, mosaic_geometry_ok, scale_tile_operands)

# Matches ops/attention.py NEG_INF: finite so fully-masked (discarded)
# rows produce finite junk instead of NaN-poisoned accumulators.
_NEG_INF = -1e30

# Packed-axis alignment the engine's pack builder must honor: segment
# starts and the packed bucket length are multiples of this, so the
# kernel's dynamic sublane slices stay tile-aligned.
PACK_ALIGN = 8

# The widest [T, Hq * D] block of queries (and of output) one program keeps
# resident: 32 heads of 128, the widest of the shapes the 32 MB scoped-VMEM
# limit was set for.
MAX_RESIDENT_WIDTH = 4096


def head_groups(n_q: int, n_kv: int, head_dim: int) -> int:
    """Groups the query heads are split into: 1 while their rows are at
    most `MAX_RESIDENT_WIDTH` wide, else the fewest groups of whole KV heads
    (each group's K/V band a multiple of the 128 lanes) that are."""
    if n_q * head_dim <= MAX_RESIDENT_WIDTH:
        return 1
    for n in range(2, n_kv + 1):
        if n_kv % n == 0 and (n_kv // n * head_dim) % 128 == 0 \
                and n_q // n * head_dim <= MAX_RESIDENT_WIDTH:
            return n
    return 1


def _prefill_kernel(block_size: int, pair: int, n_kv: int, n_q: int,
                    q_tile: int, soft_cap: Optional[float], quant: bool,
                    mask_block: int, window: Optional[int], n_groups: int,
                    # scalar-prefetch refs (SMEM)
                    bt_ref, len_ref, qstart_ref, qlen_ref,
                    # tensor refs
                    q_ref, k_hbm, v_hbm, *rest):
    if quant:
        # [1, n_tiles, Hkv, W] f32 blocks of this segment's scales,
        # tokens on the lanes (paged_attention.scale_tiles).
        ks_ref, vs_ref, o_ref, k_vmem, v_vmem, sem = rest
    else:
        o_ref, k_vmem, v_vmem, sem = rest
        ks_ref = vs_ref = None
    # `n_q`, `n_kv`: the heads of ONE group (all of them without groups);
    # with groups the grid is (group, segment), the group the slow axis so
    # that its output block stays resident over the segments.
    r = pl.program_id(0) if n_groups == 1 else pl.program_id(1)
    group = None if n_groups == 1 else pl.program_id(0)
    seq_len = len_ref[r]
    q_start = qstart_ref[r]
    q_len = qlen_ref[r]
    chunk_start = seq_len - q_len

    T, Fq = q_ref.shape
    D = Fq // n_q
    G = n_q // n_kv
    W = block_size * pair
    TQ = q_tile

    # The out block has a constant index map (revisited across programs,
    # written back once): zero it before the first segment so pad rows
    # and inter-segment alignment gaps emit zeros, not uninitialised VMEM.
    @pl.when(r == 0)
    def _():
        o_ref[:] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def fetch(buf, hbm, slot, t, j, lane):
        # Page p = t*pair + j of segment r, clamped to its last real page
        # so a tail tile's extra DMA is a harmless re-fetch (those
        # positions are masked in compute).
        last = jnp.maximum(pl.cdiv(seq_len, block_size) - 1, 0)
        p = jnp.minimum(t * pair + j, last)
        rows = pl.ds(bt_ref[r, p] * block_size, block_size)
        if n_groups == 1:
            src = hbm.at[rows]
        else:
            # This group's KV heads: its column band of the page.
            band = n_kv * D
            src = hbm.at[rows, pl.ds(pl.multiple_of(group * band, 128),
                                     band)]
        return pltpu.make_async_copy(
            src,
            buf.at[slot, pl.ds(j * block_size, block_size)],
            sem.at[slot, j, lane])

    streams = [(k_vmem, k_hbm, 0), (v_vmem, v_hbm, 1)]

    def start_tile(slot, t):
        for j in range(pair):
            for buf, hbm, lane in streams:
                fetch(buf, hbm, slot, t, j, lane).start()

    def wait_tile(slot, t):
        for j in range(pair):
            for buf, hbm, lane in streams:
                fetch(buf, hbm, slot, t, j, lane).wait()

    n_q_tiles = pl.cdiv(q_len, TQ)

    def q_tile_body(qi, _):
        # Clamp the tile window into [0, T - TQ]: a tail tile re-covers
        # rows the previous tile already wrote (recomputed identically),
        # and rows outside this segment are masked out of the store.
        # Every term is a PACK_ALIGN multiple; Mosaic needs to be told.
        base = pl.multiple_of(jnp.clip(q_start + qi * TQ, 0, T - TQ),
                              PACK_ALIGN)
        idx0 = base - q_start                    # first row's chunk index
        qp = q_ref[pl.ds(base, TQ), :]           # [TQ, Fq] pre-scaled
        row_idx = idx0 + jax.lax.broadcasted_iota(jnp.int32, (TQ, 1), 0)
        row_ok = jnp.logical_and(row_idx >= 0, row_idx < q_len)
        q_pos = chunk_start + row_idx            # [TQ, 1] absolute
        # Causality bounds the KV sweep: this tile's last query sees at
        # most position chunk_start + idx0 + TQ - 1, or under a block mask
        # the end of that position's block.
        kv_end = chunk_start + idx0 + TQ
        if mask_block > 1:
            # One past the last position a query sees: its block's end.
            q_pos = (q_pos // mask_block + 1) * mask_block - 1
            kv_end = (kv_end + mask_block - 1) // mask_block * mask_block
        kv_hi = jnp.minimum(seq_len, kv_end)
        n_kv_iters = pl.cdiv(jnp.maximum(kv_hi, 0), W)
        if window is None:
            t_lo, first_slot = 0, 0
        else:
            # The first key the tile's first row sees: the loop starts at
            # its tile and visits none that lies wholly behind the window.
            t_lo = jnp.maximum(
                chunk_start + jnp.maximum(idx0, 0) - window + 1, 0) // W
            first_slot = jax.lax.rem(t_lo, 2)

        @pl.when(n_kv_iters > t_lo)
        def _():
            start_tile(first_slot, t_lo)

        m0 = tuple(jnp.full((TQ, 1), _NEG_INF, jnp.float32)
                   for _ in range(n_q))
        l0 = tuple(jnp.zeros((TQ, 1), jnp.float32) for _ in range(n_q))
        a0 = tuple(jnp.zeros((TQ, D), jnp.float32) for _ in range(n_q))

        def kv_body(t, carry):
            ms, ls, accs = carry
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_kv_iters)
            def _():
                start_tile(jax.lax.rem(t + 1, 2), t + 1)

            wait_tile(slot, t)
            kv_pos = t * W + jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
            mask = jnp.logical_and(
                jnp.logical_and(kv_pos < seq_len, kv_pos <= q_pos), row_ok)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - kv_pos < window)

            new_m, new_l, new_a = [], [], []
            for j in range(n_q):
                h = j // G
                # int8 is exact in bf16: quantized tiles feed the MXU
                # unscaled and the [1, W] scale rows multiply the
                # products (no-op casts on a bf16 pool).
                k_h = k_vmem[slot, :, h * D:(h + 1) * D].astype(qp.dtype)
                v_h = v_vmem[slot, :, h * D:(h + 1) * D].astype(qp.dtype)
                q_j = qp[:, j * D:(j + 1) * D]                # [TQ, D]
                s = jax.lax.dot_general(
                    q_j, k_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [TQ, W]
                if quant:
                    s = s * ks_ref[0, t, h:h + 1, :]
                if soft_cap is not None:
                    s = soft_cap * jnp.tanh(s / soft_cap)
                s = jnp.where(mask, s, _NEG_INF)
                m_new = jnp.maximum(ms[j],
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(ms[j] - m_new)
                probs = jnp.exp(s - m_new)
                # Fully-masked rows: probs == 1 uniformly (finite junk);
                # their store is masked by row_ok below.
                new_m.append(m_new)
                new_l.append(ls[j] * alpha
                             + jnp.sum(probs, axis=-1, keepdims=True))
                if quant:
                    probs = probs * vs_ref[0, t, h:h + 1, :]
                pv = jax.lax.dot_general(
                    probs.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [TQ, D]
                new_a.append(accs[j] * alpha + pv)
            return tuple(new_m), tuple(new_l), tuple(new_a)

        ms, ls, accs = jax.lax.fori_loop(t_lo, n_kv_iters, kv_body,
                                         (m0, l0, a0))
        outs = [accs[j] / jnp.maximum(ls[j], 1e-30) for j in range(n_q)]
        res = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)
        cur = o_ref[pl.ds(base, TQ), :]
        # Masked store: rows outside this segment keep their value (an
        # earlier tile's output on overlap, zeros on padding) — grid
        # programs run sequentially, so segment order is respected.
        o_ref[pl.ds(base, TQ), :] = jnp.where(row_ok, res, cur)
        return 0

    jax.lax.fori_loop(0, n_q_tiles, q_tile_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "soft_cap", "interpret",
                     "pair", "q_tile", "mask_block", "window"))
def paged_prefill_attention(
    q: jax.Array,             # [T, Hq, D] packed chunk queries
    k_cache: jax.Array,       # [S, F = Hkv * D] one layer's pool keys
    v_cache: jax.Array,       # [S, F]
    block_tables: jax.Array,  # [R, P] int32 page ids per segment
    seq_lens: jax.Array,      # [R] valid context AFTER this chunk
    q_starts: jax.Array,      # [R] packed row offset of each segment
    q_lens: jax.Array,        # [R] real query rows per segment (0 = pad)
    *,
    block_size: int,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    interpret: bool = False,
    pair: Optional[int] = None,
    q_tile: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [S, Hkv] f32 (int8 pool)
    v_scale: Optional[jax.Array] = None,
    mask_block: int = 1,
    window: Optional[int] = None,
) -> jax.Array:
    """Packed ragged prefill attention over the paged pool; [T, Hq, D].

    Each segment's queries attend to its own block table's pool slots at
    `kv_pos < seq_len AND kv_pos <= q_pos` — cached-prefix attention for
    chunked/residual prefill and in-chunk causality in one mask.  With
    `mask_block` B > 1 (a block-diffusion model) the second test is
    `kv_pos // B <= q_pos // B`: a query also sees the later positions
    of its own block, so chunks must start and end on multiples of B.  The
    chunk's own K/V must already be scattered into the pool.  Numerics
    match the gather path (`kv_cache.gather_kv` + `ops.attention.
    paged_attention`) per segment: bf16 MXU passes, f32 accumulation,
    f32 softmax.

    Layout contract (the engine's pack builder provides it): T and every
    q_start are multiples of `PACK_ALIGN` (8), and T >= the q tile.  Pad
    segments carry q_len == 0.  Rows not owned by any segment come back
    zero.

    Quantized variant: int8 pool buffers plus `k_scale`/`v_scale`
    ([S, Hkv] f32), folded into the score/probability tiles (see the
    module docstring).

    `window` W: `q_pos - kv_pos < W` beside the tests above, the key loop
    started at the first visible tile (module docstring).  A model's window
    layers call `paged_window_prefill_attention`, the same program under a
    name of its own.
    """
    T, Hq, D = q.shape
    S, Fc = k_cache.shape
    Hkv = Fc // D
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if quant and k_cache.dtype != jnp.int8:
        raise ValueError(f"scales imply an int8 cache; got {k_cache.dtype}")
    if Fc % D or Hq % Hkv:
        raise ValueError(f"bad geometry: q {q.shape}, cache {k_cache.shape}")
    if window is not None and (quant or mask_block > 1):
        raise ValueError("a window composes with neither an int8 pool nor "
                         "a block mask")
    if T % PACK_ALIGN:
        raise ValueError(f"packed token axis T={T} must be a multiple of "
                         f"{PACK_ALIGN} (see pack builder alignment)")
    if not interpret and not mosaic_geometry_ok(Fc, block_size):
        raise ValueError(
            f"pallas paged prefill needs F % 128 == 0 and block_size % 8 "
            f"== 0; got F={Fc}, block_size={block_size} (use the gather "
            "path for this geometry)")
    if pair is None:
        # The bf16 tile target for int8 pools too: the per-head [TQ, W]
        # f32 score tiles, not the page scratch, fill this kernel's VMEM
        # (W=512 at TQ=128 x 32 heads needs 18.9 MB of the 16 MB scoped
        # limit on v5e; W=256 fits).
        pair = min(auto_pair(block_size, Fc,
                             jnp.dtype(k_cache.dtype).itemsize,
                             target=TARGET_TILE),
                   block_tables.shape[1])
    if q_tile is None:
        q_tile = min(128, T)
    if T < q_tile:
        raise ValueError(f"T={T} smaller than q_tile={q_tile}")
    if scale is None:
        scale = D ** -0.5
    R = block_tables.shape[0]

    # Pre-scale and flatten the queries to the kernel's 2D token-major
    # [T, Fq] view; int8 pools dequantize into q's dtype, bf16 pools
    # contract in the cache dtype (decode-kernel discipline).
    q_scaled = (q.astype(jnp.float32) * scale).astype(
        q.dtype if quant else k_cache.dtype)
    q2d = q_scaled.reshape(T, Hq * D)

    groups = 1 if quant else head_groups(Hq, Hkv, D)
    kernel = functools.partial(_prefill_kernel, block_size, pair,
                               Hkv // groups, Hq // groups, q_tile, soft_cap,
                               quant, mask_block, window, groups)
    if groups == 1:
        # Index maps receive (program_id, *scalar_prefetch_refs).
        resident = pl.BlockSpec((T, Hq * D), lambda r, *_: (0, 0))
    else:
        resident = pl.BlockSpec((T, Hq // groups * D),
                                lambda g, r, *_: (0, g))
    in_specs = [
        resident,                                  # resident queries
        pl.BlockSpec(memory_space=pl.ANY),         # K stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),         # V stays in HBM
    ]
    scratch = [
        pltpu.VMEM((2, pair * block_size, Fc // groups), k_cache.dtype),
        pltpu.VMEM((2, pair * block_size, Fc // groups), v_cache.dtype),
    ]
    inputs = [block_tables, seq_lens, q_starts, q_lens, q2d,
              k_cache, v_cache]
    if quant:
        specs, tiles = scale_tile_operands(k_scale, v_scale, block_tables,
                                           block_size, pair)
        in_specs += specs
        inputs += tiles
    scratch.append(pltpu.SemaphoreType.DMA((2, pair, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R,) if groups == 1 else (groups, R),
        in_specs=in_specs,
        out_specs=resident,
        scratch_shapes=scratch,
    )
    # The window form under a name of its own: a capture tells the window
    # layers' time from the full layers'.
    named = {} if window is None else {
        "name": "paged_window_prefill_attention"}
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T, Hq * D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        **named,
    )(*inputs)
    return out.reshape(T, Hq, D)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "soft_cap", "interpret",
                     "pair", "q_tile", "mask_block", "window"))
def paged_window_prefill_attention(q, k_cache, v_cache, block_tables,
                                   seq_lens, q_starts, q_lens, *,
                                   window: int, **kw):
    """`paged_prefill_attention` with a window, as a program of its own
    name (what a model's window layers call)."""
    return paged_prefill_attention.__wrapped__(
        q, k_cache, v_cache, block_tables, seq_lens, q_starts, q_lens,
        window=window, **kw)
