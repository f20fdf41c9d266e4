"""Pallas TPU attention over a paged LATENT cache (MLA), decode and prefill.

A latent-attention model stores one compressed row a token a layer,
`[c_kv | k_rope | zero padding]` (engine/kv_cache.py, latent mode), which all
query heads share.  Read in the weight-absorbed form
(models/llama._latent_project) attention over it is attention with ONE "KV
head" whose key is the whole row and whose value is the row's leading
`v_width` columns, under `Hq` query heads that each carry a query as wide as
the row: score `q_abs[h] . row`, output `sum p * row[:v_width]`.  So every
tile of rows is fetched from HBM once and serves the scores and the values of
every head, which is what the compressed cache is for.

These are the siblings of `paged_attention.py` (decode) and
`paged_prefill.py` (packed ragged prefill) and keep their structure: the
pool's 2D `[S, row]` layer buffer stays in HBM, `pair` pages a DMA tile,
double-buffered, online softmax in float32, bf16 MXU passes with float32
accumulation.  What differs:

- one DMA stream, not a K and a V stream; key width != value width, the
  value being a lane-aligned static slice of the key tile in VMEM;
- no GQA banding (decode) and no per-KV-head slicing (prefill): the heads
  are the rows of one `[Hq, row] x [row, W]` pass (decode), or a static loop
  of `[TQ, row] x [row, W]` passes (prefill);
- the prefill kernel's grid has a head-group axis in front of the segment
  axis: the resident query and output blocks of all `Hq` heads at once
  (20 x 640 and 20 x 512 columns of 512 packed rows) would not fit VMEM, a
  group of `head_group` does.  Each group sweeps the segment's tiles again:
  prefill is compute-bound (2 * (row + v_width) operations a query-context
  pair a head against `row` * 2 bytes a context token a group), so the
  re-read hides under the MXU.

Eligibility on the chip: `latent_geometry_ok` (row and value width multiples
of the 128 lanes, block size of 8 sublanes).  `interpret=True` runs anywhere
(CPU tests)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.paged_attention import auto_pair

_NEG_INF = -1e30            # finite: a fully masked row stays finite junk
PACK_ALIGN = 8              # as paged_prefill.PACK_ALIGN (the pack builder's)
HEAD_ALIGN = 16             # decode pads its query heads to bf16 sublanes


def latent_geometry_ok(row: int, v_width: int, block_size: int) -> bool:
    """The Mosaic eligibility rule of both kernels: the row the DMA moves
    and the value slice taken of it in VMEM are lane-aligned, the page
    sublane-aligned."""
    return row % 128 == 0 and v_width % 128 == 0 and block_size % 8 == 0


def _check(q_row: int, cache, v_width: int, block_size: int,
           interpret: bool) -> None:
    row = cache.shape[1]
    if q_row != row or not 0 < v_width <= row:
        raise ValueError(f"bad latent geometry: query width {q_row}, cache "
                         f"row {row}, value width {v_width}")
    if not interpret and not latent_geometry_ok(row, v_width, block_size):
        raise ValueError(
            "the latent attention kernels need row % 128 == 0, v_width % "
            f"128 == 0 and block_size % 8 == 0; got row={row}, "
            f"v_width={v_width}, block_size={block_size} (use the gather "
            "path for this geometry)")


# ---------------------------------------------------------------------------
# Decode


def _decode_kernel(block_size: int, pair: int, v_width: int,
                   bt_ref, len_ref,          # scalar-prefetch (SMEM)
                   q_ref, kv_hbm,            # q [1, Hq, row]; [S, row] in HBM
                   o_ref, kv_vmem, sem):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    seq_len = len_ref[b]
    W = block_size * pair
    n_iters = pl.cdiv(seq_len, W)
    Hq = q_ref.shape[1]
    q = q_ref[0]                                       # [Hq, row] pre-scaled

    def fetch(slot, seq, t, j):
        # Page t*pair + j of sequence row `seq`, clamped to its last real
        # page: a tail tile's extra DMA re-fetches it, masked in compute.
        last = jnp.maximum(pl.cdiv(len_ref[seq], block_size) - 1, 0)
        p = jnp.minimum(t * pair + j, last)
        return pltpu.make_async_copy(
            kv_hbm.at[pl.ds(bt_ref[seq, p] * block_size, block_size)],
            kv_vmem.at[slot, pl.ds(j * block_size, block_size)],
            sem.at[slot, j])

    def start_tile(slot, seq, t):
        for j in range(pair):
            fetch(slot, seq, t, j).start()

    def wait_tile(slot, seq, t):
        for j in range(pair):
            fetch(slot, seq, t, j).wait()

    # The decode kernel's pipeline (paged_attention._decode_kernel): tile 0
    # lives in slot 2, which the PREVIOUS program prefetched during its last
    # tile iff it had two tiles or more; slots 0/1 double-buffer the rest.
    prev_iters = pl.cdiv(len_ref[jnp.maximum(b - 1, 0)], W)
    prefetched = jnp.logical_and(b > 0, prev_iters > 1)

    @pl.when(jnp.logical_and(n_iters > 0, jnp.logical_not(prefetched)))
    def _():
        start_tile(2, b, 0)

    def body(t, carry):
        m, l, acc = carry
        slot = jnp.where(t == 0, 2, jax.lax.rem(t, 2))

        @pl.when(t + 1 < n_iters)
        def _():
            start_tile(jax.lax.rem(t + 1, 2), b, t + 1)

        @pl.when(jnp.logical_and(
            jnp.logical_and(t + 1 >= n_iters, t >= 1),
            jnp.logical_and(b + 1 < nb,
                            len_ref[jnp.minimum(b + 1, nb - 1)] > 0)))
        def _():
            start_tile(2, jnp.minimum(b + 1, nb - 1), 0)

        wait_tile(slot, b, t)
        rows = kv_vmem[slot]                           # [W, row]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Hq, W]
        pos = t * W + jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        s = jnp.where(pos < seq_len, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Hq, v_width]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((Hq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((Hq, 1), jnp.float32)
    a0 = jnp.zeros((Hq, v_width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_iters, body, (m0, l0, a0))
    # A padding row (seq_len 0) skips the loop: guard the divide.
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "v_width", "interpret", "pair"))
def latent_decode_attention(
    q: jax.Array,             # [B, Hq, row] absorbed queries, one a row
    kv_cache: jax.Array,      # [S, row] one layer's latent rows
    block_tables: jax.Array,  # [B, P] int32 page ids
    seq_lens: jax.Array,      # [B] int32 valid context length
    *,
    block_size: int,
    scale: float,
    v_width: int,
    interpret: bool = False,
    pair: Optional[int] = None,
) -> jax.Array:
    """Decode-step attention over the paged latent cache: the query of row b
    sees slots at positions < seq_lens[b]; returns the output in the latent
    space, [B, Hq, v_width].  Numerics match the gather path
    (`ops.attention.paged_attention` over the gathered rows as key and
    value)."""
    B, Hq, R = q.shape
    _check(R, kv_cache, v_width, block_size, interpret)
    if pair is None:
        pair = min(auto_pair(block_size, R,
                             jnp.dtype(kv_cache.dtype).itemsize),
                   block_tables.shape[1])
    q_scaled = (q.astype(jnp.float32) * scale).astype(kv_cache.dtype)
    Hp = -(-Hq // HEAD_ALIGN) * HEAD_ALIGN
    if Hp != Hq:
        q_scaled = jnp.pad(q_scaled, ((0, 0), (0, Hp - Hq), (0, 0)))
    kernel = functools.partial(_decode_kernel, block_size, pair, v_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, R), lambda b, bt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the rows stay in HBM
        ],
        out_specs=pl.BlockSpec((1, Hp, v_width), lambda b, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((3, pair * block_size, R), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((3, pair)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Hp, v_width), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables, seq_lens, q_scaled, kv_cache)
    return out[:, :Hq]


# ---------------------------------------------------------------------------
# Packed ragged prefill


def _prefill_kernel(block_size: int, pair: int, heads: int, v_width: int,
                    q_tile: int,
                    # scalar-prefetch refs (SMEM)
                    bt_ref, len_ref, qstart_ref, qlen_ref,
                    # q [1, T, heads * row]; rows [S, row] in HBM
                    q_ref, kv_hbm, o_ref, kv_vmem, sem):
    r = pl.program_id(1)
    seq_len = len_ref[r]
    q_start = qstart_ref[r]
    q_len = qlen_ref[r]
    chunk_start = seq_len - q_len
    T = q_ref.shape[1]
    R = q_ref.shape[2] // heads
    W = block_size * pair
    TQ = q_tile

    # The out block is revisited across this group's segments and written
    # back once: zero it before the first, so pad rows and alignment gaps
    # emit zeros.
    @pl.when(r == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def fetch(slot, t, j):
        last = jnp.maximum(pl.cdiv(seq_len, block_size) - 1, 0)
        p = jnp.minimum(t * pair + j, last)
        return pltpu.make_async_copy(
            kv_hbm.at[pl.ds(bt_ref[r, p] * block_size, block_size)],
            kv_vmem.at[slot, pl.ds(j * block_size, block_size)],
            sem.at[slot, j])

    def start_tile(slot, t):
        for j in range(pair):
            fetch(slot, t, j).start()

    def wait_tile(slot, t):
        for j in range(pair):
            fetch(slot, t, j).wait()

    def q_tile_body(qi, _):
        # As paged_prefill: the tile window is clamped into [0, T - TQ], a
        # tail tile re-covers rows an earlier one wrote, and rows outside
        # the segment are masked out of the store.
        base = pl.multiple_of(jnp.clip(q_start + qi * TQ, 0, T - TQ),
                              PACK_ALIGN)
        idx0 = base - q_start
        qp = q_ref[0, pl.ds(base, TQ), :]                # [TQ, heads * row]
        row_idx = idx0 + jax.lax.broadcasted_iota(jnp.int32, (TQ, 1), 0)
        row_ok = jnp.logical_and(row_idx >= 0, row_idx < q_len)
        q_pos = chunk_start + row_idx                    # [TQ, 1] absolute
        kv_hi = jnp.minimum(seq_len, chunk_start + idx0 + TQ)
        n_kv_iters = pl.cdiv(jnp.maximum(kv_hi, 0), W)

        @pl.when(n_kv_iters > 0)
        def _():
            start_tile(0, 0)

        m0 = tuple(jnp.full((TQ, 1), _NEG_INF, jnp.float32)
                   for _ in range(heads))
        l0 = tuple(jnp.zeros((TQ, 1), jnp.float32) for _ in range(heads))
        a0 = tuple(jnp.zeros((TQ, v_width), jnp.float32)
                   for _ in range(heads))

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
            rows = kv_vmem[slot]                         # [W, row]
            vals = rows[:, :v_width]
            new_m, new_l, new_a = [], [], []
            for j in range(heads):
                s = jax.lax.dot_general(
                    qp[:, j * R:(j + 1) * R], rows,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [TQ, W]
                s = jnp.where(mask, s, _NEG_INF)
                m_new = jnp.maximum(ms[j],
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(ms[j] - m_new)
                probs = jnp.exp(s - m_new)
                new_m.append(m_new)
                new_l.append(ls[j] * alpha
                             + jnp.sum(probs, axis=-1, keepdims=True))
                pv = jax.lax.dot_general(
                    probs.astype(vals.dtype), vals,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [TQ, v_width]
                new_a.append(accs[j] * alpha + pv)
            return tuple(new_m), tuple(new_l), tuple(new_a)

        _, ls, accs = jax.lax.fori_loop(0, n_kv_iters, kv_body,
                                        (m0, l0, a0))
        res = jnp.concatenate(
            [accs[j] / jnp.maximum(ls[j], 1e-30) for j in range(heads)],
            axis=1).astype(o_ref.dtype)
        cur = o_ref[0, pl.ds(base, TQ), :]
        o_ref[0, pl.ds(base, TQ), :] = jnp.where(row_ok, res, cur)
        return 0

    jax.lax.fori_loop(0, pl.cdiv(q_len, TQ), q_tile_body, 0)


def auto_head_group(heads: int, t: int, row: int, v_width: int,
                    itemsize: int = 2, budget: int = 10 * 1024 * 1024) -> int:
    """Heads a grid program holds: the largest divisor of `heads` whose
    resident query and output blocks, each double-buffered by the pipeline,
    fit `budget` bytes of VMEM."""
    best = 1
    for g in range(1, heads + 1):
        if heads % g == 0 \
                and 2 * g * t * (row + v_width) * itemsize <= budget:
            best = g
    return best


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "v_width", "interpret", "pair",
                     "q_tile", "head_group"))
def latent_prefill_attention(
    q: jax.Array,             # [T, Hq, row] packed absorbed queries
    kv_cache: jax.Array,      # [S, row] one layer's latent rows
    block_tables: jax.Array,  # [R, P] int32 page ids per segment
    seq_lens: jax.Array,      # [R] valid context AFTER this chunk
    q_starts: jax.Array,      # [R] packed row offset of each segment
    q_lens: jax.Array,        # [R] real query rows per segment (0 = pad)
    *,
    block_size: int,
    scale: float,
    v_width: int,
    interpret: bool = False,
    pair: Optional[int] = None,
    q_tile: Optional[int] = None,
    head_group: Optional[int] = None,
) -> jax.Array:
    """Packed ragged prefill attention over the paged latent cache; returns
    the output in the latent space, [T, Hq, v_width].  The contract is
    `paged_prefill_attention`'s: each segment's queries see their own block
    table's slots at `kv_pos < seq_len AND kv_pos <= q_pos`, the chunk's
    rows are already in the pool, T and every q_start are multiples of
    `PACK_ALIGN`, pad segments carry q_len 0, and rows no segment owns come
    back zero."""
    T, Hq, R = q.shape
    _check(R, kv_cache, v_width, block_size, interpret)
    if T % PACK_ALIGN:
        raise ValueError(f"packed token axis T={T} must be a multiple of "
                         f"{PACK_ALIGN}")
    itemsize = jnp.dtype(kv_cache.dtype).itemsize
    if pair is None:
        pair = min(auto_pair(block_size, R, itemsize), block_tables.shape[1])
    if q_tile is None:
        q_tile = min(128, T)
    if T < q_tile:
        raise ValueError(f"T={T} smaller than q_tile={q_tile}")
    if head_group is None:
        head_group = auto_head_group(Hq, T, R, v_width, itemsize)
    if Hq % head_group:
        raise ValueError(f"head_group={head_group} does not divide {Hq}")
    groups = Hq // head_group
    n_seg = block_tables.shape[0]

    q_scaled = (q.astype(jnp.float32) * scale).astype(kv_cache.dtype)
    # Group-major, token-major inside: [groups, T, head_group * row].
    qg = q_scaled.reshape(T, groups, head_group * R).transpose(1, 0, 2)

    kernel = functools.partial(_prefill_kernel, block_size, pair, head_group,
                               v_width, q_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(groups, n_seg),
        in_specs=[
            pl.BlockSpec((1, T, head_group * R),
                         lambda g, r, *_: (g, 0, 0)),    # resident queries
            pl.BlockSpec(memory_space=pl.ANY),           # rows stay in HBM
        ],
        out_specs=pl.BlockSpec((1, T, head_group * v_width),
                               lambda g, r, *_: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pair * block_size, R), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, pair)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, T, head_group * v_width),
                                       q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables, seq_lens, q_starts, q_lens, qg, kv_cache)
    return out.transpose(1, 0, 2).reshape(T, Hq, v_width)
