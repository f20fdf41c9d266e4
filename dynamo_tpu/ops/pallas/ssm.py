"""Pallas TPU kernels for the state-space mixer (ops/ssm.py): the decode
step's state update and the prefill chunk's chunked scan.

`state_update_kernel` steps each live row's recurrent state by one token
where the state lies: a row's [H, P, N] float32 state is read from its slot
of the `ssm` leaf block by block, stepped and written back to the same block
(the leaf is aliased to the output), so a step moves each live state once in
and once out and nothing else of the leaf.  The plain XLA form (a gather of
the rows' states, the arithmetic, a scatter back) moves them three times.

A block is whole heads of one row, as many as fit `STATE_BLOCK_BYTES`
whatever a head's size (`state_update_head_block`: 64 heads at 64 x 128, 16
at 128 x 256): its bytes, not its heads, are what hides a grid step's fixed
cost behind its DMA.  A block may span B/C groups: `B_t` and `C_t` come as
[groups in the block, N] and a head takes its group's row.

A padding row (its slot is the scratch slot, the leaf's last) moves no
state.  The rows are visited live ones first, by a permutation reckoned on
the device from the slots and carried with the live count in the scalar
prefetch: the count is a value, never a shape, so a bucket has one program
whatever it holds.  A padding row's grid steps name the block the last live
step holds, so nothing is fetched for them, their body writes the row's `y`
as zeros and nothing else, and what is written back when the grid ends is
that live block as the live step left it.  An output block is never written
back unfilled: a call without one live row hands the scratch slot's blocks
through as they were.

Layout: the state keeps `d_state` on the lanes and the head dimension on the
sublanes, so `B_t` (a row over `d_state`) broadcasts down the sublanes for
free.  The decay `exp(dt A)` is one value a (row, head) and rides the scalar
prefetch; `dt x` is one value a head-dimension row and is handed transposed
a block, [P, heads], a head a lane, so a head's column is a static lane
slice.  The state's arithmetic is `s * da + dx * b` in float32 on the VPU,
product for product what the plain form does.  The read-out `S_t C_t` is a
matmul of `C_t` against the state's rows on the MXU, idle otherwise
(float32 operands at "highest" precision, float32 sums): it leaves `y` with
the head dimension on the lanes, as the output lies, and keeps every lane
reduction off the step (PERF.md section 6, PR 49: with three a head the
body, not the DMA, bound the step at 64 x 128).

`chunk_scan_kernel` runs the chunked (SSD) scan of a prefill chunk: one grid
step a (head block, scan chunk), the scan chunks of a head block one after
another with the state in VMEM between them (transposed, [N, P], so that
every product is a plain matmul: C B^T, its masked product with the values,
C S, B^T (x dt)), float32 operands at "highest" matmul precision.  A
segment's first scan chunk takes the state from `init`; each segment's last
state leaves through an output block that stays resident while the segment's
scan chunks pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A block of the state the update kernel moves in a grid step: as many whole
# heads [P, N] float32 as fit in these bytes (64 heads at the published
# 64 x 128, 16 at 128 x 256), so that a step's fixed cost hides behind its
# DMA whatever the head's size; on the chip 2 MiB reads 1-2 % under 1 MiB at
# both (PERF.md section 6, PR 49).  A block lies in VMEM four times (in and
# out, double-buffered).
STATE_BLOCK_BYTES = 2 << 20

# Heads a grid step of the scan kernel handles (its state block is
# [HEADS, N, P] float32 in VMEM scratch).
HEAD_BLOCK = 8

_HI = jax.lax.Precision.HIGHEST


def state_update_head_block(heads: int, head_dim: int, d_state: int,
                            groups: int) -> int:
    """Heads in a block of the update kernel: the most that fit
    `STATE_BLOCK_BYTES`, divide `heads`, make whole sublane tiles of the
    output (a multiple of 8, or all heads) and either lie inside one B/C
    group or hold whole groups; 0 where none does."""
    per_group = heads // groups
    fit = min(STATE_BLOCK_BYTES // (head_dim * d_state * 4), heads)
    for hb in range(fit, 0, -1):
        if (heads % hb == 0 and (hb % 8 == 0 or hb == heads)
                and (hb % per_group == 0 or per_group % hb == 0)):
            return hb
    return 0


def state_update_geometry_ok(heads: int, head_dim: int, d_state: int,
                             groups: int) -> bool:
    """Can the kernel take this state: whole (8, 128) tiles a head, every
    row's decays in SMEM (a scalar a head, 128 heads at most), and a block of
    whole heads that fits (`state_update_head_block`)."""
    if groups < 1 or heads % groups:
        return False
    return (head_dim % 8 == 0 and d_state % 128 == 0 and heads <= 128
            and state_update_head_block(heads, head_dim, d_state, groups) > 0)


def _update_kernel(order_ref, slots_ref, live_ref, da_ref, dx_ref, b_ref,
                   c_ref, s_ref, y_ref, s_out_ref, *, per_group):
    """One block of one row: `hb` heads of its slot, stepped and read out.
    order_ref [R]: the row each grid row visits, live rows first; live_ref
    [1]: how many are live; da_ref [R * H] (SMEM): exp(dt A), a scalar a
    head; dx_ref [1, 1, P, hb]: dt x, a head a lane; b_ref, c_ref
    [1, 1, groups in the block, N]; y_ref [1, hb, P]."""
    del slots_ref                                    # read by the index maps
    i, j = pl.program_id(0), pl.program_id(1)
    hb, _, n = s_ref.shape[1:]
    live = i < live_ref[0]

    @pl.when(live)
    def _():
        # The block's first head among the decays of all rows.
        first = (order_ref[i] * pl.num_programs(1) + j) * hb
        dx_t = dx_ref[0, 0]                          # [P, hb]
        for g in range(b_ref.shape[2]):
            b = b_ref[0, 0, g:g + 1, :]              # [1, N]
            c8 = jnp.broadcast_to(c_ref[0, 0, g:g + 1, :], (8, n))
            for k in range(g * per_group, min((g + 1) * per_group, hb)):
                s = s_ref[0, k].astype(jnp.float32) * da_ref[first + k] \
                    + dx_t[:, k:k + 1] * b           # [P, N]
                s_out_ref[0, k] = s.astype(s_out_ref.dtype)
                y = jax.lax.dot_general(
                    c8, s, (((1,), (1,)), ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32)      # [8, P]
                y_ref[0, k:k + 1, :] = y[:1]

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # No live row at all: the first grid row walks the scratch slot's blocks
    # and hands each back as it was (an output block is never left unfilled).
    @pl.when(jnp.logical_and(live_ref[0] == 0, i == 0))
    def _():
        s_out_ref[...] = s_ref[...]


def state_update_kernel(ssm, slots, x, dt, a, b, c, interpret=False):
    """`ops.ssm.ssm_state_update`'s contract; called inside its jit so that
    a device trace names the call after it.  A row on the scratch slot (the
    leaf's last) moves no state and reads `y` zero."""
    R, H, P = x.shape
    S, N = ssm.shape[0], ssm.shape[-1]
    G = b.shape[1]
    per_group = H // G
    hb = state_update_head_block(H, P, N, G)
    nb = H // hb                                     # blocks a row
    gb = max(hb // per_group, 1)                     # groups a block
    # Live rows first, each kind in the order it came: row r is visited at
    # grid row at[r].
    slots = slots.astype(jnp.int32)
    is_live = slots != S - 1
    n_live = jnp.sum(is_live, dtype=jnp.int32)
    at = jnp.where(is_live, jnp.cumsum(is_live) - 1,
                   n_live + jnp.cumsum(~is_live) - 1)
    rows = jnp.arange(R, dtype=jnp.int32)
    order = jnp.sum(jnp.where(at[None, :] == rows[:, None], rows[None, :], 0),
                    axis=1, dtype=jnp.int32)
    da = jnp.exp(dt * a).astype(jnp.float32).reshape(R * H)
    dx_t = (dt[..., None] * x).astype(jnp.float32).reshape(
        R, nb, hb, P).transpose(0, 1, 3, 2)          # [R, nb, P, hb]
    # Each block's groups: block j's first head lies in group
    # j * hb // per_group, and the block holds `gb` of them.
    of_block = (jnp.arange(nb) * hb // per_group)[:, None] \
        + jnp.arange(gb)[None, :]
    b4 = b.astype(jnp.float32)[:, of_block]          # [R, nb, gb, N]
    c4 = c.astype(jnp.float32)[:, of_block]

    # A padding row's steps stay on what the last live step named (grid row
    # `held`, its last block): nothing is fetched and nothing written back
    # for them.  Without a live row grid row 0 stands in, on the scratch slot.
    def block(i, j, order, slots, live, da):
        held = jnp.minimum(i, jnp.maximum(live[0] - 1, 0))
        return order[held], jnp.where(i == held, j, nb - 1), 0, 0

    def state(i, j, order, slots, *rest):
        row, blk, _, _ = block(i, j, order, slots, *rest)
        return slots[row], blk, 0, 0

    return pl.pallas_call(
        functools.partial(_update_kernel, per_group=per_group),
        out_shape=(jax.ShapeDtypeStruct((R, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R, nb),
            in_specs=[pl.BlockSpec((1, 1, P, hb), block),
                      pl.BlockSpec((1, 1, gb, N), block),
                      pl.BlockSpec((1, 1, gb, N), block),
                      pl.BlockSpec((1, hb, P, N), state)],
            out_specs=(pl.BlockSpec(
                (1, hb, P),
                lambda i, j, order, slots, live, da: (order[i], j, 0)),
                pl.BlockSpec((1, hb, P, N), state))),
        # The leaf is stepped where it lies (operand 7, counting the four
        # scalar operands).
        input_output_aliases={7: 1},
        # A block in and out, double-buffered, and room for the rest.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * hb * P * N * 4 + (8 << 20)),
        interpret=interpret,
    )(order, slots, n_live.reshape(1), da, dx_t, b4, c4, ssm)


# ---------------------------------------------------------------------------
# The chunked scan of a prefill chunk

def chunk_scan_geometry_ok(heads: int, head_dim: int, d_state: int,
                           groups: int, chunk: int) -> bool:
    """Can the scan kernel take this mixer: whole (8, 128) tiles of the
    scan chunk and the state, a head block of whole lanes (8 heads of 64 are
    512), a head block inside one group."""
    if groups < 1 or heads % groups:
        return False
    return (head_dim % 64 == 0 and d_state % 128 == 0 and heads <= 128
            and heads % HEAD_BLOCK == 0
            and (heads // groups) % HEAD_BLOCK == 0 and chunk % 128 == 0)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _scan_kernel(first_ref, seg_ref, xdt_ref, bt_ref, c_ref, cc_ref, cr_ref,
                 init_ref, y_ref, fin_ref, state):
    """One scan chunk of one head block.  The state rides `state` (VMEM,
    [heads, N, P]: transposed, so that every product is a plain matmul)
    from scan chunk to scan chunk; a segment's first scan chunk takes it
    from `init`."""
    del seg_ref                                      # read by the index maps
    j, c_idx = pl.program_id(0), pl.program_id(1)
    q = xdt_ref.shape[1]
    p = state.shape[2]

    @pl.when(first_ref[c_idx] != 0)
    def _():
        state[...] = init_ref[0]

    bt, c = bt_ref[0], c_ref[0]                      # [N, Q], [Q, N]
    g = _dot(c, bt)                                  # [Q, Q]: C_l . B_s
    cum_cols = cc_ref[0]                             # [Q, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, cum_cols.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    for k in range(HEAD_BLOCK):
        h = j * HEAD_BLOCK + k
        cum_c = jnp.sum(jnp.where(lane == h, cum_cols, 0.0), axis=-1,
                        keepdims=True)               # [Q, 1]
        cum_r = cr_ref[0, pl.ds(h, 1), :]            # [1, Q]
        decay = jnp.exp(jnp.where(row >= col, cum_c - cum_r, -1e30))
        xdt = xdt_ref[0, :, k * p:(k + 1) * p]       # [Q, P]
        s = state[k]                                 # [N, P]
        y = _dot(g * decay, xdt) + _dot(c, s) * jnp.exp(cum_c)
        y_ref[0, :, k * p:(k + 1) * p] = y
        last = cum_c[q - 1:q, :]                     # [1, 1]
        s = s * jnp.exp(last) + _dot(bt, xdt * jnp.exp(last - cum_c))
        state[k] = s
        fin_ref[0, k] = s


def chunk_scan_kernel(x, dt, a, b, c, first, seg, init, interpret=False):
    """`ops.ssm.ssm_chunk_scan`'s contract but for the states: returns
    (y [NC, Q, H, P], fin [R + 1, H, P, N]: each segment's last state, row
    R a dummy for scan chunks that belong to none).  `seg` [NC] names every
    scan chunk's segment (R for none); `init` is [R + 1, H, P, N]."""
    NC, Q, H, P = x.shape
    G, N = b.shape[2:]
    R1 = init.shape[0]
    per_group = H // G // HEAD_BLOCK
    cum = jnp.cumsum(dt * a, axis=1)                         # [NC, Q, H]
    xdt = (x * dt[..., None]).reshape(NC, Q, H * P)
    c2 = c.reshape(NC, Q, G * N)
    bt = b.reshape(NC, Q, G * N).transpose(0, 2, 1)          # [NC, G*N, Q]
    hp = HEAD_BLOCK * P
    chunk = lambda j, ci, fr, sg: (ci, 0, j)                 # noqa: E731
    group = lambda j, ci, fr, sg: (ci, 0, j // per_group)    # noqa: E731
    group_t = lambda j, ci, fr, sg: (ci, j // per_group, 0)  # noqa: E731
    whole = lambda j, ci, fr, sg: (ci, 0, 0)                 # noqa: E731
    owner = lambda j, ci, fr, sg: (sg[ci], j, 0, 0)          # noqa: E731
    y, fin = pl.pallas_call(
        _scan_kernel,
        out_shape=(jax.ShapeDtypeStruct((NC, Q, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((R1, H, N, P), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // HEAD_BLOCK, NC),
            in_specs=[pl.BlockSpec((1, Q, hp), chunk),
                      pl.BlockSpec((1, N, Q), group_t),
                      pl.BlockSpec((1, Q, N), group),
                      pl.BlockSpec((1, Q, H), whole),
                      pl.BlockSpec((1, H, Q), whole),
                      pl.BlockSpec((1, HEAD_BLOCK, N, P), owner)],
            out_specs=(pl.BlockSpec((1, Q, hp), chunk),
                       pl.BlockSpec((1, HEAD_BLOCK, N, P), owner)),
            scratch_shapes=[pltpu.VMEM((HEAD_BLOCK, N, P), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(first.astype(jnp.int32), seg.astype(jnp.int32), xdt, bt, c2, cum,
      cum.transpose(0, 2, 1), init.transpose(0, 1, 3, 2))
    return y.reshape(NC, Q, H, P), fin.transpose(0, 1, 3, 2)
