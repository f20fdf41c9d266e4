"""Pallas TPU kernels for the state-space mixer (ops/ssm.py): the decode
step's state update and the prefill chunk's chunked scan.

`state_update_kernel` steps each row's recurrent state by one token where
the state lies: a row's [H, P, N] float32 state is read from its slot of the
`ssm` leaf block by block, stepped and written back to the same block (the
leaf is aliased to the output), so a step moves each live state once in and
once out and nothing else of the leaf.  The plain XLA form (a gather of the
rows' states, the arithmetic, a scatter back) moves them three times.

Layout: the state keeps `d_state` on the lanes and the head dimension on the
sublanes, so `B_t` and `C_t` (rows over `d_state`) broadcast down the
sublanes for free, and what is one value a head-dimension row (`dt x`, the
decay) is handed transposed, [P, H], a head a lane: a head's column is
picked by a masked lane reduction, the one cheap way to a [P, 1] value, and
broadcasts along the lanes like a softmax's row maximum does.

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

# Heads a grid step handles: a block of the state is [HEADS, P, N] float32,
# 1 MiB at the published 128 x 256, double-buffered in and out.
HEAD_BLOCK = 8


def state_update_geometry_ok(heads: int, head_dim: int, d_state: int,
                             groups: int) -> bool:
    """Can the kernel take this state: whole (8, 128) tiles, and a head
    block inside one group (its heads share B and C)."""
    if groups < 1 or heads % groups:
        return False
    return (head_dim % 8 == 0 and d_state % 128 == 0 and heads <= 128
            and heads % HEAD_BLOCK == 0
            and (heads // groups) % HEAD_BLOCK == 0)


def _update_kernel(slots_ref, da_ref, dx_ref, b_ref, c_ref, s_ref,
                   y_ref, s_out_ref):
    del slots_ref                                    # read by the index maps
    j = pl.program_id(1)
    da_t = da_ref[0]                                 # [P, H]: exp(dt A)
    dx_t = dx_ref[0]                                 # [P, H]: dt x
    b = b_ref[0]                                     # [1, N]
    c = c_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, da_t.shape, 1)

    @pl.when(j == 0)
    def _():
        y_ref[0] = jnp.zeros_like(y_ref[0])

    acc = y_ref[0]
    for k in range(HEAD_BLOCK):
        sel = lane == j * HEAD_BLOCK + k
        da = jnp.sum(jnp.where(sel, da_t, 0.0), axis=-1, keepdims=True)
        dx = jnp.sum(jnp.where(sel, dx_t, 0.0), axis=-1, keepdims=True)
        s = s_ref[0, k].astype(jnp.float32) * da + dx * b     # [P, N]
        s_out_ref[0, k] = s.astype(s_out_ref.dtype)
        y = jnp.sum(s * c, axis=-1, keepdims=True)            # [P, 1]
        acc = jnp.where(sel, y, acc)
    y_ref[0] = acc


def state_update_kernel(ssm, slots, x, dt, a, b, c, interpret=False):
    """`ops.ssm.ssm_state_update`'s contract; called inside its jit so that
    a device trace names the call after it."""
    R, H, P = x.shape
    N = ssm.shape[-1]
    G = b.shape[1]
    per_group = H // G // HEAD_BLOCK                 # head blocks a group
    da_t = jnp.broadcast_to(jnp.exp(dt * a)[:, None, :], (R, P, H))
    dx_t = (dt[..., None] * x).transpose(0, 2, 1)    # [R, P, H]
    b3 = b.astype(jnp.float32).reshape(R * G, 1, N)
    c3 = c.astype(jnp.float32).reshape(R * G, 1, N)
    row = pl.BlockSpec((1, P, H), lambda r, j, sl: (r, 0, 0))
    group = pl.BlockSpec((1, 1, N),
                         lambda r, j, sl: (r * G + j // per_group, 0, 0))
    state = pl.BlockSpec((1, HEAD_BLOCK, P, N),
                         lambda r, j, sl: (sl[r], j, 0, 0))
    y_t, ssm = pl.pallas_call(
        _update_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, H // HEAD_BLOCK),
            in_specs=[row, row, group, group, state],
            out_specs=(row, state)),
        # The leaf is stepped where it lies (operand 5, counting the slots).
        input_output_aliases={5: 1},
        interpret=interpret,
    )(slots.astype(jnp.int32), da_t.astype(jnp.float32),
      dx_t.astype(jnp.float32), b3, c3, ssm)
    return y_t.transpose(0, 2, 1), ssm


# ---------------------------------------------------------------------------
# The chunked scan of a prefill chunk

_HI = jax.lax.Precision.HIGHEST


def chunk_scan_geometry_ok(heads: int, head_dim: int, d_state: int,
                           groups: int, chunk: int) -> bool:
    """Can the scan kernel take this mixer: whole (8, 128) tiles of the
    scan chunk and the state, a head block of whole lanes (8 heads of 64 are
    512), a head block inside one group."""
    return (state_update_geometry_ok(heads, head_dim, d_state, groups)
            and head_dim % 64 == 0 and chunk % 128 == 0)


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
