"""The grouped expert kernel as it stood before PR 54: the three (two) weight
matrices handed to the grid's own two-buffer pipeline as `BlockSpec`s.  Kept
verbatim as the oracle `tests/test_moe_ring.py` holds the fetch ring to bit
for bit, and as what `tools/expert_kernel_chip_check.py` times as (a) and
(b).  Not imported by the package."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.moe_grouped import (
    DEFAULT_BLOCK_ROWS, _DEFAULT_SCOPED_VMEM, auto_block_f,
    moe_grouped_geometry_ok)


def _ffn_kernel(n_blocks_f: int, quant: bool,
                # scalar prefetch
                te_ref, live_ref,
                # inputs
                x_ref, wg_ref, wu_ref, wd_ref, *rest):
    # Tiles past the last group hold no row and nobody gathers theirs:
    # skip their matmuls (their weight block index repeats the last live
    # tile's, F block and all, so they cost no DMA either).
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        _ffn_tile(n_blocks_f, quant, f, x_ref, wg_ref, wu_ref, wd_ref,
                  *rest)


def _ffn_tile(n_blocks_f: int, quant: bool, f,
              x_ref, wg_ref, wu_ref, wd_ref, *rest):
    if quant:
        sg_ref, su_ref, sd_ref, o_ref, acc = rest
    else:
        o_ref, acc = rest
        sg_ref = su_ref = sd_ref = None
    x = x_ref[...]                                   # [bm, H]

    def load_w(ref, s_ref):
        w = ref[0]                                   # [H, bf] / [bf, H]
        if not quant:
            return w
        # Dequant on the VMEM-resident block, reproducing
        # dequantize_moe_params element-for-element: f32 multiply by the
        # per-output-column scale, then cast to the activation dtype.
        return (w.astype(jnp.float32) * s_ref[...]).astype(x.dtype)

    wg = load_w(wg_ref, sg_ref)                      # [H, bf]
    wu = load_w(wu_ref, su_ref)                      # [H, bf]
    wd = load_w(wd_ref, sd_ref)                      # [bf, H]
    # f32 MXU accumulation then cast back to the activation dtype, as
    # XLA does inside moe_dense's einsums (see the numerics contract).
    h = jnp.dot(x, wg, preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32).astype(x.dtype)
    # Elementwise work in f32 with the activation dtype's rounding after
    # each operation, as XLA computes a bf16 `silu(h) * u` (v5e has no
    # bf16 vector unit, and Mosaic does not lower a bf16 logistic for it).
    f32 = jnp.float32
    gate = jax.nn.silu(h.astype(f32)).astype(x.dtype)
    act = (gate.astype(f32) * u.astype(f32)).astype(x.dtype)   # [bm, bf]
    part = jax.lax.dot_general(
        act, wd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bm, H] f32

    @pl.when(f == 0)
    def _():
        acc[...] = part

    @pl.when(f > 0)
    def _():
        acc[...] += part

    @pl.when(f == n_blocks_f - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "block_f", "interpret"))
def grouped_expert_ffn(
    x_pad: jax.Array,        # [S_pad, H] expert-sorted, group-padded rows
    tile_expert: jax.Array,  # [S_pad // block_rows] int32 tile→expert map
    w_gate: jax.Array,       # [E, H, F] (bf16/f32, or int8 with scales)
    w_up: jax.Array,         # [E, H, F]
    w_down: jax.Array,       # [E, F, H]
    *,
    w_gate_scale: Optional[jax.Array] = None,  # [E, F] f32 (int8 weights)
    w_up_scale: Optional[jax.Array] = None,    # [E, F] f32
    w_down_scale: Optional[jax.Array] = None,  # [E, H] f32
    live_tiles: Optional[jax.Array] = None,    # [1] int32: tiles with rows
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_f: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged grouped expert FFN: row tile t runs expert
    `tile_expert[t]`'s SwiGLU MLP.  Returns [S_pad, H] in x's dtype.
    Padding rows are all-zero by construction (ops/moe.py) and compute
    harmless zeros that the caller never gathers.  `live_tiles`: how many
    leading tiles hold a real row; the rest are skipped and their output
    rows are undefined (None: every tile runs)."""
    S_pad, H = x_pad.shape
    E, _, F = w_gate.shape
    quant = w_gate_scale is not None
    if quant != (w_up_scale is not None) or quant != (
            w_down_scale is not None):
        raise ValueError("pass all three weight scales or none")
    if quant and w_gate.dtype != jnp.int8:
        raise ValueError(f"scales imply int8 weights; got {w_gate.dtype}")
    if S_pad % block_rows:
        raise ValueError(
            f"S_pad={S_pad} must be a block_rows={block_rows} multiple")
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    if not interpret and not moe_grouped_geometry_ok(
            H, F, itemsize, block_rows):
        raise ValueError(
            f"grouped MoE kernel needs H % 128 == 0, F % 128 == 0 and "
            f"block_rows % 8 == 0; got H={H}, F={F}, "
            f"block_rows={block_rows} (use moe_mode='dense' for this "
            "geometry)")
    if block_f is None:
        block_f = min(F, auto_block_f(H, F, itemsize)) if not interpret \
            else F
    if F % block_f:
        raise ValueError(f"F={F} must divide by block_f={block_f}")
    nf = F // block_f
    T = S_pad // block_rows
    if live_tiles is None:
        live_tiles = jnp.full((1,), T, jnp.int32)

    # Index maps see the scalar-prefetch tile_expert array: consecutive
    # tiles of one expert map to the SAME weight block, so the pipeline
    # skips the refetch — the "stream each expert's weights exactly
    # once" property in the decode regime.  With more than one F block a
    # skipped tile must also hold its F index still (the last live tile's
    # last block): walking f it would fetch its expert's three matrices
    # again for nothing, 52 of 56 tiles of a one-row step at 64 experts.
    if nf == 1:
        def fb(t, f, lv):
            return f
    else:
        def fb(t, f, lv):
            return jnp.where(t < lv[0], f, nf - 1)
    in_specs = [
        pl.BlockSpec((block_rows, H), lambda t, f, te, lv: (t, 0)),
        pl.BlockSpec((1, H, block_f),
                     lambda t, f, te, lv: (te[t], 0, fb(t, f, lv))),
        pl.BlockSpec((1, H, block_f),
                     lambda t, f, te, lv: (te[t], 0, fb(t, f, lv))),
        pl.BlockSpec((1, block_f, H),
                     lambda t, f, te, lv: (te[t], fb(t, f, lv), 0)),
    ]
    inputs = [tile_expert, live_tiles, x_pad, w_gate, w_up, w_down]
    if quant:
        in_specs += [
            pl.BlockSpec((1, block_f),
                         lambda t, f, te, lv: (te[t], fb(t, f, lv))),
            pl.BlockSpec((1, block_f),
                         lambda t, f, te, lv: (te[t], fb(t, f, lv))),
            pl.BlockSpec((1, H), lambda t, f, te, lv: (te[t], 0)),
        ]
        inputs += [w_gate_scale, w_up_scale, w_down_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, H),
                               lambda t, f, te, lv: (t, 0)),
        scratch_shapes=[pltpu.VMEM((block_rows, H), jnp.float32)],
    )
    # Double-buffered weight blocks, the row tile in and out, the f32
    # accumulator and the [bm, bf] intermediates.
    need = (2 * 3 * H * block_f * itemsize
            + 4 * block_rows * H * x_pad.dtype.itemsize
            + 4 * block_rows * (H + 3 * block_f))
    params = {}
    if not interpret and need + (4 << 20) > _DEFAULT_SCOPED_VMEM:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=need + (8 << 20))
    return pl.pallas_call(
        functools.partial(_ffn_kernel, nf, quant),
        out_shape=jax.ShapeDtypeStruct((S_pad, H), x_pad.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="moe_grouped_ffn",
        **params,
    )(*inputs)


# -- the ungated form: relu(x W_up)^2 W_down -------------------------------

def _ffn2_kernel(n_blocks_f: int, te_ref, live_ref, x_ref, wu_ref, wd_ref,
                 o_ref, acc):
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        x = x_ref[...]                               # [bm, H]
        # f32 MXU accumulation then the activation dtype's rounding, as
        # XLA's einsums do inside the dense oracle; the square in f32 (v5e
        # has no bf16 vector unit).
        h = jnp.dot(x, wu_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        r = jnp.maximum(h.astype(jnp.float32), 0.0)
        act = (r * r).astype(x.dtype)                # [bm, bf]
        part = jax.lax.dot_general(
            act, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bm, H] f32

        @pl.when(f == 0)
        def _():
            acc[...] = part

        @pl.when(f > 0)
        def _():
            acc[...] += part

        @pl.when(f == n_blocks_f - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "block_f", "interpret"))
def grouped_expert_ffn_relu2(
    x_pad: jax.Array,        # [S_pad, H] expert-sorted, group-padded rows
    tile_expert: jax.Array,  # [S_pad // block_rows] int32 tile→expert map
    w_up: jax.Array,         # [E, H, F]
    w_down: jax.Array,       # [E, F, H]
    *,
    live_tiles: Optional[jax.Array] = None,    # [1] int32: tiles with rows
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_f: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """`grouped_expert_ffn` for experts of two matrices and no gate: row
    tile t runs `relu(x W_up)^2 W_down` of expert `tile_expert[t]`.  The
    same grid, tile→expert map and skipping of tiles past `live_tiles`; a
    jit and a kernel name of its own, so that a device trace tells the two
    forms apart."""
    S_pad, H = x_pad.shape
    E, _, F = w_up.shape
    if S_pad % block_rows:
        raise ValueError(
            f"S_pad={S_pad} must be a block_rows={block_rows} multiple")
    itemsize = jnp.dtype(w_up.dtype).itemsize
    if not interpret and not moe_grouped_geometry_ok(
            H, F, itemsize, block_rows):
        raise ValueError(
            f"grouped MoE kernel needs H % 128 == 0, F % 128 == 0 and "
            f"block_rows % 8 == 0; got H={H}, F={F}, "
            f"block_rows={block_rows} (use moe_mode='dense' for this "
            "geometry)")
    if block_f is None:
        block_f = F if interpret else min(
            F, auto_block_f(H, F, itemsize, matrices=2))
    if F % block_f:
        raise ValueError(f"F={F} must divide by block_f={block_f}")
    nf = F // block_f
    T = S_pad // block_rows
    if live_tiles is None:
        live_tiles = jnp.full((1,), T, jnp.int32)

    def fb(t, f, lv):        # a skipped tile holds its F index still
        return f if nf == 1 else jnp.where(t < lv[0], f, nf - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nf),
        in_specs=[
            pl.BlockSpec((block_rows, H), lambda t, f, te, lv: (t, 0)),
            pl.BlockSpec((1, H, block_f),
                         lambda t, f, te, lv: (te[t], 0, fb(t, f, lv))),
            pl.BlockSpec((1, block_f, H),
                         lambda t, f, te, lv: (te[t], fb(t, f, lv), 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, H),
                               lambda t, f, te, lv: (t, 0)),
        scratch_shapes=[pltpu.VMEM((block_rows, H), jnp.float32)],
    )
    need = (2 * 2 * H * block_f * itemsize
            + 4 * block_rows * H * x_pad.dtype.itemsize
            + 4 * block_rows * (H + 2 * block_f))
    params = {}
    if not interpret and need + (4 << 20) > _DEFAULT_SCOPED_VMEM:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=need + (8 << 20))
    return pl.pallas_call(
        functools.partial(_ffn2_kernel, nf),
        out_shape=jax.ShapeDtypeStruct((S_pad, H), x_pad.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="moe_grouped_ffn_relu2",
        **params,
    )(tile_expert, live_tiles, x_pad, w_up, w_down)
