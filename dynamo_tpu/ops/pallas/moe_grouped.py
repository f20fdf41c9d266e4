"""Grouped MoE expert-FFN Pallas kernel (the MoE fast-decode compute).

`moe_dense` (ops/moe.py) runs EVERY expert over EVERY token and
zero-gates the non-selected ones — E/k× the minimal FLOPs and, worse for
decode, E/k× the minimal HBM weight traffic (decode MoE is weight-
bandwidth-bound exactly like decode attention is KV-bandwidth-bound).
This kernel computes only the selected (token, expert) assignments:

- the caller sorts assignments by expert on device and pads each expert's
  group to a `block_rows` multiple (ops/moe.py moe_grouped — sort /
  scatter / combine live there; this module is just the ragged GEMM);
- the grid walks row tiles; the expert weights stay in HBM and reach VMEM
  through a fetch ring the kernel drives itself (`_ring_kernel`), in the
  order a scalar-prefetch `tile_expert` map gives: consecutive tiles of
  one expert REUSE its VMEM-resident block, so in the decode regime
  (≤ block_rows assignments per expert) each active expert's weights
  stream HBM→VMEM exactly once, experts with no assigned tokens are never
  read, and while one expert's tiles compute the next experts' weights
  are already in flight;
- the intermediate dim F is blocked (`block_f`) with an f32 VMEM
  accumulator so serving-size experts (H×F ≫ VMEM) still fit: a ring slot
  holds one [H, bf] gate/up slice and one [bf, H] down slice, beside the
  [bm, H] accumulator.

int8-weight variant (mirrors the PR 6 KV-cache discipline): expert
weights quantize per-expert-per-output-column (`quantize_moe_params`),
the int8 blocks and their f32 scale slivers DMA together, and
dequantization happens on the VMEM-resident block — HBM weight traffic
halves vs bf16.  Dequant reproduces `dequantize_moe_params` numerics
element-for-element, so the grouped int8 output is byte-identical to
`moe_dense` run on the host-dequantized weights.

Numerics contract: each matmul accumulates in f32 and casts back to the
activation dtype (`preferred_element_type` then `.astype`), mirroring
what XLA's einsum does inside `moe_dense`.  The kernel is held to the
dense oracle by a tolerance, not by bytes: the compiler may keep the
activation's product in f32 on its way into the down projection where
the oracle's einsums round it to the activation dtype in between, one
rounding of the activation dtype a term (float32: a few ulp; bf16: 2**-8
relative).  An earlier revision pinned that rounding with
`jax.lax.optimization_barrier`, which Mosaic cannot lower for v5e: the
kernel then never ran where it was meant to.  `tests/test_moe.py`
states the tolerances.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row-tile default: big enough that one MXU pass amortises the weight
# DMA, small enough that a decode batch (N*k assignments over E experts)
# doesn't drown in per-expert padding.
DEFAULT_BLOCK_ROWS = 64
# Row tiles `auto_block_rows` chooses from.  8 is the f32 sublane quantum
# (a bf16 tile of 8 rows is half a packed vreg; Mosaic pads it).
_BLOCK_ROW_LADDER = (8, 16, 32, 64, 128)


def auto_block_rows(assignments: int, experts: int) -> int:
    """Row tile for `assignments` (token, expert) pairs over `experts`
    held experts: the smallest tile of the ladder that holds twice the
    mean group.  The packed buffer (`packed_rows`) is `assignments +
    min(assignments, experts) * (tile - 1)` rows at worst, so a tile far
    over the mean group is mostly padding (at
    128 experts a decode step's 32-256 assignments are one to two rows an
    expert), and one at or under it spills the larger groups into a second
    tile.  Measured on a v5e at H 2048, F 768, E 128, k 8 (PERF.md section
    6, PR 27; ms a call at tiles 8 / 16 / 32 / 64 / 128): 8 tokens 0.74 /
    0.75 / 0.75 / 0.75 / 0.86; 32 tokens 1.54 / 1.56 / 1.56 / 1.57 / 1.68;
    128 tokens 1.96 / 1.85 / 1.87 / 1.91 / 2.04; 512 tokens 3.16 / 2.72 /
    2.47 / 2.49 / 2.69: the rule picks 8, 8, 16 and 64."""
    mean = -(-assignments // max(experts, 1))
    for rows in _BLOCK_ROW_LADDER:
        if rows >= 2 * mean:
            return rows
    return _BLOCK_ROW_LADDER[-1]


def grouped_block_rows(assignments: int, experts: int, held: int) -> int:
    """Row tile `ops/moe.py::moe_grouped` takes where it is given none:
    `auto_block_rows` over the assignments the `held` of the model's
    `experts` experts can expect (all of them where all are held; a quarter
    where a chip holds a quarter: the router spreads over all, and a tile
    sized for every assignment landing here would be mostly padding)."""
    return auto_block_rows(assignments * held // max(experts, 1), held)


def packed_rows(assignments: int, experts: int, block_rows: int) -> int:
    """Rows of the packed buffer `ops/moe.py::moe_grouped` hands the
    kernel: the largest padded total any routing of `assignments` (token,
    expert) pairs over `experts` groups can reach, each group rounded up
    to `block_rows`.  At most `min(assignments, experts)` groups hold a
    row and each wastes at most `block_rows - 1`; every group's padded
    length, and so their sum, is a multiple of `block_rows`, hence the
    floor.  With as many assignments as experts or more this is `experts`
    ragged groups' worth; with fewer (a decode step of a few rows) a tile
    an assignment: one row over 64 experts at 4 experts a token packs 4
    tiles, not 56, and the kernel's grid is that much shorter (PERF.md
    section 6, PR 38).  A static shape: the engine tallies it on the host
    (`dynamo_worker_moe_packed_rows_total`)."""
    groups = min(assignments, experts)
    return max(block_rows, (assignments + groups * (block_rows - 1))
               // block_rows * block_rows)


# Budget for TWO weight blocks (gate + up [H, bf] + down [bf, H]), which is
# what sizes the F block (`auto_block_f`); the fetch ring holds
# `ring_depth` of them.  A chip has 128 MiB of VMEM and the compiler's
# default scoped limit is 16 MiB: a working set over `_DEFAULT_SCOPED_VMEM`
# raises the kernel's own limit (`vmem_limit_bytes`) to what it needs.
# The budget is sized so that an expert of a few million parameters rides
# ONE F block: with more than one, every grid step wants another block and
# consecutive tiles of one expert re-fetch its weights.
_WEIGHT_BUDGET = 24 * 1024 * 1024
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_TARGET_BLOCK_F = 2048


def moe_grouped_geometry_ok(hidden: int, intermediate: int,
                            itemsize: int = 2,
                            block_rows: int = DEFAULT_BLOCK_ROWS) -> bool:
    """THE Mosaic eligibility rule for the grouped kernel, read by
    `resolve_moe_mode` (engine moe_mode auto;
    tests/test_moe.py::test_resolve_moe_mode_ladder) — same discipline
    as `mosaic_geometry_ok` for the attention kernels.  Lane dims (H for
    the row tiles and the down-projection, F for gate/up) must be
    128-aligned and the row tile 8-aligned; the smallest F block must
    fit the weight budget."""
    return (hidden % 128 == 0 and intermediate % 128 == 0
            and block_rows % 8 == 0
            and 2 * 3 * hidden * min(intermediate, 128) * itemsize
            <= _WEIGHT_BUDGET)


def auto_block_f(hidden: int, intermediate: int, itemsize: int = 2,
                 matrices: int = 3) -> int:
    """F-block sizing: the largest divisor of F that is a multiple of the
    128 lane quantum, at most `_TARGET_BLOCK_F` (fewer accumulator
    passes) and of which two gate+up+down blocks fit the weight budget;
    the lane quantum itself where none does.  `matrices` 2
    (the ungated form, up and down alone): the whole of F where it fits the
    budget, so that an expert's weights are one block and stream once."""
    if matrices == 2 and intermediate % 128 == 0 \
            and 2 * 2 * hidden * intermediate * itemsize <= _WEIGHT_BUDGET:
        return intermediate
    best = 128
    for bf in range(128, min(intermediate, _TARGET_BLOCK_F) + 1, 128):
        if intermediate % bf == 0 \
                and 2 * matrices * hidden * bf * itemsize <= _WEIGHT_BUDGET:
            best = bf
    return best


def _accumulate(n_blocks_f: int, f, part, o_ref, acc):
    """A tile's partial sum over F block `f` into the f32 accumulator; the
    last block writes the tile out."""
    @pl.when(f == 0)
    def _():
        acc[...] = part

    @pl.when(f > 0)
    def _():
        acc[...] += part

    @pl.when(f == n_blocks_f - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


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
    _accumulate(n_blocks_f, f, part, o_ref, acc)


def _ffn2_tile(n_blocks_f: int, f, x_ref, wu_ref, wd_ref, o_ref, acc):
    """The ungated form: relu(x W_up)^2 W_down."""
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
    _accumulate(n_blocks_f, f, part, o_ref, acc)


# -- the fetch ring: how a live tile's weight blocks reach VMEM ------------

def ring_depth(block_bytes: int) -> int:
    """Slots of the fetch ring for weight blocks of `block_bytes` a fetch
    (all of an (expert, F block)'s matrices together): while one block is
    computed on, `depth - 1` later ones are in flight.  Three, where they
    fit half the chip's 128 MiB of VMEM (every served shape: 9.4-12.6 MB a
    block), else the two a pipeline needs.  On a v5e depths 2, 3 and 4 read
    the same time at every served shape to a part in a thousand
    (`tools/expert_kernel_chip_check.py`; PERF.md section 6, PR 54): the
    third slot is room for a landed block while a many-tile expert computes,
    not a measured gain."""
    return 3 if 3 * block_bytes <= 64 << 20 else 2


def _ring_kernel(body, nf: int, block_f: int, depth: int, blocked: tuple,
                 # scalar prefetch
                 te_ref, live_ref,
                 # the row tile, then the weight operands, left in HBM
                 x_ref, *rest):
    """Grid step (t, f) of the grouped FFN.  `body(f, x_ref, *blocks, o_ref,
    acc)` is the tile's arithmetic; its weight blocks come out of a ring of
    `depth` VMEM slots that this kernel fills itself, in the order the grid
    will want them: a step that opens a new (expert, F block) first issues
    the fetch `depth - 1` blocks ahead into the slot the block before its
    own has just left, and only then waits for its own.  So a weight DMA is
    outstanding from the first live step to the last one's wait, also while
    an expert's second and third tile compute (they fetch nothing, and the
    grid's own pipeline, which looks one step ahead, had nothing in flight
    then).

    Which steps open a block is the grid pipeline's rule: with one F block
    a tile whose expert differs from the tile before it; with more, every
    step (f moved); a tile past `live_tiles` opens nothing, fetches nothing
    and computes nothing.  The ring finds the next opening step by walking
    `tile_expert` on the scalar core: no list is reckoned outside."""
    n = len(blocked)
    hbm, (o_ref, acc), bufs = rest[:n], rest[n:n + 2], rest[n + 2:2 * n + 2]
    sem, state = rest[2 * n + 2:]
    HEAD, ISSUED, OPENED = 0, 1, 2    # next step to look at; fetches begun
    t, f = pl.program_id(0), pl.program_id(1)
    tiles = te_ref.shape[0]
    last_live = live_ref[0] * nf      # steps [0, last_live) are live

    def opens(step):
        """Whether live step `step` (`t` where there is one F block) opens
        a block of its own."""
        at = jnp.minimum(step, tiles - 1)
        return jnp.logical_or(
            step == 0, te_ref[at] != te_ref[jnp.maximum(at - 1, 0)])

    def copies(step, slot):
        """The DMAs of the block that live step `step` opens, into `slot`."""
        expert = te_ref[step if nf == 1 else step // nf]
        out = []
        for i, (w, buf, axis) in enumerate(zip(hbm, bufs, blocked)):
            at = [pl.ds(expert, 1), slice(None), slice(None)]
            if nf > 1 and axis is not None:
                at[axis] = pl.ds(
                    pl.multiple_of(jax.lax.rem(step, nf) * block_f, block_f),
                    block_f)
            out.append(pltpu.make_async_copy(
                w.at[tuple(at)], buf.at[pl.ds(slot, 1)], sem.at[slot, i]))
        return out

    def issue():
        """Begin the next block's fetch, if a live step is left to open
        one."""
        head = state[HEAD]
        if nf == 1:
            head = jax.lax.while_loop(
                lambda s: jnp.logical_and(s < last_live,
                                          jnp.logical_not(opens(s))),
                lambda s: s + 1, head)

        @pl.when(head < last_live)
        def _():
            for dma in copies(head, jax.lax.rem(state[ISSUED], depth)):
                dma.start()
            state[ISSUED] += 1
        state[HEAD] = head + 1

    step = t * nf + f

    @pl.when(step == 0)
    def _():
        for i in range(3):
            state[i] = 0
        for _ in range(depth - 1):
            issue()

    @pl.when(t < live_ref[0])
    def _():
        @pl.when(True if nf > 1 else opens(t))
        def _():
            issue()
            for dma in copies(step, jax.lax.rem(state[OPENED], depth)):
                dma.wait()
            state[OPENED] += 1
        slot = jax.lax.rem(state[OPENED] - 1, depth)
        # A matrix block as the body reads it, [1, rows, cols] (it takes
        # `ref[0]`); a scale sliver [1, cols].
        body(f, x_ref, *(buf.at[slot] if buf.shape[1] == 1
                         else buf.at[pl.ds(slot, 1)] for buf in bufs),
             o_ref, acc)


def _grouped_call(name: str, make_body, x_pad, tile_expert, live_tiles,
                  operands, *, block_rows: int, block_f: Optional[int],
                  interpret: bool) -> jax.Array:
    """The grid, the ring and the checks both forms share.  `operands`:
    (array [E, rows, cols], the axis F lies on or None) for each weight
    matrix and scale sliver (rows 1), in the order `make_body(nf)`'s tile
    takes them; the matrices first."""
    S_pad, H = x_pad.shape
    mats = [w for w, _ in operands if w.shape[1] > 1]
    F = mats[0].shape[operands[0][1]]
    if S_pad % block_rows:
        raise ValueError(
            f"S_pad={S_pad} must be a block_rows={block_rows} multiple")
    itemsize = jnp.dtype(mats[0].dtype).itemsize
    if not interpret and not moe_grouped_geometry_ok(
            H, F, itemsize, block_rows):
        raise ValueError(
            f"grouped MoE kernel needs H % 128 == 0, F % 128 == 0 and "
            f"block_rows % 8 == 0; got H={H}, F={F}, "
            f"block_rows={block_rows} (use moe_mode='dense' for this "
            "geometry)")
    if block_f is None:
        block_f = F if interpret else min(
            F, auto_block_f(H, F, itemsize, matrices=len(mats)))
    if F % block_f:
        raise ValueError(f"F={F} must divide by block_f={block_f}")
    nf = F // block_f
    T = S_pad // block_rows
    if live_tiles is None:
        live_tiles = jnp.full((1,), T, jnp.int32)

    # One slot of an operand's ring: the array less its E axis, F blocked.
    blocks = [tuple(block_f if a == axis else d
                    for a, d in enumerate(w.shape))[1:]
              for w, axis in operands]
    block_bytes = sum(math.prod(b) * w.dtype.itemsize
                      for b, (w, _) in zip(blocks, operands))
    depth = ring_depth(block_bytes)
    # A tile past the live ones names the last live tile's rows: nothing
    # moves in or out for it (its own output rows are undefined anyway).
    row_tile = pl.BlockSpec(
        (block_rows, H),
        lambda t, f, te, lv: (jnp.minimum(t, jnp.maximum(lv[0] - 1, 0)), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nf),
        in_specs=[row_tile] + [pl.BlockSpec(memory_space=pl.ANY)
                               for _ in operands],    # weights stay in HBM
        out_specs=row_tile,
        scratch_shapes=(
            [pltpu.VMEM((block_rows, H), jnp.float32)]
            + [pltpu.VMEM((depth,) + b, w.dtype)
               for b, (w, _) in zip(blocks, operands)]
            + [pltpu.SemaphoreType.DMA((depth, len(operands))),
               pltpu.SMEM((3,), jnp.int32)]),
    )
    # The ring's slots, the row tile in and out (two buffers each), the f32
    # accumulator and the [bm, bf] intermediates.
    need = (depth * block_bytes
            + 4 * block_rows * H * x_pad.dtype.itemsize
            + 4 * block_rows * (H + len(mats) * block_f))
    params = {}
    if not interpret:
        # The ring runs through the grid in order: no step may move.
        limit = {} if need + (4 << 20) <= _DEFAULT_SCOPED_VMEM else {
            "vmem_limit_bytes": need + (8 << 20)}
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), **limit)
    return pl.pallas_call(
        functools.partial(_ring_kernel, make_body(nf), nf, block_f, depth,
                          tuple(axis for _, axis in operands)),
        out_shape=jax.ShapeDtypeStruct((S_pad, H), x_pad.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
        **params,
    )(tile_expert, live_tiles, x_pad, *(w for w, _ in operands))


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
    quant = w_gate_scale is not None
    if quant != (w_up_scale is not None) or quant != (
            w_down_scale is not None):
        raise ValueError("pass all three weight scales or none")
    if quant and w_gate.dtype != jnp.int8:
        raise ValueError(f"scales imply int8 weights; got {w_gate.dtype}")
    operands = [(w_gate, 2), (w_up, 2), (w_down, 1)]
    if quant:
        # The scale slivers ride the ring beside their int8 blocks, as
        # [E, 1, F]: a fetch takes expert e's off an axis no tiling covers.
        operands += [(w_gate_scale[:, None], 2), (w_up_scale[:, None], 2),
                     (w_down_scale[:, None], None)]
    return _grouped_call(
        "moe_grouped_ffn",
        lambda nf: functools.partial(_ffn_tile, nf, quant),
        x_pad, tile_expert, live_tiles, operands, block_rows=block_rows,
        block_f=block_f, interpret=interpret)


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
    same grid, ring and skipping of tiles past `live_tiles`; a jit and a
    kernel name of its own, so that a device trace tells the two forms
    apart."""
    return _grouped_call(
        "moe_grouped_ffn_relu2",
        lambda nf: functools.partial(_ffn2_tile, nf),
        x_pad, tile_expert, live_tiles, [(w_up, 2), (w_down, 1)],
        block_rows=block_rows, block_f=block_f, interpret=interpret)


# -- int8 expert weights (static params-pytree branch, like kv_quant) ----

def moe_params_quantized(p_moe: dict) -> bool:
    """Static branch predicate: quantized expert params carry sibling
    `*_scale` entries (the same pytree-shape discipline the int8 KV
    cache uses — the compiled program branches on structure, never on
    values)."""
    return "w_gate_scale" in p_moe


def quantize_moe_params(p_moe: dict) -> dict:
    """int8-quantize the expert weights per-expert-per-output-column
    (absmax over the contraction dim), keeping the router full-precision
    — routing decides token placement and is tiny.  Returns a new pytree
    with int8 `w_gate`/`w_up`/`w_down` plus f32 `*_scale` siblings."""
    out = {"router": p_moe["router"]}
    for name in ("w_gate", "w_up", "w_down"):
        w = p_moe[name].astype(jnp.float32)          # [E, in, out]
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=1) / 127.0, 1e-8)
        out[name] = jnp.round(w / scale[:, None, :]).astype(jnp.int8)
        out[name + "_scale"] = scale                 # [E, out]
    return out


def dequantize_moe_params(p_moe: dict, dtype) -> dict:
    """Host-side inverse (the oracle path): reproduces the kernel's
    in-VMEM dequant element-for-element, so `moe_dense` on the result is
    the byte-exact reference for the grouped int8 output."""
    out = {"router": p_moe["router"]}
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = (p_moe[name].astype(jnp.float32)
                     * p_moe[name + "_scale"][:, None, :]).astype(dtype)
    return out
