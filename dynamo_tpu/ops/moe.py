"""Mixture-of-Experts compute paths: the dense | grouped | dispatch ladder.

The reference orchestrates wide-EP engines (SGLang wide-EP container,
`container/Dockerfile.sglang-wideep`; expert-distribution telemetry
`components/backends/sglang/src/dynamo/sglang/common/base_handlers.py:
40-62`) but owns no MoE math.  Here the engine is ours, so EP is a
first-class compute path (SURVEY §2.5 row "EP / MoE"):

- `moe_dense` — every device runs ALL tokens through its local experts and
  zero-gates the non-selected ones.  Always exact; the CPU-test oracle.
  Costs E/k× the minimal FLOPs *and weight bytes* (VERDICT r2 weak #4) —
  that waste is precisely what the other two rungs remove.
- `moe_grouped` — the single-chip/per-shard fast path: assignments are
  sorted by expert on device, each expert's group padded to a row-tile
  multiple, and ONE ragged grouped GEMM (ops/pallas/moe_grouped.py)
  runs only the selected (token, expert) work, streaming each active
  expert's weights HBM→VMEM once in the decode regime.  bf16/f32
  weights or the int8-weight pytree (`quantize_moe_params` — static
  structure branch, same discipline as kv_quant).
- `moe_dispatch` — Switch-Transformer-style token dispatch with a STATIC
  per-expert capacity (XLA needs fixed shapes): tokens are scattered into
  per-expert buffers, `jax.lax.all_to_all` moves buffers to the shard
  owning each expert over the `ep` mesh axis, local experts run one
  batched einsum, and a second all_to_all brings outputs home for the
  gate-weighted combine.  Under ep × tp meshes each expert's MLP is
  additionally tp-sharded on the intermediate dim (`tp_axis`): gate/up
  project into a local F/tp slice, the down projection partial-sums, and
  one psum over tp completes it — tokens and routing stay replicated
  across tp, the all_to_all stays an ep-only collective.
- Capacity semantics: `capacity` = tokens per expert per source shard.
  With `capacity >= tokens_per_shard` routing is EXACT (an expert can
  receive at most every local token once — top-k choices are distinct
  experts).  Smaller capacities drop overflow assignments (their gate
  mass is lost, Switch convention): the throughput/exactness knob is the
  deployment's (`ModelConfig.moe_capacity`), not the kernel's — serving
  defaults to exact, and drops are COUNTED, never silent.

The chip's share of a layer (`ModelConfig.experts_held` = (first, count)):
`moe_dense` and `moe_grouped` route over ALL of the model's experts, keep the
assignments whose expert is held here (the weights are `[count, ...]`) and
return the part of the result those experts give; what the absent experts
would have added is left out (another chip's part of the sum; no code stands
in for it).  The stats vector stays `[E+1]` over all the router chose.
`x_expert`: the rows the experts multiply where they are not the router's
(experts that work in a latent space); the result then has their width.  A
params dict without `w_gate` is the ungated form, `relu(x W_up)^2 W_down`.

Expert-load telemetry: every path returns an int32 stats vector of
length E+1 — per-expert assignment counts plus a dropped-assignments
tail slot (always 0 for the exact paths) — so the worker can publish
the expert distribution the reference exposes AND an honest drop
counter when a bounded capacity is configured.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.runtime.contracts import hot_path

from dynamo_tpu.models.config import ModelConfig

Params = dict


def router_topk(cfg: ModelConfig, p_moe: Params, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing over all of the model's experts.  x: [N, H] →
    (expert_ids [N, k], gates [N, k]).

    The gates are the softmax over the selected experts' logits (the
    Mixtral convention), which equals the full softmax renormalised over
    the chosen: `norm_topk_prob`, the only setting a configuration states
    so far (`ModelConfig` refuses the other).

    `router_scoring` "sigmoid" (the DeepSeek-V3 `noaux_tc` router): scores
    s = sigmoid(x W) in float32; the experts with the largest s + b are
    chosen (`router_bias`, learned, moves the choice alone); the gates are
    the chosen s renormalised to sum 1, times `routed_scaling_factor`."""
    k = cfg.num_experts_per_token
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(jnp.dot(
            x, p_moe["router"], preferred_element_type=jnp.float32))
        # (A model whose router has no learned bias chooses by s alone.)
        biased = (scores + p_moe["router_bias"] if "router_bias" in p_moe
                  else scores)
        _, top_idx = jax.lax.top_k(biased, k)
        chosen = jnp.take_along_axis(scores, top_idx, axis=-1)
        gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        return top_idx, (gates * cfg.routed_scaling_factor).astype(x.dtype)
    logits = (x @ p_moe["router"]).astype(jnp.float32)       # [N, E]
    top_vals, top_idx = jax.lax.top_k(logits, k)             # [N, k]
    gates = jax.nn.softmax(top_vals, axis=-1)                # renormalised
    return top_idx, gates.astype(x.dtype)


def expert_ffn(p_moe: Params, h: jax.Array) -> jax.Array:
    """Batched expert MLPs: h [E, C, H] with weights [E, H, F]."""
    up = jax.nn.silu(jnp.einsum("ech,ehf->ecf", h, p_moe["w_gate"]))
    up = up * jnp.einsum("ech,ehf->ecf", h, p_moe["w_up"])
    return jnp.einsum("ecf,efh->ech", up, p_moe["w_down"])


def _with_drop_tail(load: jax.Array, dropped=None) -> jax.Array:
    """[E] per-expert counts → [E+1] stats vector with the dropped-
    assignments tail slot (0 for exact paths)."""
    tail = (jnp.zeros((1,), jnp.int32) if dropped is None
            else jnp.reshape(dropped.astype(jnp.int32), (1,)))
    return jnp.concatenate([load.astype(jnp.int32), tail])


def _local_ids(cfg: ModelConfig, expert_ids: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Chosen experts as indices into the weights held here, `count` (one
    past the last) where the expert is another chip's, and which are held."""
    first, count = cfg.experts_local
    held = jnp.logical_and(expert_ids >= first, expert_ids < first + count)
    return jnp.where(held, expert_ids - first, count), held


def moe_dense(cfg: ModelConfig, p_moe: Params, x: jax.Array,
              x_expert: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Exact dense-compute MoE.  x: [B, T, H] → (out, stats [E+1]): every
    expert HELD HERE (all of them where none is another chip's) over every
    token, the chosen and held ones gate-combined.

    Routing/gating go through the SAME `router_topk` the grouped and
    dispatch paths use (not a masked full-E softmax, whose tie handling
    at the k-th logit differs — bf16 actually produces such ties), and
    the combine reduces over the k selected experts in EXPERT-INDEX
    order — the one combine structure every path in this module shares,
    which is what lets the grouped output be byte-identical to this
    oracle instead of 1 ulp away."""
    B, T, H = x.shape
    top_idx, gates = router_topk(cfg, p_moe, x.reshape(B * T, H))
    top_idx = top_idx.reshape(B, T, -1)                      # [B, T, k]
    gates = gates.reshape(B, T, -1)

    rows = x if x_expert is None else x_expert
    if "w_gate" in p_moe:
        hidden = jax.nn.silu(
            jnp.einsum("bth,ehf->betf", rows, p_moe["w_gate"]))
        hidden = hidden * jnp.einsum("bth,ehf->betf", rows, p_moe["w_up"])
    else:
        hidden = jnp.square(jax.nn.relu(
            jnp.einsum("bth,ehf->betf", rows, p_moe["w_up"])))
    expert_out = jnp.einsum("betf,efh->beth", hidden, p_moe["w_down"])
    kord = jnp.argsort(top_idx, axis=-1, stable=True)        # [B, T, k]
    idx_sorted = jnp.take_along_axis(top_idx, kord, axis=-1)
    g_sel = jnp.take_along_axis(gates, kord, axis=-1)        # [B, T, k]
    if cfg.experts_held is not None:
        # Another chip's expert: some held expert's row, weighing nothing.
        idx_sorted, held = _local_ids(cfg, idx_sorted)
        idx_sorted = jnp.minimum(idx_sorted, cfg.experts_local[1] - 1)
        g_sel = jnp.where(held, g_sel, 0)
    picked = jnp.take_along_axis(
        expert_out.transpose(0, 2, 1, 3),                    # [B, T, E, W]
        idx_sorted[..., None], axis=2)                       # [B, T, k, W]
    out = jnp.einsum("btkh,btk->bth", picked, g_sel)
    load = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.int32),
        axis=(0, 1, 2))
    return out, _with_drop_tail(load)


@hot_path
def moe_grouped(cfg: ModelConfig, p_moe: Params, x: jax.Array,
                *, block_rows: Optional[int] = None,
                interpret: bool = False,
                x_expert: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Grouped-GEMM MoE (the single-chip fast path).  x: [B, T, H] →
    (out, stats [E+1]).  Exact — no capacity, nothing dropped.

    Device-side plumbing around ops/pallas/moe_grouped.py:
    sort the N*k (token, expert) assignments by expert (stable argsort),
    pad each expert's group to a `block_rows` multiple (padding rows are
    zero and compute harmless zeros) in a buffer of `packed_rows` rows
    (the most the groups that can exist fill: with fewer assignments than
    experts a tile an assignment, not one an expert), hand the packed
    buffer plus a tile→expert map to the ragged kernel, then gather each assignment's
    output row back and combine with the top-k gates — the same
    f32-free, x-dtype combine `moe_dense`'s gate einsum performs, which
    is what keeps the two paths comparable.

    `block_rows` None sizes the row tile from the static shape
    (`auto_block_rows`: the mean group, S / E, rounded up to a tile the
    MXU takes): at 128 experts a decode step's 32-256 assignments are one
    to two rows an expert, and a 64-row tile would pad each group 30
    times over.  The kernel is told how many tiles hold real rows and
    skips the rest."""
    from dynamo_tpu.ops.pallas.moe_grouped import (
        grouped_block_rows, grouped_expert_ffn, grouped_expert_ffn_relu2,
        moe_params_quantized, packed_rows)

    B, T, H = x.shape
    N = B * T
    k = cfg.num_experts_per_token
    S = N * k
    share = cfg.experts_held is not None
    # E: the experts held here; G: the groups the packed buffer holds.
    E = cfg.experts_local[1]
    G = E + 1 if share else E
    bm = block_rows or grouped_block_rows(S, cfg.num_experts, E)

    expert_ids, gates = router_topk(cfg, p_moe, x.reshape(N, H))  # [N, k]
    x2 = x.reshape(N, H) if x_expert is None \
        else x_expert.reshape(N, x_expert.shape[-1])
    H = x2.shape[-1]
    flat_e = expert_ids.reshape(-1)                          # [S]
    stats = counts = jnp.sum(
        jax.nn.one_hot(flat_e, cfg.num_experts, dtype=jnp.int32),
        axis=0)                                              # [E]
    if share:
        # Another chip's experts are one more group behind the held ones:
        # their assignments sort last and take rows of the packed buffer
        # like any other (every index below stays in bounds), but no tile
        # of theirs is live, their rows are read as zero and weigh nothing.
        flat_e, held = _local_ids(cfg, flat_e)
        first = cfg.experts_local[0]
        counts = stats[first:first + E]
        counts = jnp.concatenate([counts, (S - jnp.sum(counts))[None]])
        gates = jnp.where(held.reshape(N, k), gates, 0)

    # Static padded buffer: each group rounds up to bm rows, and at most
    # min(S, G) groups hold a row, so the total is at most
    # S + min(S, G)*(bm-1), itself a bm multiple (`packed_rows`).
    padded = -(-counts // bm) * bm                           # [G]
    S_pad = packed_rows(S, G, bm)
    n_tiles = S_pad // bm
    pend = jnp.cumsum(padded)                                # [G]
    offs = pend - padded                                     # exclusive

    # Destination row of each assignment: its group's offset plus its rank
    # within the group (ranks read off the stable sort).
    order = jnp.argsort(flat_e, stable=True)                 # [S]
    es = flat_e[order]
    starts = jnp.cumsum(counts) - counts         # [G]: in the sorted order
    rank = jnp.arange(S, dtype=jnp.int32) - starts[es]
    dest_sorted = offs[es] + rank                            # [S]
    if share:
        # The buffer and the way back BY GATHER: each row of the buffer
        # looks its assignment up (its group by its place, its rank in it,
        # the sorted assignment at that rank), each assignment its row
        # through the sort's inverse.  The scatters of the form below stall
        # a v5e at 704 assignments (22 a token x 32 rows) once other
        # programs have run on it: a vector load out of VMEM's range, no
        # error (PERF.md section 6, PR 47); every index here is one XLA
        # clamps.
        r = jnp.arange(S_pad, dtype=jnp.int32)
        g = jnp.minimum(jnp.searchsorted(pend, r, side="right"), G - 1)
        at = r - offs[g]
        src = order[jnp.clip(starts[g] + at, 0, S - 1)] // k
        x_pad = jnp.where((at < counts[g])[:, None], x2[src], 0)
        dest = dest_sorted[jnp.argsort(order)]
    else:
        token_of = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
        x_pad = jnp.zeros((S_pad, H), x2.dtype).at[dest_sorted].set(
            x2[token_of[order]])
        dest = jnp.zeros((S,), jnp.int32).at[order].set(dest_sorted)

    # tile→expert map (scalar prefetch): the expert whose padded span
    # covers the tile's first row.  Tiles past the last span clamp to
    # E-1 and chew zeros nobody gathers.
    tile_expert = jnp.clip(
        jnp.searchsorted(pend, jnp.arange(n_tiles, dtype=jnp.int32) * bm,
                         side="right"),
        0, E - 1).astype(jnp.int32)

    kw = {}
    if moe_params_quantized(p_moe):
        kw = {"w_gate_scale": p_moe["w_gate_scale"],
              "w_up_scale": p_moe["w_up_scale"],
              "w_down_scale": p_moe["w_down_scale"]}
    # Tiles past the last held expert's padded end hold no row of an expert
    # here: the kernel skips them (their output rows are never read), and
    # they name the last live tile's expert so that no weight block moves
    # for them.
    live_tiles = (pend[E - 1] // bm).astype(jnp.int32).reshape(1)
    tile_expert = jnp.where(
        jnp.arange(n_tiles, dtype=jnp.int32) < live_tiles[0], tile_expert,
        tile_expert[jnp.maximum(live_tiles[0] - 1, 0)])
    if "w_gate" in p_moe:
        y_pad = grouped_expert_ffn(
            x_pad, tile_expert, p_moe["w_gate"], p_moe["w_up"],
            p_moe["w_down"], live_tiles=live_tiles, block_rows=bm,
            interpret=interpret, **kw)
    else:
        y_pad = grouped_expert_ffn_relu2(
            x_pad, tile_expert, p_moe["w_up"], p_moe["w_down"],
            live_tiles=live_tiles, block_rows=bm, interpret=interpret)

    # Gather each assignment's output back and gate-combine.  The k
    # choices are re-sorted by EXPERT INDEX first: the dense oracle's
    # combine einsum reduces over the expert axis in index order (an FMA
    # chain where the zero-gated terms are exact no-ops), and matching
    # that accumulation order is what makes the two paths byte-identical
    # rather than 1-ulp apart.
    rows = y_pad[dest]
    if share:
        # The rows of skipped tiles are undefined: read as zero.
        rows = jnp.where(held[:, None], rows, 0)
    kord = jnp.argsort(expert_ids, axis=1, stable=True)      # [N, k]
    picked = jnp.take_along_axis(
        rows.reshape(N, k, H), kord[:, :, None], axis=1)
    g_ord = jnp.take_along_axis(gates.reshape(N, k), kord, axis=1)
    out = jnp.einsum("nkh,nk->nh", picked, g_ord)
    return out.reshape(B, T, H).astype(x.dtype), _with_drop_tail(stats)


@hot_path
def _dispatch_one_shard(cfg: ModelConfig, p_moe: Params, x: jax.Array,
                        capacity: int, ep_axis: Optional[str],
                        tp_axis: Optional[str] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Per-shard dispatch body.  x: [N, H] local tokens; expert weights
    local slices [E_local, ...].  Runs standalone (ep_axis None → E_local
    == E, no collective) or inside shard_map over `ep_axis`.  With
    `tp_axis`, each expert's MLP is additionally tp-sharded on the
    intermediate dim: the weight slices are [E_local, H, F/tp] /
    [E_local, F/tp, H], the down projection produces a partial sum, and
    ONE psum over tp completes it — tokens, routing and the all_to_all
    are tp-replicated, so the collective stays ep-only."""
    N, H = x.shape
    E = cfg.num_experts
    k = cfg.num_experts_per_token
    C = capacity
    ep = 1 if ep_axis is None else jax.lax.axis_size(ep_axis)
    E_local = p_moe["w_gate"].shape[0]

    # The router weight is replicated (every shard routes its own tokens
    # over ALL experts); only the expert weights are E-sharded.
    expert_ids, gates = router_topk(cfg, p_moe, x)

    # Position of each (token, choice) within its expert's buffer.
    flat_e = expert_ids.reshape(-1)                          # [N*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # [N*k, E]
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # [N*k]
    keep = pos < C
    load = onehot.sum(0)                                     # [E] pre-drop
    dropped = jnp.sum(~keep).astype(jnp.int32)               # capacity honesty

    token_of = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    # Scatter kept tokens into per-destination-expert buffers.  Dropped
    # assignments scatter to an out-of-range row (mode="drop").
    send = jnp.zeros((E, C, H), x.dtype)
    rows = jnp.where(keep, flat_e, E)
    cols = jnp.where(keep, pos, 0)
    send = send.at[rows, cols].set(x[token_of], mode="drop")

    if ep_axis is not None and ep > 1:
        # [E, C, H] = [ep, E_local, C, H]: dim 0 indexes destination shard.
        send = send.reshape(ep, E_local * C, H)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv dim 0 now indexes SOURCE shard.
        h_in = recv.reshape(ep, E_local, C, H).transpose(1, 0, 2, 3)
        h_in = h_in.reshape(E_local, ep * C, H)
    else:
        h_in = send                                          # [E, C, H]

    h_out = expert_ffn(p_moe, h_in)                          # [E_l, ep*C, H]
    if tp_axis is not None:
        # F-sharded expert MLPs: each tp member computed a partial down
        # projection over its F/tp slice.
        h_out = jax.lax.psum(h_out, tp_axis)

    if ep_axis is not None and ep > 1:
        back = h_out.reshape(E_local, ep, C, H).transpose(1, 0, 2, 3)
        back = back.reshape(ep, E_local * C, H)
        got = jax.lax.all_to_all(back, ep_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        out_buf = got.reshape(E, C, H)
    else:
        out_buf = h_out                                      # [E, C, H]

    # Combine: out[t] = sum_j gate[t,j] * out_buf[e(t,j), pos(t,j)],
    # dropped assignments contribute zero.
    picked = out_buf[rows.clip(0, E - 1), cols]              # [N*k, H]
    picked = jnp.where(keep[:, None], picked, 0).reshape(N, k, H)
    out = jnp.einsum("nkh,nk->nh", picked.astype(jnp.float32),
                     gates.reshape(N, k).astype(jnp.float32))
    return out.astype(x.dtype), _with_drop_tail(load, dropped)


def moe_dispatch(cfg: ModelConfig, p_moe: Params, x: jax.Array,
                 capacity: Optional[int] = None,
                 ep_axis: Optional[str] = None,
                 load_psum_axes: Tuple[str, ...] = (),
                 tp_axis: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """All-to-all MoE.  x: [B, T, H] → (out [B, T, H], stats [E+1]).

    Call either outside any mesh (single shard, `ep_axis=None`) or inside
    `shard_map` with the token batch sharded over `ep_axis` (and possibly
    dp) and expert weights' E axis sharded over `ep_axis`.  `tp_axis`:
    the mesh axis each expert MLP's intermediate dim is sharded over
    (ep × tp meshes) — see _dispatch_one_shard.
    `load_psum_axes`: mesh axes to sum the per-shard stats over so the
    returned load/dropped counts are the global distribution
    (replicated).  NEVER include tp_axis here — routing is tp-replicated
    and summing over tp would multiply every count by tp."""
    B, T, H = x.shape
    N = B * T
    if capacity is None:
        capacity = N  # exact: no assignment can overflow
    out, stats = _dispatch_one_shard(
        cfg, p_moe, x.reshape(N, H), capacity, ep_axis, tp_axis)
    if load_psum_axes:
        stats = jax.lax.psum(stats, load_psum_axes)
    return out.reshape(B, T, H), stats
