"""Attention over the paged KV cache — unified prefill/decode step.

One op serves chunked prefill, full prefill and single-token decode: the
current chunk's Q attends to every cached context slot (the chunk's own K/V
having just been written), with a mask `kv_pos <= q_pos` on absolute
positions.  With chunk length T=1 this is decode; with T=prompt length it is
full prefill; anything between is the chunked-prefill path the reference
models in its mocker (`lib/llm/src/mocker/scheduler.rs`, chunked prefill
budget) and delegates to vLLM for real.

Design notes (TPU-first):
- Gather-based context reads: the whole batch's context K/V is materialised
  as `[B, C, H, D]` via one `take` on the flat slot axis.  XLA fuses the
  gather into the attention einsum's operand pipeline; a dedicated Pallas
  paged-attention kernel (dynamo_tpu/ops/pallas/) replaces this on the
  decode hot path to avoid the HBM round-trip.
- GQA grouping stays explicit (`[B, G, Hkv, ...]` einsums) instead of
  `repeat`ing KV heads — avoids materialising repeated KV.
- Softmax in float32 regardless of cache dtype; logits scaled pre-softmax.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention(
    q: jax.Array,           # [B, T, Hq, D] current chunk queries
    k_ctx: jax.Array,       # [B, C, Hkv, D] gathered context keys
    v_ctx: jax.Array,       # [B, C, Hkv, D] gathered context values
    q_positions: jax.Array, # [B, T] absolute position of each query token
    kv_positions: jax.Array,# [B, C] absolute position of each context slot
    seq_lens: jax.Array,    # [B] valid context length per sequence
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    mask_block: int = 1,
    window: Optional[int] = None,
) -> jax.Array:
    """Masked GQA attention of chunk queries against gathered context.

    Mask: a context slot c is visible to query t iff
    `kv_positions[c] < seq_lens` (slot is real) and
    `kv_positions[c] // mask_block <= q_positions[t] // mask_block`:
    causality on absolute positions at `mask_block` 1, and for a
    block-diffusion model (block length B = `mask_block`) every position
    of a query's own block beside everything before it.  With `window` W a
    query also sees no slot W or more positions behind it
    (`q_positions[t] - kv_positions[c] < W`).

    Returns [B, T, Hq, D] in q's dtype.
    """
    B, T, Hq, D = q.shape
    _, C, Hkv, _ = k_ctx.shape
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    # Blocked GQA convention (HF Llama): q head h shares kv head h // G —
    # the reshape keeps kv as the SLOW axis.  (An interleaved reshape is
    # self-consistent for random weights but silently wrong for real
    # checkpoints.)
    #
    # K/V stay in cache dtype (bf16 on TPU) with f32 MXU accumulation —
    # casting the gathered context to f32 (r2) materialised 2x the bytes
    # per layer per step for no accuracy the f32 accumulator doesn't
    # already provide.  Softmax itself runs in f32.
    qg = q.reshape(B, T, Hkv, G, D)

    # [B, Hkv, G, T, C]
    scores = jnp.einsum("btkgd,bckd->bkgtc", qg, k_ctx,
                        preferred_element_type=jnp.float32) * scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)

    valid = kv_positions[:, None, :] < seq_lens[:, None, None]        # [B, 1, C]
    if mask_block > 1:
        causal = (kv_positions[:, None, :] // mask_block
                  <= q_positions[:, :, None] // mask_block)           # [B, T, C]
    else:
        causal = kv_positions[:, None, :] <= q_positions[:, :, None]  # [B, T, C]
    if window is not None:
        causal = causal & (q_positions[:, :, None]
                           - kv_positions[:, None, :] < window)
    mask = (valid & causal)[:, None, None, :, :]                      # [B,1,1,T,C]
    scores = jnp.where(mask, scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    # Fully-masked rows (padding queries) produce uniform probs over junk;
    # callers discard padding-token outputs, so no NaN guard is needed
    # beyond softmax's own max-subtraction.
    out = jnp.einsum("bkgtc,bckd->btkgd", probs.astype(v_ctx.dtype), v_ctx,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, Hq, D).astype(q.dtype)


def causal_attention(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain causal self-attention (no cache) — used by tests as the ground
    truth the paged path must reproduce, and by ring attention as the
    per-shard inner op."""
    B, T, Hq, D = q.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    seq_lens = jnp.full((B,), T, dtype=jnp.int32)
    return paged_attention(q, k, v, positions, positions, seq_lens, scale=scale)
