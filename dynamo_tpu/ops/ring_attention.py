"""Ring attention: causal self-attention with sequence-sharded K/V.

The long-context prefill primitive SURVEY §2.5 demands as a TPU-native
addition (the reference core has no CP/ring path — its long-context
levers are conditional disaggregation and engine flags).  The design is
blockwise ring attention (Liu et al.; the public JAX formulation in the
scaling-book's collective-matmul pattern): the sequence axis is sharded
over the `sp` mesh axis, every shard keeps its Q block resident, and
K/V blocks rotate one hop per step around the ICI ring via
`lax.ppermute` while an online-softmax accumulator folds each visiting
block in.  After sp steps every Q block has seen every K/V block; peak
memory per chip is O(T/sp).

Comm/compute overlap on this path is SCHEDULER-DEPENDENT: there is no
data dependence between a step's ppermute and its einsums, so XLA *may*
overlap them, but nothing guarantees it, and the per-hop `s`/`p`
intermediates round-trip HBM either way.  The Pallas flash ring
(`ops/pallas/ring_attention.py`) makes the overlap structural — the
next hop's RDMA is issued before the local block's fold — and eligible
geometry dispatches it instead (llama._sp_ring_attention); THIS module
remains the fallback for ineligible shapes and the parity oracle both
implementations are pinned against.

Causality is enforced with ABSOLUTE positions carried alongside the
rotating K/V — masks stay correct for any block interleaving, and fully
masked (padding) rows are guarded at the final divide.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


NEG = -1e30


def ring_causal_attention(
    q: jax.Array,            # [B, T_loc, Hq, D]
    k: jax.Array,            # [B, T_loc, Hkv, D] (int8 when k_scale given)
    v: jax.Array,            # [B, T_loc, Hkv, D]
    q_positions: jax.Array,  # [B, T_loc] absolute token positions
    kv_positions: Optional[jax.Array] = None,  # defaults to q_positions
    axis_name: Optional[str] = None,  # None → single shard (degenerates
                                      # to masked causal attention)
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [B, T_loc, Hkv] f32 (int8 k/v)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Blockwise-causal attention; call inside `shard_map` with the T axis
    sharded over `axis_name` (or standalone with axis_name=None).

    Returns [B, T_loc, Hq, D] in q's dtype.  Numerics match
    ops/attention.py `causal_attention` (same mask, f32 softmax path).

    Quantized exchange (ISSUE 12 leg 1): with `k_scale`/`v_scale`, K/V
    are int8 rows quantized EXACTLY as the paged cache stores them
    (kv_cache.quantize_kv_rows) and the per-token-per-head f32 scales
    rotate around the ring WITH their rows — each hop dequantizes the
    visiting block in-register (kv_cache.dequantize_rows to q's compute
    dtype, f32 inside the softmax math), so ring attention sees the same
    dequantized operands every cache-read path sees, and the per-hop ICI
    payload drops from 2·F·itemsize to F + 4·Hkv bytes per token.
    """
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if kv_positions is None:
        kv_positions = q_positions
    sp = 1 if axis_name is None else jax.lax.axis_size(axis_name)

    qg = (q.astype(jnp.float32) * scale).reshape(B, T, Hkv, G, D)

    m = jnp.full((B, Hkv, G, T, 1), NEG, jnp.float32)
    l = jnp.zeros((B, Hkv, G, T, 1), jnp.float32)
    acc = jnp.zeros((B, T, Hkv, G, D), jnp.float32)

    # Visiting order starts with the shard's OWN block (the causal
    # diagonal): every real q row sees at least its own key in step 0, so
    # m leaves the finite NEG floor immediately and later fully-masked
    # blocks contribute exp(NEG - m) == 0 rather than exp(0).  (A ring
    # order that visited a later shard's block first would need the
    # -inf/NaN dance instead.)
    k_cur, v_cur, kv_pos = k, v, kv_positions
    ks_cur, vs_cur = k_scale, v_scale
    for step in range(sp):
        if ks_cur is None:
            kf = k_cur.astype(jnp.float32)
            vf = v_cur.astype(jnp.float32)
        else:
            from dynamo_tpu.engine.kv_cache import dequantize_rows

            # Dequant to q's compute dtype first, THEN f32 — the exact
            # operand path gather_kv_quant feeds the XLA fallback, so
            # ring and gather attention agree bit-for-bit pre-softmax.
            kf = dequantize_rows(k_cur, ks_cur, q.dtype).astype(jnp.float32)
            vf = dequantize_rows(v_cur, vs_cur, q.dtype).astype(jnp.float32)
        # [B, Hkv, G, T, Tk]
        s = jnp.einsum("btkgd,bckd->bkgtc", qg, kf)
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        mask = (kv_pos[:, None, :] <= q_positions[:, :, None]
                )[:, None, None, :, :]
        s = jnp.where(mask, s, NEG)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgtc,bckd->btkgd", p, vf)
        acc = acc * alpha.transpose(0, 3, 1, 2, 4) + pv
        m = m_new

        if axis_name is not None and step + 1 < sp:
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
            if ks_cur is not None:
                # Scales ride the ring WITH their int8 rows — a block and
                # its scales can never desynchronize across hops.
                ks_cur = jax.lax.ppermute(ks_cur, axis_name, perm)
                vs_cur = jax.lax.ppermute(vs_cur, axis_name, perm)

    # Fully-masked rows (padding) keep l == 0: guard the divide.
    out = acc / jnp.maximum(l.transpose(0, 3, 1, 2, 4), 1e-30)
    return out.reshape(B, T, Hq, D).astype(q.dtype)
