"""Llama-family decoder (dense + Mixtral-style MoE) as pure JAX functions.

Params are plain pytrees (nested dicts of arrays) so sharding is a pytree of
`NamedSharding`s (dynamo_tpu/parallel/sharding.py) and the forward step jits
under any mesh.  The reference has no model code (it delegates to vLLM —
SURVEY.md §2.3); this module is the TPU replacement for that delegation.

Forward contract (unified prefill/decode, see dynamo_tpu/ops/attention.py):

    logits, cache = forward_step(cfg, params, cache, tokens, positions,
                                 seq_lens, block_tables, sample_positions)

- tokens/positions: [B, T] — T is the chunk length (1 for decode).
- seq_lens: [B] total valid context length *after* this chunk.
- block_tables: [B, P] page ids into the paged cache.
- sample_positions: [B] index WITHIN the chunk whose logits the caller
  wants (chunk_len - 1 for a completing prefill, 0 for decode); logits
  come back [B, V] for exactly those positions.  Materialising the full
  [B, T, V] f32 logits of a batched 512-token prefill is a multi-GB
  allocation for nothing — the LM head runs on one hidden row per
  sequence.
- The chunk's K/V are scattered into the cache first, then the chunk
  attends to all cached context with an absolute-position causal mask, so
  the same compiled function serves prefill, chunked prefill and decode.

MoE layers run the dense | grouped | dispatch ladder (ops/moe.py): the
exact dense oracle, the meshless grouped-GEMM fast path, or all-to-all
token dispatch over the `ep` mesh axis (tp-sharding each expert's MLP
under ep × tp meshes) — see `_moe_block`.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models.config import (
    STATE_MESHLESS, WINDOW_MESHLESS, ModelConfig)
from dynamo_tpu.runtime.contracts import hot_path
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.ssm import mamba_decode, mamba_prefill

Params = Dict

# What every builder and the engine say when a latent-attention model is
# asked for a plane it has no form for.
LATENT_MESHLESS = (
    "latent attention (MLA) serves meshless: its cache is one latent row a "
    "token shared by all heads, which has no head-sharded (tp), "
    "slot-sharded (dp attention), pipeline or ring/sequence-parallel form")


# ---------------------------------------------------------------------------
# Init


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params (bench/tests); real checkpoints load via
    dynamo_tpu.models.loader with the same pytree structure."""
    cfg.validate()
    dtype = dtype or cfg.dtype
    h = cfg.hidden_size

    def dense(key, fan_in, *shape):
        std = fan_in ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    # Key budget: a stride of 8 keys per layer (dense uses 7, MoE 5; with
    # latent attention 8 and 6), plus embed + lm_head at the tail —
    # per-layer strides keep keys unique without branch-dependent
    # bookkeeping.
    keys = jax.random.split(key, cfg.num_layers * 8 + 2)

    layers = []
    for li in range(cfg.num_layers):
        ki = iter(range(li * 8, (li + 1) * 8))
        if cfg.has_pattern:
            layers.append(_init_pattern_layer(
                cfg, cfg.layer_kind(li), keys[li * 8:(li + 1) * 8], dense,
                dtype))
            continue
        if cfg.is_latent:
            qr, r = cfg.q_lora_rank, cfg.kv_lora_rank
            attn = {
                "wq_a": dense(keys[next(ki)], h, h, qr),
                "q_a_norm": jnp.ones((qr,), dtype),
                "wq_b": dense(keys[next(ki)], qr, qr, cfg.q_size),
                "wkv_a": dense(keys[next(ki)], h, h, cfg.latent_dim),
                "kv_a_norm": jnp.ones((r,), dtype),
                "wkv_b": dense(keys[next(ki)], r, r, cfg.num_heads * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "wo": dense(keys[next(ki)], cfg.attn_out_size,
                            cfg.attn_out_size, h),
            }
        else:
            attn = {
                "wq": dense(keys[next(ki)], h, h, cfg.q_size),
                "wk": dense(keys[next(ki)], h, h, cfg.kv_size),
                "wv": dense(keys[next(ki)], h, h, cfg.kv_size),
                "wo": dense(keys[next(ki)], cfg.q_size, cfg.q_size, h),
            }
        layer = {
            "attn": attn,
            "attn_norm": jnp.ones((h,), dtype),
            "mlp_norm": jnp.ones((h,), dtype),
        }
        if cfg.parallel_block:
            del layer["mlp_norm"]   # one norm: both parts read the same h
        if cfg.qk_norm:
            layer["attn"]["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
            layer["attn"]["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
        if cfg.has_ssm:
            layer["ssm"] = _init_ssm(cfg, keys[li * 8 + 7], dense, dtype)
        if cfg.post_norms:
            layer["post_attn_norm"] = jnp.ones((h,), dtype)
            layer["post_mlp_norm"] = jnp.ones((h,), dtype)
        if cfg.layer_is_moe(li):
            e, f = cfg.num_experts, cfg.expert_size
            held = cfg.experts_local[1]
            kk = jax.random.split(keys[next(ki)], 8)
            layer["moe"] = {
                "router": dense(kk[0], h, h, e),
                "w_gate": dense(kk[1], h, held, h, f),
                "w_up": dense(kk[2], h, held, h, f),
                "w_down": dense(kk[3], f, held, f, h),
            }
            if cfg.router_scoring == "sigmoid" and cfg.router_score_bias:
                # The learned correction bias, float32 as published.  Not
                # zero: choosing by s + b and weighing by s could not be
                # told apart from choosing by s.
                layer["moe"]["router_bias"] = 0.1 * jax.random.normal(
                    kk[4], (e,), jnp.float32)
            if cfg.n_shared_experts:
                fs = cfg.n_shared_experts * f
                layer["moe"]["shared"] = {
                    "w_gate": dense(kk[5], h, h, fs),
                    "w_up": dense(kk[6], h, h, fs),
                    "w_down": dense(kk[7], fs, fs, h),
                }
        else:
            f = cfg.intermediate_size
            layer["mlp"] = {
                "w_gate": dense(keys[next(ki)], h, h, f),
                "w_up": dense(keys[next(ki)], h, h, f),
                "w_down": dense(keys[next(ki)], f, f, h),
            }
        layers.append(layer)

    params: Params = {
        "embed": dense(keys[-2], h, cfg.vocab_size, h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[-1], h, h, cfg.vocab_size)
    return params


def _init_pattern_layer(cfg: ModelConfig, kind: str, keys, dense,
                        dtype) -> Params:
    """One layer of a model whose layers differ by a pattern: its one norm
    and its one mixer or feed-forward part.  Of an expert layer the router
    over ALL the model's experts, the weights of the experts held here
    (`cfg.experts_local`), the two latent maps and the shared expert."""
    h = cfg.hidden_size
    layer = {"norm": jnp.ones((h,), dtype)}
    if kind == "M":
        layer["ssm"] = _init_ssm(cfg, keys[7], dense, dtype)
    elif kind == "*":
        layer["attn"] = {
            "wq": dense(keys[0], h, h, cfg.q_size),
            "wk": dense(keys[1], h, h, cfg.kv_size),
            "wv": dense(keys[2], h, h, cfg.kv_size),
            "wo": dense(keys[3], cfg.q_size, cfg.q_size, h)}
    else:
        e, f = cfg.num_experts, cfg.expert_size
        held = cfg.experts_local[1]
        lat = cfg.moe_latent_size or h
        kk = jax.random.split(keys[0], 8)
        moe = {"router": dense(kk[0], h, h, e),
               "w_up": dense(kk[2], lat, held, lat, f),
               "w_down": dense(kk[3], f, held, f, lat)}
        if cfg.gated_mlp:
            moe["w_gate"] = dense(kk[1], lat, held, lat, f)
        if cfg.router_scoring == "sigmoid":
            # Seeded and not zero, as in the block where all layers are
            # alike: choosing by s + b must differ from choosing by s.
            moe["router_bias"] = 0.1 * jax.random.normal(
                kk[4], (e,), jnp.float32)
        if cfg.moe_latent_size:
            moe["latent_in"] = dense(keys[1], h, h, lat)
            moe["latent_out"] = dense(keys[2], lat, lat, h)
        if cfg.shared_size:
            fs = cfg.shared_size
            moe["shared"] = {"w_up": dense(kk[6], h, h, fs),
                             "w_down": dense(kk[7], fs, fs, h)}
            if cfg.gated_mlp:
                moe["shared"]["w_gate"] = dense(kk[5], h, h, fs)
        layer["moe"] = moe
    return layer


def _init_ssm(cfg: ModelConfig, key: jax.Array, dense, dtype) -> Params:
    """A layer's state-space mixer (the dense block leaves each layer's
    eighth key free).  `A_log`, `dt_bias` and `D` as the Mamba-2 reference
    package initialises them: A uniform in 1..16, dt log-uniform in
    1e-3..1e-1 through the inverse softplus, D ones; float32, as the scan
    reads them."""
    kk = jax.random.split(key, 6)
    H, d, K = cfg.mamba_n_heads, cfg.mamba_d_ssm, cfg.mamba_d_conv
    dt = jnp.exp(jax.random.uniform(kk[4], (H,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    out = {
        "w_in": dense(kk[0], cfg.hidden_size, cfg.hidden_size,
                      cfg.mamba_proj_size),
        "conv_w": dense(kk[1], K, K, cfg.mamba_conv_dim),
        "A_log": jnp.log(jax.random.uniform(kk[3], (H,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "w_out": dense(kk[5], d, d, cfg.hidden_size),
    }
    if cfg.mamba_conv_bias:
        out["conv_b"] = dense(kk[2], K, cfg.mamba_conv_dim)
    if cfg.mamba_rms_norm:
        out["norm"] = jnp.ones((d,), dtype)
    return out


# ---------------------------------------------------------------------------
# Building blocks


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             offset: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    if offset:
        wf = wf + 1.0  # Gemma convention: scale is (1 + w)
    return (norm * wf).astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Cohere's norm: the mean subtracted, the variance over the hidden
    size, in float32; a weight and no bias."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    norm = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (norm * w.astype(jnp.float32)).astype(x.dtype)


def _norm(cfg: ModelConfig, x: jax.Array, w: jax.Array) -> jax.Array:
    """A layer's (or the model's final) norm, in the form the model states."""
    if cfg.norm_kind == "layer":
        return layer_norm(x, w, cfg.rms_norm_eps)
    return rms_norm(x, w, cfg.rms_norm_eps, cfg.rms_offset)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         interleaved: bool = False) -> jax.Array:
    """Rotary embedding, interleaved-half convention.  x: [B, T, H, D].
    `interleaved`: over the pairs (x0, x1), (x2, x3) ... (`rope_gptj`), pair
    i turned by the same angle as the half-split form turns (x_i,
    x_{i + D/2})."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if interleaved:
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _project_qkv(cfg: ModelConfig, p_attn: Params, x: jax.Array,
                 positions: jax.Array, layer: int = 0):
    """q, k, v of a [B, T, H] chunk as [B, T, heads, D]: the three
    projections, RMSNorm over each head of q and k where the model has it
    (one weight vector of head_dim shared by all heads), then the rotary
    embedding where layer `layer` applies it (`cfg.rope_of`)."""
    B, T, _ = x.shape
    q = (x @ p_attn["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = (x @ p_attn["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p_attn["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.key_multiplier != 1.0:
        k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p_attn["q_norm"], cfg.rms_norm_eps, cfg.rms_offset)
        k = rms_norm(k, p_attn["k_norm"], cfg.rms_norm_eps, cfg.rms_offset)
    if cfg.rope_of(layer):
        q = rope(q, positions, cfg.rope_theta, cfg.rope_interleaved)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_interleaved)
    return q, k, v


@hot_path
def _sp_ring_attention(cfg, q, k, v, positions, ring_quant, sp_mesh,
                       sp_pallas):
    """Sequence-parallel whole-prompt attention dispatch: the Pallas
    flash ring kernel (double-buffered RDMA exchange hidden under the
    local flash fold — ops/pallas/ring_attention.py) when selected and
    eligible, else the XLA ppermute ring, which stays the oracle.

    Selection is static at trace time (shapes and mesh are): the SAME
    `ring_kernel_supported` predicate the engine's kernel-path counter
    and the measurement tools consult, so the served path and every
    tool agree on which ring a geometry runs.  Ineligible geometry
    under `sp_pallas` falls back LOUDLY here rather than silently
    wrong-shaping inside Mosaic."""
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.pallas.ring_attention import (
        ring_flash_attention, ring_kernel_supported)
    from dynamo_tpu.ops.ring_attention import ring_causal_attention

    interp = jax.default_backend() != "tpu"
    sp = sp_mesh.shape["sp"]
    tp = sp_mesh.shape["tp"]
    B, T = positions.shape
    feat = cfg.num_kv_heads * cfg.head_dim // max(tp, 1)
    dp = sp_mesh.shape["dp"]
    use_kernel = sp_pallas and ring_kernel_supported(
        feat, T // sp, max(B // dp, 1), cfg.num_heads // max(tp, 1),
        cfg.head_dim, interp)

    # Heads stay tp-sharded inside the ring (attention is
    # head-independent): without "tp" in the specs GSPMD would
    # all-gather the column-parallel q/k/v projections and every tp
    # shard would redo all heads' attention.
    spec4 = P("dp", "sp", "tp", None)
    if use_kernel:
        def ring(qs, ks, vs, ps, ksc=None, vsc=None):
            return ring_flash_attention(
                qs, ks, vs, ps, mesh=sp_mesh, scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, k_scale=ksc, v_scale=vsc,
                interpret=interp)
    else:
        def ring(qs, ks, vs, ps, ksc=None, vsc=None):
            return ring_causal_attention(
                qs, ks, vs, ps, axis_name="sp", scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, k_scale=ksc, v_scale=vsc)

    if ring_quant is not None:
        # Quantized exchange: int8 chunk rows + per-token-per-head
        # scales ride the ring together and each hop dequantizes
        # in-register (both ring paths share kv_cache.dequantize_rows
        # numerics) — the per-hop ICI payload drops to F + 4·Hkv
        # bytes/token.
        spec3 = P("dp", "sp", "tp")
        kq4, vq4, ks3, vs3 = ring_quant
        return jax.shard_map(
            lambda qs, ks_, vs_, ksc, vsc, ps: ring(
                qs, ks_, vs_, ps, ksc, vsc),
            mesh=sp_mesh,
            in_specs=(spec4, spec4, spec4, spec3, spec3,
                      P("dp", "sp")),
            out_specs=spec4,
            check_vma=False,
        )(q, kq4, vq4, ks3, vs3, positions)
    return jax.shard_map(
        lambda qs, ks, vs, ps: ring(qs, ks, vs, ps),
        mesh=sp_mesh,
        in_specs=(spec4, spec4, spec4, P("dp", "sp")),
        out_specs=spec4,
        check_vma=False,
    )(q, k, v, positions)


def _dp_local_attention(cfg: ModelConfig, p_attn: Params, q, k, v,
                        positions, seq_lens, block_tables, block_size: int,
                        bufs: Dict, mesh, pallas: bool) -> Tuple:
    """Device-local dp-attention decode -> (attn_out, bufs'): the chunk's
    K/V written and its queries read in ONE shard-local body, through `wo`.
    `bufs` is the layer's cache buffers ({"k", "v"[, "k_scale",
    "v_scale"]}), standalone arrays (not slices of a stacked cache) so the
    scatter aliases in place under donation / loop carries.

    Cache slots shard over the flat (dp, tp) grid (VERDICT r3 weak #4),
    rows ride their slot's device, and the locality-aware allocator
    guarantees every live page of a row is in that device's slot range —
    so write, gather and attend all run shard-locally with ZERO cross-chip
    traffic.  Out-of-range rebased slots are exactly (a) pad writes to the
    null block (dropped; they land in the real null block on the device
    that owns it) and (b) pad-context gathers already masked by seq_lens.

    `pallas` (ISSUE 9 leg 2): block tables rebase to the shard's LOCAL
    page range and the Pallas kernel streams pages from the local cache
    shard — the "global slot indexing" that used to force the gather path
    becomes local indexing inside the body.  Clamped out-of-range entries
    (other shards' null block in pad columns) sit past each row's
    ceil(seq_len/bs) real pages, which is all the kernel ever reads.
    Quantized caches thread their scale shards the same way and reuse the
    kernel's k_scale/v_scale variant."""
    from jax.sharding import PartitionSpec as P

    B, T = q.shape[:2]
    quant = "k_scale" in bufs
    interp = jax.default_backend() != "tpu"

    def body(qs, ks, vs, kc, vc, bts, pos_s, sls, *scales):
        b_loc, t_loc = qs.shape[0], qs.shape[1]
        s_local = kc.shape[0]
        tp_sz = jax.lax.axis_size("tp")
        flat = jax.lax.axis_index("dp") * tp_sz + jax.lax.axis_index("tp")
        offset = flat * s_local
        wslots = kvc.slots_for_positions(bts, pos_s, block_size)
        wslots = wslots.reshape(b_loc * t_loc) - offset
        kr = ks.reshape(b_loc * t_loc, cfg.kv_size)
        vr = vs.reshape(b_loc * t_loc, cfg.kv_size)
        if scales:
            ksc, vsc = scales
            kc, vc, ksc, vsc = kvc.write_kv_quant(
                kc, vc, ksc, vsc, wslots, kr, vr)
        else:
            kc, vc = kvc.write_kv(kc, vc, wslots, kr, vr)
            ksc = vsc = None
        if pallas:
            from dynamo_tpu.ops.pallas import paged_decode_attention

            pages_local = s_local // block_size
            bt_local = jnp.clip(bts - flat * pages_local,
                                0, pages_local - 1)
            o = paged_decode_attention(
                qs[:, 0], kc, vc, bt_local, sls,
                block_size=block_size, scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, interpret=interp,
                k_scale=ksc, v_scale=vsc)[:, None]
        else:
            Pw = bts.shape[1]
            C = Pw * block_size
            ctx_pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                       (b_loc, C))
            cslots = kvc.slots_for_positions(bts, ctx_pos, block_size)
            cslots = jnp.clip(cslots - offset, 0, s_local - 1)
            if scales:
                k_ctx, v_ctx = kvc.gather_kv_quant(
                    kc, vc, ksc, vsc, cslots, cfg.num_kv_heads,
                    out_dtype=qs.dtype)
            else:
                k_ctx, v_ctx = kvc.gather_kv(kc, vc, cslots,
                                             cfg.num_kv_heads)
            o = paged_attention(qs, k_ctx, v_ctx, pos_s, ctx_pos, sls,
                                scale=cfg.query_scale,
                                soft_cap=cfg.attn_soft_cap)
        if scales:
            return o, kc, vc, ksc, vsc
        return o, kc, vc

    row = P(("dp", "tp"))
    slot = P(("dp", "tp"), None)
    in_specs = [P(("dp", "tp"), None, None, None),
                P(("dp", "tp"), None, None, None),
                P(("dp", "tp"), None, None, None),
                slot, slot, slot, P(("dp", "tp"), None), row]
    out_specs = [P(("dp", "tp"), None, None, None), slot, slot]
    args = [q, k, v, bufs["k"], bufs["v"], block_tables, positions,
            seq_lens]
    if quant:
        in_specs += [slot, slot]
        out_specs += [slot, slot]
        args += [bufs["k_scale"], bufs["v_scale"]]
    res = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)
    out = res[0].reshape(B, T, cfg.q_size) @ p_attn["wo"]
    return out, dict(zip(_KV_LEAVES, res[1:]))


def _attention_write(cfg: ModelConfig, q, k, v, write_slots, bufs: Dict,
                     ring: bool = False) -> Tuple:
    """The chunk's K and V into one layer's cache buffers `bufs` ({"k",
    "v"[, "k_scale", "v_scale"]}): all of a layer's attention (meshless, tp
    or sp) that a later position's attention depends on.  Returns (bufs',
    ring_quant): `ring_quant` the quantized chunk an int8 ring rotates,
    else None."""
    B, T = q.shape[:2]

    def rows(a):
        return a.reshape(B * T, cfg.kv_size)

    if "k_scale" not in bufs:
        return dict(zip(_KV_LEAVES, kvc.write_kv(
            bufs["k"], bufs["v"], write_slots, rows(k), rows(v)))), None
    old = tuple(bufs[n] for n in _KV_LEAVES)
    if not ring:
        return dict(zip(_KV_LEAVES, kvc.write_kv_quant(
            *old, write_slots, rows(k), rows(v)))), None
    # ISSUE 12 leg 1 (int8 × ring-SP): quantize the chunk ONCE — the
    # same int8 rows + [chunk, Hkv] scales are scattered into the
    # cache AND rotated around the ring, so ring attention attends
    # exactly the values every dequantized cache-read path sees.
    # (Attending the pre-quantization chunk, as the pre-ISSUE-12
    # raise documented, would silently diverge from decode.)
    kq, ksc = kvc.quantize_kv_rows(rows(k), cfg.num_kv_heads)
    vq, vsc = kvc.quantize_kv_rows(rows(v), cfg.num_kv_heads)
    new = kvc.scatter_kv_quant(*old, write_slots, kq, vq, ksc, vsc)
    return dict(zip(_KV_LEAVES, new)), (
        kq.reshape(B, T, cfg.num_kv_heads, cfg.head_dim),
        vq.reshape(B, T, cfg.num_kv_heads, cfg.head_dim),
        ksc.reshape(B, T, cfg.num_kv_heads),
        vsc.reshape(B, T, cfg.num_kv_heads),
    )


def _attention_read(cfg: ModelConfig, p_attn: Params, q, k, v, bufs: Dict,
                    ring_quant, positions, seq_lens, ctx_slots, kv_positions,
                    block_tables, block_size: int, sp_mesh=None,
                    sp_pallas=False, pallas_mesh=None,
                    window: Optional[int] = None) -> jax.Array:
    """The chunk's queries over the cache as `_attention_write` left it
    (`bufs`, `ring_quant`), through `wo`: the part of a layer's attention
    that only the chunk's own positions depend on.  `window`: a window
    layer's length (meshless; `block_tables` and `ctx_slots` are then the
    window group's)."""
    B, T = q.shape[:2]
    k_layer, v_layer = bufs["k"], bufs["v"]
    ks_layer, vs_layer = bufs.get("k_scale"), bufs.get("v_scale")
    quant = ks_layer is not None
    if sp_mesh is not None:
        # Sequence-parallel full-prompt prefill: the chunk IS the whole
        # sequence, sharded over sp — ring attention visits every K/V
        # block over the ICI ring (the Pallas flash kernel or the XLA
        # ppermute oracle, picked in _sp_ring_attention); no cached
        # context is read (chunked continuation stays on the paths
        # below).  Cache writes above remain GSPMD-managed.
        out = _sp_ring_attention(cfg, q, k, v, positions, ring_quant,
                                 sp_mesh, sp_pallas)
    elif ctx_slots is None:
        # Decode hot path: stream pages via the Pallas kernel — no
        # materialised context gather (ops/pallas/paged_attention.py).
        from dynamo_tpu.ops.pallas import paged_decode_attention

        interp = jax.default_backend() != "tpu"
        if pallas_mesh is not None:
            # Sharded serving: GSPMD can't partition a custom call, so
            # the kernel runs under shard_map — heads over tp (each shard
            # sees its [S, F/tp] cache slice, a self-consistent smaller
            # GQA geometry), batch over dp.  Quantized caches shard the
            # [S, Hkv] scale buffers over the SAME head axis (tp | Hkv),
            # so each shard dequantizes its own heads with local scales
            # — the kernel's existing k_scale/v_scale variant, per shard.
            from jax.sharding import PartitionSpec as P

            head = P(None, "tp")
            if quant:
                out = jax.shard_map(
                    lambda qs, ks, vs, ksc, vsc, bts, sls:
                        paged_decode_attention(
                            qs, ks, vs, bts, sls, block_size=block_size,
                            scale=cfg.query_scale,
                            soft_cap=cfg.attn_soft_cap,
                            interpret=interp, k_scale=ksc, v_scale=vsc),
                    mesh=pallas_mesh,
                    in_specs=(P("dp", "tp", None), head, head, head, head,
                              P("dp", None), P("dp")),
                    out_specs=P("dp", "tp", None),
                    check_vma=False,
                )(q[:, 0], k_layer, v_layer, ks_layer, vs_layer,
                  block_tables, seq_lens)[:, None]
            else:
                out = jax.shard_map(
                    lambda qs, ks, vs, bts, sls: paged_decode_attention(
                        qs, ks, vs, bts, sls, block_size=block_size,
                        scale=cfg.query_scale, soft_cap=cfg.attn_soft_cap,
                        interpret=interp),
                    mesh=pallas_mesh,
                    in_specs=(P("dp", "tp", None), head, head,
                              P("dp", None), P("dp")),
                    out_specs=P("dp", "tp", None),
                    check_vma=False,
                )(q[:, 0], k_layer, v_layer, block_tables, seq_lens)[:, None]
        elif T > 1:
            # A block of a block-diffusion model: its T queries all see
            # the cache and the block itself (just written, seq_len = the
            # block's end) and ride the kernel's head-group axis.
            from dynamo_tpu.ops.pallas import paged_block_attention

            out = paged_block_attention(
                q, k_layer, v_layer, block_tables, seq_lens,
                block_size=block_size, scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, interpret=interp,
                k_scale=ks_layer, v_scale=vs_layer)
        elif window is not None:
            from dynamo_tpu.ops.pallas import paged_window_decode_attention

            out = paged_window_decode_attention(
                q[:, 0], k_layer, v_layer, block_tables, seq_lens,
                block_size=block_size, scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, interpret=interp,
                window=window)[:, None]
        else:
            out = paged_decode_attention(
                q[:, 0], k_layer, v_layer, block_tables, seq_lens,
                block_size=block_size, scale=cfg.query_scale,
                soft_cap=cfg.attn_soft_cap, interpret=interp,
                k_scale=ks_layer, v_scale=vs_layer,
            )[:, None]
    else:
        if quant:
            # Gather + in-register dequant (prefill attention and the
            # non-Pallas decode fallback): same dequant numerics as the
            # kernel's VMEM path (kv_cache.dequantize_rows), cast to q's
            # compute dtype.
            k_ctx, v_ctx = kvc.gather_kv_quant(
                k_layer, v_layer, ks_layer, vs_layer, ctx_slots,
                cfg.num_kv_heads, out_dtype=q.dtype)
        else:
            k_ctx, v_ctx = kvc.gather_kv(k_layer, v_layer, ctx_slots,
                                         cfg.num_kv_heads)
        windowed = {} if window is None else {"window": window}
        out = paged_attention(q, k_ctx, v_ctx, positions, kv_positions,
                              seq_lens, scale=cfg.query_scale,
                              soft_cap=cfg.attn_soft_cap,
                              mask_block=cfg.diffusion_block_length,
                              **windowed)
    return out.reshape(B, T, cfg.q_size) @ p_attn["wo"]


# ---------------------------------------------------------------------------
# Latent attention (MLA)


def _latent_project(cfg: ModelConfig, p_attn: Params, x: jax.Array,
                    positions: jax.Array):
    """A [B, T, H] chunk -> (q_abs [B, T, heads, row], rows [B, T, row]).

    `rows` is what the cache stores of each token: `[c_kv | k_rope | 0]`,
    c_kv = RMSNorm(x W_kva)[:r] and k_rope the rotated tail, one row for all
    heads, zero-padded to `cfg.latent_row`.  `q_abs` is each head's query
    against such a row, the key up-projection absorbed into it: `[q_nope
    W_kvb,K^T | q_rope | 0]`, so that `q_abs . row` is the published score
    `q_nope . k_nope + q_rope . k_rope` without a key ever being built."""
    B, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps = cfg.rms_norm_eps
    c_q = rms_norm(x @ p_attn["wq_a"], p_attn["q_a_norm"], eps)
    q = (c_q @ p_attn["wq_b"]).reshape(B, T, H, dn + dr)
    q_rope = rope(q[..., dn:], positions, cfg.rope_theta)
    kv = x @ p_attn["wkv_a"]                                  # [B, T, r + dr]
    c_kv = rms_norm(kv[..., :r], p_attn["kv_a_norm"], eps)
    k_rope = rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    w_k = p_attn["wkv_b"].reshape(r, H, -1)[..., :dn]         # [r, H, dn]
    q_lat = jnp.einsum("bthd,rhd->bthr", q[..., :dn], w_k)
    pad = cfg.latent_row - cfg.latent_dim
    q_abs = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((B, T, H, pad), q.dtype)], axis=-1)
    rows = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((B, T, pad), c_kv.dtype)], axis=-1)
    return q_abs, rows


def _latent_out(cfg: ModelConfig, p_attn: Params, o_lat: jax.Array):
    """Attention's output in the latent space [B, T, heads, r] -> [B, T,
    hidden]: each head's value up-projection, then `wo`."""
    B, T = o_lat.shape[:2]
    w_v = p_attn["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)[
        ..., cfg.qk_nope_head_dim:]                           # [r, H, dv]
    o = jnp.einsum("bthr,rhd->bthd", o_lat, w_v)
    return o.reshape(B, T, cfg.attn_out_size) @ p_attn["wo"]


def _latent_scale(cfg: ModelConfig) -> float:
    return cfg.query_scale or cfg.head_dim ** -0.5


def _latent_read(cfg: ModelConfig, p_attn: Params, q_abs, kv_cache,
                 positions, seq_lens, ctx_slots, kv_positions, block_tables,
                 block_size: int) -> jax.Array:
    """One layer's latent attention over the rows as written, in the
    weight-absorbed form everywhere: one "KV head" whose key is the whole
    row and whose value is the row's leading `kv_lora_rank` columns, under
    `num_heads` query heads.  With `ctx_slots` None (a T == 1 step on the
    kernel path) the rows stream through the latent decode kernel, else
    they are gathered."""
    r = cfg.kv_lora_rank
    if ctx_slots is None:
        from dynamo_tpu.ops.pallas.latent_attention import (
            latent_decode_attention)

        o_lat = latent_decode_attention(
            q_abs[:, 0], kv_cache, block_tables, seq_lens,
            block_size=block_size, scale=_latent_scale(cfg), v_width=r,
            interpret=jax.default_backend() != "tpu")[:, None]
    else:
        ctx = jnp.take(kv_cache, ctx_slots, axis=0, mode="clip")[:, :, None]
        o_lat = paged_attention(q_abs, ctx, ctx, positions, kv_positions,
                                seq_lens, scale=_latent_scale(cfg))[..., :r]
    return _latent_out(cfg, p_attn, o_lat)


def _dense_mlp(p: Params, x: jax.Array, activation: str = "silu",
               multipliers=(1.0, 1.0)) -> jax.Array:
    """`multipliers`: on the gate's pre-activation and on the output (the
    falcon_h1 block's `mlp_multipliers`; nothing is traced for 1).  Without
    `w_gate` the ungated form: `relu(x W_up)^2 W_down` (`relu2`)."""
    if "w_gate" not in p:
        return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]
    act = (jax.nn.silu if activation == "silu"
           else lambda v: jax.nn.gelu(v, approximate=True))
    gate = x @ p["w_gate"]
    if multipliers[0] != 1.0:
        gate = gate * jnp.asarray(multipliers[0], gate.dtype)
    out = (act(gate) * (x @ p["w_up"])) @ p["w_down"]
    if multipliers[1] != 1.0:
        out = out * jnp.asarray(multipliers[1], out.dtype)
    return out


def _state_slots(cache: Dict, state_slots, live: jax.Array) -> jax.Array:
    """Each row's (or segment's) state slot, the scratch slot (the leaves'
    last index) for one that is not live."""
    if state_slots is None:
        raise ValueError("a model with state-space layers needs each row's "
                         "state slot (`state_slots`)")
    scratch = cache["ssm"][0].shape[0] - 1
    return jnp.where(live, state_slots.astype(jnp.int32), scratch)


def _window_tables(cfg: ModelConfig, window_tables):
    if cfg.has_window and window_tables is None:
        raise ValueError("a model with window layers needs each row's "
                         "table of window-group pages (`window_tables`)")
    return window_tables if cfg.has_window else None


def _mix_branches(cfg: ModelConfig, attn_out: jax.Array,
                  ssm_out: jax.Array) -> jax.Array:
    """The two mixers of a falcon_h1 layer, each under its multiplier."""
    return (ssm_out * jnp.asarray(cfg.ssm_out_multiplier, ssm_out.dtype)
            + attn_out * jnp.asarray(cfg.attention_out_multiplier,
                                     attn_out.dtype))


def _head_logits(cfg: ModelConfig, params: Params, x: jax.Array):
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    logits = (x @ w).astype(jnp.float32)
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    if cfg.final_soft_cap is not None:
        logits = cfg.final_soft_cap * jnp.tanh(logits / cfg.final_soft_cap)
    return logits


def _moe_block(cfg: ModelConfig, p: Params, x: jax.Array,
               moe_mode: str, mesh, x_expert=None
               ) -> Tuple[jax.Array, jax.Array]:
    """One MoE layer → (out, stats [E+1]: per-expert assignment counts
    plus the dropped-assignments tail slot — ops/moe.py contract).

    The mode ladder (parallel/sharding.resolve_moe_mode):
    - "dense": exact dense-compute (oracle; expert einsums carry an
      explicit E axis so an `ep` mesh axis can shard them under GSPMD).
    - "grouped": meshless fast path — ragged grouped GEMM over
      expert-sorted assignments (ops/pallas/moe_grouped.py), exact and
      byte-identical to the dense oracle.
    - "dispatch": all-to-all token dispatch under shard_map over the
      mesh's dp/ep axes; under ep × tp meshes each expert's MLP is
      additionally tp-sharded on the intermediate dim (partial down
      projection + psum inside the body).  Capacity comes from
      `cfg.moe_capacity` (None = exact, the serving default; bounded
      capacities drop overflow assignments into the counted tail)."""
    from dynamo_tpu.ops import moe as moe_ops

    if "shared" in p:
        # The always-on expert beside the routed ones: y = shared(x) + the
        # routed sum.  Meshless (the engine refuses this block a mesh).
        if mesh is not None:
            raise ValueError("a shared expert has no sharded form")
        routed = {k: v for k, v in p.items() if k != "shared"}
        out, stats = _moe_block(cfg, routed, x, moe_mode, None)
        shared = _dense_mlp(p["shared"], x, cfg.activation)
        if cfg.shared_experts_mean:
            # The one wide SwiGLU is the SUM of the n shared experts.
            shared = shared * jnp.asarray(1.0 / cfg.n_shared_experts,
                                          shared.dtype)
        return out + shared, stats
    if "latent_in" in p:
        # Experts that work in a latent space: the router sees the layer's
        # full-width input, the experts one map of it, and their gated sum
        # (this chip's part of it, where it holds a share) goes back up
        # through the other.  Meshless, as the shared expert is.
        if mesh is not None:
            raise ValueError("latent experts have no sharded form")
        routed = {k: v for k, v in p.items()
                  if k not in ("latent_in", "latent_out")}
        out, stats = _moe_block(cfg, routed, x, moe_mode, None,
                                x_expert=x @ p["latent_in"])
        return out @ p["latent_out"], stats
    if mesh is None:
        # `x_expert`: the rows the experts take where they are not the
        # router's (the latent form above; no argument otherwise).
        kw = {} if x_expert is None else {"x_expert": x_expert}
        if moe_mode == "grouped":
            return moe_ops.moe_grouped(
                cfg, p, x, interpret=jax.default_backend() != "tpu", **kw)
        return moe_ops.moe_dense(cfg, p, x, **kw)
    if moe_mode == "dense":
        return moe_ops.moe_dense(cfg, p, x)

    from jax.sharding import PartitionSpec as P

    # tp > 1: expert weight slices arrive F-sharded ([E_local, H, F/tp] /
    # [E_local, F/tp, H]) and the body psums the partial down projection.
    # tp == 1 keeps the exact pre-ISSUE-17 program (specs with a size-1
    # "tp" axis partition nothing and tp_axis=None adds no collective).
    tp_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
    wrapped = jax.shard_map(
        lambda xs, ps: moe_ops.moe_dispatch(
            cfg, ps, xs, capacity=cfg.moe_capacity, ep_axis="ep",
            load_psum_axes=("dp", "ep"), tp_axis=tp_axis),
        mesh=mesh,
        in_specs=(P(("dp", "ep"), None, None),
                  {"router": P(None, None),
                   "w_gate": P("ep", None, "tp"),
                   "w_up": P("ep", None, "tp"),
                   "w_down": P("ep", "tp", None)}),
        out_specs=(P(("dp", "ep"), None, None), P(None)),
        check_vma=False,
    )
    return wrapped(x, p)


def _touched(cfg: ModelConfig, load: jax.Array) -> jax.Array:
    """Distinct experts HELD HERE that got at least one row, from a layer's
    [E+1] load (all of the model's experts where none is another chip's)."""
    first, count = cfg.experts_local
    return jnp.sum(load[first:first + count] > 0, dtype=jnp.int32)


def _moe_routing(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """The experts each token of x [B, T, H] chose in this layer, [B*T, k]:
    the router's top-k once more, for a step that hands the choices out.
    The same operations on the same values as inside `_moe_block`: XLA
    keeps one copy, and none of this where the output is not read."""
    from dynamo_tpu.ops import moe as moe_ops

    return moe_ops.router_topk(cfg, p, x.reshape(-1, x.shape[-1]))[0]


def _cache_places(cfg: ModelConfig) -> Tuple[Dict[int, int], Dict[int, int]]:
    """Where a layer's leaves lie in the cache's lists: ({attention layer:
    its place in `k` and `v`}, {state layer: its place in `ssm` and
    `conv`}).  The identity where every layer is alike."""
    return ({layer: j for j, layer in enumerate(cfg.attention_layers)},
            {layer: j for j, layer in enumerate(cfg.state_layers)})


# ---------------------------------------------------------------------------
# The layer walk

# The cache's leaves by the kind of layer that keeps them: an attention
# layer its latent rows or its K and V pages (int8: with their scales), a
# state layer its recurrent state and its convolution's tail.  A layer
# finds its own among a leaf's buffers by `_cache_places`.
_KV_LEAVES = ("k", "v", "k_scale", "v_scale")
_ATTENTION_LEAVES = ("kv",) + _KV_LEAVES
_STATE_LEAVES = ("ssm", "conv")


class OpenCache:
    """The cache pytree opened for one step: every leaf a list of
    standalone per-layer buffers (not slices of a stacked cache, so a
    scatter into one aliases in place under donation / loop carries) that
    the walk replaces a layer at a time.  The pytree's structure is the
    mode bit (`kv_cache.cache_is_latent`, `cache_is_quantized`): a layer
    is handed the leaves the cache has, by name."""

    def __init__(self, cfg: ModelConfig, cache: Dict):
        self._bufs = {name: list(bufs) for name, bufs in cache.items()}
        kv_at, state_at = _cache_places(cfg)
        self._place = {**dict.fromkeys(_ATTENTION_LEAVES, kv_at),
                       **dict.fromkeys(_STATE_LEAVES, state_at)}

    def layer(self, i: int, names: Tuple[str, ...]) -> Dict:
        """Layer i's buffer of each of `names` that the cache has."""
        return {n: self._bufs[n][self._place[n][i]]
                for n in names if n in self._bufs}

    def put(self, i: int, bufs: Dict) -> None:
        for n, buf in bufs.items():
            self._bufs[n][self._place[n][i]] = buf

    def close(self) -> Dict:
        return dict(self._bufs)


class Mixers(NamedTuple):
    """How the mixers meet the cache for one call's shape: all a step
    builder gives `walk_layers`, which owns the rest of every layer.  Each
    takes the layer's own weights, its normed input `h` [B, T, H] and the
    layer's cache buffers by leaf name, and returns the buffers it wrote;
    the attention pair also takes the layer's index `i`, by which a layer
    has a window or applies the rotary embedding (`cfg.window_of`,
    `cfg.rope_of`).

    - `attn_write(p_attn, h, bufs, i) -> (bufs', carried)`: the chunk's K
      and V (or latent rows) into the cache, which is all of the layer a
      later position depends on; `carried` is what the read wants of it.
    - `attn_read(p_attn, bufs', carried, i) -> out`: the chunk's queries
      over the cache as written, through `wo`.
    - `state(p_ssm, h, bufs) -> (out, bufs')`: the state-space mixer
      advanced over the chunk from each row's (segment's) slot."""
    attn_write: Callable
    attn_read: Callable
    state: Callable


def _layer_parts(cfg: ModelConfig, i: int, layer: Params) -> Tuple:
    """What layer i is made of, by its kind: (norm, part, post-norm) in
    turn, each one `x = x + post(part(norm(x)))`.  Where every layer is
    alike, attention (the state branch of a hybrid beside it) and then
    experts or a dense MLP, each behind its own norm; under a pattern one
    `f` a layer on the layer's one normed input.  `part` a tuple: parts
    side by side on the one normed input, `x + Attn(h) + FFN(h)` (the
    parallel block)."""
    kind = cfg.layer_kind(i)
    if kind:
        return (("norm", {"M": "state", "*": "attention"}.get(
            kind, "experts"), None),)
    if cfg.parallel_block:
        return (("attn_norm", ("attention", "experts"), None),)
    post = ("post_attn_norm", "post_mlp_norm") if cfg.post_norms \
        else (None, None)
    if "moe" in layer:      # not a leading dense layer
        return (("attn_norm", "attention", post[0]),
                ("mlp_norm", "experts", None))
    return (("attn_norm", "attention", post[0]),
            ("mlp_norm", "mlp", post[1]))


def _no_tally(cfg: ModelConfig) -> Dict:
    """[E+1]: per-expert counts + dropped tail (ops/moe.py contract)."""
    return {"load": jnp.zeros((cfg.num_experts + 1 if cfg.is_moe else 1,),
                              jnp.int32),
            "touched": jnp.zeros((), jnp.int32), "routing": []}


def _tallied(cfg: ModelConfig, tally: Dict, p_moe: Params, h: jax.Array,
             load: jax.Array, moe_aux: bool) -> Dict:
    """`tally` with one expert layer's report added."""
    out = dict(tally, load=tally["load"] + load,
               touched=tally["touched"] + _touched(cfg, load))
    if moe_aux:
        out["routing"] = tally["routing"] + [_moe_routing(cfg, p_moe, h)]
    return out


def walk_layers(cfg: ModelConfig, layers, x: jax.Array, cache: OpenCache,
                mixers: Mixers, moe_mode: str = "dense", mesh=None,
                moe_aux: bool = False, pause: bool = False):
    """THE loop over a model's layers, which every step program goes
    through: each layer by its kind (`cfg.layer_kind`, `_layer_parts`) on
    x [B, T, H], its cache buffers replaced in `cache` as it goes.  A
    generator, to be resumed with `next`: it yields (x, tally) at the end,
    `tally` the expert layers' {load [E+1], touched, routing: a list of
    [B*T, k], one a layer (`moe_aux`)}.

    `pause`: it yields the tally so far once before that, between the last
    layer's K/V write and its read.  A layer's K and V are made from its
    input: once the last layer's are written the cache holds all a later
    position will read, and what is left only this chunk's own logits
    depend on; it reports for itself, in the tally yielded at the end."""
    tally = _no_tally(cfg)
    last = len(layers) - 1

    def state(i, layer, h):
        out, bufs = mixers.state(layer["ssm"], h,
                                 cache.layer(i, _STATE_LEAVES))
        cache.put(i, bufs)
        return out

    for i, layer in enumerate(layers):
        for norm, parts, post in _layer_parts(cfg, i, layer):
            h = _norm(cfg, x, layer[norm])
            for part in (parts if isinstance(parts, tuple) else (parts,)):
                if part == "attention":
                    hybrid = "ssm" in layer
                    h_attn = h
                    if hybrid:
                        ssm_out = state(i, layer, h)
                        if cfg.attention_in_multiplier != 1.0:
                            h_attn = h * jnp.asarray(
                                cfg.attention_in_multiplier, h.dtype)
                    bufs, carried = mixers.attn_write(
                        layer["attn"], h_attn,
                        cache.layer(i, _ATTENTION_LEAVES), i)
                    cache.put(i, bufs)
                    paused = pause and i == last
                    if paused:
                        yield tally
                    out = mixers.attn_read(layer["attn"], bufs, carried, i)
                    if paused:
                        # What is left counts for itself (made after the
                        # read: the order the accepted block programs'
                        # operations have).
                        tally = _no_tally(cfg)
                    if hybrid:
                        out = _mix_branches(cfg, out, ssm_out)
                elif part == "state":
                    out = state(i, layer, h)
                elif part == "experts":
                    out, load = _moe_block(cfg, layer["moe"], h, moe_mode,
                                           mesh)
                else:
                    out = _dense_mlp(layer["mlp"], h, cfg.activation,
                                     cfg.mlp_multipliers)
                if post:
                    out = _norm(cfg, out, layer[post])
                x = x + out
                if part == "experts":
                    tally = _tallied(cfg, tally, layer["moe"], h, load,
                                     moe_aux)
    yield x, tally


def _embedding_scaled(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.embed_scale:
        # Gemma convention: embeddings scale by sqrt(hidden), with
        # the multiplier cast to the model dtype first (HF parity).
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _head(cfg: ModelConfig, params: Params, x: jax.Array, rows,
          hidden: bool = False) -> jax.Array:
    """The final norm, then the LM head on `rows(x)` alone: the one hidden
    row a sequence whose logits the caller wants ([B, H] @ [H, V]) — full
    [B, T, V] logits of a batched 512-token prefill would be a multi-GB
    f32 allocation for nothing."""
    x = rows(_norm(cfg, x, params["final_norm"]))
    if hidden:
        # Embeddings path: the last-token final-norm hidden state IS the
        # embedding (causal-LM convention, e5-mistral-style); the LM head
        # is skipped entirely.
        return x.astype(jnp.float32)
    return _head_logits(cfg, params, x)


def _step_result(out: jax.Array, cache: Dict, tally: Dict,
                 with_expert_load: bool, moe_aux: bool) -> Tuple:
    """(out, cache), and the expert layers' tally where the step hands it
    out: the [E+1] load, or with `moe_aux` {load, touched, routing
    [L, B*T, k]}."""
    if not with_expert_load:
        return out, cache
    if not moe_aux:
        return out, cache, tally["load"]
    return out, cache, {
        "load": tally["load"], "touched": tally["touched"],
        "routing": jnp.stack(tally["routing"]).astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Fused decode window


def make_decode_window(cfg: ModelConfig, block_size: int, window: int,
                       use_pallas_decode: bool = False,
                       greedy_only: bool = False,
                       mesh=None,
                       dp_local: bool = False,
                       moe_mode: str = "dense",
                       with_expert_load: bool = False,
                       moe_aux: bool = False):
    """K decode steps in ONE device dispatch, tokens fed back on-device.

    The per-token host loop costs a host sync per step — the latency
    SURVEY §7 flags as the decode hard part.  `lax.fori_loop` keeps K
    steps on device:
    each iteration writes the fed token's KV, computes one-position logits,
    samples the next token, and feeds it to the next iteration.  The host
    reads the [K, B] token block lazily, windows behind the dispatch
    (engine pipelining), so steady-state decode never blocks on the wire.

    Sampling: per-row (temperature, top_k, top_p) are fixed across the
    window; per-row keys derive on-device as fold_in(base_key, offset + i)
    so seeded streams stay reproducible across window boundaries and
    batch mixes.  `greedy_only` compiles the argmax-only variant (no sort,
    no keys — the common serving mix).

    Returns run(params, cache, last_tokens[B], positions0[B], seq_lens0[B],
                block_tables[B,P], temp[B], top_k[B], top_p[B],
                base_key_data[B,2] uint32, key_offsets[B])
        -> (cache, tokens[K, B], positions0+K, seq_lens0+K, key_offsets+K).
    A model with state-space layers takes one argument more, `state_slots[B]`
    (each row's slot of recurrent state; `make_forward_step`), a model with
    window layers `window_tables[B, P]` in its place.

    The advanced positions/seq_lens/offsets come back as DEVICE arrays so
    the engine can feed the next window with zero host→device transfers.

    `with_expert_load` appends the [E+1] load summed over the window's
    steps; with `moe_aux` (meshless) what is appended is the dict
    `make_forward_step` gives under that flag, summed over the steps:
    `load`, `touched` (distinct experts with a row, a layer a step) and
    `routing` [K, L, B, k], the experts each row chose in each expert
    layer at each step.  A few KB that stay on the device unless asked for.
    """
    from dynamo_tpu.engine.sampling import sample

    step = make_forward_step(cfg, block_size, use_pallas_decode,
                             mesh=mesh, dp_local=dp_local,
                             moe_mode=moe_mode,
                             with_expert_load=with_expert_load,
                             moe_aux=moe_aux)

    def run(params, cache, last_tokens, positions0, seq_lens0, block_tables,
            temp, top_k, top_p, base_key_data, key_offsets,
            state_slots=None, window_tables=None):
        B = last_tokens.shape[0]
        zero_pos = jnp.zeros((B,), jnp.int32)
        # Keys travel as RAW uint32 key data [B, 2] and wrap on device:
        # host code can then build them as plain numpy, which the
        # multihost path requires (typed key arrays can't cross the
        # host→global-array boundary).
        base_keys = (None if greedy_only
                     else jax.random.wrap_key_data(base_key_data))
        # Padding rows (seq_lens0 == 0) must stay dead across device-side
        # advances: their seq_lens pin at 0 (attention loop skipped, no
        # unbounded block-table indices) and their positions pin at the
        # null-resolving pad position.
        live = seq_lens0 > 0
        # A model with state-space layers: each row's state slot, the same
        # through the window's steps (the state itself rides the cache).
        state = {"state_slots": state_slots} if cfg.has_ssm else {}
        # A model with window layers: each row's window-group table, which
        # holds the pages of every position the window's steps reach.
        if cfg.has_window:
            state = {"window_tables": window_tables}

        def body(i, carry):
            cache, toks, out, load = carry
            adv = jnp.where(live, i, 0)
            res = step(
                params, cache, toks[:, None],
                (positions0 + adv)[:, None], seq_lens0 + adv,
                block_tables, zero_pos, **state)
            if with_expert_load:
                # MoE telemetry threads THROUGH the loop carry (the
                # reason windows were dense-only before r5): per-step
                # assignment counts accumulate on device.
                logits, cache, step_load = res
                if moe_aux:
                    load = {"load": load["load"] + step_load["load"],
                            "touched": load["touched"]
                            + step_load["touched"],
                            "routing": load["routing"].at[i].set(
                                step_load["routing"])}
                else:
                    load = load + step_load
            else:
                logits, cache = res
            if greedy_only:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                keys = jax.vmap(jax.random.fold_in)(base_keys,
                                                    key_offsets + i)
                nxt = sample(logits, temp, top_k, top_p, keys)
            return cache, nxt, out.at[i].set(nxt), load

        out0 = jnp.zeros((window, B), jnp.int32)
        # [E+1]: per-expert counts + dropped tail (ops/moe.py contract).
        load0 = jnp.zeros((cfg.num_experts + 1,), jnp.int32) \
            if with_expert_load else jnp.zeros((), jnp.int32)
        if with_expert_load and moe_aux:
            load0 = {"load": load0, "touched": jnp.zeros((), jnp.int32),
                     "routing": jnp.zeros(
                         (window, cfg.num_moe_layers, B,
                          cfg.num_experts_per_token), jnp.int32)}
        cache, _, out, load = jax.lax.fori_loop(
            0, window, body, (cache, last_tokens, out0, load0))
        adv = jnp.where(live, window, 0)
        base = (cache, out, positions0 + adv, seq_lens0 + adv,
                key_offsets + window)
        return base + (load,) if with_expert_load else base

    return run


# ---------------------------------------------------------------------------
# Block-diffusion step


def make_block_step(cfg: ModelConfig, block_size: int,
                    use_pallas_decode: bool = False,
                    greedy_only: bool = False,
                    moe_mode: str = "dense",
                    record: bool = False):
    """One block of a block-diffusion model in ONE device dispatch: the
    denoising forwards with the unmasking between them, then the commit
    forward.  It stands where `make_decode_window` stands for a causal
    model, and like it keeps every forward of the call on the device.

    A row is one sequence's current block of B = `diffusion_block_length`
    positions.  Positions the host already knows (the tail of a prompt
    whose length is no multiple of B) arrive as their tokens, the rest as
    `mask_token_id`.  While any live row has a masked position, a
    denoising forward runs over all rows: each row's B queries see its
    cache and its whole block (the forward writes the block's K/V into
    the block's own slots first; only this block reads them, and the
    commit overwrites them), the proposal at a position is the argmax (or
    a sample) of its logits with the mask token's logit at -inf, and
    `sampling.diffusion_unmask` decides which proposals stand.  A decided
    position is never masked again.  The first forward that finds nothing
    masked is the commit: it writes the final tokens' K/V in every layer
    and stops there.  A layer's K and V are made from its input, so the
    last layer's attention read, `wo`, experts or MLP, the final norm, the
    head, the proposal and the unmasking of a commit would produce what
    nobody reads; the body keeps them in one `cond` on what it observes
    (no live row with a masked position), which the rule in force, the
    sampler and the model's kind (routed or dense) all share.  Tokens,
    masks and the count of decided positions pass through it unchanged and
    it folds in no key.  Every forward is one pass of one `while_loop`
    body.  With the static rule and B / denoising_steps a forward that is
    `denoising_steps` + 1 forwards for a fresh block.

    Returns run(params, cache, tokens[R, B], positions[R, B],
                seq_lens[R] (the block's end; 0 = dead row),
                block_tables[R, P], temp[R], top_k[R], top_p[R],
                base_key_data[R, 2] uint32, key_offsets[R])
        -> (cache, tokens[R, B], stats int32[3] = (denoising forwards
            run, positions decided in live rows, forwards that ran past
            their last K/V write), moe, trail) where `moe` is {load [E+1],
            touched} summed over the expert layers the call ran (zeros for
            a dense model) and `trail` what a comparison with a reference
            reads of each forward of the call, in order (index `stats[0]`
            is the commit): the tokens fed [F, R, B], the positions still
            masked going in [F, R, B] and the experts chosen
            [F, L, R * B, k], -1 where a layer routed nothing (the
            commit's last).  A few hundred KB that stay on the device
            unless somebody asks.

    `record` adds the logits at every position [F, R, B, V] to the trail:
    0.8 GB at 64 rows, so a program of its own, for few rows.  Its logits
    are read, the commit's too, so every forward of it runs whole."""
    from dynamo_tpu.engine.sampling import diffusion_unmask, sample

    B = cfg.diffusion_block_length
    n_static = cfg.unmask_per_step
    max_iters = -(-B // n_static)
    dynamic = cfg.remasking == "low_confidence_dynamic"
    mask_id = cfg.mask_token_id
    moe = cfg.is_moe
    # A jit of its own: JAX lowers a `while_loop` body through a cache that
    # blanks the locations of the calls made directly in it, and a Pallas
    # call is named in a device trace after the jit that encloses it.  One
    # level down the names stand (`paged_decode_attention`,
    # `grouped_expert_ffn`); XLA inlines the call.
    step = jax.jit(make_forward_step(cfg, block_size, use_pallas_decode,
                                     moe_mode=moe_mode, with_expert_load=moe,
                                     moe_aux=moe),
                   static_argnames=("finish",))
    n_load = cfg.num_experts + 1 if moe else 1
    n_fwd = max_iters + 1
    top_k_experts = cfg.num_experts_per_token

    def run(params, cache, tokens, positions, seq_lens, block_tables,
            temp, top_k, top_p, base_key_data, key_offsets):
        R = tokens.shape[0]
        live = (seq_lens > 0)[:, None]
        base_keys = (None if greedy_only
                     else jax.random.wrap_key_data(base_key_data))
        moe0 = {"load": jnp.zeros((n_load,), jnp.int32),
                "touched": jnp.zeros((), jnp.int32)}

        def add_moe(acc, report):
            return {"load": acc["load"] + report["load"],
                    "touched": acc["touched"] + report["touched"]}

        rec0 = {"fed": jnp.zeros((n_fwd, R, B), jnp.int32),
                "masked": jnp.zeros((n_fwd, R, B), bool)}
        if record:
            rec0["logits"] = jnp.zeros((n_fwd, R, B, cfg.vocab_size),
                                       jnp.float32)
        if moe:
            rec0["routing"] = jnp.zeros(
                (n_fwd, cfg.num_layers, R * B, top_k_experts), jnp.int32)

        def cond(carry):
            return jnp.logical_not(carry[0])          # not yet committed

        def body(carry):
            """One forward of the call.  While a live row has a masked
            position it is a denoising forward; the first one that finds
            none is the commit: fed the final tokens, it writes their K/V
            over the block's slots, decides nothing and (unless its logits
            are recorded) stops there.  One body for both keeps one copy
            of every kernel in the program."""
            _, i, cache, toks, masked, decided, scored, acc, rec = carry
            commit = jnp.logical_not(
                jnp.any(jnp.logical_and(masked, live)))

            def score(rest):
                """What follows the last K/V write: the logits, a proposal
                at every position and the unmasking."""
                # Through a jit of its own, as `step` is: a `cond` branch
                # blanks the names of its kernels like a loop body does.
                logits, report = jax.jit(rest)()
                out = {"logits": logits} if record else {}
                logits = logits.at[..., mask_id].set(-jnp.inf)
                if greedy_only:
                    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    # One key a (row, position, forward): a seeded stream
                    # depends on the seed and the token's index alone.
                    offs = (key_offsets[:, None] * n_fwd + i) * B \
                        + jnp.arange(B, dtype=key_offsets.dtype)[None, :]
                    keys = jax.vmap(jax.vmap(
                        jax.random.fold_in, in_axes=(None, 0)))(
                            base_keys, offs)
                    rep = lambda a: jnp.repeat(a, B)   # noqa: E731
                    x0 = sample(logits.reshape(R * B, -1), rep(temp),
                                rep(top_k), rep(top_p),
                                keys.reshape(R * B)).reshape(R, B)
                _conf, decide = diffusion_unmask(
                    logits, x0, masked, n_static, cfg.confidence_threshold,
                    dynamic)
                # A decided position is masked no longer, so a commit that
                # runs whole (and a dead row, fed no mask) decides nothing.
                out.update(
                    toks=jnp.where(decide, x0, toks),
                    masked=jnp.logical_and(masked, ~decide),
                    decided=decided + jnp.sum(
                        jnp.logical_and(decide, live), dtype=jnp.int32),
                    scored=scored + 1)
                if moe:
                    out.update(load=report["load"],
                               touched=report["touched"],
                               routing=report["routing"][0].astype(jnp.int32))
                return out

            def stop():
                """The commit: nothing is masked, so nothing after the last
                K/V write would be read.  Its last layer routed nothing."""
                out = {"toks": toks, "masked": masked, "decided": decided,
                       "scored": scored}
                if moe:
                    out.update(moe0, routing=jnp.full(
                        (R * B, top_k_experts), -1, jnp.int32))
                return out

            def finish(rest):
                if record:       # its logits are read: the commit runs whole
                    return score(rest)
                return jax.lax.cond(commit, stop, lambda: score(rest))

            out, cache, before = step(params, cache, toks, positions,
                                      seq_lens, block_tables, None,
                                      finish=finish)
            rec = dict(rec, fed=rec["fed"].at[i].set(toks),
                       masked=rec["masked"].at[i].set(masked))
            if record:
                rec["logits"] = rec["logits"].at[i].set(out["logits"])
            if moe:
                rec["routing"] = rec["routing"].at[i].set(jnp.stack(
                    before["routing"] + [out["routing"]]).astype(jnp.int32))
                acc = add_moe(add_moe(acc, before), out)
            return (commit, i + 1, cache, out["toks"], out["masked"],
                    out["decided"], out["scored"], acc, rec)

        masked0 = tokens == mask_id
        zero = jnp.zeros((), jnp.int32)
        (_, n, cache, toks, _masked, decided, scored, acc,
         rec) = jax.lax.while_loop(
            cond, body, (jnp.zeros((), bool), zero, cache, tokens, masked0,
                         zero, zero, moe0, rec0))
        # n forwards ran: n - 1 denoising forwards and the commit.
        return cache, toks, jnp.stack([n - 1, decided, scored]), acc, rec

    return run


# ---------------------------------------------------------------------------
# Packed ragged prefill


def make_packed_prefill_step(cfg: ModelConfig, block_size: int,
                             moe_mode: str = "dense",
                             moe_aux: bool = False):
    """Build the packed ragged prefill step (ISSUE 10 tentpole leg 2).

    Several sequences' prefill chunks ride ONE flat `[T]` token axis
    ("segments") instead of padded `[R, T]` rows, and attention streams
    K/V pages straight from the block pool through the Pallas
    flash-prefill kernel (ops/pallas/paged_prefill.py) — no `gather_kv`
    materialisation, no per-(rows, chunk) bucket lattice.  One compiled
    shape per (packed-token bucket, page bucket) serves any mix of chunk
    lengths, so the cold-prefill shape set collapses to a handful the
    worker can prewarm at startup.

    Signature:

        logits, cache = step(params, cache, tokens[T], positions[T],
                             seg_ids[T], block_tables[R, P], q_starts[R],
                             q_lens[R], seq_lens[R], sample_positions[R])

    - tokens/positions: the packed chunks; pad rows (alignment gaps,
      tail) carry the engine's pad position, which resolves to the null
      block.
    - seg_ids: owning segment per token (selects the block-table row for
      the KV scatter); pad rows may carry any id — their pad position
      nulls the write.
    - q_starts/q_lens: each segment's packed row range (PACK_ALIGN'd
      starts); q_len 0 marks a pad segment.
    - seq_lens: total valid context per segment AFTER this chunk —
      cached-prefix residual prefill just starts the chunk positions
      past the resident prefix.
    - sample_positions: packed row whose logits each segment wants (its
      last real token); logits come back `[R, V]`.
    - window_tables[R, P] (a model with window layers only; no such
      argument otherwise): each segment's table of window-group pages, by
      position like `block_tables`, the null block where a page was let go.
    - state_slots[R] (a model with state-space layers only; no such
      argument otherwise): each segment's slot of recurrent state.  A
      segment whose first position is 0 starts from zero state, any other
      from its slot; every segment writes its last state back.

    int8 pools route through the kernel's dequant-in-VMEM variant
    (static branch on the cache pytree, like the padded step).  MoE
    models compose (ISSUE 17 killed the old exclusion): the packed
    [1, T, H] hidden rides `_moe_block` with the meshless `moe_mode`
    ("dense" oracle or "grouped" fast path — packed prefill is a
    meshless-engine plane) and the step returns a THIRD output, the
    [E+1] expert-load stats vector; with `moe_aux` that output is the
    dict `make_forward_step` gives under the same flag (`load`, `touched`,
    `routing` [L, T, k]).  The kernel runs in interpret mode off-TPU, so
    the packed plane is CPU-testable like the decode kernel.
    """
    cfg.validate()
    from dynamo_tpu.ops.pallas import (
        paged_prefill_attention, paged_window_prefill_attention)
    from dynamo_tpu.ops.pallas.latent_attention import (
        latent_prefill_attention)

    def step(params, cache, tokens, positions, seg_ids, block_tables,
             q_starts, q_lens, seq_lens, sample_positions, state_slots=None,
             window_tables=None):
        T = tokens.shape[0]
        interp = jax.default_backend() != "tpu"

        def slots_through(tables):
            # Per-token write slots through the owning segment's table.
            bt_tok = jnp.take(tables, seg_ids, axis=0)          # [T, P]
            return kvc.slots_for_positions(
                bt_tok, positions[:, None], block_size).reshape(T)

        write_slots = slots_through(block_tables)
        window_tables = _window_tables(cfg, window_tables)
        if window_tables is not None:
            window_slots = slots_through(window_tables)

        x = _embedding_scaled(
            cfg, jnp.take(params["embed"], tokens, axis=0)[None])  # [1, T, H]
        pos2 = positions[None]                                  # [1, T]
        if cfg.has_ssm:
            # Each segment's scan and convolution restart at its first
            # token: from zero where that is the sequence's first, from the
            # slot where the prompt continues from an earlier chunk.
            slots = _state_slots(cache, state_slots, q_lens > 0)
            fresh = jnp.take(positions, jnp.clip(q_starts, 0, T - 1)) == 0

        def attn_write(p_attn, h, bufs, i=0):
            if cfg.is_latent:
                q_abs, rows = _latent_project(cfg, p_attn, h, pos2)
                return {"kv": kvc.write_latent(bufs["kv"], write_slots,
                                               rows[0])}, q_abs
            q, k, v = _project_qkv(cfg, p_attn, h, pos2, i)
            slots = window_slots if cfg.window_of(i) else write_slots
            return _attention_write(cfg, q, k, v, slots, bufs)[0], q

        def attn_read(p_attn, bufs, q, i=0):
            # Write-then-attend: the chunk's own K/V (or rows) are
            # pool-resident now, so cached prefix and in-chunk causality
            # are one position mask inside the kernel.
            if cfg.is_latent:
                o_lat = latent_prefill_attention(
                    q[0], bufs["kv"], block_tables, seq_lens, q_starts,
                    q_lens, block_size=block_size, scale=_latent_scale(cfg),
                    v_width=cfg.kv_lora_rank, interpret=interp)
                return _latent_out(cfg, p_attn, o_lat[None])
            window = cfg.window_of(i)
            if window:
                out = paged_window_prefill_attention(
                    q[0], bufs["k"], bufs["v"], window_tables, seq_lens,
                    q_starts, q_lens, block_size=block_size,
                    scale=cfg.query_scale, soft_cap=cfg.attn_soft_cap,
                    interpret=interp, window=window)
                return out.reshape(1, T, cfg.q_size) @ p_attn["wo"]
            out = paged_prefill_attention(
                q[0], bufs["k"], bufs["v"], block_tables, seq_lens,
                q_starts, q_lens, block_size=block_size,
                scale=cfg.query_scale, soft_cap=cfg.attn_soft_cap,
                interpret=interp,
                k_scale=bufs.get("k_scale"), v_scale=bufs.get("v_scale"),
                mask_block=cfg.diffusion_block_length)
            return out.reshape(1, T, cfg.q_size) @ p_attn["wo"]

        def state(p_ssm, h, bufs):
            out, ssm, conv = mamba_prefill(
                cfg, p_ssm, h[0], bufs["ssm"], bufs["conv"], slots, seg_ids,
                q_starts, q_lens, fresh)
            return out[None], {"ssm": ssm, "conv": conv}

        open_cache = OpenCache(cfg, cache)
        x, tally = next(walk_layers(
            cfg, params["layers"], x, open_cache,
            Mixers(attn_write, attn_read, state), moe_mode, None, moe_aux))
        # LM head on one packed row per segment ([R, H] @ [H, V]).
        logits = _head(cfg, params, x, lambda x: jnp.take(
            x[0], sample_positions.astype(jnp.int32), axis=0))
        return _step_result(logits, open_cache.close(), tally, cfg.is_moe,
                            moe_aux)

    return step


# ---------------------------------------------------------------------------
# Forward


def chunk_mixers(cfg: ModelConfig, block_size: int, positions, seq_lens,
                 block_tables, write_slots, ctx_slots, ctx_positions,
                 slots=None, sp_mesh=None, sp_pallas=False, pallas_mesh=None,
                 dp_local_mesh=None, dp_local_pallas=False,
                 window_tables=None) -> Mixers:
    """The mixers of a padded [B, T] chunk (`make_forward_step`'s, and a
    pipeline stage's): the chunk's K/V go to `write_slots` [B*T]; its
    queries read the gathered context (`ctx_slots`, `ctx_positions` [B, C])
    or, with those None, one of `_attention_read`'s other forms (the Pallas
    decode kernel, under `pallas_mesh` sharded; the ring over `sp_mesh`) or
    the device-local body (`dp_local_mesh`).  `slots` [B]: each row's slot
    of recurrent state (a model with state-space layers).  `window_tables`
    [B, P]: each row's table of window-group pages (a model with window
    layers), through which its window layers write and read."""
    B, T = positions.shape
    if window_tables is not None:
        window_write = kvc.slots_for_positions(
            window_tables, positions, block_size).reshape(B * T)
        window_ctx = None if ctx_slots is None else kvc.slots_for_positions(
            window_tables, ctx_positions, block_size)

    def attn_write(p_attn, h, bufs, i=0):
        if cfg.is_latent:
            q_abs, rows = _latent_project(cfg, p_attn, h, positions)
            return {"kv": kvc.write_latent(bufs["kv"], write_slots,
                                           rows.reshape(B * T, -1))}, q_abs
        q, k, v = _project_qkv(cfg, p_attn, h, positions, i)
        if cfg.window_of(i):
            return _attention_write(cfg, q, k, v, window_write, bufs)[0], (
                q, k, v, None)
        if dp_local_mesh is not None:
            # Write and read are one shard-local body there.
            out, bufs = _dp_local_attention(
                cfg, p_attn, q, k, v, positions, seq_lens, block_tables,
                block_size, bufs, dp_local_mesh, dp_local_pallas)
            return bufs, out
        bufs, ring_quant = _attention_write(cfg, q, k, v, write_slots, bufs,
                                            ring=sp_mesh is not None)
        return bufs, (q, k, v, ring_quant)

    def attn_read(p_attn, bufs, carried, i=0):
        if cfg.is_latent:
            return _latent_read(cfg, p_attn, carried, bufs["kv"], positions,
                                seq_lens, ctx_slots, ctx_positions,
                                block_tables, block_size)
        if dp_local_mesh is not None:
            return carried
        q, k, v, ring_quant = carried
        window = cfg.window_of(i)
        if window:
            return _attention_read(cfg, p_attn, q, k, v, bufs, None,
                                   positions, seq_lens, window_ctx,
                                   ctx_positions, window_tables, block_size,
                                   window=window)
        return _attention_read(cfg, p_attn, q, k, v, bufs, ring_quant,
                               positions, seq_lens, ctx_slots, ctx_positions,
                               block_tables, block_size, sp_mesh, sp_pallas,
                               pallas_mesh)

    def state(p_ssm, h, bufs):
        """A T == 1 step advances the state by its token; a chunk is one
        segment a row, from the row's first position on."""
        if T == 1:
            out, ssm, conv = mamba_decode(cfg, p_ssm, h[:, 0], bufs["ssm"],
                                          bufs["conv"], slots)
            return out[:, None], {"ssm": ssm, "conv": conv}
        first = positions[:, 0]
        out, ssm, conv = mamba_prefill(
            cfg, p_ssm, h.reshape(B * T, -1), bufs["ssm"], bufs["conv"],
            slots, jnp.repeat(jnp.arange(B, dtype=jnp.int32), T),
            jnp.arange(B, dtype=jnp.int32) * T,
            jnp.clip(seq_lens - first, 0, T), first == 0)
        return out.reshape(B, T, -1), {"ssm": ssm, "conv": conv}

    return Mixers(attn_write, attn_read, state)


def make_forward_step(cfg: ModelConfig, block_size: int,
                      use_pallas_decode: bool = False,
                      moe_mode: str = "dense",
                      mesh=None,
                      with_expert_load: bool = False,
                      sp_ring: bool = False,
                      sp_ring_pallas: bool = False,
                      return_hidden: bool = False,
                      with_input_embeds: bool = False,
                      dp_local: bool = False,
                      moe_aux: bool = False):
    """Build the jitted unified step for a given cache geometry.

    Separate factory (rather than passing block_size as a traced value)
    because slot math needs the block size statically for XLA to fold the
    index arithmetic.  With `use_pallas_decode`, T==1 traces route
    attention through the Pallas paged-decode kernel instead of the
    gathered-context XLA path (chunk length is static at trace time, so
    the same factory serves both prefill and decode compilations).

    MoE: `moe_mode` "dense" (exact oracle), "grouped" (meshless ragged
    grouped GEMM) or "dispatch" (all-to-all over the mesh's ep axis —
    needs `mesh`).  `with_expert_load=True` makes the step return
    (logits, cache, stats[E+1]) — per-expert assignment counts plus the
    dropped-assignments tail, the telemetry the reference exposes per
    worker (`base_handlers.py:40-62`); the default 2-tuple return keeps
    every non-MoE call site unchanged.

    `sp_ring`: sequence-parallel FULL-PROMPT prefill — the T axis shards
    over the mesh's sp axis and attention runs on the ICI ring.  The
    chunk must be the whole sequence (no prior cached context is read);
    build via parallel.sharding.make_sp_prefill_step.  With
    `sp_ring_pallas`, eligible geometry runs the Pallas flash ring
    kernel (ops/pallas/ring_attention.py — RDMA exchange hidden under
    the fold) instead of the XLA ppermute ring.

    A block-diffusion model (`cfg.diffusion_block_length` B > 1) masks
    by block (`kv // B <= q // B`); a chunk of exactly B tokens is one
    block, whose queries all see `[0, seq_len)`, and with
    `use_pallas_decode` rides the decode kernel like a T == 1 step.

    `moe_aux` (with `with_expert_load`): the third output is a dict —
    `load` [E+1] as before, `touched` (distinct experts with at least one
    row, summed over the layers) and `routing` [L, B*T, k] (the experts
    each token chose in each layer).

    `state_slots` (an argument of the step for a model with state-space
    layers, and of no other): each row's slot of recurrent state in the
    cache's `ssm` and `conv` leaves.  A T == 1 step advances the state by
    its token; a chunk scans it from the slot, or from zero where the row's
    first position is 0, and writes its last state back.  Padding rows
    (seq_len 0) use the scratch slot.

    `window_tables` (an argument of the step for a model with window layers,
    and of no other): each row's table of window-group pages, by position
    like `block_tables`; its window layers write and read through it, and an
    entry whose page the sequence let go is the null block.

    `finish` (an argument of the step, meshless; None everywhere but in
    `make_block_step`): for a caller that may not need the logits.  Once
    the last layer's K and V are written the step calls `finish(rest)`,
    where `rest()` runs what is left of the forward (that layer's
    attention read and MLP or experts, the final norm, the head) and
    returns (logits, the last layer's report), and returns (what `finish`
    returned, cache, the report of the layers before) with each report
    {load, touched, routing: a list of [B*T, k], one a layer}.
    """
    cfg.validate()
    block_len = cfg.diffusion_block_length
    if cfg.is_latent and (mesh is not None or sp_ring or dp_local):
        raise ValueError(LATENT_MESHLESS)
    if cfg.has_ssm and (mesh is not None or sp_ring or dp_local):
        raise ValueError(STATE_MESHLESS)
    if cfg.has_ssm and with_input_embeds:
        raise ValueError("multimodal input embeddings are not wired for a "
                         "model with state-space layers")
    if cfg.has_window and (mesh is not None or sp_ring or dp_local):
        raise ValueError(WINDOW_MESHLESS)

    def step(
        params: Params,
        cache: Dict,
        tokens: jax.Array,            # [B, T]
        positions: jax.Array,         # [B, T]
        seq_lens: jax.Array,          # [B]
        block_tables: jax.Array,      # [B, P]
        sample_positions=None,        # [B] chunk-local index, or None = all
        input_embeds=None,            # [B, T, H] (with_input_embeds only)
        embed_mask=None,              # [B, T] bool: row uses input_embeds
        finish=None,                  # what may stop at the last K/V write
        state_slots=None,             # [B] (state-space layers only)
        window_tables=None,           # [B, P] (window layers only)
    ) -> Tuple[jax.Array, Dict]:
        B, T = tokens.shape
        P = block_tables.shape[1]
        C = P * block_size  # max context representable by the table

        write_slots = kvc.slots_for_positions(block_tables, positions, block_size)
        write_slots = write_slots.reshape(B * T)

        if ((use_pallas_decode or dp_local) and T == 1) \
                or (sp_ring and T > 1) \
                or (use_pallas_decode and block_len > 1 and T == block_len
                    and mesh is None):
            ctx_positions = ctx_slots = None  # no materialised ctx gather
        else:
            ctx_positions = jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32), (B, C)
            )
            ctx_slots = kvc.slots_for_positions(
                block_tables, ctx_positions, block_size)

        x = jnp.take(params["embed"], tokens, axis=0)
        if with_input_embeds:
            # Multimodal prefill: masked chunk positions take provided
            # embeddings (the encode worker's vision-tower output) in
            # place of the token lookup (llm/multimodal.py).
            x = jnp.where(embed_mask[:, :, None],
                          input_embeds.astype(x.dtype), x)
        x = _embedding_scaled(cfg, x)
        mixers = chunk_mixers(
            cfg, block_size, positions, seq_lens, block_tables, write_slots,
            ctx_slots, ctx_positions,
            # Recurrent state beside the pages: a row's slot, the scratch
            # slot for a padding row.
            slots=(_state_slots(cache, state_slots, seq_lens > 0)
                   if cfg.has_ssm else None),
            sp_mesh=mesh if (sp_ring and T > 1) else None,
            sp_pallas=sp_ring_pallas,
            # dp_local owns its own shard_map body; pallas routing
            # there happens INSIDE it (local slot rebase), not via
            # the head-sharded pallas_mesh wrapper.
            pallas_mesh=(mesh if (use_pallas_decode and T == 1
                                  and mesh is not None
                                  and not dp_local) else None),
            dp_local_mesh=(mesh if (dp_local and T == 1
                                    and mesh is not None) else None),
            dp_local_pallas=use_pallas_decode and dp_local,
            window_tables=_window_tables(cfg, window_tables))

        def rows(x):
            # None keeps every position (tests, logprob paths).
            if sample_positions is None:
                return x
            return jnp.take_along_axis(
                x, sample_positions[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]

        def head(x):
            return _head(cfg, params, x, rows, return_hidden)

        open_cache = OpenCache(cfg, cache)
        steps = walk_layers(cfg, params["layers"], x, open_cache, mixers,
                            moe_mode, mesh, moe_aux,
                            pause=finish is not None)
        if finish is not None:
            # (Meshless: `make_block_step`'s.)
            before = next(steps)

            def rest():
                y, tail = next(steps)
                return head(y), tail

            return finish(rest), open_cache.close(), before

        x, tally = next(steps)
        return _step_result(head(x), open_cache.close(), tally,
                            with_expert_load and not return_hidden, moe_aux)

    return step
