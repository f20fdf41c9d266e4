"""Plain reference forward pass of the block-diffusion decoder over routed
experts: RMSNorm, rotary grouped-query attention with RMSNorm on each head of
q and k, a block-causal mask, a mixture of routed SwiGLU experts, and a head
whose logits predict their own position.

Written from the published description of SDAR (JetLM/SDAR-30B-A3B-Chat,
`model_type: sdar_moe`, whose block is Qwen3-MoE's) and of block diffusion
(Arriola et al., "Block Diffusion", arXiv:2503.09573), not from
`dynamo_tpu/models`.  With B the block length and `blk(i) = i // B`:

- attention, pre-norm: `h = RMSNorm(x)`; `q = h Wq` as [T, heads, D],
  `k = h Wk`, `v = h Wv` as [T, kv_heads, D]; `q = RMSNorm_D(q; w_q)`,
  `k = RMSNorm_D(k; w_k)` over the head dimension, one weight vector of D
  shared by all heads; rotary embedding on halves of the head (the Hugging
  Face `rotate_half` layout), base `rope_theta`; scores scaled by D**-0.5;
  position i sees position j iff `blk(j) <= blk(i)` (every position of its
  own block, in both directions, beside everything before it);
  `x = x + softmax(scores) v Wo`;
- experts: `h = RMSNorm(x)`; `r = softmax(h W_r)` in float32 over all E
  experts; S = the k largest; `g_e = r_e / sum_S r` (`norm_topk_prob`);
  `y = sum_{e in S} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e`; `x = x + y`.  This
  equals the softmax over the chosen experts' logits (what the program's
  `ops/moe.router_topk` computes): `r_e / sum_S r = exp(l_e) / sum_S exp(l)`;
- head: `logits_i = RMSNorm(x_i) W_head`, the prediction for position i
  itself (no shift).

Generation (the sampler; `comparisons/block_denoise_logits.py` follows it):
a block of B positions after the committed text starts as `[MASK]`; while any
is masked, one forward over committed text + block, `x0_i = argmax logits_i`,
`c_i = softmax(logits_i)[x0_i]` at masked positions; the `B /
denoising_steps` most confident are unmasked (`low_confidence_static`), or
every position with `c_i > threshold` if that is at least as many
(`low_confidence_dynamic`); a decided position is never masked again.

Departures, each stated where it applies:
- the mask token's logit is set to -inf before the argmax and the softmax
  of the confidence (by the comparison and by the program alike), so that a
  generated token is never the mask.  `forward` returns the raw logits;
- `choices` (optional): the experts each token is to use in each layer,
  [L, T, k], rows of -1 = choose here.  bfloat16 flips which expert is the
  k-th largest on seeded weights (PERF.md section 6, PR 23), and a float32
  forward that chose for itself would measure those flips and not the
  arithmetic: the comparison hands over the choices the engine made.  The
  gates are still this file's own float32 softmax over the chosen;
- `held` (optional): (first, count) of the experts whose weights `params`
  holds, for a deployment that shares a layer's experts over chips; routing
  is over all E, and assignments to experts held elsewhere add nothing.

float32 throughout with `jax.default_matmul_precision("highest")`.  No cache,
no kernels, no batching: one sequence at a time, the whole block-causal
forward.  Weights arrive in the type they are served in and are up-cast a
few experts (and a slice of the vocabulary) at a time, so the reference fits
beside ten gigabytes of served weights.  Only the weight LAYOUT is the
program's (`embed`, `layers[i]` {`attn`: wq wk wv wo [in, out], q_norm,
k_norm [D]; `attn_norm`, `mlp_norm`; `moe`: router [H, E], w_gate, w_up
[E, H, F], w_down [E, F, H]}, `final_norm`, `lm_head`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_CHUNK = 16        # experts up-cast at a time (16 x 3 x H x F floats)
VOCAB_CHUNK = 32768      # head columns up-cast at a time


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotary(x, theta):
    """x: [T, heads, D]; position t rotates pair (i, i + D/2) by
    t * theta**(-2i/D)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) * 2.0 / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "block"))
def _attention(x, norm_w, wq, wk, wv, wo, q_norm, k_norm, *, heads, kv_heads,
               head_dim, theta, eps, block):
    t = x.shape[0]
    h = _rms_norm(x, norm_w, eps)
    q = (h @ wq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, head_dim)
    q, k = _rms_norm(q, q_norm, eps), _rms_norm(k, k_norm, eps)
    q, k = _rotary(q, theta), _rotary(k, theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (head_dim ** -0.5)
    blk = jnp.arange(t) // block
    sees = blk[None, :] <= blk[:, None]          # [query, key]
    scores = jnp.where(sees[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return x + out @ wo.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renorm"))
def _route(x, norm_w, router, chosen, *, eps, top_k, renorm):
    """(h, gates [T, E]): the normed input and each token's gate on every
    expert, zero on those it does not use.  `chosen` [T, k]: rows of -1
    choose here, others are used as given."""
    h = _rms_norm(x, norm_w, eps)
    r = jax.nn.softmax(h @ router.astype(F32), axis=-1)          # [T, E]
    _, own = jax.lax.top_k(r, top_k)
    use = jnp.where(chosen[:, :1] < 0, own, chosen)              # [T, k]
    picked = jax.nn.one_hot(use, r.shape[-1], dtype=F32).sum(axis=1)
    gates = r * picked
    if renorm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return h, gates


@jax.jit
def _experts(h, gates, w_gate, w_up, w_down):
    """sum over this chunk's experts of gate * SwiGLU expert: [T, H]."""
    a = jnp.einsum("th,ehf->etf", h, w_gate.astype(F32))
    b = jnp.einsum("th,ehf->etf", h, w_up.astype(F32))
    out = jnp.einsum("etf,efh->eth", jax.nn.silu(a) * b,
                     w_down.astype(F32))
    return jnp.einsum("eth,te->th", out, gates)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


@jax.jit
def _head(x, w):
    return x @ w.astype(F32)


def moe_layer(hf: dict, layer: dict, x, chosen=None, held=None):
    """One expert layer's contribution y (so that x + y goes on): [T, H].
    `held` = (first, count): `layer["moe"]` holds those experts only."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    top_k = hf["num_experts_per_tok"]
    m = layer["moe"]
    t = x.shape[0]
    if chosen is None:
        chosen = jnp.full((t, top_k), -1, jnp.int32)
    h, gates = _route(x, layer["mlp_norm"], m["router"],
                      jnp.asarray(chosen, jnp.int32), eps=eps, top_k=top_k,
                      renorm=bool(hf.get("norm_topk_prob", True)))
    first, count = held if held is not None else (0, m["w_gate"].shape[0])
    y = jnp.zeros_like(x)
    for e0 in range(0, count, EXPERT_CHUNK):
        e1 = min(e0 + EXPERT_CHUNK, count)
        y = y + _experts(h, gates[:, first + e0: first + e1],
                         m["w_gate"][e0:e1], m["w_up"][e0:e1],
                         m["w_down"][e0:e1])
    return y


def forward(hf: dict, params: dict, tokens, choices=None, positions=None,
            held=None):
    """Logits (float32) of one sequence of token ids under the block-causal
    mask: [T, vocab], or [len(positions), vocab] for the positions asked."""
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    kv_heads = hf.get("num_key_value_heads", heads)
    eps = float(hf.get("rms_norm_eps", 1e-6))
    theta = float(hf.get("rope_theta", 10000.0))
    block = int(hf["diffusion_block_length"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        for li, layer in enumerate(params["layers"]):
            a = layer["attn"]
            x = _attention(x, layer["attn_norm"], a["wq"], a["wk"], a["wv"],
                           a["wo"], a["q_norm"], a["k_norm"], heads=heads,
                           kv_heads=kv_heads, head_dim=head_dim, theta=theta,
                           eps=eps, block=block)
            x = x + moe_layer(hf, layer, x,
                              None if choices is None else choices[li], held)
        if positions is not None:
            x = jnp.take(x, jnp.asarray(np.asarray(positions), jnp.int32),
                         axis=0)
        x = _norm(x, params["final_norm"], eps=eps)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        vocab = head.shape[1]
        return jnp.concatenate(
            [_head(x, head[:, v0: v0 + VOCAB_CHUNK])
             for v0 in range(0, vocab, VOCAB_CHUNK)], axis=-1)
