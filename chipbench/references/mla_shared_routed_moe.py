"""Plain reference forward pass of the latent-attention decoder with a shared
expert beside sigmoid-routed experts behind leading dense layers: the block
of `glm4_moe_lite` (GLM-4.7-Flash), which is DeepSeek-V3's at other sizes.

Written from the published configuration
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) and the
published description of the block (DeepSeek-V2, arXiv:2405.04434, section
2.1 for the attention; DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for the
router), not from `dynamo_tpu/models`.  Pre-norm residual decoder, RMSNorm,
no biases, SiLU.  With H heads, `dn` = qk_nope_head_dim, `dr` =
qk_rope_head_dim, `dv` = v_head_dim, `r` = kv_lora_rank:

- attention: `h = RMSNorm(x)`; `c_q = RMSNorm(h W_qa)` (q_lora_rank);
  `q = c_q W_qb` as [T, H, dn + dr], split `q_nope | q_rope`;
  `h W_kva` (r + dr) splits into `c_kv = RMSNorm(first r)` and
  `k_rope = RoPE(last dr)`, one for all heads; `c_kv W_kvb` as
  [T, H, dn + dv] splits `k_nope | v`; `q_rope = RoPE(q_rope)`;
  `k = [k_nope | k_rope]`; scores `q . k * (dn + dr)**-0.5`, causal softmax;
  `o = sum p v`, heads concatenated (H * dv) through `W_o`.  This is the
  MATERIALISED form: keys and values are built for every position.  (The
  program reads its cache in the weight-absorbed form, which is the same
  sum in another order; a test holds the two together in float32.)
- the first `first_k_dense_replace` layers: SwiGLU MLP of
  `intermediate_size`; the others: `s = sigmoid(h W_g)` in float32 over the
  E routed experts; S = the k experts with the largest `s + b` (`b` the
  learned correction bias, `noaux_tc`; `n_group` = `topk_group` = 1, so no
  group limit); `w_e = s_e / sum_S s * routed_scaling_factor`
  (`norm_topk_prob`); `y = shared(h) + sum_{e in S} w_e expert_e(h)`, each a
  SwiGLU of `moe_intermediate_size` (the shared one `n_shared_experts`
  times as wide);
- final RMSNorm, untied head.

Departures, each stated where it applies:
- the multi-token-prediction module (`num_nextn_predict_layers`) is left
  out: no logit depends on it (the configuration lists it under `reduced`);
- rotary pairing: pair (i, i + dr/2), the Hugging Face `rotate_half` layout.
  `config.json` has no key for it; with seeded weights the interleaved
  pairing differs by a fixed permutation of W_qb / W_kva columns (the
  configuration's `assumed`);
- `choices` (optional): the experts each token is to use in each EXPERT
  layer, [L_moe, T, k], rows of -1 = choose here.  bfloat16 flips which
  expert is the k-th largest on seeded weights, and a float32 forward that
  chose for itself would measure those flips and not the arithmetic: the
  comparison hands over the choices the engine made.  The scores and the
  weights are still this file's own float32;
- `shortfall` (optional, with `choices`): also hand back how far the
  choices given lie under this file's own, so that taking them cannot hide a
  router that chooses wrongly: for each expert a token was given, the k-th
  best float32 `s + b` of that token less the given expert's `s + b`, 0 where
  it is among the k best; the largest over all expert layers and tokens.  A
  bfloat16 flip at the k-th place reads a few thousandths, a choice by `s`
  alone or with `b` left out a few tenths.  `forward` then returns
  (logits, shortfall);
- `positions` (optional): the positions whose logits are wanted.

float32 throughout with `jax.default_matmul_precision("highest")`.  No
cache, no kernels, no batching: one sequence at a time, the whole causal
forward, computed in blocks so that 12,000 tokens fit beside ten gigabytes
of served weights: attention a block of queries at a time, experts a few at
a time over a block of tokens, the head a slice of the vocabulary at a time.
Weights arrive in the type they are served in and are up-cast as they are
used.  Only the weight LAYOUT is the program's (`embed`, `layers[i]`
{`attn`: wq_a q_a_norm wq_b wkv_a kv_a_norm wkv_b wo, all [in, out];
`attn_norm`, `mlp_norm`; `mlp` {w_gate w_up w_down} or `moe` {router [H, E],
router_bias [E], w_gate w_up [E, H, F], w_down [E, F, H], shared {w_gate
w_up w_down}}}, `final_norm`, `lm_head`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256        # queries whose scores [H, block, T] are live at once
TOKEN_BLOCK = 1024       # tokens an expert chunk is applied to at once
EXPERT_CHUNK = 8         # experts up-cast at a time (8 x 3 x H x F floats)
VOCAB_CHUNK = 32768      # head columns up-cast at a time


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotary(x, theta, first=0):
    """x: [T, heads, D]; position first + t rotates pair (i, i + D/2) by
    (first + t) * theta**(-2i/D)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) * 2.0 / d)
    ang = (first + jnp.arange(t, dtype=F32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "dn", "r", "theta",
                                             "eps"))
def _keys_values(h, wkv_a, kv_a_norm, wkv_b, *, heads, dn, r, theta, eps):
    """Every position's key [T, H, dn + dr] and value [T, H, dv], built."""
    t = h.shape[0]
    kv = h @ wkv_a.astype(F32)                                  # [T, r + dr]
    c_kv = _rms_norm(kv[:, :r], kv_a_norm, eps)
    k_rope = _rotary(kv[:, None, r:], theta)                    # [T, 1, dr]
    up = (c_kv @ wkv_b.astype(F32)).reshape(t, heads, -1)       # [T,H,dn+dv]
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_rope, (t, heads, k_rope.shape[-1]))],
        axis=-1)
    return k, up[..., dn:]


@functools.partial(jax.jit, static_argnames=("heads", "dn", "theta", "eps"))
def _attend(h_blk, first, k, v, wq_a, q_a_norm, wq_b, wo, *, heads, dn,
            theta, eps):
    """A block of queries (positions first ..) over all keys: [Bq, hidden]."""
    bq, t = h_blk.shape[0], k.shape[0]
    c_q = _rms_norm(h_blk @ wq_a.astype(F32), q_a_norm, eps)
    q = (c_q @ wq_b.astype(F32)).reshape(bq, heads, -1)         # [Bq,H,dn+dr]
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta, first)],
                        axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (q.shape[-1] ** -0.5)
    sees = jnp.arange(t)[None, :] <= (first + jnp.arange(bq))[:, None]
    scores = jnp.where(sees[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(bq, -1)
    return out @ wo.astype(F32)


def attention_layer(hf: dict, layer: dict, x):
    """x + attention(RMSNorm(x)), the materialised published form."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    theta = float(hf.get("rope_theta", 10000.0))
    kw = dict(heads=hf["num_attention_heads"], dn=hf["qk_nope_head_dim"],
              theta=theta, eps=eps)
    a = layer["attn"]
    h = _norm(x, layer["attn_norm"], eps=eps)
    k, v = _keys_values(h, a["wkv_a"], a["kv_a_norm"], a["wkv_b"],
                        r=hf["kv_lora_rank"], **kw)
    t = x.shape[0]
    out = [_attend(h[q0: q0 + QUERY_BLOCK], q0, k, v, a["wq_a"],
                   a["q_a_norm"], a["wq_b"], a["wo"], **kw)
           for q0 in range(0, t, QUERY_BLOCK)]
    return x + jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("top_k", "factor"))
def _route(h, router, bias, chosen, *, top_k, factor):
    """Each token's weight on every routed expert [T, E], zero on those it
    does not use, and how far the experts it uses lie under its own k best
    (a scalar, the largest over tokens).  `chosen` [T, k]: rows of -1 choose
    here (by s + b), others are used as given; the weights come from s
    either way."""
    s = jax.nn.sigmoid(h @ router.astype(F32))                  # [T, E]
    biased = s + bias.astype(F32)
    best, own = jax.lax.top_k(biased, top_k)
    use = jnp.where(chosen[:, :1] < 0, own, chosen)             # [T, k]
    short = best[:, -1:] - jnp.take_along_axis(biased, use, axis=-1)
    picked = jax.nn.one_hot(use, s.shape[-1], dtype=F32).sum(axis=1)
    w = s * picked
    return (w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * factor,
            jnp.max(jnp.maximum(short, 0.0)))


@jax.jit
def _experts(h, weights, w_gate, w_up, w_down):
    """sum over this chunk's experts of weight * SwiGLU expert: [T, H]."""
    a = jnp.einsum("th,ehf->etf", h, w_gate.astype(F32))
    b = jnp.einsum("th,ehf->etf", h, w_up.astype(F32))
    out = jnp.einsum("etf,efh->eth", jax.nn.silu(a) * b,
                     w_down.astype(F32))
    return jnp.einsum("eth,te->th", out, weights)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    gate = jax.nn.silu(h @ w_gate.astype(F32))
    return (gate * (h @ w_up.astype(F32))) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


@jax.jit
def _head(x, w):
    return x @ w.astype(F32)


def moe_layer(hf: dict, layer: dict, x, chosen=None):
    """One expert layer's contribution y (so that x + y goes on): the
    shared expert and the weighted routed ones, [T, H]."""
    return _moe_layer(hf, layer, x, chosen)[0]


def _moe_layer(hf: dict, layer: dict, x, chosen):
    """(`moe_layer`'s y, the shortfall of `chosen` under this layer's own
    choice: `_route`)."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    top_k = hf["num_experts_per_tok"]
    if not hf.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not described here")
    m = layer["moe"]
    t = x.shape[0]
    if chosen is None:
        chosen = jnp.full((t, top_k), -1, jnp.int32)
    h = _norm(x, layer["mlp_norm"], eps=eps)
    weights, short = _route(
        h, m["router"], m["router_bias"], jnp.asarray(chosen, jnp.int32),
        top_k=top_k, factor=float(hf.get("routed_scaling_factor", 1.0)))
    n = m["w_gate"].shape[0]
    blocks = []
    for t0 in range(0, t, TOKEN_BLOCK):
        hb, wb = h[t0: t0 + TOKEN_BLOCK], weights[t0: t0 + TOKEN_BLOCK]
        y = jnp.zeros_like(hb)
        if "shared" in m:
            sh = m["shared"]
            y = _swiglu(hb, sh["w_gate"], sh["w_up"], sh["w_down"])
        for e0 in range(0, n, EXPERT_CHUNK):
            e1 = min(e0 + EXPERT_CHUNK, n)
            y = y + _experts(hb, wb[:, e0:e1], m["w_gate"][e0:e1],
                             m["w_up"][e0:e1], m["w_down"][e0:e1])
        blocks.append(y)
    return jnp.concatenate(blocks, axis=0), short


def forward(hf: dict, params: dict, tokens, choices=None, positions=None,
            shortfall=False):
    """Logits (float32) of one sequence of token ids under the causal mask:
    [T, vocab], or [len(positions), vocab] for the positions asked; with
    `shortfall`, (logits, the largest shortfall of `choices`: a float)."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    shorts = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        moe_i = 0
        for layer in params["layers"]:
            x = attention_layer(hf, layer, x)
            if "moe" in layer:
                y, short = _moe_layer(hf, layer, x, None if choices is None
                                      else choices[moe_i])
                x = x + y
                shorts.append(short)
                moe_i += 1
            else:
                m = layer["mlp"]
                x = x + _swiglu(_norm(x, layer["mlp_norm"], eps=eps),
                                m["w_gate"], m["w_up"], m["w_down"])
        if positions is not None:
            x = jnp.take(x, jnp.asarray(np.asarray(positions), jnp.int32),
                         axis=0)
        x = _norm(x, params["final_norm"], eps=eps)
        head = params["lm_head"]
        vocab = head.shape[1]
        logits = jnp.concatenate(
            [_head(x, head[:, v0: v0 + VOCAB_CHUNK])
             for v0 in range(0, vocab, VOCAB_CHUNK)], axis=-1)
    if shortfall:
        return logits, max((float(v) for v in shorts), default=0.0)
    return logits
