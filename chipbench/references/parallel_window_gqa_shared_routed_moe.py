"""Plain reference forward pass of the decoder whose layers are window or full
attention by a list, with attention and routed experts side by side on one
norm: the block of `cohere2_moe` (Command A+).

Written from the published configuration
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json)
and its keys' published meaning (Cohere's Command R / Command A model code
for the norm, the parallel block and the interleaved rotary embedding; the
DeepSeek-V3 router, arXiv:2412.19437 section 2.1.2, for sigmoid selection),
not from `dynamo_tpu/models`.  No biases, SiLU.  For every layer l of
`layer_types`:

- `h = LayerNorm(x)`: the mean subtracted, the variance over the hidden size,
  times a weight, no bias, `layer_norm_eps` (`rms_norm_eps` is null: it is
  not an RMSNorm);
- parallel block (`use_parallel_block`): `x' = x + Attn(h) + FFN(h)`, both on
  the one normed input; no second norm;
- `Attn`: q, k, v, o projections, `num_attention_heads` query heads and
  `num_key_value_heads` key/value heads of `head_dim` (query head j reads
  key/value head j // group), no q/k norm, scores times `head_dim ** -0.5`.
  A `sliding_attention` layer turns q and k by the rotary embedding over
  INTERLEAVED pairs (`position_embedding_type: rope_gptj`: pair i is
  (x[2i], x[2i + 1]), angle position * rope_theta ** (-2i / head_dim),
  `rotary_pct` 1) and query i sees key j iff `0 <= i - j < sliding_window`.
  A `full_attention` layer applies NO position term and is causal over the
  whole context;
- `FFN`: scores `s = sigmoid(h W_r)` in float32 over `num_experts`, the
  `num_experts_per_tok` largest chosen (no correction bias: the config has
  no such key), gates `s / sum(s chosen)` (`norm_topk_prob`), routed = sum of
  gate * SwiGLU_e(h) at width `intermediate_size`; shared = the MEAN of
  `num_shared_experts` always-on SwiGLUs of that width
  (`shared_expert_combination_strategy: average`); `FFN = routed + shared`;
- head: final LayerNorm, logits = `logit_scale` * h Emb^T (tied).

Departures, each stated where it applies:
- `routed_experts_held` ({"first", "count", "of"}, the deployment's key):
  the router scores all `of` experts and chooses among all of them; of the
  chosen, only those in [first, first + count) are computed and summed, and
  what the others would have added is left out, as on the chip that holds
  that share (the gates are still renormalised over ALL the chosen);
- the vocabulary slice: `embed` holds the rows it is given;
- the shared experts arrive as the program stores them, one SwiGLU of width
  `num_shared_experts * intermediate_size` whose column blocks are the
  experts; they are applied one by one here and averaged;
- the "average": the mean of the shared experts ADDED to the routed sum (the
  configuration's `assumed`; the other reading, (routed + shared) / 2, is
  not this file's);
- `choices` (optional): the experts each token is to use in each layer,
  [L, T, k], rows of -1 = choose here.  bfloat16 flips which expert is the
  k-th largest on seeded weights; the comparison hands over the choices the
  engine made.  Scores and gates are still this file's own float32;
- `shortfall` (optional, with `choices`): also hand back how far the choices
  given lie under this file's own: the k-th best float32 `s` of a token less
  the given expert's `s`, 0 where it is among the k best; the largest over
  layers and tokens.  `forward` then returns (logits, shortfall);
- `positions` (optional): the positions whose logits are wanted.

float32 throughout with `jax.default_matmul_precision("highest")`.  No
cache, no kernels, no batching: one sequence, the whole forward, in blocks
so that 9,216 tokens fit beside ten gigabytes of served weights (a block of
queries at a time over the keys it can see, experts a few at a time over a
block of tokens, the head a slice of the vocabulary at a time).  Weights
arrive in the type they are served in and are up-cast as they are used.
Only the weight LAYOUT is the program's (`embed`, `layers[i]` {`attn_norm`,
`attn`: wq wk wv wo, all [in, out]; `moe` {router [H, E], w_gate w_up
[held, H, F], w_down [held, F, H], shared {w_gate w_up [H, n F], w_down
[n F, H]}}}, `final_norm`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 64         # queries whose scores [heads, block, keys] are live
KEY_BLOCK = 2048         # the keys handed to a block of queries come in these
TOKEN_BLOCK = 1024       # tokens an expert chunk is applied to at once
EXPERT_CHUNK = 2         # experts up-cast at a time (2 x 3 x H x F floats)
VOCAB_CHUNK = 16384      # head columns up-cast at a time


@functools.partial(jax.jit, static_argnames=("eps",))
def _layer_norm(x, w, *, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotary_pairs(x, theta, first):
    """x: [T, heads, D]; position first + t turns pair (x[2i], x[2i + 1]) by
    (first + t) * theta ** (-2i / D)."""
    t, heads, d = x.shape
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) * 2.0 / d)
    ang = (first + jnp.arange(t, dtype=F32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(t, heads, d)


@functools.partial(jax.jit, static_argnames=("kv_heads", "theta", "rotary"))
def _keys_values(h, wk, wv, *, kv_heads, theta, rotary):
    t = h.shape[0]
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, -1)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, -1)
    return (_rotary_pairs(k, theta, 0) if rotary else k), v


@functools.partial(jax.jit, static_argnames=("heads", "theta", "rotary",
                                             "window"))
def _attend(h_blk, first, k0, k, v, wq, wo, *, heads, theta, rotary, window):
    """A block of queries (positions first ..) over the keys at positions
    k0 ..: [Bq, hidden]."""
    bq, n, kv_heads = h_blk.shape[0], k.shape[0], k.shape[1]
    q = (h_blk @ wq.astype(F32)).reshape(bq, heads, -1)
    if rotary:
        q = _rotary_pairs(q, theta, first)
    q = q.reshape(bq, kv_heads, heads // kv_heads, -1)
    scores = jnp.einsum("qkgd,nkd->kgqn", q, k) * (q.shape[-1] ** -0.5)
    behind = (first + jnp.arange(bq))[:, None] - (k0 + jnp.arange(n))[None, :]
    sees = behind >= 0
    if window is not None:
        sees = jnp.logical_and(sees, behind < window)
    scores = jnp.where(sees[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgqn,nkd->qkgd", probs, v).reshape(bq, -1)
    return out @ wo.astype(F32)


def attention(hf: dict, layer: dict, h, kind: str):
    """Attn(h) of one layer: `kind` "sliding_attention" (interleaved rotary
    embedding, a window) or "full_attention" (no position term, causal)."""
    sliding = kind == "sliding_attention"
    if not sliding and kind != "full_attention":
        raise ValueError(f"layer type {kind!r} is not described here")
    window = int(hf["sliding_window"]) if sliding else None
    theta = float(hf.get("rope_theta", 10000.0))
    a = layer["attn"]
    k, v = _keys_values(h, a["wk"], a["wv"],
                        kv_heads=hf["num_key_value_heads"], theta=theta,
                        rotary=sliding)
    out = []
    for q0 in range(0, h.shape[0], QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, h.shape[0])
        # A span of keys that holds all this block can see, cut at multiples
        # of KEY_BLOCK so that few shapes are compiled: each key's mask is
        # still applied inside.
        k0 = 0 if window is None else max(
            0, (q0 - window + 1) // KEY_BLOCK * KEY_BLOCK)
        k1 = min(h.shape[0], -(-q1 // KEY_BLOCK) * KEY_BLOCK)
        out.append(_attend(h[q0:q1], q0, k0, k[k0:k1], v[k0:k1], a["wq"],
                           a["wo"], heads=hf["num_attention_heads"],
                           theta=theta, rotary=sliding, window=window))
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, chosen, *, top_k):
    """Each token's gate on every expert [T, E], zero on those it does not
    use, and how far the experts it uses lie under its own k best."""
    s = jax.nn.sigmoid(h @ router.astype(F32))                  # [T, E]
    best, own = jax.lax.top_k(s, top_k)
    use = jnp.where(chosen[:, :1] < 0, own, chosen)             # [T, k]
    short = best[:, -1:] - jnp.take_along_axis(s, use, axis=-1)
    picked = jax.nn.one_hot(use, s.shape[-1], dtype=F32).sum(axis=1)
    w = s * picked
    return (w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20),
            jnp.max(jnp.maximum(short, 0.0)))


@jax.jit
def _experts(h, gates, w_gate, w_up, w_down):
    """sum over this chunk's experts of gate * SwiGLU expert: [T, H]."""
    a = jnp.einsum("th,ehf->etf", h, w_gate.astype(F32))
    b = jnp.einsum("th,ehf->etf", h, w_up.astype(F32))
    out = jnp.einsum("etf,efh->eth", jax.nn.silu(a) * b, w_down.astype(F32))
    return jnp.einsum("eth,te->th", out, gates)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    gate = jax.nn.silu(h @ w_gate.astype(F32))
    return (gate * (h @ w_up.astype(F32))) @ w_down.astype(F32)


@jax.jit
def _head(x, emb):
    return x @ emb.astype(F32).T


def held_experts(hf: dict):
    """(first, count) of the routed experts computed here, and how many the
    router scores."""
    held = hf.get("routed_experts_held")
    if held:
        return int(held["first"]), int(held["count"]), int(held["of"])
    return 0, int(hf["num_experts"]), int(hf["num_experts"])


def ffn(hf: dict, layer: dict, h, chosen=None):
    """(FFN(h) of one layer: the held routed experts' gated sum and the mean
    of the shared experts; the shortfall of `chosen`)."""
    if not hf.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not described here")
    if hf.get("shared_expert_combination_strategy", "average") != "average":
        raise ValueError("only the averaged shared experts are described")
    top_k = int(hf["num_experts_per_tok"])
    first, count, _ = held_experts(hf)
    m = layer["moe"]
    t = h.shape[0]
    if chosen is None:
        chosen = jnp.full((t, top_k), -1, jnp.int32)
    gates, short = _route(h, m["router"], jnp.asarray(chosen, jnp.int32),
                          top_k=top_k)
    gates = gates[:, first:first + count]
    n_shared = int(hf.get("num_shared_experts") or 0)
    width = int(hf["intermediate_size"])
    blocks = []
    for t0 in range(0, t, TOKEN_BLOCK):
        hb, gb = h[t0: t0 + TOKEN_BLOCK], gates[t0: t0 + TOKEN_BLOCK]
        y = jnp.zeros_like(hb)
        for e0 in range(0, count, EXPERT_CHUNK):
            e1 = min(e0 + EXPERT_CHUNK, count)
            y = y + _experts(hb, gb[:, e0:e1], m["w_gate"][e0:e1],
                             m["w_up"][e0:e1], m["w_down"][e0:e1])
        shared = jnp.zeros_like(hb)
        for i in range(n_shared):
            sh, cols = m["shared"], slice(i * width, (i + 1) * width)
            shared = shared + _swiglu(hb, sh["w_gate"][:, cols],
                                      sh["w_up"][:, cols],
                                      sh["w_down"][cols])
        blocks.append(y + shared / max(n_shared, 1))
    return jnp.concatenate(blocks, axis=0), short


def layer_forward(hf: dict, layer: dict, x, kind: str, chosen=None):
    """(x + Attn(h) + FFN(h) with h = LayerNorm(x), the shortfall)."""
    h = _layer_norm(x, layer["attn_norm"],
                    eps=float(hf.get("layer_norm_eps", 1e-5)))
    y, short = ffn(hf, layer, h, chosen)
    return x + attention(hf, layer, h, kind) + y, short


def forward(hf: dict, params: dict, tokens, choices=None, positions=None,
            shortfall=False):
    """Logits (float32) of one sequence of token ids: [T, vocab], or
    [len(positions), vocab] for the positions asked; with `shortfall`,
    (logits, the largest shortfall of `choices`: a float)."""
    if not hf.get("use_parallel_block", True):
        raise ValueError("only the parallel block is described here")
    kinds = hf["layer_types"]      # a layer of the weights, in order
    shorts = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        for i, (layer, kind) in enumerate(zip(params["layers"], kinds)):
            x, short = layer_forward(
                hf, layer, x, kind,
                None if choices is None else choices[i])
            shorts.append(short)
        if positions is not None:
            x = jnp.take(x, jnp.asarray(np.asarray(positions), jnp.int32),
                         axis=0)
        x = _layer_norm(x, params["final_norm"],
                        eps=float(hf.get("layer_norm_eps", 1e-5)))
        emb = params["embed"]
        logits = float(hf.get("logit_scale", 1.0)) * jnp.concatenate(
            [_head(x, emb[v0: v0 + VOCAB_CHUNK])
             for v0 in range(0, emb.shape[0], VOCAB_CHUNK)], axis=-1)
    if shortfall:
        return logits, max((float(v) for v in shorts), default=0.0)
    return logits
