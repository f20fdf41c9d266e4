"""Plain reference forward pass of the dense causal decoder block: RMSNorm,
rotary grouped-query attention, SwiGLU MLP.

Written from the published description (Mistral 7B, arXiv:2310.06825), not
from `dynamo_tpu/models`: pre-norm decoder; RMSNorm; rotary embedding on
halves of the head (the Hugging Face `rotate_half` layout) with base
`rope_theta`; grouped-query causal attention scaled by head_dim**-0.5;
SwiGLU MLP.  Departures from it: none in the mathematics; the paper's
sliding window is not applied (the configuration it serves states
`sliding_window: null`, as Mistral-7B-v0.3 does).  A block with routed
experts, another mask or a latent cache is another file beside this one,
named by the configuration that needs it (`chipbench/README.md`).

float32 throughout with `jax.default_matmul_precision("highest")` (on a TPU
an f32 matmul otherwise runs in bf16 passes).  No cache, no kernels, no
batching: one sequence at a time, the full causal forward.  Weights arrive
in the type they are served in and are up-cast one matrix at a time, so the
reference fits beside the served weights.

Only the weight LAYOUT is the program's (a pytree with `embed`, `layers[i]`
{`attn`: wq wk wv wo as [in, out]; `attn_norm`, `mlp_norm`; `mlp`},
`final_norm`, `lm_head`); weights are data here, like the prompt."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "theta", "eps"))
def _attention(x, norm_w, wq, wk, wv, wo, *, heads, kv_heads, head_dim,
               theta, eps):
    t = x.shape[0]
    h = _rms_norm(x, norm_w, eps)
    q = (h @ wq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, head_dim)
    q, k = _rotary(q, theta), _rotary(k, theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (head_dim ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return x + out @ wo.astype(F32)


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


def forward(hf: dict, params: dict, tokens):
    """Logits [T, vocab] (float32) of one sequence of token ids."""
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    kv_heads = hf.get("num_key_value_heads", heads)
    eps = float(hf.get("rms_norm_eps", 1e-5))
    theta = float(hf.get("rope_theta", 10000.0))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        for layer in params["layers"]:
            a = layer["attn"]
            x = _attention(x, layer["attn_norm"], a["wq"], a["wk"], a["wv"],
                           a["wo"], heads=heads, kv_heads=kv_heads,
                           head_dim=head_dim, theta=theta, eps=eps)
            h = _norm(x, layer["mlp_norm"], eps=eps)
            m = layer["mlp"]
            x = x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"])
        x = _norm(x, params["final_norm"], eps=eps)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        return _head(x, head)
