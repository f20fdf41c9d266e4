"""Plain reference forward pass of the parallel hybrid block: a Mamba-2
state-space mixer and rotary grouped-query attention on one normed input,
summed into the residual, then a SwiGLU MLP; muP multipliers throughout.

Written from the published modeling code of the family, `transformers`'
`models/falcon_h1/modeling_falcon_h1.py` (FalconH1DecoderLayer,
FalconH1Attention, FalconH1Mixer.torch_forward, FalconH1RMSNormGated,
FalconH1MLP, compute_mup_vector, FalconH1ForCausalLM.forward), not from
`dynamo_tpu/models`.  A layer:

    h   = RMSNorm_in(x)
    x'  = x + ssm_out_multiplier * Mamba2(h)
            + attention_out_multiplier * Attn(attention_in_multiplier * h)
    out = x' + MLP(RMSNorm_ff(x'))
    MLP(u) = (W_up u * silu(gate_mult * W_gate u)) W_down * down_mult
    Attn: k multiplied by key_multiplier after k_proj; rotary on halves of
          the head; causal; scaled by head_dim**-0.5
    Mamba2(h): p = (W_in (ssm_in_multiplier * h)) * mup   [z | x | B | C | dt]
               xBC = silu(causal depthwise conv1d(xBC) + bias)
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t
               y_t = S_t C_t + D x_t
               y = RMSNorm_grouped(y * silu(z)) * w_norm;  return W_out y
    logits = lm_head(RMSNorm_f(x)) * lm_head_multiplier; the embedding's
    output times embedding_multiplier.

Departures from `torch_forward`:
- the scan is written as the recurrence above, one token after another
  (`jax.lax.scan` over the sequence), where `torch_forward` computes the
  same sums in the chunked form (`mamba_chunk_size`); the chunk size then
  appears nowhere here.  `torch_forward`'s single-token branch is this
  recurrence;
- `torch_forward` clamps dt to `time_step_limit` = (0, inf): softplus is
  positive, so the clamp is the identity and is left out;
- `mamba_norm_before_gate` true, `mamba_rms_norm` false, projection and
  attention biases and `attn_layer_indices` are not written down (the
  configuration this serves states none of them);
- the head is computed in blocks over the vocabulary, so that a float32
  head of 261,120 x 5,120 is never whole.

float32 throughout with `jax.default_matmul_precision("highest")`.  No cache,
no kernels, no batching: one sequence at a time, the full causal forward.
Weights arrive in the type they are served in and are up-cast one matrix at a
time.  Only the weight LAYOUT is the program's (`embed`, `layers[i]` {`attn`:
wq wk wv wo as [in, out]; `ssm`: w_in, conv_w [taps, channels], conv_b,
A_log, D, dt_bias, norm, w_out; `attn_norm`, `mlp_norm`; `mlp`},
`final_norm`, `lm_head`); weights are data here, like the prompt.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 32768


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
    "heads", "kv_heads", "head_dim", "theta", "key_mult"))
def _attention(h, wq, wk, wv, wo, *, heads, kv_heads, head_dim, theta,
               key_mult):
    t = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, head_dim) * key_mult
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
    return out @ wo.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "d_ssm", "n_heads", "n_groups", "d_state", "taps", "in_mult", "mup",
    "eps"))
def _mamba2(h, w_in, conv_w, conv_b, a_log, d_skip, dt_bias, w_norm, w_out,
            stop=None,
            *, d_ssm, n_heads, n_groups, d_state, taps, in_mult, mup, eps):
    t = h.shape[0]
    gn = n_groups * d_state
    head_dim = d_ssm // n_heads
    p = (h * in_mult) @ w_in.astype(F32)
    scale = jnp.concatenate([
        jnp.full((w,), m, F32) for w, m in zip(
            (d_ssm, d_ssm, gn, gn, n_heads), mup)])
    p = p * scale
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], \
        p[:, 2 * d_ssm + 2 * gn:]
    # Causal depthwise convolution: out[t] = sum_k w[k] in[t - (taps-1) + k].
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[k:k + t] * conv_w[k].astype(F32) for k in range(taps))
    if conv_b is not None:
        conv = conv + conv_b.astype(F32)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_ssm].reshape(t, n_heads, head_dim)
    b = xbc[:, d_ssm:d_ssm + gn].reshape(t, n_groups, d_state)
    c = xbc[:, d_ssm + gn:].reshape(t, n_groups, d_state)
    rep = n_heads // n_groups
    b = jnp.repeat(b, rep, axis=1)              # head i uses group i // rep
    c = jnp.repeat(c, rep, axis=1)
    dt = jax.nn.softplus(dt + dt_bias.astype(F32))          # [T, heads]
    a = -jnp.exp(a_log.astype(F32))

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) \
            + d_skip.astype(F32)[:, None] * x_t
        return state, y_t

    zero = jnp.zeros((n_heads, head_dim, d_state), F32)
    if stop is None:
        state, y = jax.lax.scan(token, zero, (x, b, c, dt))
    else:
        # The state after token `stop` - 1 beside the outputs: tokens from
        # `stop` on (padding) leave it as it was.
        def counted(carry, inp):
            state, i = carry
            new, y_t = token(state, inp)
            return (jnp.where(i < stop, new, state), i + 1), y_t

        (state, _), y = jax.lax.scan(counted, (zero, jnp.zeros((), jnp.int32)),
                                     (x, b, c, dt))
    y = y.reshape(t, d_ssm) * jax.nn.silu(z)
    yg = y.reshape(t, n_groups, d_ssm // n_groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    y = yg.reshape(t, d_ssm) * w_norm.astype(F32)
    return y @ w_out.astype(F32), state


@functools.partial(jax.jit, static_argnames=("gate_mult", "down_mult"))
def _swiglu(h, w_gate, w_up, w_down, *, gate_mult, down_mult):
    gate = jax.nn.silu((h @ w_gate.astype(F32)) * gate_mult)
    return ((h @ w_up.astype(F32)) * gate) @ w_down.astype(F32) * down_mult


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


@jax.jit
def _head_block(x, w):
    return x @ w.astype(F32)


def _head(x, w):
    """x @ w in blocks over the vocabulary (w [hidden, vocab])."""
    v = w.shape[1]
    return jnp.concatenate(
        [_head_block(x, w[:, i:i + VOCAB_BLOCK])
         for i in range(0, v, VOCAB_BLOCK)], axis=1)


def forward(hf: dict, params: dict, tokens, positions=None, state_at=None):
    """Logits (float32) of one sequence of token ids: [T, vocab], or
    [len(positions), vocab] for the positions asked.  With `state_at` = n,
    (logits, the first layer's scan state [heads, head_dim, d_state] after
    token n - 1): what a serving engine's state slot holds of a sequence of
    n tokens, for a comparison that reads the slot."""
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    kv_heads = hf.get("num_key_value_heads", heads)
    eps = float(hf.get("rms_norm_eps", 1e-5))
    theta = float(hf.get("rope_theta", 10000.0))
    d_ssm = hf.get("mamba_d_ssm") or int(
        hf["mamba_expand"] * hf["hidden_size"])
    gate_mult, down_mult = (float(m) for m in hf["mlp_multipliers"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32) * float(hf["embedding_multiplier"])
        first_state = None
        for layer in params["layers"]:
            h = _norm(x, layer["attn_norm"], eps=eps)
            a, s = layer["attn"], layer["ssm"]
            mixed = float(hf["attention_out_multiplier"]) * _attention(
                h * float(hf["attention_in_multiplier"]), a["wq"], a["wk"],
                a["wv"], a["wo"], heads=heads, kv_heads=kv_heads,
                head_dim=head_dim, theta=theta,
                key_mult=float(hf["key_multiplier"]))
            out, state = _mamba2(
                h, s["w_in"], s["conv_w"], s.get("conv_b"), s["A_log"],
                s["D"], s["dt_bias"], s["norm"], s["w_out"],
                None if state_at is None else jnp.asarray(state_at,
                                                          jnp.int32),
                d_ssm=d_ssm, n_heads=hf["mamba_n_heads"],
                n_groups=hf["mamba_n_groups"],
                d_state=hf["mamba_d_state"], taps=hf["mamba_d_conv"],
                in_mult=float(hf["ssm_in_multiplier"]),
                mup=tuple(float(m) for m in hf["ssm_multipliers"]),
                eps=eps)
            if first_state is None:
                first_state = state
            x = x + mixed + float(hf["ssm_out_multiplier"]) * out
            m = layer["mlp"]
            x = x + _swiglu(_norm(x, layer["mlp_norm"], eps=eps),
                            m["w_gate"], m["w_up"], m["w_down"],
                            gate_mult=gate_mult, down_mult=down_mult)
        if positions is not None:
            x = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
        x = _norm(x, params["final_norm"], eps=eps)
        logits = _head(x, params["lm_head"]) * float(
            hf["lm_head_multiplier"])
        return logits if state_at is None else (logits, first_state)
