"""Plain reference forward pass of the pattern block: layers of three kinds by
a pattern, each `x + f(RMSNorm(x))` with ONE `f`: a Mamba-2 state-space
mixer ("M"), grouped-query attention without a position term ("*"), or
sigmoid-routed experts that work in a latent space beside a shared expert
("E"), of which this chip holds a range; a last RMSNorm before the untied
head.

Written from the published configuration of the family (`nemotron_h`:
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
and the published description of its parts, not from `dynamo_tpu/models`:
the mixer is `transformers`' `models/mamba2/modeling_mamba2.py`
(Mamba2Mixer.torch_forward, MambaRMSNormGated), the router DeepSeek-V3's
(arXiv:2412.19437, section 2.1.2; `models/deepseek_v3`).  The family's own
`modeling_nemotron_h.py` is not on this machine: what the configuration's
keys do not settle is the configuration file's `assumed`, and is marked
"assumed" here.

    M:  [z | xBC | dt] = W_in h
        xBC = silu(causal depthwise conv1d(xBC, taps) + bias)
        x -> [heads, head_dim]; B, C -> [groups, state]; head i uses group
        i // (heads / groups)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t;  y_t = S_t C_t + D x_t
        y = RMSNorm_grouped(y * silu(z)) * w_norm      (assumed: the gate
            before the norm, one norm a group, as MambaRMSNormGated)
        return W_out y
    *:  causal softmax attention, `num_attention_heads` query heads over
        `num_key_value_heads` key/value heads of `head_dim`, scale
        head_dim**-0.5, NO rotary or other position term (assumed: the
        published attention applies none; `rope_theta` is read by nothing)
    E:  s = sigmoid(W_r h) in float32 over all `of` experts of the model
        chosen = top-k of (s + b)        (b = e_score_correction_bias)
        g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
        u = W_lat_in h                   (assumed: no bias, no norm between)
        r = sum_{e in chosen, e held here} g_e W_down_e relu(W_up_e u)^2
        return W_lat_out r + W_sdown relu(W_sup h)^2   (the shared expert on
            the layer's full-width input: assumed)

The chip's share (`routed_experts_held` = {first, count, of}): the router is
`of` wide and picks k; the experts `first .. first + count` are held
(`params` holds their weights only) and the others' terms of the sum are
LEFT OUT, here as in the program: that partial result goes on to the next
layer.  Without the key every expert is held.

Departures from the published description, each where it applies:
- the scan is written as the recurrence, one token after another
  (`jax.lax.scan`), where the published code computes the same sums in
  chunks of `chunk_size`; the chunk size appears nowhere here;
- dt is not clamped (`time_step_limit` (0, inf): softplus is positive);
- the multi-token-prediction head (`num_nextn_predict_layers`) is left out:
  no logit depends on it (the configuration lists it under `reduced`);
- `choices` (optional): the experts each token is to use in each EXPERT
  layer, [L_moe, T, k], rows of -1 = choose here.  bfloat16 flips which
  expert is the k-th largest on seeded weights, and a float32 forward that
  chose for itself would measure the flips and not the arithmetic.  The
  scores and the gates are still this file's own float32;
- `shortfall` (with `choices`): also hand back how far the choices given lie
  under this file's own: for each expert a token was given, this file's k-th
  best `s + b` of that token less the given expert's, 0 where it is among
  the k best; the largest over expert layers and tokens;
- `positions`: the positions whose logits are wanted; `state_at` = n: also
  the first STATE layer's scan state after token n - 1.

float32 throughout with `jax.default_matmul_precision("highest")`.  No cache,
no kernels, no batching: one sequence at a time, the whole causal forward;
the experts a few at a time and the head a slice of the vocabulary at a
time, so that it fits beside the served weights.  Weights arrive in the type
they are served in and are up-cast as they are used.  Only the weight LAYOUT
is the program's (`embed`, `layers[i]` {`norm`; `ssm`: w_in, conv_w [taps,
channels], conv_b, A_log, D, dt_bias, norm, w_out | `attn`: wq wk wv wo as
[in, out] | `moe`: router [H, of], router_bias [of], latent_in [H, lat],
latent_out [lat, H], w_up [count, lat, F], w_down [count, F, lat], shared
{w_up, w_down}}, `final_norm`, `lm_head`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_BLOCK = 32768
EXPERT_CHUNK = 8         # experts up-cast at a time (8 x 2 x lat x F floats)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim"))
def _attention(h, wq, wk, wv, wo, *, heads, kv_heads, head_dim):
    t = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads                   # q head i uses kv head i // g
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (head_dim ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return out @ wo.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "n_groups", "d_state", "taps", "eps"))
def _mamba2(h, w_in, conv_w, conv_b, a_log, d_skip, dt_bias, w_norm, w_out,
            stop=None, *, n_heads, head_dim, n_groups, d_state, taps, eps):
    t = h.shape[0]
    d_ssm = n_heads * head_dim
    gn = n_groups * d_state
    p = h @ w_in.astype(F32)
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

    def token(carry, inp):
        state, i = carry
        x_t, b_t, c_t, dt_t = inp
        new = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", new, c_t) \
            + d_skip.astype(F32)[:, None] * x_t
        # Tokens from `stop` on (padding) leave the state handed back as it
        # was after token `stop` - 1.
        keep = new if stop is None else jnp.where(i < stop, new, state)
        return (keep, i + 1), y_t

    zero = jnp.zeros((n_heads, head_dim, d_state), F32)
    (state, _), y = jax.lax.scan(token, (zero, jnp.zeros((), jnp.int32)),
                                 (x, b, c, dt))
    y = y.reshape(t, d_ssm) * jax.nn.silu(z)    # assumed: gate, then norm
    yg = y.reshape(t, n_groups, d_ssm // n_groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    y = yg.reshape(t, d_ssm) * w_norm.astype(F32)
    return y @ w_out.astype(F32), state


@functools.partial(jax.jit, static_argnames=("k", "scale", "first", "count"))
def _route(h, w_r, bias, given, *, k, scale, first, count):
    """-> (gates over the experts held here [T, count], the largest
    shortfall of the choices given).  `given` [T, k], rows of -1 = choose
    here."""
    s = jax.nn.sigmoid(h @ w_r.astype(F32))                 # [T, of] float32
    biased = s + bias.astype(F32)
    best, own = jax.lax.top_k(biased, k)
    use_own = given[:, :1] < 0
    chosen = jnp.where(use_own, own, given)
    short = jnp.where(
        use_own, 0.0,
        jnp.maximum(best[:, -1:] - jnp.take_along_axis(biased, chosen, 1),
                    0.0))
    s_ch = jnp.take_along_axis(s, chosen, axis=1)
    g = s_ch / (jnp.sum(s_ch, axis=1, keepdims=True) + 1e-20) * scale
    # What the absent experts would add is left out: their gates fall on no
    # column of the held range.
    local = chosen - first
    held = jnp.logical_and(local >= 0, local < count)
    dense = jnp.zeros((h.shape[0], count + 1), F32).at[
        jnp.arange(h.shape[0])[:, None],
        jnp.where(held, local, count)].add(jnp.where(held, g, 0.0))
    return dense[:, :count], jnp.max(short)


@jax.jit
def _expert_chunk(u, gates, w_up, w_down):
    """sum over the chunk's experts of g_e * relu(u W_up_e)^2 W_down_e."""
    act = jnp.square(jax.nn.relu(
        jnp.einsum("tl,elf->etf", u, w_up.astype(F32))))
    out = jnp.einsum("etf,efl->etl", act, w_down.astype(F32))
    return jnp.einsum("etl,te->tl", out, gates)


@jax.jit
def _relu2_mlp(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up.astype(F32))) @ w_down.astype(F32)


@jax.jit
def _matmul(x, w):
    return x @ w.astype(F32)


def _head(x, w):
    """x @ w in blocks over the vocabulary (w [hidden, vocab])."""
    return jnp.concatenate(
        [_matmul(x, w[:, i:i + VOCAB_BLOCK])
         for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=1)


def expert_layer(hf: dict, m: dict, h, given=None):
    """One "E" layer's `f` on its normed input h [T, H] -> (out [T, H], the
    shortfall of `given`).  `m` holds the weights of the experts
    `routed_experts_held` names (all of them without the key)."""
    held = hf.get("routed_experts_held") or {
        "first": 0, "count": m["w_up"].shape[0], "of": m["router"].shape[1]}
    t = h.shape[0]
    k = int(hf["num_experts_per_tok"])
    if given is None:
        given = jnp.full((t, k), -1, jnp.int32)
    gates, short = _route(
        h, m["router"], m["router_bias"], jnp.asarray(given, jnp.int32),
        k=k, scale=float(hf.get("routed_scaling_factor", 1.0)),
        first=int(held["first"]), count=int(held["count"]))
    u = _matmul(h, m["latent_in"]) if "latent_in" in m else h
    r = jnp.zeros_like(u)
    for e in range(0, int(held["count"]), EXPERT_CHUNK):
        r = r + _expert_chunk(u, gates[:, e:e + EXPERT_CHUNK],
                              m["w_up"][e:e + EXPERT_CHUNK],
                              m["w_down"][e:e + EXPERT_CHUNK])
    out = _matmul(r, m["latent_out"]) if "latent_out" in m else r
    if "shared" in m:
        out = out + _relu2_mlp(h, m["shared"]["w_up"], m["shared"]["w_down"])
    return out, short


def forward(hf: dict, params: dict, tokens, positions=None, state_at=None,
            choices=None, shortfall=False):
    """Logits (float32) of one sequence of token ids: [T, vocab], or
    [len(positions), vocab].  With `state_at` = n also the first state
    layer's scan state [heads, head_dim, state] after token n - 1; with
    `shortfall` also the largest shortfall of `choices`.  Returns logits,
    or (logits, *extras) in the order (state, shortfall)."""
    pattern = hf["hybrid_override_pattern"]
    heads = hf["num_attention_heads"]
    eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        first_state, worst, moe_i = None, jnp.zeros((), F32), 0
        for kind, layer in zip(pattern, params["layers"]):
            h = _norm(x, layer["norm"], eps=eps)
            if kind == "M":
                s = layer["ssm"]
                out, state = _mamba2(
                    h, s["w_in"], s["conv_w"], s.get("conv_b"), s["A_log"],
                    s["D"], s["dt_bias"], s["norm"], s["w_out"],
                    None if state_at is None else jnp.asarray(
                        state_at, jnp.int32),
                    n_heads=hf["mamba_num_heads"],
                    head_dim=hf["mamba_head_dim"], n_groups=hf["n_groups"],
                    d_state=hf["ssm_state_size"], taps=hf["conv_kernel"],
                    eps=eps)
                if first_state is None:
                    first_state = state
            elif kind == "*":
                a = layer["attn"]
                out = _attention(
                    h, a["wq"], a["wk"], a["wv"], a["wo"], heads=heads,
                    kv_heads=hf.get("num_key_value_heads", heads),
                    head_dim=hf.get("head_dim") or hf["hidden_size"] // heads)
            elif kind == "E":
                out, short = expert_layer(
                    hf, layer["moe"], h,
                    None if choices is None else choices[moe_i])
                worst = jnp.maximum(worst, short)
                moe_i += 1
            else:
                raise ValueError(f"layer kind {kind!r} is not written down")
            x = x + out
        if positions is not None:
            x = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
        logits = _head(_norm(x, params["final_norm"], eps=eps),
                       params["lm_head"])
    extras = ([first_state] if state_at is not None else []) \
        + ([worst] if shortfall else [])
    return (logits, *extras) if extras else logits
