"""The Mamba-2 state-space mixer of a `falcon_h1` layer (and, without the
multipliers, of a `nemotron_h` "M" layer): the projection, the
causal depthwise convolution, the selective scan and the gated norm, over the
per-sequence state slots of engine/kv_cache.py.

    p = (W_in (ssm_in_multiplier * h)) * mup          [z | xBC | dt]
    xBC = silu(conv1d(xBC))                           carries its last taps - 1 inputs
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t;  y_t = S_t C_t + D x_t
    out = W_out (RMSNorm_grouped(y * silu(z)) * w_norm)

Two forms of the scan, each inside a jit of its own name so that a device
trace shows it (`ssm_state_update`, `ssm_chunk_scan`); on the TPU, at a
geometry of whole tiles, each is a Pallas kernel (ops/pallas/ssm.py), else
the plain XLA form below it, which is also the kernels' oracle:

- `ssm_state_update`: one token a row (a decode step).  The live rows'
  states are read from their slots, stepped and written back in place;
  nothing else of the `ssm` leaf moves (the kernel leaves the scratch slot
  of the padding rows where it lies too).
- `ssm_chunk_scan`: the chunked (SSD) form over a prefill chunk laid out in
  scan chunks of `mamba_chunk_size` tokens, each belonging to one segment
  (one sequence's part of a packed chunk): inside a scan chunk the
  quadratic masked form, between scan chunks the state, which restarts from
  the segment's slot (or from zero, at a sequence's first token) at a
  segment's first scan chunk.  Everything that touches the state
  accumulates in float32.

`mamba_prefill` and `mamba_decode` are the whole mixer around them.  The
state is float32 whatever the model's dtype; the leaves' own dtype is
honoured on read and write, so a comparison's control can store it lower.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig

_HI = jax.lax.Precision.HIGHEST


def _mup_vector(cfg: ModelConfig, dtype) -> jax.Array:
    """The five `ssm_multipliers` over [z | x | B | C | dt]."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, dtype)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


def _project(cfg: ModelConfig, p, h: jax.Array):
    """h [..., hidden] -> z [..., d_ssm], xBC [..., conv_dim], dt [..., H],
    multipliers applied where the published code applies them."""
    if cfg.uses_multipliers:
        proj = (h * jnp.asarray(cfg.ssm_in_multiplier, h.dtype)) @ p["w_in"]
        proj = proj * _mup_vector(cfg, proj.dtype)
    else:       # a mixer alone in its layer states none: nothing is traced
        proj = h @ p["w_in"]
    d, c = cfg.mamba_d_ssm, cfg.mamba_conv_dim
    return proj[..., :d], proj[..., d:d + c], proj[..., d + c:]


def _split_xbc(cfg: ModelConfig, xbc: jax.Array):
    """Activated [..., conv_dim] -> x [..., H, P], B and C [..., G, N]."""
    d = cfg.mamba_d_ssm
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    b = xbc[..., d:d + gn].reshape(*lead, cfg.mamba_n_groups,
                                   cfg.mamba_d_state)
    c = xbc[..., d + gn:].reshape(*lead, cfg.mamba_n_groups,
                                  cfg.mamba_d_state)
    return x, b, c


def _gated_out(cfg: ModelConfig, p, y: jax.Array, z: jax.Array, dtype):
    """y [..., H, P] float32, z [..., d_ssm] -> [..., hidden]: the gate, the
    grouped RMSNorm (`mamba_rms_norm`), the output projection."""
    lead = y.shape[:-2]
    y = y.reshape(*lead, cfg.mamba_d_ssm) * jax.nn.silu(
        z.astype(jnp.float32))
    if cfg.mamba_rms_norm:
        g = cfg.mamba_n_groups
        yg = y.reshape(*lead, g, cfg.mamba_d_ssm // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = yg.reshape(*lead, cfg.mamba_d_ssm) * p["norm"].astype(jnp.float32)
    return y.astype(dtype) @ p["w_out"]


def _dt(p, dt_raw: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])


# ---------------------------------------------------------------------------
# One token a row


def state_update_plain(ssm, slots, x, dt, a, b, c):
    """`ssm_state_update` in plain XLA: a gather of the rows' states, the
    arithmetic, a scatter back.  The kernel's oracle."""
    rep = ssm.shape[1] // b.shape[1]
    s = jnp.take(ssm, slots, axis=0).astype(jnp.float32)
    bh = jnp.repeat(b, rep, axis=1)                         # [R, H, N]
    ch = jnp.repeat(c, rep, axis=1)
    s = s * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.sum(s * ch[:, :, None, :], axis=-1)
    return y, ssm.at[slots].set(s.astype(ssm.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update(ssm: jax.Array, slots: jax.Array, x: jax.Array,
                     dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                     interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Step each row's state by one token, in place.

    ssm [S, H, P, N]; slots [R]; x [R, H, P]; dt [R, H] (after softplus);
    a [H] (negative); b, c [R, G, N].  Returns (y [R, H, P] = S_t C_t, ssm').
    Rows that share a slot (padding rows on the scratch slot, the leaf's
    last) leave any one of their results there or none, and what they read
    as `y` means nothing: the kernel moves no state for them and hands them
    zeros."""
    from dynamo_tpu.ops.pallas.ssm import (
        state_update_geometry_ok, state_update_kernel)

    H, P, N = ssm.shape[1:]
    if state_update_geometry_ok(H, P, N, b.shape[1]) and (
            interpret or jax.default_backend() == "tpu"):
        return state_update_kernel(ssm, slots, x, dt, a, b, c,
                                   interpret=interpret)
    return state_update_plain(ssm, slots, x, dt, a, b, c)


def mamba_decode(cfg: ModelConfig, p, h: jax.Array, ssm: jax.Array,
                 conv: jax.Array, slots: jax.Array):
    """The mixer over one token a row.  h [R, hidden] (the layer's normed
    input); `slots` [R] each row's state slot (padding rows: the scratch
    slot).  Returns (out [R, hidden], ssm', conv')."""
    z, xbc, dt_raw = _project(cfg, p, h)
    tail = jnp.take(conv, slots, axis=0)                    # [R, K-1, C]
    window = jnp.concatenate([tail, xbc[:, None].astype(conv.dtype)], axis=1)
    act = jnp.sum(window.astype(jnp.float32)
                  * p["conv_w"].astype(jnp.float32)[None], axis=1)
    if "conv_b" in p:
        act = act + p["conv_b"].astype(jnp.float32)
    x, b, c = _split_xbc(cfg, jax.nn.silu(act))
    y, ssm = ssm_state_update(ssm, slots, x, _dt(p, dt_raw),
                              -jnp.exp(p["A_log"]), b, c)
    y = y + p["D"][None, :, None] * x
    conv = conv.at[slots].set(window[:, 1:])
    return _gated_out(cfg, p, y, z, h.dtype), ssm, conv


# ---------------------------------------------------------------------------
# A prefill chunk


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, first: jax.Array, seg: jax.Array,
                   init: jax.Array, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """The chunked scan over scan chunks that each belong to one segment.

    x [NC, Q, H, P]; dt [NC, Q, H] (after softplus; 0 on padding, which
    then passes the state through); a [H]; b, c [NC, Q, G, N]; first [NC]
    (a segment's first scan chunk: its state starts from `init[seg]`, else
    from the scan chunk before); seg [NC] (each scan chunk's segment, R for
    one that belongs to none); init [R + 1, H, P, N] float32.  Returns
    (y [NC, Q, H, P] = S_t C_t, fin [R + 1, H, P, N]: the state at the end
    of each segment's last scan chunk; a segment without scan chunks, and
    row R, hold nothing meant).  float32 throughout."""
    from dynamo_tpu.ops.pallas.ssm import (
        chunk_scan_geometry_ok, chunk_scan_kernel)

    NC, Q, H, P = x.shape
    G, N = b.shape[2:]
    if chunk_scan_geometry_ok(H, P, N, G, Q) and (
            interpret or jax.default_backend() == "tpu"):
        return chunk_scan_kernel(x, dt, a, b, c, first, seg, init,
                                 interpret=interpret)
    rep = H // G
    la = dt * a                                             # [NC, Q, H] <= 0
    cum = jnp.cumsum(la, axis=1)
    xdt = (x * dt[..., None]).reshape(NC, Q, G, rep, P)
    cum_g = cum.reshape(NC, Q, G, rep)
    # Inside a scan chunk: the masked quadratic form.
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    diff = cum_g[:, :, None] - cum_g[:, None, :]            # [NC, l, s, G, r]
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                              -jnp.inf))
    g = jnp.einsum("clgn,csgn->clsg", c, b, precision=_HI)
    y = jnp.einsum("clsgr,csgrp->clgrp", g[..., None] * decay, xdt,
                   precision=_HI)
    # What each scan chunk adds to the state by its end, and how much of
    # the state it was handed is left by then.
    to_end = jnp.exp(cum_g[:, -1:] - cum_g)                 # [NC, Q, G, r]
    added = jnp.einsum("csgn,csgrp->cgrpn", b, xdt * to_end[..., None],
                       precision=_HI).reshape(NC, H, P, N)
    kept = jnp.exp(cum[:, -1, :])                           # [NC, H]

    def carry(prev, inp):
        is_first, r, add, keep = inp
        s_in = jnp.where(is_first, init[r], prev)
        s_out = s_in * keep[:, None, None] + add
        return s_out, (s_in, s_out)

    _, (s_in, s_out) = jax.lax.scan(
        carry, jnp.zeros_like(init[0]), (first, seg, added, kept))
    y = y + jnp.einsum("clgn,cgrpn->clgrp", c,
                       s_in.reshape(NC, G, rep, P, N), precision=_HI) \
        * jnp.exp(cum_g)[..., None]
    last = jnp.zeros((init.shape[0],), jnp.int32).at[seg].max(
        jnp.arange(NC, dtype=jnp.int32))
    return y.reshape(NC, Q, H, P), jnp.take(s_out, last, axis=0)


def mamba_prefill(cfg: ModelConfig, p, h: jax.Array, ssm: jax.Array,
                  conv: jax.Array, slots: jax.Array, seg_ids: jax.Array,
                  q_starts: jax.Array, q_lens: jax.Array, fresh: jax.Array):
    """The mixer over a packed prefill chunk.  h [T, hidden] (the layer's
    normed input on the flat token axis); segment r is rows
    [q_starts[r], q_starts[r] + q_lens[r]) (q_len 0: a padding segment),
    owned by the sequence in state slot `slots[r]` (padding: the scratch
    slot); `fresh[r]`: its first token is the sequence's first, so the
    convolution and the scan start from zero whatever the slot holds, else
    from the slot.  seg_ids [T] names each row's segment; rows outside every
    segment are padding.  Each segment's last state is written back.
    Returns (out [T, hidden], ssm', conv')."""
    T = h.shape[0]
    R = q_starts.shape[0]
    K = cfg.mamba_d_conv
    H, P, N, G = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                  cfg.mamba_n_groups)
    z, xbc, dt_raw = _project(cfg, p, h)
    seg = jnp.clip(seg_ids, 0, R - 1)
    off = jnp.arange(T, dtype=jnp.int32) - q_starts[seg]    # offset in segment
    valid = jnp.logical_and(off >= 0, off < q_lens[seg])
    keep = jnp.logical_not(fresh)

    # The convolution: a token's taps reach back into its own segment, and
    # past its start into the slot's tail (zeros for a fresh sequence).
    tail = jnp.take(conv, slots, axis=0)                    # [R, K-1, C]
    tail = jnp.where(keep[:, None, None], tail, jnp.zeros_like(tail))
    xbc_c = xbc.astype(conv.dtype)
    w = p["conv_w"].astype(jnp.float32)
    act = xbc_c.astype(jnp.float32) * w[K - 1]
    tail_flat = tail.reshape(R * (K - 1), -1)
    for j in range(1, K):
        back = jnp.roll(xbc_c, j, axis=0)                   # row t - j
        from_tail = jnp.take(
            tail_flat, seg * (K - 1) + jnp.clip(K - 1 + off - j, 0, K - 2),
            axis=0)
        src = jnp.where((off >= j)[:, None], back, from_tail)
        act = act + src.astype(jnp.float32) * w[K - 1 - j]
    if "conv_b" in p:
        act = act + p["conv_b"].astype(jnp.float32)
    act = jnp.where(valid[:, None], jax.nn.silu(act), 0.0)
    # Each segment's new tail: its last K - 1 inputs, reaching into the old
    # tail where it is shorter than that.
    i = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    at = q_lens[:, None] - (K - 1) + i                      # offset, [R, K-1]
    new_tail = jnp.where(
        (at >= 0)[..., None],
        jnp.take(xbc_c, jnp.clip(q_starts[:, None] + at, 0, T - 1), axis=0),
        jnp.take_along_axis(
            tail, jnp.clip(K - 1 + at, 0, K - 2)[..., None], axis=1))
    conv = conv.at[slots].set(new_tail)

    # Scan chunks: each segment's tokens laid out from a scan chunk's start,
    # so that a scan chunk belongs to one segment.
    Q = min(cfg.mamba_chunk_size, T)
    NC = -(-T // Q) + R
    n_chunks = -(-q_lens // Q)
    base = jnp.cumsum(n_chunks) - n_chunks                  # [R]
    dest = jnp.where(valid, (base[seg] + off // Q) * Q + off % Q, NC * Q)
    src_row = jnp.full((NC * Q,), T, jnp.int32).at[dest].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")
    dt = jnp.where(valid[:, None], _dt(p, dt_raw), 0.0)     # [T, H]
    rows = jnp.concatenate([act, dt], axis=-1)
    rows = jnp.concatenate([rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
    rows = jnp.take(rows, src_row, axis=0).reshape(NC, Q, -1)
    x, b, c = _split_xbc(cfg, rows[..., :cfg.mamba_conv_dim])
    dt_c = rows[..., cfg.mamba_conv_dim:]
    live = q_lens > 0
    first = jnp.zeros((NC,), jnp.int32).at[base].add(
        live.astype(jnp.int32), mode="drop") > 0
    # Each scan chunk's segment (segments lie one after another; one
    # without tokens owns no scan chunk), R for the scan chunks left over.
    seg_of = jnp.searchsorted(jnp.cumsum(n_chunks),
                              jnp.arange(NC, dtype=jnp.int32),
                              side="right").astype(jnp.int32)
    init = jnp.take(ssm, slots, axis=0).astype(jnp.float32)
    init = jnp.where(keep[:, None, None, None], init, 0.0)
    y, fin = ssm_chunk_scan(
        x, dt_c, -jnp.exp(p["A_log"]), b, c, first, seg_of,
        jnp.concatenate([init, jnp.zeros_like(init[:1])]))
    y = y + p["D"][None, None, :, None] * x
    # A padding segment owns no scan chunk: it hands its (scratch) slot what
    # it read there.
    new_state = jnp.where(live[:, None, None, None], fin[:R], init)
    ssm = ssm.at[slots].set(new_state.astype(ssm.dtype))
    y = jnp.take(y.reshape(NC * Q, H, P), jnp.clip(dest, 0, NC * Q - 1),
                 axis=0)
    y = jnp.where(valid[:, None, None], y, 0.0)
    return _gated_out(cfg, p, y, z, h.dtype), ssm, conv
