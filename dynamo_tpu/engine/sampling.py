"""On-device token sampling.

The reference passes sampling options through to vLLM
(`lib/llm/src/protocols/common.rs` SamplingOptionsProvider); here sampling
runs on-TPU at the end of the decode step so only sampled token ids cross
the device boundary each step (SURVEY.md §7 "per-token latency path").

Batched and branch-free: every sequence carries its own (temperature,
top_k, top_p, seed) and the same compiled kernel serves any mix of greedy
and stochastic requests — greedy is temperature == 0 via `jnp.where`, not a
Python branch, so no recompiles as the batch mix changes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling config (reference: protocols/common.rs
    SamplingOptions / StopConditions)."""

    temperature: float = 0.0     # 0 → greedy
    top_k: int = 0               # 0 → disabled
    top_p: float = 1.0           # 1 → disabled
    max_tokens: int = 16
    stop_token_ids: tuple = ()
    seed: Optional[int] = None
    # Return the log-probability of each sampled token (reference
    # perf/logprobs surface; OpenAI `logprobs`).  Requests with this set
    # take the single-step decode path (the fused window doesn't thread
    # the logprob aux).
    logprobs: bool = False
    # Migration support (reference migration.rs:148-163): tokens already
    # generated before a retry are appended to the prompt and max_tokens is
    # decremented by the caller.  `seed_offset` carries how many tokens a
    # previous incarnation of this stream already emitted, so seeded rows
    # keep the (seed, token-index) contract across a cross-worker
    # migration: the engine folds seed_offset into the per-token fold_in
    # index exactly like a local preemption's prior_output.
    seed_offset: int = 0


def chosen_logprobs(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """log p(token) under softmax(logits): [B, V], [B] → [B] float32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return picked - logz


def _filtered_logits(
    logits: jax.Array,        # [B, V] float32
    temperature: jax.Array,   # [B]
    top_k: jax.Array,         # [B] int32, 0 = off
    top_p: jax.Array,         # [B] float32, 1.0 = off
) -> jax.Array:
    """Temperature-scaled logits with top-k/top-p survivors kept and the
    rest at -inf — the distribution both `sample` and the speculative
    accept/resample draw from (one shared implementation, so spec decode
    is lossless against exactly what `sample` would have drawn)."""
    B, V = logits.shape
    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]

    # One descending sort serves both filters (this is the ITL-critical
    # sampling path; a second O(V log V) sort would be pure waste).
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]          # [B, V]

    # top-k: mask everything below the k-th largest logit.
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=1)
    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)

    # top-p (nucleus) on the top-k-masked distribution: in sorted space the
    # top-k survivors are exactly the first k_eff columns, so mask the rest
    # and take the smallest prefix with cumulative prob >= top_p.  top_p >=
    # 1 is "off" and must bypass the cutoff entirely: float32 cumsum can
    # round below 1.0, which would otherwise make argmax pick index 0 and
    # collapse sampling to greedy.
    col = jnp.arange(V)[None, :]
    sorted_masked = jnp.where(col < k_eff[:, None], sorted_desc, -jnp.inf)
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    # index of first position where cumulative >= top_p (inclusive)
    cutoff_idx = jnp.argmax(cumprobs >= top_p[:, None], axis=-1)
    cutoff_logit = jnp.take_along_axis(sorted_masked, cutoff_idx[:, None], axis=1)
    top_p_on = (top_p < 1.0)[:, None]
    return jnp.where(top_p_on & (scaled < cutoff_logit), -jnp.inf, scaled)


def sample(
    logits: jax.Array,        # [B, V] float32
    temperature: jax.Array,   # [B]
    top_k: jax.Array,         # [B] int32, 0 = off
    top_p: jax.Array,         # [B] float32, 1.0 = off
    key: jax.Array,           # PRNG key, single or [B] batch of keys
) -> jax.Array:
    """Sample one token per row.  Greedy where temperature == 0.

    `key` may be a batch of per-row keys (shape [B] of typed keys): seeded
    requests get reproducible streams independent of which other requests
    share the batch (the engine folds request seed + step index per row).
    """
    greedy_tok = jnp.argmax(logits, axis=-1)
    scaled = _filtered_logits(logits, temperature, top_k, top_p)
    if key.ndim > 0:
        sampled = jax.vmap(jax.random.categorical)(key, scaled)
    else:
        sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy_tok).astype(jnp.int32)


def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def speculative_verify(
    logits: jax.Array,        # [B, K+1, V] f32: verify-step logits, where
                              # position j is the model's distribution for
                              # the token FOLLOWING draft prefix d_0..d_{j-1}
    drafts: jax.Array,        # [B, K] int32 drafted tokens
    temperature: jax.Array,   # [B]
    top_k: jax.Array,         # [B] int32, 0 = off
    top_p: jax.Array,         # [B] float32, 1.0 = off
    keys: jax.Array,          # [B] typed PRNG keys (ignored by greedy rows)
    *,
    greedy_only: bool = False,  # STATIC: all-greedy batch fast path
) -> tuple:
    """Batched draft verification with rejection-sampling fallback
    (Leviathan et al. 2023, specialised to a DETERMINISTIC drafter whose
    proposal q is a point mass at d_j):

    - greedy rows (temperature <= 0): accept d_j while it equals the
      model's argmax; the emitted stream is the argmax chain — BYTE
      IDENTICAL to non-speculative greedy decode by construction;
    - stochastic rows: accept d_j with probability p_j(d_j) under the
      temperature/top-k/top-p-filtered distribution (q(d_j) = 1, so the
      min(1, p/q) acceptance test is just a uniform draw against p); on
      the first rejection, resample from the residual
      norm(max(p - q, 0)) = p with d_j removed and renormalised — the
      emitted marginal at every position is exactly `sample`'s, so a
      server-side --spec-decode flag never changes the output
      distribution (lossless by construction);
    - all K accepted: one bonus token samples normally from position K's
      distribution (the verify forward already paid for it).

    Returns (emitted [B, K+1] int32, n_emit [B] int32 in [1, K+1]):
    row b's step output is emitted[b, :n_emit[b]].

    `greedy_only` (static, the dominant serving case): skips the
    stochastic machinery entirely — no full-vocab sort, no softmax, no
    categorical draws; one argmax and an accept scan.  XLA can't DCE
    the stochastic branch on its own because temperature is traced.
    """
    B, T, V = logits.shape
    K = T - 1
    if greedy_only:
        argmax_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
        if K > 0:
            accept = drafts == argmax_tok[:, :K]
            n_accept = jnp.sum(jnp.cumprod(
                accept.astype(jnp.int32), axis=1), axis=1)
        else:
            n_accept = jnp.zeros((B,), jnp.int32)
        # At the first rejection argmax != draft, and the bonus position
        # has no draft — plain argmax IS the fallback everywhere.
        pos = jnp.arange(T)[None, :]
        emitted = jnp.where(
            pos < n_accept[:, None],
            jnp.concatenate([drafts, jnp.zeros((B, 1), drafts.dtype)],
                            axis=1),
            argmax_tok).astype(jnp.int32)
        return emitted, (n_accept + 1).astype(jnp.int32)

    flat = _filtered_logits(
        logits.reshape(B * T, V),
        jnp.repeat(temperature, T), jnp.repeat(top_k, T),
        jnp.repeat(top_p, T)).reshape(B, T, V)
    argmax_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]

    # Per-(row, position) keys: one fold per position from the row's base
    # key, split into an accept-draw stream and a resample stream, so a
    # seeded request's spec stream is a pure function of (seed, step).
    def row_keys(key):
        a, r = jax.random.split(key, 2)
        ak = jax.vmap(lambda j: jax.random.fold_in(a, j))(jnp.arange(K))
        rk = jax.vmap(lambda j: jax.random.fold_in(r, j))(jnp.arange(T))
        return ak, rk

    akeys, rkeys = jax.vmap(row_keys)(keys)      # [B, K], [B, T]

    if K > 0:
        probs = jax.nn.softmax(flat[:, :K], axis=-1)          # [B, K, V]
        p_draft = jnp.take_along_axis(
            probs, drafts[:, :, None], axis=-1)[..., 0]       # [B, K]
        u = jax.vmap(jax.vmap(jax.random.uniform))(akeys)     # [B, K]
        stochastic = (temperature > 0)[:, None]
        accept = jnp.where(stochastic, u < p_draft,
                           drafts == argmax_tok[:, :K])       # [B, K]
        n_accept = jnp.sum(jnp.cumprod(
            accept.astype(jnp.int32), axis=1), axis=1)        # [B]
    else:
        n_accept = jnp.zeros((B,), jnp.int32)

    # Fallback token per position: the residual draw.  Positions j < K
    # mask the (rejected) draft column out of the filtered logits —
    # categorical over the rest IS norm(max(p - q, 0)); greedy rows take
    # argmax of the same masked logits (rejection implies the argmax
    # differs from the draft, so masking never changes it).  The bonus
    # position K stays unmasked: nothing was proposed there.
    col = jnp.arange(V)[None, None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.full((B, 1), -1, drafts.dtype)], axis=1)  # [B, T]
    masked = jnp.where(col == drafts_pad[:, :, None], -jnp.inf, flat)
    resampled = jax.vmap(jax.vmap(jax.random.categorical))(
        rkeys, masked).astype(jnp.int32)                       # [B, T]
    masked_argmax = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    bonus_or_greedy = jnp.where((temperature > 0)[:, None],
                                resampled, masked_argmax)
    # Bonus position must NOT use the draft-masked distribution for
    # greedy (masked == flat there anyway since drafts_pad[:, K] = -1,
    # an id no vocab column matches) — masked_argmax[K] == argmax[K].

    pos = jnp.arange(T)[None, :]
    emitted = jnp.where(pos < n_accept[:, None],
                        jnp.concatenate(
                            [drafts, jnp.zeros((B, 1), drafts.dtype)],
                            axis=1),
                        bonus_or_greedy).astype(jnp.int32)
    n_emit = (n_accept + 1).astype(jnp.int32)
    return emitted, n_emit


def diffusion_unmask(logits: jax.Array, x0: jax.Array, masked: jax.Array,
                     n_static: int, threshold: float,
                     dynamic: bool):
    """One unmasking decision of block-diffusion decoding, on the device.

    logits [N, B, V] float32 of a denoising forward over N blocks of B
    positions (the mask token's logit already at -inf), x0 [N, B] the token
    proposed at each position (their argmax, or a sample), masked [N, B]
    which positions are still undecided.  The confidence of a masked
    position is the model's probability of its proposal,
    `softmax(logits)[x0]`; a decided position has none (-inf) and is never
    masked again.

    `low_confidence_static` (dynamic False): the `n_static` most confident
    masked positions of each block are decided (all that are left, if
    fewer).  `low_confidence_dynamic`: every masked position whose
    confidence is over `threshold`, if those are at least `n_static`;
    else the static rule.  Ties go to the earlier position.

    Returns (confidence [N, B], decide [N, B] bool)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
    conf = jnp.where(masked, jnp.exp(chosen - lse), -jnp.inf)
    # rank 0 = most confident; a stable sort keeps the earlier position
    # ahead on ties.
    rank = jnp.argsort(jnp.argsort(-conf, axis=-1, stable=True), axis=-1)
    n_masked = jnp.sum(masked, axis=-1, keepdims=True)
    take = jnp.minimum(n_static, n_masked)
    if dynamic:
        over = jnp.sum(conf > threshold, axis=-1, keepdims=True)
        take = jnp.where(over >= n_static, over, take)
    return conf, masked & (rank < take)
