"""Self-speculative decoding (ISSUE 6b): batched verify + rejection
sampling + pluggable drafters.

The load-bearing property is LOSSLESSNESS: greedy spec output is
byte-identical to plain greedy (argmax chain), and stochastic spec
preserves the exact sampling distribution (Leviathan-style rejection
sampling with a point-mass proposal).  Draft quality may only change
speed, never bytes.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.drafter import DraftModelDrafter, NgramDrafter
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams, speculative_verify
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg

TINY = mcfg.get_config("tiny-test")


def small_engine(**kw) -> EngineCore:
    defaults = dict(
        model=TINY,
        num_blocks=64,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16)),
    )
    defaults.update(kw)
    return EngineCore(EngineConfig(**defaults))


def run_to_completion(core, max_steps=800):
    outputs = {}
    for _ in range(max_steps):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if core.scheduler.num_active == 0 and not core._requests:
            break
    return outputs


# -- speculative_verify ------------------------------------------------------


def _verify(logits, drafts, temp, keys, top_k=None, top_p=None):
    B = logits.shape[0]
    return speculative_verify(
        jnp.asarray(logits, jnp.float32), jnp.asarray(drafts, jnp.int32),
        jnp.asarray(temp, jnp.float32),
        jnp.asarray(top_k if top_k is not None else np.zeros(B), jnp.int32),
        jnp.asarray(top_p if top_p is not None else np.ones(B),
                    jnp.float32),
        keys)


def test_greedy_verify_is_argmax_chain():
    """Greedy rows: accept while draft == argmax; emitted tokens are
    exactly the argmax chain (positions 0..n_emit-1)."""
    V, K = 8, 3
    logits = np.full((2, K + 1, V), -5.0, np.float32)
    # Row 0: argmax sequence [2, 4, 6, 1]; draft [2, 4, 0] → accept 2,
    # emit [2, 4, 6] (6 = argmax at the first rejection).
    for j, t in enumerate([2, 4, 6, 1]):
        logits[0, j, t] = 5.0
    # Row 1: argmax [3, 3, 3, 3]; draft [3, 3, 3] → full accept + bonus.
    for j in range(K + 1):
        logits[1, j, 3] = 5.0
    drafts = np.array([[2, 4, 0], [3, 3, 3]], np.int32)
    keys = jax.random.split(jax.random.key(0), 2)
    emitted, n_emit = _verify(logits, drafts, [0.0, 0.0], keys)
    emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
    assert n_emit.tolist() == [3, 4]
    assert emitted[0, :3].tolist() == [2, 4, 6]
    assert emitted[1, :4].tolist() == [3, 3, 3, 3]

    # The static greedy_only fast path (argmax-only, no sort/softmax/
    # categorical — what all-greedy serving batches compile) must agree
    # exactly with the traced temperature==0 path.
    em2, ne2 = speculative_verify(
        jnp.asarray(logits), jnp.asarray(drafts),
        jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2), keys,
        greedy_only=True)
    assert np.asarray(ne2).tolist() == n_emit.tolist()
    for b in range(2):
        assert (np.asarray(em2)[b, :n_emit[b]].tolist()
                == emitted[b, :n_emit[b]].tolist())


def test_rejection_sampling_preserves_distribution():
    """The lossless-acceptance core: with a point-mass draft, the
    marginal of the FIRST emitted token must equal the target softmax —
    draft accepted (emit d) with prob p(d), else residual resample."""
    V, N = 6, 6000
    row_logits = np.array([2.0, 1.0, 0.5, 0.0, -1.0, -2.0], np.float32)
    target = np.exp(row_logits) / np.exp(row_logits).sum()
    logits = np.broadcast_to(row_logits, (N, 2, V)).copy()
    drafts = np.full((N, 1), 1, np.int32)  # draft token 1 (p ≈ 0.26)
    keys = jax.random.split(jax.random.key(7), N)
    emitted, n_emit = _verify(logits, drafts, np.ones(N, np.float32), keys)
    first = np.asarray(emitted)[:, 0]
    emp = np.bincount(first, minlength=V) / N
    np.testing.assert_allclose(emp, target, atol=0.03)
    # And acceptance happened at roughly p(draft).
    acc_rate = (np.asarray(n_emit) > 1).mean()
    assert abs(acc_rate - target[1]) < 0.03


def test_verify_respects_top_k_filter():
    """A draft outside the top-k set must never be accepted, and the
    resample must come from the filtered set."""
    V, N = 8, 500
    row_logits = np.array([3.0, 2.5, 2.0, -1, -1, -1, -1, -1], np.float32)
    logits = np.broadcast_to(row_logits, (N, 2, V)).copy()
    drafts = np.full((N, 1), 7, np.int32)       # far outside top-3
    keys = jax.random.split(jax.random.key(9), N)
    emitted, n_emit = _verify(logits, drafts, np.ones(N, np.float32),
                              keys, top_k=np.full(N, 3))
    assert np.all(np.asarray(n_emit) == 1)       # never accepted
    assert set(np.asarray(emitted)[:, 0].tolist()) <= {0, 1, 2}


# -- drafters ----------------------------------------------------------------


def test_ngram_drafter_self_extends():
    """The truncated-continuation fix: a period-1 cycle must draft k
    tokens, not 1 (the match near the tail yields a 1-token continuation
    that re-lookup extends)."""
    d = NgramDrafter(ngram=3)
    hist = [7, 8, 9] + [5] * 6
    assert d.propose(hist, 4) == [5, 5, 5, 5]
    # Period-2 cycle extends too.
    hist2 = [1, 2] * 6
    assert d.propose(hist2, 4) == [1, 2, 1, 2]
    # No repetition → no draft.
    assert d.propose([1, 2, 3, 4, 5, 6], 4) == []
    assert d.propose([1, 2], 4) == []


def test_draft_model_drafter_adapter():
    calls = []

    def fn(hist, k):
        calls.append((len(hist), k))
        return [42] * (k + 5)  # over-long: adapter truncates

    d = DraftModelDrafter(fn)
    assert d.propose([1, 2, 3], 3) == [42, 42, 42]
    assert calls == [(3, 3)]


def test_pluggable_drafter_wrong_drafts_stay_lossless():
    """A deliberately WRONG drafter: outputs must still equal plain
    greedy (verify rejects everything), acceptance telemetry reads 0."""
    prompt = [5, 6, 7, 8] * 4

    plain = small_engine(decode_window=1)
    plain.add_request("a", prompt, SamplingParams(max_tokens=10))
    want = run_to_completion(plain)

    class WrongDrafter:
        def propose(self, history, k):
            return [0] * k  # token 0 is (practically) never the argmax

    spec = small_engine(speculative_tokens=3, drafter=WrongDrafter())
    spec.add_request("a", prompt, SamplingParams(max_tokens=10))
    got = run_to_completion(spec)
    assert got == want
    stats = spec.metrics.spec_decode_stats
    assert stats.num_drafts > 0 and stats.num_accepted_tokens == 0


# -- engine integration ------------------------------------------------------


def test_seeded_stochastic_keeps_plain_path_contract():
    """Seeded stochastic rows are routed AROUND the spec path (a jointly
    drawn burst can't honor the (seed, token-index) stream contract), so
    --spec-decode must not change a seeded request's bytes at all."""
    sp = SamplingParams(temperature=0.8, seed=42, max_tokens=10)
    prompt = [5, 6, 7, 8] * 3 + [5, 6]

    plain = small_engine()
    plain.add_request("r", prompt, sp)
    want = run_to_completion(plain)["r"]

    spec = small_engine(speculative_tokens=3)
    spec.add_request("r", prompt, sp)
    got = run_to_completion(spec)["r"]
    assert got == want and len(got) == 10
    # And the spec path really was bypassed for the seeded request.
    assert spec.counters.spec_dispatches == 0


def test_unseeded_stochastic_spec_runs():
    """Unseeded stochastic rows stay spec-eligible (rejection sampling
    preserves their distribution); the stream completes at length.  A
    constant-draft drafter forces the verify step to dispatch — sampled
    output rarely repeats, so the n-gram drafter alone would sit out."""
    class ConstantDrafter:
        def propose(self, history, k):
            return [history[-1]] * k

    core = small_engine(speculative_tokens=3, drafter=ConstantDrafter())
    core.add_request("r", [5, 6, 7, 8] * 3 + [5, 6],
                     SamplingParams(temperature=0.8, max_tokens=10))
    out = run_to_completion(core)["r"]
    assert len(out) == 10
    assert core.counters.spec_dispatches > 0


def test_mixed_greedy_and_stochastic_spec_batch():
    """Greedy and stochastic rows share one verify step; the greedy
    row's output must still be byte-identical to its solo plain run."""
    prompt_g = [5, 6, 7, 8] * 4
    plain = small_engine(decode_window=1)
    plain.add_request("g", prompt_g, SamplingParams(max_tokens=10))
    want_g = run_to_completion(plain)["g"]

    core = small_engine(speculative_tokens=3)
    core.add_request("g", prompt_g, SamplingParams(max_tokens=10))
    core.add_request("s", [9, 9, 8, 9, 9, 8],
                     SamplingParams(temperature=0.9, max_tokens=10))
    got = run_to_completion(core)
    assert got["g"] == want_g
    assert len(got["s"]) == 10


def test_spec_metrics_exported():
    """Acceptance-rate + effective-bytes series reach /metrics via
    KvCacheMetrics.observe_engine."""
    from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

    core = small_engine(speculative_tokens=3)
    core.add_request("a", [5, 6, 7, 8] * 4, SamplingParams(max_tokens=24))
    run_to_completion(core)
    stats = core.metrics.spec_decode_stats
    assert stats.num_drafts > 0 and stats.num_accepted_tokens > 0
    assert core.counters.spec_dispatches > 0
    assert core.counters.effective_bytes_per_token > 0

    reg = MetricsRegistry()
    kvm = KvCacheMetrics(reg)
    kvm.observe_engine(core)
    text = reg.expose()
    assert kvm.spec_drafted.value() == stats.num_drafts
    assert kvm.spec_accepted.value() == stats.num_accepted_tokens
    assert kvm.spec_acceptance_rate.value() == (
        stats.num_accepted_tokens / stats.num_drafts)
    assert "dynamo_spec_decode_acceptance_rate" in text
    assert "dynamo_kv_effective_bytes_per_token" in text


def test_acceptance_floor_on_repetitive_workload():
    """On a cyclic prompt (code loops, quoted context, agent echoes
    degenerate to it under greedy continuation) the n-gram drafter is
    accepted at >= 0.6, later draft positions are accepted no more often
    than the first, and the output is byte-identical to the
    non-speculative engine's."""
    prompt = [5 + (i % 4) for i in range(24)]

    def run(k):
        core = small_engine(
            num_blocks=33, speculative_tokens=k, speculative_ngram=3,
            decode_window=8 if k else 1, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=24,
                decode_buckets=(1, 2, 4, 8, 16, 32, 64),
                prefill_buckets=(16, 32, 64, 128, 256, 512)))
        core.add_request("spec0", prompt, SamplingParams(max_tokens=32))
        return core, run_to_completion(core, max_steps=100_000)

    spec_core, spec_out = run(4)
    _, base_out = run(0)
    stats = spec_core.metrics.spec_decode_stats
    assert stats.num_accepted_tokens / stats.num_drafts >= 0.6
    per_pos = list(stats.num_accepted_tokens_per_pos)
    assert per_pos[0] >= per_pos[-1]
    assert spec_out == base_out
