"""Engine-core and scheduler tests on the tiny model (CPU devices).

The load-bearing property: batching must be semantically invisible —
greedy outputs of concurrent requests equal those of the same requests run
alone (padding discipline, slot isolation, chunked prefill).  This is the
engine-level analog of the reference's mocker-based routing tests
(SURVEY.md §4).
"""

import asyncio

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore, InferenceEngine
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    BlockAllocator,
    FinishReason,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
)
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


def run_to_completion(core: EngineCore, max_steps=500):
    outputs = {}
    finished = {}
    for _ in range(max_steps):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
            if d.finished:
                finished[d.request_id] = d.finish_reason
        if core.scheduler.num_active == 0 and not core._requests:
            break
    return outputs, finished


# -- scheduler unit tests ----------------------------------------------------


def _req(rid, prompt_len, max_tokens=4):
    return Request(request_id=rid, prompt_tokens=list(range(1, prompt_len + 1)),
                   sampling=SamplingParams(max_tokens=max_tokens))


def test_admission_respects_watermark():
    alloc = BlockAllocator(num_blocks=9)  # 8 usable
    sched = Scheduler(SchedulerConfig(
        max_seqs=4, block_size=8, max_pages_per_seq=4, watermark=0.3), alloc)
    # Each prompt of 15 tokens (+1) needs 2 pages; watermark = 2.4 blocks.
    for i in range(4):
        sched.add_request(_req(f"r{i}", 15))
    sched.plan()
    # 8 usable: r0 (2), r1 (2) admitted → free 4; admitting r2 would leave
    # 2 < 2.4 → blocked.
    admitted = [r.request_id for r in sched.running]
    assert admitted == ["r0", "r1"]
    assert alloc.free_blocks == 4


def test_chunked_prefill_budget():
    alloc = BlockAllocator(num_blocks=64)
    sched = Scheduler(SchedulerConfig(
        max_seqs=4, block_size=8, max_pages_per_seq=8,
        max_prefill_chunk=16, max_batched_tokens=24), alloc)
    sched.add_request(_req("a", 40))
    sched.add_request(_req("b", 40))
    plan = sched.plan()
    # Budget 24: a gets a 16-chunk, b gets the remaining 8 — packed into
    # ONE batched device call.
    assert [(w.request.request_id, w.length) for w in plan.prefill.items] == \
        [("a", 16), ("b", 8)]
    assert plan.prefill.rows == 2 and plan.prefill.chunk == 16
    for w in plan.prefill.items:
        sched.prefill_done(w)
    assert sched.running[0].prefilled == 16


def test_finish_releases_pages():
    alloc = BlockAllocator(num_blocks=16)
    sched = Scheduler(SchedulerConfig(
        max_seqs=2, block_size=8, max_pages_per_seq=8), alloc)
    sched.add_request(_req("a", 20))
    sched.plan()
    assert alloc.free_blocks < 15
    sched.finish(sched.running[0], FinishReason.STOP)
    assert alloc.free_blocks == 15


def test_too_long_prompt_rejected():
    alloc = BlockAllocator(num_blocks=16)
    sched = Scheduler(SchedulerConfig(
        max_seqs=2, block_size=8, max_pages_per_seq=2), alloc)
    req = _req("a", 20)  # 20 + 4 > 16 max context
    sched.add_request(req)
    assert req.state is RequestState.FINISHED
    assert req.finish_reason is FinishReason.LENGTH


# -- engine end-to-end -------------------------------------------------------


def test_single_request_generates():
    core = small_engine()
    core.add_request("r1", [5, 6, 7, 8], SamplingParams(max_tokens=6))
    outputs, finished = run_to_completion(core)
    assert len(outputs["r1"]) == 6
    assert finished["r1"] is FinishReason.LENGTH
    assert core.allocator.free_blocks == 63  # everything released


def test_batching_invisible_to_greedy_outputs():
    prompts = {
        "a": [1, 2, 3],
        "b": list(range(10, 31)),       # forces chunked prefill (21 > 16)
        "c": [9, 8, 7, 6, 5],
    }
    solo = {}
    for rid, p in prompts.items():
        core = small_engine()
        core.add_request(rid, p, SamplingParams(max_tokens=8))
        out, _ = run_to_completion(core)
        solo[rid] = out[rid]

    core = small_engine()
    for rid, p in prompts.items():
        core.add_request(rid, p, SamplingParams(max_tokens=8))
    batched, finished = run_to_completion(core)

    assert batched == solo
    assert all(r is FinishReason.LENGTH for r in finished.values())


def test_stop_token_finishes_early():
    core = small_engine()
    core.add_request("r1", [5, 6, 7, 8], SamplingParams(max_tokens=32))
    # Find what greedy emits first, then re-run with it as a stop token.
    outputs, _ = run_to_completion(core)
    first = outputs["r1"][0]

    core2 = small_engine()
    core2.add_request("r1", [5, 6, 7, 8],
                      SamplingParams(max_tokens=32, stop_token_ids=(first,)))
    outputs2, finished2 = run_to_completion(core2)
    assert outputs2["r1"] == [first]
    assert finished2["r1"] is FinishReason.STOP


def test_kv_events_emitted_with_chained_hashes():
    """Plain-allocator event contract: STORED on seal, REMOVED on finish
    (no residency after release).  Managed-cache semantics are tested in
    test_prefix_cache_* below."""
    from dynamo_tpu.tokens import compute_block_hashes

    events = []
    core = EngineCore(
        EngineConfig(
            model=TINY, num_blocks=64, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16)),
        ),
        kv_event_sink=events.append,
    )
    prompt = list(range(1, 20))  # 19 tokens → 2 complete blocks of 8
    core.add_request("r1", prompt, SamplingParams(max_tokens=6))
    run_to_completion(core)

    stored = [e for e in events if e.data.store is not None]
    removed = [e for e in events if e.data.remove is not None]
    assert stored and removed
    all_stored = [h for e in stored for h in e.data.store.block_hashes]
    # 19 prompt + 6 output = 25 tokens → 3 sealed blocks of 8.
    # Recompute expected hashes from the actual generated tokens:
    core2 = small_engine()
    core2.add_request("r1", prompt, SamplingParams(max_tokens=6))
    out, _ = run_to_completion(core2)
    expected = compute_block_hashes(prompt + out["r1"], block_size=8)[:3]
    assert all_stored == list(expected)
    # Removal covers exactly what was stored.
    assert sorted(h for e in removed for h in e.data.remove.block_hashes) == \
        sorted(all_stored)
    # Event ids strictly increasing.
    ids = [e.event_id for e in events]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)


def test_cancel_mid_stream():
    core = small_engine()
    core.add_request("r1", [1, 2, 3], SamplingParams(max_tokens=32))
    core.step()  # prefill + first token
    core.cancel("r1")
    deltas = core.step()
    assert any(d.finished and d.finish_reason is FinishReason.CANCELLED
               for d in deltas)
    assert core.allocator.free_blocks == 63


def test_async_engine_streams():
    async def main():
        core = small_engine()
        eng = InferenceEngine(core)
        await eng.start()
        try:
            got = []
            async for delta in eng.generate(
                    "r1", [5, 6, 7], SamplingParams(max_tokens=5)):
                got.extend(delta.token_ids)
                if delta.finished:
                    break
            return got
        finally:
            await eng.stop()

    got = asyncio.run(main())
    assert len(got) == 5


def test_async_engine_concurrent_requests():
    async def main():
        core = small_engine()
        eng = InferenceEngine(core)
        await eng.start()

        async def one(rid, prompt):
            toks = []
            async for d in eng.generate(rid, prompt,
                                        SamplingParams(max_tokens=4)):
                toks.extend(d.token_ids)
            return toks

        try:
            return await asyncio.gather(
                one("a", [1, 2, 3]), one("b", [4, 5, 6]), one("c", [7, 8]))
        finally:
            await eng.stop()

    a, b, c = asyncio.run(main())
    assert len(a) == len(b) == len(c) == 4


def test_engine_matches_single_forward_contract():
    """Engine greedy decode must equal re-prefilling the whole sequence from
    scratch each step (locks the decode position contract; ADVICE r1 found a
    +1 shift here that batching-invariance tests could not see)."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models.llama import init_params, make_forward_step

    prompt = [5, 6, 7, 8, 9]
    n_out = 6

    core = small_engine()
    core.add_request("r1", prompt, SamplingParams(max_tokens=n_out))
    outputs, _ = run_to_completion(core)
    engine_out = outputs["r1"]

    # Ground truth: full fresh prefill of (prompt + generated-so-far) each
    # step; argmax of the last position's logits.
    cfg = TINY
    params = init_params(cfg, jax.random.key(0))
    step = jax.jit(make_forward_step(cfg, 8))
    ref_out = []
    toks = list(prompt)
    for _ in range(n_out):
        L = len(toks)
        pages = (L + 7) // 8
        cache = kvc.init_cache(
            kvc.KvCacheConfig.for_model(cfg, num_blocks=16, block_size=8))
        logits, _ = step(
            params, cache,
            jnp.asarray([toks], jnp.int32),
            jnp.arange(L, dtype=jnp.int32)[None, :],
            jnp.asarray([L], jnp.int32),
            jnp.asarray([list(range(1, pages + 1)) + [0] * (16 - pages)],
                        jnp.int32),
        )
        nxt = int(jnp.argmax(logits[0, L - 1]))
        ref_out.append(nxt)
        toks.append(nxt)

    assert engine_out == ref_out


def test_preemption_invisible_to_greedy_output():
    """Under block contention one request is preempted (recompute) — its
    final output must match an uncontended run exactly."""
    prompts = {"a": [1, 2, 3, 4, 5, 6, 7, 8], "b": [9, 10, 11, 12, 13, 14]}
    n_out = 30

    solo = {}
    for rid, p in prompts.items():
        core = small_engine(num_blocks=64)
        core.add_request(rid, p, SamplingParams(max_tokens=n_out))
        out, _ = run_to_completion(core)
        solo[rid] = out[rid]

    # 9 blocks → 8 usable pages of 8 tokens; two requests growing to
    # ~38 tokens each (5 pages) must collide and preempt.
    core = small_engine(num_blocks=9)
    for rid, p in prompts.items():
        core.add_request(rid, p, SamplingParams(max_tokens=n_out))
    batched, finished = run_to_completion(core, max_steps=2000)

    assert batched == solo
    assert all(r is FinishReason.LENGTH for r in finished.values())


def test_prefix_cache_hit_skips_prefill_and_matches():
    """Second identical prompt must hit G1 prefix blocks (live wiring of the
    managed block source — ADVICE r1 found it dead) and produce identical
    output."""
    prompt = list(range(1, 25))  # 3 sealed blocks of 8

    core = small_engine()
    core.add_request("a", prompt, SamplingParams(max_tokens=4))
    out_a, _ = run_to_completion(core)
    hits_before = core.allocator.manager.device.hits

    core.add_request("b", prompt, SamplingParams(max_tokens=4))
    out_b, _ = run_to_completion(core)
    assert core.allocator.manager.device.hits > hits_before
    assert out_b["b"] == out_a["a"]
    # The cached-prefix request recomputed only the last prompt token.


def test_managed_eviction_emits_removed_and_offloads():
    """Filling the pool evicts an earlier request's registered blocks →
    REMOVED KV events fire from the eviction hook, and with a G2 tier the
    block survives and onboards back on a later match."""
    events = []
    core = EngineCore(
        EngineConfig(
            model=TINY, num_blocks=9, host_blocks=16,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16)),
        ),
        kv_event_sink=events.append,
    )
    prompt_a = list(range(1, 17))  # 2 sealed blocks
    core.add_request("a", prompt_a, SamplingParams(max_tokens=2))
    out_a1, _ = run_to_completion(core)

    # Churn through enough distinct blocks to evict a's.
    for i in range(3):
        core.add_request(f"c{i}", [100 + 8 * i + j for j in range(16)],
                         SamplingParams(max_tokens=2))
        run_to_completion(core)

    removed = [h for e in events if e.data.remove is not None
               for h in e.data.remove.block_hashes]
    assert removed, "eviction must emit REMOVED events"
    assert core.allocator.manager.offloaded_blocks > 0

    # Re-running prompt_a onboards from G2 (hash-correct KV) and matches.
    onboarded_before = core.allocator.manager.onboarded_blocks
    core.add_request("a2", prompt_a, SamplingParams(max_tokens=2))
    out_a2, _ = run_to_completion(core)
    assert out_a2["a2"] == out_a1["a"]
    assert core.allocator.manager.onboarded_blocks > onboarded_before


def test_seeded_sampling_reproducible_across_batch_mix():
    """A seeded stochastic request must not depend on batch-mates."""
    seeded = dict(prompt=[3, 1, 4, 1, 5],
                  sampling=SamplingParams(temperature=0.9, seed=1234,
                                          max_tokens=6))

    core = small_engine()
    core.add_request("s", seeded["prompt"], seeded["sampling"])
    solo, _ = run_to_completion(core)

    core2 = small_engine()
    core2.add_request("other1", [9, 9, 9], SamplingParams(max_tokens=6))
    core2.add_request("s", seeded["prompt"], seeded["sampling"])
    core2.add_request("other2", [7, 7], SamplingParams(temperature=1.5,
                                                       max_tokens=6))
    mixed, _ = run_to_completion(core2)

    assert mixed["s"] == solo["s"]


def test_speculative_decode_matches_plain_greedy():
    """Prompt-lookup speculative decoding must be output-invisible: the
    accepted-token stream equals the plain engine's greedy output
    exactly, while accepting >0 drafted tokens on repetitive text."""
    # Repetitive prompt: n-gram lookup finds continuations to draft.
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    n_out = 24

    plain = small_engine(num_blocks=64, decode_window=1)
    plain.add_request("a", prompt, SamplingParams(max_tokens=n_out))
    want, _ = run_to_completion(plain)

    spec = small_engine(num_blocks=64, speculative_tokens=3)
    spec.add_request("a", prompt, SamplingParams(max_tokens=n_out))
    got, _ = run_to_completion(spec)

    assert got["a"] == want["a"]
    stats = spec.metrics.spec_decode_stats
    assert stats is not None and stats.num_drafts > 0
    # The whole point: some drafts verified (repetitive text accepts).
    assert stats.num_accepted_tokens > 0


def test_speculative_decode_batched_and_preemption_safe():
    """Two concurrent requests under spec decoding, tight block budget:
    outputs still match solo runs (fallback path covers capacity
    refusals)."""
    prompts = {"a": [1, 2, 3, 1, 2, 3, 1, 2], "b": [9, 9, 8, 9, 9, 8]}
    n_out = 20
    solo = {}
    for rid, p in prompts.items():
        core = small_engine(num_blocks=64, decode_window=1)
        core.add_request(rid, p, SamplingParams(max_tokens=n_out))
        out, _ = run_to_completion(core)
        solo[rid] = out[rid]

    core = small_engine(num_blocks=10, speculative_tokens=3)
    for rid, p in prompts.items():
        core.add_request(rid, p, SamplingParams(max_tokens=n_out))
    got, _ = run_to_completion(core, max_steps=2000)
    assert got == solo


def test_mixed_budget_caps_prefill_when_decoding():
    """VERDICT r4 weak #4: with streams decoding, prefill gets at most
    mixed_prefill_tokens per step, not max_batched_tokens."""
    alloc = BlockAllocator(num_blocks=64)
    sched = Scheduler(SchedulerConfig(
        max_seqs=4, block_size=8, max_pages_per_seq=8,
        max_prefill_chunk=16, max_batched_tokens=64,
        mixed_prefill_tokens=8), alloc)
    sched.add_request(_req("dec", 8))
    plan = sched.plan()
    for w in plan.prefill.items:
        sched.prefill_done(w)
    assert sched.running[0].state.value == "decode"
    sched.add_request(_req("new1", 40))
    sched.add_request(_req("new2", 40))
    plan = sched.plan()
    assert plan.decode is not None
    assert sum(w.length for w in plan.prefill.items) <= 8
    # Without decode streams the full budget applies.
    sched.finish(sched.running[0], FinishReason.LENGTH)
    plan = sched.plan()
    assert sum(w.length for w in plan.prefill.items) > 8


def _rule_engine(max_seqs=64, **kw):
    """Window mode at 64 slots and chunks of up to 128 tokens, on an
    injected clock that moves as a device queue would
    (`tests/test_request_state_clock.py:_device_clock`)."""
    from tests.test_request_state_clock import _device_clock

    core = small_engine(
        decode_window=2, window_pipeline_depth=2, num_blocks=2200,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(
            max_seqs=max_seqs, block_size=16, max_pages_per_seq=128,
            max_prefill_chunk=128, prefill_buckets=(16, 128)))
    return core, _device_clock(core, **kw)


def test_a_fallen_behind_engine_catches_up():
    """The trap of the controller this rule replaced: 4 rows decode and 60
    prompts of 100-300 tokens wait.  Its budget was in proportion to the
    rows decoding, so at 4 rows it granted a floor of 64 tokens behind
    every eighth window: few rows, small chunks, fewer prompts finished,
    few rows, and 60 prompts took some 1,500 windows.  Under the rule a
    chunk carries whatever waits up to `max_prefill_chunk` whatever the
    rows, the queue empties in a number of windows that 15 / 85 of their
    seconds bound, and the rows decoding rise."""
    core, log = _rule_engine(window_s=1.0, chunk_s=0.5)
    for i in range(4):
        core.add_request(f"row{i}", list(range(1, 9)),
                         SamplingParams(max_tokens=1500))
    while core.counters.window_s is None:
        core.step()
    del log[:]
    rng = np.random.default_rng(51)
    lens = [int(n) for n in rng.integers(100, 301, size=60)]
    for i, n in enumerate(lens):
        core.add_request(f"p{i}", [3 + i % 7] * n,
                         SamplingParams(max_tokens=400))
    assert len(core.scheduler.waiting) == 60
    prefilling = lambda: core.scheduler.waiting or any(  # noqa: E731
        r.state is RequestState.PREFILL for r in core.scheduler.running)
    while prefilling():
        core.step()
        assert len(log) < 5000
    chunks = [e for e in log if e[0] == "c"]
    windows = [e[1] for e in log if e[0] == "w"]
    # Every chunk carries what waits, up to max_prefill_chunk: never the
    # 64 tokens of a floor.
    assert all(tokens == min(128, backlog) for _, tokens, backlog in chunks)
    assert len(chunks) <= -(-sum(lens) // 128) + 60
    # Bounded: a chunk of half a window's seconds needs the credit of
    # 0.5 / (15 / 85) = 2.83 windows.
    assert len(windows) <= len(chunks) * 2.84 + 20
    # The rows decoding rise with every prompt that finishes.
    assert windows[0] == 4 and windows[-1] >= 40


@pytest.mark.parametrize("rows", [1, 4, 40])
def test_the_seconds_granted_do_not_depend_on_the_rows_decoding(rows):
    """With the same measured seconds a window and a chunk, a backlog gets
    the same chunks behind 1, 4 and 40 rows: over 40 windows, the tokens
    of 40 x 15 / 85 window-seconds at 0.5 s a chunk of 128."""
    core, log = _rule_engine(window_s=1.0, chunk_s=0.5)
    for i in range(rows):
        core.add_request(f"row{i}", list(range(1, 9)),
                         SamplingParams(max_tokens=400))
    while core.counters.window_s is None:
        core.step()
    core._chunk_credit_s = 0.0
    del log[:]
    core.add_request("long", [7] * 1900, SamplingParams(max_tokens=2))
    while sum(1 for e in log if e[0] == "w") < 40:
        core.step()
    assert all(e == ("w", rows) for e in log if e[0] == "w")
    tokens = sum(e[1] for e in log if e[0] == "c")
    # The first chunk rides unmeasured and pays a window's seconds (the
    # cap then), the rest 0.5 s each out of 40 x 0.1765 s: 12 chunks are
    # behind the first 40 windows, whatever the rows.
    assert tokens == 12 * 128


def test_a_window_engine_lifts_the_static_cap_once_and_no_other_does():
    """A window engine on one host plans whatever waits up to
    `max_prefill_chunk` (the rule bounds when it rides); a single-step
    engine and a speculative one keep the scheduler's static cap, and the
    options of the controller that is gone are no options."""
    window = small_engine(decode_window=4)
    assert (window.scheduler.mixed_budget_override
            == window.scheduler.config.max_prefill_chunk)
    assert small_engine(
        decode_window=1).scheduler.mixed_budget_override is None
    assert small_engine(
        decode_window=4,
        speculative_tokens=2).scheduler.mixed_budget_override is None
    for gone in ("mixed_prefill_duty", "mixed_prefill_adaptive",
                 "mixed_prefill_target"):
        with pytest.raises(TypeError):
            small_engine(**{gone: 1})
    window.add_request("a", [1, 2, 3], SamplingParams(max_tokens=12))
    run_to_completion(window)
    assert (window.scheduler.mixed_budget_override
            == window.scheduler.config.max_prefill_chunk)


def test_windows_continue_through_prefill_injection():
    """Decode windows must keep running while injected prompts prefill
    (bounded chunks ride behind each window), and every stream must
    still produce exactly max_tokens unique-positioned tokens."""
    core = small_engine(
        num_blocks=128,
        decode_window=4,
        window_pipeline_depth=2,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=16,
            max_prefill_chunk=16, mixed_prefill_tokens=16,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16)))
    n_out = 96
    for i in range(2):
        core.add_request(f"steady{i}", list(range(1, 12)),
                         SamplingParams(max_tokens=n_out))
    outputs: dict = {}
    windows_during_prefill = 0
    injected = False
    for _ in range(600):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        steady_progress = len(outputs.get("steady0", []))
        if not injected and steady_progress >= 8:
            for i in range(4):
                core.add_request(f"inj{i}", list(range(20, 50)),
                                 SamplingParams(max_tokens=n_out))
            injected = True
        if injected and core._inflight and any(
                r.state is RequestState.PREFILL
                for r in core.scheduler.running):
            windows_during_prefill += 1
        if injected and not core._requests:
            break
    assert not core._requests, "requests stalled"
    assert not core._pending_batches and not core._pending_first
    for rid, toks in outputs.items():
        assert len(toks) == n_out, (rid, len(toks))
    # The point of the machinery: at least one window dispatched while
    # injected prompts were still prefilling (no full-batch stall).
    assert windows_during_prefill > 0


def test_mixed_injection_preserves_greedy_stream():
    """A steady greedy stream's tokens must be unaffected by a mid-flight
    injection (same tokens as an undisturbed run)."""
    def run(inject: bool):
        core = small_engine(
            num_blocks=128,
            decode_window=4,
            window_pipeline_depth=2,
            enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=16,
                max_prefill_chunk=16, mixed_prefill_tokens=16,
                decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16)))
        core.add_request("s", list(range(1, 12)),
                         SamplingParams(max_tokens=64))
        out: list = []
        injected = False
        for _ in range(600):
            for d in core.step():
                if d.request_id == "s":
                    out.extend(d.token_ids)
            if inject and not injected and len(out) >= 8:
                core.add_request("j", list(range(20, 44)),
                                 SamplingParams(max_tokens=8))
                injected = True
            if not core._requests:
                break
        return out

    assert run(inject=True) == run(inject=False)
