"""Packed ragged prefill plane (ISSUE 10).

Layers under test, cheapest first: the Pallas flash-prefill kernel
against the gather oracle (interpret mode, no engine), the scheduler's
pack sizing, the measured-cost EWMA calibration, and then the engine
plane end to end — packed vs padded token parity (bf16 AND int8, with
and without a cached prefix resident in the pool), the prewarm shape-set
pin, and the steady-decode-counters byte-identity with the plane idle.

Engine-build discipline (tier-1 timing budget): every engine test shares
ONE tiny geometry (`GEOM`) so the persistent XLA compile cache serves
repeated shapes across tests, and runs are a handful of short requests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    PrefillBatch,
    PrefillWork,
    SchedulerConfig,
    pack_prefill_chunks,
)
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas import paged_prefill_attention
from dynamo_tpu.runtime.metrics import EngineStepCounters

TINY = mcfg.get_config("tiny-test")

# One shared geometry for every engine in this file (compile-cache reuse).
GEOM = dict(max_seqs=8, block_size=8, max_pages_per_seq=16,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(8, 16, 32))


def make_core(packed, kv_quant="none", prefix_cache=False, **over):
    cfg = dict(model=TINY, num_blocks=128, packed_prefill=packed,
               kv_quant=kv_quant, enable_prefix_cache=prefix_cache,
               scheduler=SchedulerConfig(**GEOM))
    cfg.update(over)
    return EngineCore(EngineConfig(**cfg))


def serve(core, rid, prompt, max_tokens=4):
    core.add_request(rid, prompt, SamplingParams(max_tokens=max_tokens))
    out = []
    for _ in range(400):
        for d in core.step():
            out.extend(d.token_ids)
        if not core._requests:
            break
    return out


def run_fleet(core, prompts, max_tokens=4):
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
    out = {}
    for _ in range(600):
        for d in core.step():
            out.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    return out


# -- kernel vs gather oracle -------------------------------------------------


def _oracle_segment(kc, vc, bt_row, seq_len, chunk_start, q_seg, bs, Hkv,
                    scales=None):
    P = bt_row.shape[0]
    C = P * bs
    ctx_pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (1, C))
    slots = kvc.slots_for_positions(bt_row[None], ctx_pos, bs)
    if scales is None:
        k_ctx, v_ctx = kvc.gather_kv(kc, vc, slots, Hkv)
    else:
        ks, vs = scales
        k_ctx, v_ctx = kvc.gather_kv_quant(kc, vc, ks, vs, slots, Hkv,
                                           out_dtype=jnp.bfloat16)
    ql = q_seg.shape[0]
    q_pos = jnp.arange(chunk_start, chunk_start + ql,
                       dtype=jnp.int32)[None]
    return paged_attention(q_seg[None], k_ctx, v_ctx, q_pos, ctx_pos,
                           jnp.asarray([seq_len], jnp.int32))[0]


def test_paged_prefill_kernel_matches_gather_oracle():
    """Packed multi-segment kernel == per-segment gather path: a full
    prompt, a residual chunk over a CACHED PREFIX (chunk_start > 0 —
    cached-prefix attention), and a pad segment; pad/gap rows come back
    zero."""
    rng = np.random.default_rng(0)
    Hq, Hkv, D, bs, P = 8, 4, 16, 8, 6
    S = 40 * bs
    kc = jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.bfloat16)
    segs = [(0, 24), (16, 9), (0, 0)]  # (chunk_start, q_len)
    T = 48
    starts, qlens, seqlens, off = [], [], [], 0
    q = np.zeros((T, Hq, D), np.float32)
    for cs, ql in segs:
        starts.append(off)
        qlens.append(ql)
        seqlens.append(cs + ql)
        if ql:
            q[off:off + ql] = rng.normal(size=(ql, Hq, D))
        off += -(-ql // 8) * 8
    bt = np.zeros((len(segs), P), np.int32)
    bt[0] = [3, 9, 17, 2, 25, 30]
    bt[1] = [11, 4, 21, 7, 0, 0]
    qj = jnp.asarray(q, jnp.float32)

    out = np.asarray(paged_prefill_attention(
        qj, kc, vc, jnp.asarray(bt), jnp.asarray(seqlens, jnp.int32),
        jnp.asarray(starts, jnp.int32), jnp.asarray(qlens, jnp.int32),
        block_size=bs, interpret=True))

    owned = set()
    for r, (cs, ql) in enumerate(segs):
        if not ql:
            continue
        ref = _oracle_segment(kc, vc, jnp.asarray(bt[r]), seqlens[r], cs,
                              qj[starts[r]:starts[r] + ql], bs, Hkv)
        np.testing.assert_allclose(
            out[starts[r]:starts[r] + ql], np.asarray(ref),
            rtol=3e-2, atol=3e-2)
        owned.update(range(starts[r], starts[r] + ql))
    pad_rows = sorted(set(range(T)) - owned)
    assert np.all(out[pad_rows] == 0)


def test_paged_prefill_kernel_int8_variant():
    """int8 pool + [S, Hkv] scales: dequant-in-VMEM numerics match the
    gather_kv_quant oracle, cached-prefix residual included."""
    rng = np.random.default_rng(1)
    Hq, Hkv, D, bs, P = 8, 4, 16, 8, 4
    S = 24 * bs
    kq, ks = kvc.quantize_kv_rows(
        jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.float32), Hkv)
    vq, vs = kvc.quantize_kv_rows(
        jnp.asarray(rng.normal(size=(S, Hkv * D)), jnp.float32), Hkv)
    segs = [(0, 16), (8, 5)]
    starts, qlens, seqlens, T = [0, 16], [16, 5], [16, 13], 24
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.bfloat16)
    bt = np.zeros((2, P), np.int32)
    bt[0] = [3, 9, 1, 2]
    bt[1] = [11, 4, 0, 0]

    out = np.asarray(paged_prefill_attention(
        q, kq, vq, jnp.asarray(bt), jnp.asarray(seqlens, jnp.int32),
        jnp.asarray(starts, jnp.int32), jnp.asarray(qlens, jnp.int32),
        block_size=bs, interpret=True, k_scale=ks,
        v_scale=vs).astype(jnp.float32))
    for r, (cs, ql) in enumerate(segs):
        ref = _oracle_segment(kq, vq, jnp.asarray(bt[r]), seqlens[r], cs,
                              q[starts[r]:starts[r] + ql], bs, Hkv,
                              scales=(ks, vs))
        np.testing.assert_allclose(
            out[starts[r]:starts[r] + ql],
            np.asarray(ref.astype(jnp.float32)), rtol=4e-2, atol=4e-2)


# -- pack sizing + measured-cost calibration (deviceless) --------------------


def test_pack_prefill_chunks_budget_alignment_segments():
    def w(n):
        return PrefillWork(request=None, start=0, length=n)

    # Aligned lengths pack to the budget, order preserved (FCFS).
    packs = pack_prefill_chunks([w(9), w(16), w(7), w(30)], budget=32,
                                max_segments=8, align=8)
    assert [[x.length for x in p] for p in packs] == [[9, 16], [7], [30]]
    # Segment cap splits even when tokens fit.
    packs = pack_prefill_chunks([w(4)] * 5, budget=512, max_segments=2,
                                align=8)
    assert [len(p) for p in packs] == [2, 2, 1]
    # An oversize chunk still ships (its own pack), never dropped.
    packs = pack_prefill_chunks([w(600)], budget=512, max_segments=8)
    assert [[x.length for x in p] for p in packs] == [[600]]
    assert pack_prefill_chunks([], budget=512, max_segments=8) == []


def test_packed_bucket_lattice():
    sched = SchedulerConfig(**GEOM)
    assert sched.packed_buckets() == (32,)   # top covers max_prefill_chunk
    assert sched.bucket_for_packed(9) == 32
    assert sched.page_bucket_ladder() == (2, 4, 8, 16)
    serving = SchedulerConfig()              # defaults: chunk 512
    assert serving.packed_buckets() == (128, 512)
    assert serving.bucket_for_packed(96) == 128
    assert serving.bucket_for_packed(200) == 512
    assert serving.bucket_for_packed(9999) == 512  # clamped to top


def test_window_intervals_measure_seconds_by_bucket():
    """What the rule of mixed prefill goes by: plain window intervals feed
    `window_s`, an interval with a chunk behind it feeds `chunk_s` of the
    chunk's token bucket with its excess over `window_s`, both in
    seconds, and the share gauge is the excess over all of it."""
    c = EngineStepCounters()
    assert c.window_s is None and c.chunk_s == {}
    c.note_window_interval(0.8, 0)
    assert c.window_s == 0.8
    c.note_window_interval(0.8 + 0.3, 128)
    c.note_window_interval(0.8 + 1.2, 512)
    assert c.chunk_s == {128: pytest.approx(0.3), 512: pytest.approx(1.2)}
    # EWMAs, a quarter the newest sample; an interval shorter than a
    # plain window's is a chunk of no seconds, not a negative one.
    c.note_window_interval(0.4, 0)
    assert c.window_s == pytest.approx(0.7)
    c.note_window_interval(0.5, 128)
    assert c.chunk_s[128] == pytest.approx(0.75 * 0.3)
    assert c.chunk_seconds == pytest.approx(1.5)
    assert c.interval_seconds == pytest.approx(0.8 + 1.1 + 2.0 + 0.4 + 0.5)
    # A stall of the host inside a plain interval counts as a window of
    # twice the last ones at most.
    c.note_window_interval(50.0, 0)
    assert c.window_s == pytest.approx(0.75 * 0.7 + 0.25 * 1.4)
    # Degenerate intervals are ignored, and one with a chunk before any
    # plain window is dropped (no window to subtract).
    c2 = EngineStepCounters()
    c2.note_window_interval(1.0, 128)
    c2.note_window_interval(0.0, 0)
    c2.note_window_interval(-1.0, 512)
    assert c2.window_s is None and c2.chunk_s == {}


def _rule_core():
    """An engine to ask the rule on (nothing is dispatched): the serving
    buckets, a chunk of 128 tokens or of 512."""
    return make_core(False, decode_window=2, scheduler=SchedulerConfig(
        max_seqs=8, decode_buckets=(1, 2, 4, 8)))


def _chunk(tokens):
    return PrefillBatch(items=[PrefillWork(request=None, start=0,
                                           length=tokens)],
                        rows=1, chunk=tokens, pages=2)


@pytest.mark.parametrize("case", ["never_measured", "capped", "mixed"])
def test_credit_in_seconds(case):
    """The rule alone, on measurements set by hand (window 0.1 s, so a
    window earns 0.1 x 15 / 85 s): a bucket never measured rides at once
    and only once while its window is unread; the credit of an idle
    stretch is capped at the dearest chunk measured; a bucket pays what it
    was measured to cost."""
    core = _rule_core()
    c = core.counters
    c.window_s = 0.1
    earn = 0.1 * 0.15 / 0.85
    if case == "never_measured":
        assert core._chunk_rides(_chunk(100))          # no debt: rides
        assert core._chunk_key == 128
        assert core._chunk_credit_s == pytest.approx(earn - 0.1)
        core._inflight.append({"chunk": 128})          # its window, unread
        core._chunk_credit_s = 1.0
        assert not core._chunk_rides(_chunk(100))
        assert core._chunk_rides(_chunk(300))          # another bucket
        assert core._chunk_key == 512
        core._inflight.clear()                         # read, sample lost
        assert core._chunk_rides(_chunk(100))
    elif case == "capped":
        c.chunk_s = {128: 0.02, 512: 0.06}
        for _ in range(100):
            assert not core._chunk_rides(None)
        assert core._chunk_credit_s == pytest.approx(0.06)
        rode = [core._chunk_rides(_chunk(300)) for _ in range(5)]
        assert rode == [True, False, False, False, True]
    else:
        c.chunk_s = {128: 0.02, 512: 0.06}
        rode = [core._chunk_rides(_chunk(100)) for _ in range(40)]
        assert sum(rode) * 0.02 <= 40 * earn + 1e-9
        assert sum(rode) * 0.02 >= 40 * earn - 0.02


# -- engine plane: token parity ----------------------------------------------

RAGGED_PROMPTS = [list(range(1, 40)), list(range(60, 69)),
                  list(range(100, 123))]


def test_prewarm_shape_set_and_packed_parity_bf16():
    """Two pins sharing one packed/padded engine pair (engine builds are
    the expensive unit in this file — tier-1 timing budget):

    1. The packed shape lattice is small by construction — pinned so a
       future change can't silently explode what --prewarm-prefill
       compiles — and serving a ragged fleet lands entirely inside the
       prewarmed set (no new packed-program shapes after startup).
    2. Packed ragged plane == padded-bucket oracle, token for token, on
       a ragged 3-prompt fleet (mixed chunk counts, mixed lengths)."""
    packed = make_core(True)
    shapes = packed.packed_prefill_shape_set()
    # GEOM: one packed token bucket (32) x page ladder (2, 4, 8, 16).
    assert shapes == [(32, 8, 2), (32, 8, 4), (32, 8, 8), (32, 8, 16)]
    assert packed.prewarm_prefill() == len(shapes)
    seen = {k for k in packed.counters._seen_shapes
            if k[0] == "prefill_packed"}
    assert seen == {("prefill_packed",) + s for s in shapes}

    out_packed = run_fleet(packed, RAGGED_PROMPTS, max_tokens=5)
    assert packed.counters.packed_prefill_dispatches > 0
    after = {k for k in packed.counters._seen_shapes
             if k[0] == "prefill_packed"}
    assert after == seen  # serving never compiled a new packed shape

    padded = make_core(False)
    # Padded-plane engines report 0 without touching the packed step.
    assert padded.prewarm_prefill() == 0
    out_padded = run_fleet(padded, RAGGED_PROMPTS, max_tokens=5)
    assert padded.counters.packed_prefill_dispatches == 0
    assert out_packed == out_padded


def test_packed_engine_token_parity_int8():
    # decode_window=1: the plane under test is prefill; skipping the
    # window-program compile keeps this inside the tier-1 time budget
    # (the bf16 test above covers packed prefill + window interleaving).
    out_packed = run_fleet(make_core(True, kv_quant="int8",
                                     decode_window=1),
                           RAGGED_PROMPTS, max_tokens=5)
    out_padded = run_fleet(make_core(False, kv_quant="int8",
                                     decode_window=1),
                           RAGGED_PROMPTS, max_tokens=5)
    assert out_packed == out_padded


def test_packed_cached_prefix_residual_parity():
    """With the tiered prefix cache resident, a repeat prompt's
    admission match leaves only a RESIDUAL chunk to prefill
    (chunk_start > 0, prior context = pool pages) — the packed plane
    must reproduce the padded plane's tokens through that path too."""
    prefix = list(range(1, 25))
    results = {}
    for packed in (True, False):
        # decode_window=1 for the same budget reason as the int8 test.
        core = make_core(packed, prefix_cache=True, decode_window=1)
        seed = serve(core, "seed", prefix + [30, 31])
        hits_before = core.scheduler.prefix_hit_tokens
        reuse = serve(core, "reuse", prefix + [40, 41, 42])
        assert core.scheduler.prefix_hit_tokens > hits_before  # real hit
        results[packed] = (seed, reuse,
                           core.scheduler.prefix_hit_tokens)
    assert results[True] == results[False]


# -- prewarm + idle-plane counters -------------------------------------------


def test_steady_decode_counters_identical_with_plane_idle():
    """The packed plane must cost the steady decode window NOTHING while
    idle: with prefill long finished, 20 window steps produce
    byte-identical counter deltas whether the plane is on or off."""
    deltas = {}
    for packed in (True, False):
        core = make_core(packed, decode_window=2, window_pipeline_depth=2)
        # prompt + max_tokens must fit max_context (16 pages x 8); the
        # budget must also outlast warmup + 20 windows so the cohort
        # stays in window mode for the whole pinned range.
        core.add_request("a", list(range(1, 41)),
                         SamplingParams(max_tokens=80))
        for _ in range(10):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
        deltas[packed] = core.counters.delta(base)
        # The measurement rides the existing window syncs: plain windows
        # must have fed `window_s` without adding a single host sync
        # (the delta equality below pins it).
        assert core.counters.window_s > 0
    assert deltas[True] == deltas[False]


def test_packed_bucket_config_validation():
    """Bad packed_prefill_buckets fail at construction (were a numpy
    broadcast ValueError inside the hot loop / a kernel PACK_ALIGN
    error at dispatch)."""
    with pytest.raises(ValueError, match="PACK_ALIGN"):
        SchedulerConfig(**{**GEOM, "packed_prefill_buckets": (12, 32)})
    # Top bucket must hold the align-rounded max_prefill_chunk: the
    # pack builder gives an over-budget chunk "a pack of its own" and
    # the dispatch buffer is sized to the top bucket.
    with pytest.raises(ValueError, match="cannot hold"):
        SchedulerConfig(**{**GEOM, "packed_prefill_buckets": (16,)})
    ok = SchedulerConfig(**{**GEOM, "packed_prefill_buckets": (16, 32)})
    assert ok.packed_buckets() == (16, 32)


def test_explicit_packed_rejects_ineligible_tpu_geometry(monkeypatch):
    """packed_prefill=True must apply the same mosaic_geometry_ok rule
    the auto path does — a pointed config error at construction, not a
    Mosaic lowering error on the first prefill.  (Off-TPU the kernel
    runs in interpret mode, so any geometry constructs — the bf16/int8
    parity tests above rely on that.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # tiny-test geometry: F = num_kv_heads * head_dim is not 128-aligned.
    assert (TINY.num_kv_heads * TINY.head_dim) % 128 != 0
    with pytest.raises(ValueError, match="Mosaic-eligible"):
        make_core(True, decode_window=1)


def test_multihost_decides_without_the_clock():
    """A window interval is one host's wall clock, and the hosts of a
    lockstep engine must plan alike: under `_mh` the rule keeps its
    clock-free form (a chunk behind every second window) whatever was
    measured, earns no credit, and a multihost engine keeps the
    scheduler's static cap."""
    core = _rule_core()
    assert (core.scheduler.mixed_budget_override
            == core.scheduler.config.max_prefill_chunk)
    core.counters.window_s = 0.1
    core.counters.chunk_s = {128: 1e-6}
    core._mh = True
    rode = [core._chunk_rides(_chunk(100)) for _ in range(6)]
    assert rode == [True, False, True, False, True, False]
    assert core._chunk_credit_s == 0.0
    assert not core._chunk_rides(None) and core._chunk_rides(_chunk(100))
    core._mh = False
    assert all(core._chunk_rides(_chunk(100)) for _ in range(6))
    # No plain window measured yet: the same form.
    core.counters.window_s = None
    rode = [core._chunk_rides(_chunk(100)) for _ in range(4)]
    assert rode.count(True) == 2


def test_explicit_packed_rejects_misaligned_derived_buckets():
    """Token buckets DERIVED from prefill_buckets obey the kernel's
    PACK_ALIGN contract too (interpret mode included) — a misaligned
    ladder fails at construction, not as a kernel ValueError inside the
    hot loop."""
    sched = SchedulerConfig(**{**GEOM, "prefill_buckets": (12, 20),
                               "max_prefill_chunk": 20})
    assert sched.packed_buckets() == (20,)   # derived, misaligned
    with pytest.raises(ValueError, match="PACK_ALIGN"):
        EngineCore(EngineConfig(model=TINY, num_blocks=128,
                                packed_prefill=True, scheduler=sched))
