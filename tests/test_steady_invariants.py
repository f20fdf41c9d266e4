"""What steady decode may cost, counted on one tiny engine.

Each instrument has its own on-against-off pin beside its other tests
(the flight recorder, the device profiler, the request ledger, the KV
telemetry).  The cases here are the ones no other file holds: tracing
compared with tracing off and not only with a ceiling, every instrument
on at once (a worker started with every flag), and the meshless fused
single step, which the tp2 and pp2 engines pin in
test_sharded_serving.py and test_compose_matrix.py.
"""

import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.runtime import (
    device_profiler, flight_recorder, ledger, tracing)
from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

STEPS = 20
RID = "a"


def _engine(decode_window: int) -> EngineCore:
    # test_decode_window's steady geometry: the prompt keeps the page
    # bucket in one power-of-two band over the measured steps, so a
    # recompile there is a defect and not a width flip.
    return EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=128,
        enable_prefix_cache=False, decode_window=decode_window,
        window_pipeline_depth=2,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=32,
            max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 128))))


def _steady(decode_window: int = 2, instruments=()):
    """Over STEPS steady steps with `instruments` on and every other one
    off: the counters' deltas, spans recorded and ring writes; and what
    the warm-up left behind (spans, harvested programs), which says the
    instruments were live."""
    tracer = tracing.get_tracer()
    rec = flight_recorder.get_recorder()
    prof = device_profiler.get_profiler()
    on = set(instruments)
    try:
        tracer.reset()
        tracer.configure(enabled="tracing" in on, sampling=1.0)
        rec.reset()
        rec.configure(enabled="recorder" in on, ring_size=4096)
        prof.reset()
        prof.configure(enabled="profiler" in on)
        ledger.set_enabled("ledger" in on)
        core = _engine(decode_window)
        # The serving layer's bind step: without it no span is recorded
        # and the case would pass for the wrong reason.
        tracer.bind(RID, tracing.TraceContext("t-steady", "s0"))
        core.add_request(RID, list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):      # prefill, then the pipeline fills
            core.step()
        telemetry = KvCacheMetrics(MetricsRegistry())
        base = core.counters.snapshot()
        spans, writes = tracer.spans_recorded, rec.events_written
        for _ in range(STEPS):
            core.step()
            if "telemetry" in on:
                telemetry.observe_engine(core)
        return dict(delta=core.counters.delta(base),
                    spans=tracer.spans_recorded - spans,
                    writes=rec.events_written - writes,
                    warmup_spans=spans, programs=prof.registry.size())
    finally:
        tracer.unbind(RID)
        tracer.enabled = False
        tracer.reset()
        rec.reset()
        rec.configure(enabled=False, ring_size=flight_recorder.DEFAULT_RING)
        prof.reset()
        prof.configure(enabled=False)
        ledger.set_enabled(True)        # the process default


@pytest.fixture(scope="module")
def bare():
    """The window path with every instrument off."""
    run = _steady()
    assert run["spans"] == run["writes"] == run["warmup_spans"] == 0
    assert run["programs"] == 0
    assert run["delta"]["window_dispatches"] == STEPS
    return run["delta"]


@pytest.mark.parametrize("instruments", [
    ("tracing",),
    ("tracing", "recorder", "profiler", "ledger", "telemetry"),
], ids=["tracing", "every_instrument"])
def test_instruments_add_nothing_to_the_steady_window(bare, instruments):
    """Counters byte-identical with the instruments on: no host sync, no
    dispatch, no upload, no compiled shape more.  Spans land once a
    request (at its first token), never a window; the recorder writes at
    most once a window dispatch, plus the periodic breadcrumb."""
    run = _steady(instruments=instruments)
    assert run["delta"] == bare, (run["delta"], bare)
    # Queue wait, prefill and TTFT, recorded at the first token.
    assert run["warmup_spans"] == 3 and run["spans"] == 0
    if "recorder" in instruments:
        assert 0 < run["writes"] <= bare["window_dispatches"] + 1
        assert run["programs"] > 0
    else:
        assert run["writes"] == run["programs"] == 0


def test_fused_single_step_is_one_dispatch_and_one_sync():
    """The meshless single-step path (a window of 1; the tail of every
    windowed request) in steady state: one fused program and one host
    sync an engine iteration, no window, no compiled shape more."""
    delta = _steady(decode_window=1)["delta"]
    assert delta["single_step_dispatches"] == STEPS, delta
    assert delta["host_syncs"] == STEPS, delta
    assert delta["window_dispatches"] == 0, delta
    assert delta["xla_cache_misses"] == 0, delta
    assert delta["prefill_dispatches"] == 0, delta


def test_the_block_program_holds_one_copy_of_each_kernel():
    """The block program of a block-diffusion model, lowered through both
    kernels: one `grouped_expert_ffn` and one `paged_decode_attention`, by
    the names a device trace tells them apart by, each called once a layer
    from the one loop body.  The served program holds one branch more than
    its twin that hands out logits (a commit stops at its last K/V write;
    the twin's runs whole) and nothing else of its own."""
    import re
    from collections import Counter

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    cfg = mcfg.get_config("tiny-sdar")
    R, P, B = 4, 2, cfg.diffusion_block_length
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: kvc.init_cache(
        kvc.KvCacheConfig.for_model(cfg, num_blocks=8, block_size=16)))
    kernel = r"@((?:grouped_expert_ffn|paged_decode_attention)\w*)"
    branches = {}
    for record in (False, True):
        text = jax.jit(llama.make_block_step(
            cfg, 16, use_pallas_decode=True, greedy_only=True,
            moe_mode="grouped", record=record)).lower(
            params, cache, sds((R, B), i32), sds((R, B), i32),
            sds((R,), i32), sds((R, P), i32), sds((R,), f32), sds((R,), i32),
            sds((R,), f32), sds((R, 2), jnp.uint32), sds((R,), i32)).as_text()
        assert sorted(re.findall(r"func\.func private " + kernel, text)) \
            == ["grouped_expert_ffn", "paged_decode_attention"]
        assert Counter(re.findall(r"call " + kernel, text)) == {
            "grouped_expert_ffn": cfg.num_layers,
            "paged_decode_attention": cfg.num_layers}
        assert len(re.findall(r"stablehlo\.while", text)) >= 1
        # (The kernels in interpret mode lower to branches of their own.)
        branches[record] = len(re.findall(r"stablehlo\.(?:case|if)\b", text))
    assert branches[False] == branches[True] + 1
