"""The engine thread's phase clock (EngineStepCounters.enter), its two
sinks (worker /metrics; `engine.<phase>` events inside a device capture),
`prefill_tokens_dispatched`, and the program-build listener
(runtime/compile_cache.py).

Engine-backed tests share test_device_profiler's tiny geometry so every
EngineCore build hits the persistent XLA compile cache."""

import asyncio
import glob
import json
import os
import threading
import time

import jax
import pytest

from dynamo_tpu.runtime import compile_cache, device_profiler
from dynamo_tpu.runtime.metrics import (
    ENGINE_PHASES,
    PHASE_EMIT,
    PHASE_IDLE,
    PHASE_PLAN,
    PHASE_WAIT_DEVICE,
    EngineStepCounters,
)


@pytest.fixture()
def profiler(tmp_path):
    prof = device_profiler.get_profiler()
    prof.reset()
    prof.configure(enabled=True, service="test", dump_dir=str(tmp_path),
                   max_capture_ms=device_profiler.DEFAULT_MAX_CAPTURE_MS)
    yield prof
    prof.reset()
    prof.configure(enabled=False, service="dynamo",
                   max_capture_ms=device_profiler.DEFAULT_MAX_CAPTURE_MS)
    prof.dump_dir = None


def _tiny_engine(**kw):
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg

    defaults = dict(
        model=mcfg.get_config("tiny-test"), num_blocks=128,
        enable_prefix_cache=False, decode_window=2,
        window_pipeline_depth=2,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=32,
            max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 128)))
    defaults.update(kw)
    return EngineCore(EngineConfig(**defaults))


async def _burst(engine, tag: str, n_requests: int = 4,
                 max_tokens: int = 24) -> int:
    from dynamo_tpu.engine.sampling import SamplingParams

    async def one(i):
        n = 0
        async for d in engine.generate(
                f"{tag}-{i}", [1 + i] * (20 + 3 * i),
                SamplingParams(max_tokens=max_tokens)):
            n += len(d.token_ids)
        return n

    return sum(await asyncio.gather(*[one(i) for i in range(n_requests)]))


def _served(fn, **engine_kw):
    """Run `await fn(engine, core)` against a started InferenceEngine."""
    from dynamo_tpu.engine.engine import InferenceEngine

    core = _tiny_engine(**engine_kw)

    async def main():
        engine = InferenceEngine(core)
        await engine.start()
        try:
            return await fn(engine, core)
        finally:
            await engine.stop()

    return asyncio.run(asyncio.wait_for(main(), 120))


# -- the clock ---------------------------------------------------------------

def test_phases_are_exclusive_and_exhaustive_over_a_served_burst():
    """Σ phase seconds == the engine thread's wall time: read from this
    thread at two instants around a burst and an idle stretch, the sums'
    difference is the time between the reads (within 1 %).  Every blocking
    read is a `wait_device` entry and nothing else is."""
    async def run(engine, core):
        c = core.counters
        await _burst(engine, "warm")
        base = c.snapshot()
        t0, s0 = time.perf_counter(), c.phase_seconds()
        assert await _burst(engine, "run") == 4 * 24
        await asyncio.sleep(0.15)                       # idle stretch
        s1, t1 = c.phase_seconds(), time.perf_counter()
        return base, s0, s1, t1 - t0

    base, s0, s1, wall = _served(run)
    assert set(s1) == set(ENGINE_PHASES)
    delta = {p: s1[p] - s0[p] for p in ENGINE_PHASES}
    assert all(v >= 0 for v in delta.values()), delta
    assert sum(delta.values()) == pytest.approx(wall, rel=0.01)
    for phase in ("idle", "commands", "plan", "dispatch_window",
                  "dispatch_prefill", "wait_device", "emit", "deliver"):
        assert delta[phase] > 0, (phase, delta)
    assert delta["idle"] >= 0.1


@pytest.mark.parametrize("decode_window", [2, 1],
                         ids=["windows", "single-steps"])
def test_wait_device_entries_are_exactly_the_host_syncs(decode_window):
    from dynamo_tpu.engine.sampling import SamplingParams

    core = _tiny_engine(decode_window=decode_window)
    for i in range(3):
        core.add_request(f"r{i}", list(range(1, 30 + 7 * i)),
                         SamplingParams(max_tokens=13 + i))
    while core.has_work:
        core.step()
    c = core.counters
    assert c.host_syncs > 0
    assert (c.single_step_dispatches > 0) == (decode_window == 1)
    assert (c.window_syncs > 0) == (decode_window == 2)
    assert c.phase_entries[PHASE_WAIT_DEVICE] == c.host_syncs
    # Every sync is followed by a token loop.
    assert c.phase_entries[PHASE_EMIT] == c.host_syncs


def test_counter_deltas_identical_with_annotation_on_and_off():
    """The annotation sink must not move a single integer counter."""
    from dynamo_tpu.engine.sampling import SamplingParams

    def steady_run(trace: bool):
        core = _tiny_engine()
        core.counters.trace_phases = trace
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
        core.counters.trace_phases = False
        core.counters.enter(PHASE_IDLE)          # closes the open span
        return core.counters.delta(base)

    d_off, d_on = steady_run(False), steady_run(True)
    assert d_on == d_off, (d_on, d_off)
    assert d_on["window_dispatches"] == 20
    assert all(isinstance(v, int) for v in d_on.values())
    assert "phase_ns" not in d_on and "prefill_tokens_dispatched" in d_on


def test_snapshot_copies_the_clock_and_restart_charges_nobody():
    c = EngineStepCounters()
    c.enter(PHASE_PLAN)
    snap = c.snapshot()
    c.enter(PHASE_EMIT)
    c.enter(PHASE_PLAN)
    assert snap.phase_entries[PHASE_EMIT] == 0
    assert c.phase_entries[PHASE_EMIT] == 1
    assert snap.phase_ns is not c.phase_ns
    before = sum(c.phase_ns)
    time.sleep(0.02)
    c.restart_phase_clock()
    c.enter(PHASE_PLAN)
    assert sum(c.phase_ns) - before < 0.01 * 1e9      # the 20 ms: no phase's


def test_a_scrape_inside_a_transition_counts_no_second_twice():
    """A scrape that lands after the engine thread has added the closed
    phase's time but before the transition is whole must not add that time
    again as the open phase's elapsed part: it waits the transition out."""
    c = EngineStepCounters()
    mid, read = threading.Event(), {}

    class StallsAfterTheAdd(list):
        def __setitem__(self, i, v):
            super().__setitem__(i, v)
            mid.set()
            time.sleep(0.005)            # the scrape runs into this

    def scrape():
        mid.wait(5)
        read["secs"] = c.phase_seconds()
        read["at"] = time.perf_counter_ns()

    start = time.perf_counter_ns()
    c.restart_phase_clock(PHASE_WAIT_DEVICE)
    c.phase_ns = StallsAfterTheAdd(c.phase_ns)
    scraper = threading.Thread(target=scrape)
    scraper.start()
    time.sleep(0.05)                     # a 50 ms wait for the device
    c.enter(PHASE_EMIT)
    scraper.join(5)
    wall = (read["at"] - start) / 1e9
    assert read["secs"]["wait_device"] == pytest.approx(0.05, abs=0.02)
    assert wall - 0.002 <= sum(read["secs"].values()) <= wall


@pytest.mark.parametrize("packed", [False, True],
                         ids=["padded", "packed"])
def test_prefill_tokens_dispatched_is_prompt_tokens_less_prefix_hits(packed):
    from dynamo_tpu.engine.sampling import SamplingParams

    core = _tiny_engine(enable_prefix_cache=True, packed_prefill=packed)
    shared = list(range(1, 41))
    prompts = [shared + [100 + i] * (5 + 9 * i) for i in range(3)]
    for i, prompt in enumerate(prompts):           # one after the other,
        core.add_request(f"r{i}", prompt,          # so later ones hit
                         SamplingParams(max_tokens=3))
        while core.has_work:
            core.step()
    sched = core.scheduler
    assert sched.prefix_hit_tokens > 0
    assert sched.prefix_hit_tokens + sched.prefix_miss_tokens \
        == sum(len(p) for p in prompts)
    c = core.counters
    assert c.prefill_tokens_dispatched == sched.prefix_miss_tokens
    assert (c.packed_prefill_dispatches > 0) == packed


# -- sink 1: /metrics --------------------------------------------------------

def test_phase_and_build_series_in_prometheus_text():
    c = EngineStepCounters()
    c.enter(PHASE_WAIT_DEVICE)
    time.sleep(0.01)
    c.enter(PHASE_EMIT)
    page = {}
    for line in c.phase_metrics_lines() + compile_cache.metrics_lines():
        key, value = line.rsplit(" ", 1)
        page[key] = float(value)
    for phase in ENGINE_PHASES:
        assert f'dynamo_worker_engine_phase_seconds_total{{phase="{phase}"}}' \
            in page
    assert page[
        'dynamo_worker_engine_phase_seconds_total{phase="wait_device"}'] \
        >= 0.01
    assert page[
        'dynamo_worker_engine_phase_entries_total{phase="emit"}'] == 1
    # conftest enabled the cache, so this process listens.
    for stage in ("trace", "lower", "backend", "cache_read"):
        assert 'dynamo_worker_program_build_seconds_total' \
            f'{{stage="{stage}"}}' in page
    for name in ("program_builds", "compile_cache_hits"):
        assert f"dynamo_worker_{name}_total" in page


# -- sink 2: the device capture ----------------------------------------------

def _capture_events(res):
    """[[(name, seconds) of each event] of each line of the host plane]."""
    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(res["dir"], "**", "*.xplane.pb"),
                          recursive=True))[-1]
    lines = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            lines += [[(e.name, e.duration_ns / 1e9) for e in line.events]
                      for line in plane.lines]
    return lines


def _capture_lines(res):
    return [[name for name, _s in line] for line in _capture_events(res)]


@pytest.mark.parametrize("python", [False, True],
                         ids=["default", "python=1"])
def test_capture_holds_engine_phases_and_python_frames_only_on_request(
        profiler, python):
    async def run(engine, core):
        await _burst(engine, "warm")
        cap = asyncio.create_task(
            asyncio.to_thread(profiler.capture, 400, python))
        await asyncio.sleep(0.1)
        await _burst(engine, "traced", max_tokens=32)
        res = await cap
        assert core.counters.trace_phases is False
        return res

    res = _served(run)
    assert res["ok"] and res["python"] is python, res
    with open(os.path.join(res["dir"], "capture_meta.json")) as f:
        meta = json.load(f)
    assert meta["python"] is python
    assert meta["wall_start"] <= meta["wall_end"]
    lines = _capture_lines(res)
    with_phases = [names for names in lines
                   if any(n.startswith("engine.") for n in names)]
    assert len(with_phases) == 1, "the phases sit on one line: the " \
        "engine thread's"
    seen = {n for n in with_phases[0] if n.startswith("engine.")}
    assert seen <= {"engine." + p for p in ENGINE_PHASES}
    assert {"engine.wait_device", "engine.emit", "engine.dispatch_window",
            "engine.plan", "engine.deliver"} <= seen, seen
    frames = sum(1 for names in lines for n in names if n.startswith("$"))
    assert (frames > 0) == python, frames


def test_capture_over_an_idle_engine_holds_one_idle_event(profiler):
    """The engine idles before, through and after the capture, so its phase
    never changes: the event opens at the idle loop's next tick after the
    capture began and closes at the first one after its bound, before the
    trace stops."""
    async def run(engine, core):
        await _burst(engine, "warm")
        await asyncio.sleep(0.05)
        res = await asyncio.to_thread(profiler.capture, 300)
        assert not core.counters.phase_event_open
        assert core.counters.trace_phases is False
        return res

    res = _served(run)
    assert res["ok"], res
    phases = [(name, secs) for line in _capture_events(res)
              for name, secs in line if name.startswith("engine.")]
    assert [name for name, _s in phases] == ["engine.idle"], phases
    assert 0.27 <= phases[0][1] <= 0.3 + device_profiler.PHASE_CLOSE_WAIT_S


def test_profile_command_value_and_route_pass_python_through(profiler,
                                                             monkeypatch):
    import aiohttp

    from dynamo_tpu.runtime.status import StatusServer

    parse = device_profiler.parse_profile_command
    assert parse("750") == (750, False)
    assert parse("750 python") == (750, True)
    assert parse(None) == (500, False) and parse("soon") == (500, False)

    calls = []
    monkeypatch.setattr(
        type(profiler), "capture",
        lambda self, ms, python=False: calls.append((ms, python))
        or {"ok": True})

    async def main():
        status = StatusServer()
        port = await status.start()
        try:
            async with aiohttp.ClientSession() as s:
                for query in ("ms=40", "ms=40&python=1", "ms=40&python=0"):
                    async with s.get(f"http://127.0.0.1:{port}"
                                     f"/debug/deviceprofile?{query}") as r:
                        assert r.status == 200
        finally:
            await status.stop()

    asyncio.run(asyncio.wait_for(main(), 60))
    assert calls == [(40, False), (40, True), (40, False)]


# -- program-build accounting ------------------------------------------------

def test_build_listener_counts_a_compile_then_a_cache_hit_registered_once():
    """A program no earlier run can have cached (a fresh constant in its
    HLO) is one build and no persistent-cache hit; the same HLO from a
    new jit is one build, one hit and some read time.  Calling
    enable_compile_cache() again adds no second listener."""
    import jax.numpy as jnp

    for _ in range(3):
        compile_cache.enable_compile_cache("tests")
    k = float(time.time_ns() % 1_000_003) + 0.5

    x = jnp.arange(8.0)          # its own small program, built before b0

    def build():
        return jax.jit(lambda v: v * 3.0 + k)(x)

    b0 = compile_cache.program_builds()
    build().block_until_ready()
    b1 = compile_cache.program_builds()
    assert b1["builds"] - b0["builds"] == 1
    assert b1["cache_hits"] == b0["cache_hits"]
    for stage in ("trace", "lower", "backend"):
        assert b1["seconds"][stage] > b0["seconds"][stage], stage
    assert b1["seconds"]["cache_read"] == b0["seconds"]["cache_read"]

    build().block_until_ready()          # new lambda, same HLO: read back
    b2 = compile_cache.program_builds()
    assert b2["builds"] - b1["builds"] == 1
    assert b2["cache_hits"] - b1["cache_hits"] == 1
    assert b2["seconds"]["cache_read"] > b1["seconds"]["cache_read"]
    # `backend` contains the read: a reader subtracts.
    assert (b2["seconds"]["backend"] - b1["seconds"]["backend"]
            >= b2["seconds"]["cache_read"] - b1["seconds"]["cache_read"])


def test_nested_traces_are_counted_once():
    """A jitted function's trace event holds those of the jitted functions
    it calls; the tally takes each second once, so a build's `trace` is its
    outermost event's duration, not the sum of all of them."""
    import jax.numpy as jnp
    from jax import monitoring

    inner = jax.jit(lambda v: jnp.where(v > 0, v, 0) * 2)

    def outer(v):
        for _ in range(4):
            v = inner(v) + jnp.argmax(v)
        return v

    x = jnp.arange(8.0)
    raw = []

    def listener(event, duration_secs, **_kw):
        if event.endswith("jaxpr_trace_duration"):
            raw.append(duration_secs)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        t0 = compile_cache.program_builds()["seconds"]["trace"]
        jax.jit(outer).lower(x)
        got = compile_cache.program_builds()["seconds"]["trace"] - t0
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert len(raw) > 5 and sum(raw) > 1.3 * raw[-1]   # nesting happened
    assert got == pytest.approx(raw[-1], rel=1e-6)     # the outermost only
