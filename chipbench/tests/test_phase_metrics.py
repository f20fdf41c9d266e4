"""The layer metrics that read the engine's phase clock, the program-build
accounting and the idle-gap labels: each reader on a synthetic run, and None
on a program that has none of these series (the parent of the PR that added
them), so that its result line just leaves the metric out."""

import json
import os
import types

import pytest

from chipbench import phase_readers, run

PHASE = 'dynamo_worker_engine_phase_seconds_total{phase="%s"}'
ENTRIES = 'dynamo_worker_engine_phase_entries_total{phase="%s"}'
BUILD = 'dynamo_worker_program_build_seconds_total{stage="%s"}'
START = {"idle": 100.0, "commands": 1.0, "settle_first": 0.5, "plan": 2.0,
         "dispatch_window": 5.0, "dispatch_prefill": 3.0,
         "wait_device": 60.0, "emit": 4.0, "single_step": 0.25,
         "deliver": 1.0}
# 40 s later: 30 blocked, 4 idle, 6 of host work (1.0 of it in emit).
STEP = {"idle": 4.0, "commands": 0.5, "settle_first": 0.25, "plan": 1.0,
        "dispatch_window": 2.0, "dispatch_prefill": 1.0,
        "wait_device": 30.0, "emit": 1.0, "single_step": 0.0,
        "deliver": 0.25}


def _page(phases, builds, counters, t, entries=None):
    page = {PHASE % k: v for k, v in phases.items()}
    page.update({ENTRIES % k: entries for k in phases if entries is not None})
    page.update({BUILD % k: v for k, v in builds.items()})
    page.update({f"dynamo_worker_{k}": v for k, v in counters.items()})
    page["_t"] = t
    return page


def _ctx(with_series: bool = True, gaps=None):
    builds = {"trace": 60.0, "lower": 40.0, "backend": 120.0,
              "cache_read": 100.0}
    c0 = {"engine_window_dispatches": 1000, "engine_host_syncs": 1100,
          "engine_decode_tokens_emitted": 20000,
          "engine_prefill_tokens_dispatched": 500000,
          "program_builds_total": 300, "compile_cache_hits_total": 290}
    c1 = {"engine_window_dispatches": 1500, "engine_host_syncs": 1640,
          "engine_decode_tokens_emitted": 28000,
          "engine_prefill_tokens_dispatched": 536000,
          "program_builds_total": 302, "compile_cache_hits_total": 292}
    if with_series:
        # Every phase entered 300 times more: 3000 entries over 500 windows.
        a = _page(START, builds, c0, 10.0, entries=7000)
        b = _page({k: START[k] + STEP[k] for k in START}, builds, c1, 50.0,
                  entries=7300)
    else:
        old = {"engine_window_dispatches": 1000, "engine_host_syncs": 1100}
        a, b = _page({}, {}, old, 10.0), _page({}, {}, old, 50.0)
    scrapes = {"window_start": {"worker": a, "frontend": {}},
               "window_end": {"worker": b, "frontend": {}}}

    def delta(source, key, scope="window"):
        x = scrapes.get(f"{scope}_start", {}).get(source)
        y = scrapes.get(f"{scope}_end", {}).get(source)
        if not x or not y or key not in x or key not in y:
            return None
        return y[key] - x[key]

    trace = None if gaps is None else {"idle_gaps": gaps}
    return types.SimpleNamespace(scrapes=scrapes, delta=delta, trace=trace)


GAPS = [["jit_run->jit_run | host: engine.dispatch_window", 0.006],
        ["jit_run->jit_step | host: engine.plan", 0.002],
        ["jit_step->jit_run | host: PjitFunction(run)", 0.001],
        ["tail", 0.001]]

EXPECTED = {
    "engine_wait_device_share": 75.0,          # 30 of 40 s
    "engine_idle_share": 10.0,                 # 4 of 40 s
    "engine_host_ms_per_window": 12.0,         # 6 s over 500 windows
    "engine_emit_us_per_token": 125.0,         # 1 s over 8000 tokens
    "engine_phase_entries_per_window": 6.0,    # 3000 over 500 windows
    "prefill_tokens_per_window": 72.0,         # 36000 over 500 windows
    "idle_gap_named_share": 80.0,              # 8 of 10 ms
    "compiles_in_window": 0.0,                 # 2 builds, both cache hits
    "build_trace_s": 60.0,
    "build_lower_s": 40.0,
    "build_cache_read_s": 100.0,
    "build_compile_s": 20.0,                   # backend 120 less reads 100
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_synthetic_run(name):
    read = run.load_reader("layer_metrics", name).read
    assert read(_ctx(gaps=GAPS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_a_program_without_the_series(name):
    """The parent's pages hold none of the series and its gap labels name
    no engine phase; an untraced run has no trace at all."""
    read = run.load_reader("layer_metrics", name).read
    assert read(_ctx(with_series=False)) is None
    if name == "idle_gap_named_share":
        assert read(_ctx(gaps=[])) is None
        assert read(_ctx(gaps=[["jit_run->jit_run | host: time_sleep",
                                0.01]])) == 0.0


def test_every_new_metric_is_in_the_manifest():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(EXPECTED) <= listed


def test_phase_deltas_sum_to_the_time_between_the_scrapes():
    ctx = _ctx()
    d = phase_readers.phase_deltas(ctx)
    a, b = (ctx.scrapes[k]["worker"]["_t"]
            for k in ("window_start", "window_end"))
    assert sum(d.values()) == pytest.approx(b - a)
    assert phase_readers.phase_deltas(ctx, scope="capture") is None
