#!/usr/bin/env python
"""Regression-gate entry point: BENCH JSON vs baseline, exit nonzero on
regression.

    # gate a fresh bench run against a recorded one
    python tools/bench_gate.py BENCH_new.json --baseline BENCH_old.json

    # CPU-only smoke (tier-1): synthesize → analyze → mocker replay →
    # gate, asserting the whole loop end to end
    python tools/bench_gate.py --smoke

Exit codes: 0 gate passed, 1 regression or invalid run, 2 usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.bench import gate  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_gate(args) -> int:
    baseline = args.baseline
    result = gate.gate_files(args.new, baseline, threshold=args.threshold)
    out = result.to_dict()
    out["baseline_path"] = baseline
    print(json.dumps(out, indent=2))
    return 0 if result.ok else 1


def tracing_overhead_checks() -> dict:
    """Tracing must be free where it matters: steady-state decode with
    sampling=1.0 adds ZERO host syncs and ZERO per-window span records
    (lifecycle spans land once per request at first token, never per
    window), and the per-span record cost bounds any request's total
    tracing work under 1% of its decode wall time.

    The wall-clock ratio between a traced and untraced run is reported
    for the record but NOT gated on — CPU timer jitter at tiny-model
    window times dwarfs a 1% budget; the counting assertions are exact
    and deterministic (the same EngineStepCounters delta discipline as
    tests/test_decode_window.py)."""
    import time

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime import tracing

    tracer = tracing.get_tracer()

    def steady_run():
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            enable_prefix_cache=False, decode_window=2,
            window_pipeline_depth=2,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(16, 128))))
        # Bind a trace context so first-token lifecycle spans actually
        # record when tracing is on (the serving layer's bind step).
        tracer.bind("a", tracing.TraceContext("t-bench", "s-bench"))
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        spans0 = tracer.spans_recorded
        t0 = time.perf_counter()
        for _ in range(20):
            core.step()
        wall = time.perf_counter() - t0
        tracer.unbind("a")
        return (core.counters.delta(base), wall,
                tracer.spans_recorded - spans0)

    try:
        tracer.enabled = False
        tracer.reset()
        d_off, t_off, _ = steady_run()
        tracer.reset()
        tracer.configure(enabled=True, sampling=1.0)
        d_on, t_on, steady_spans = steady_run()
    finally:
        # Never leak enabled tracing into the rest of the smoke run —
        # the other checks' determinism depends on the default-off state.
        tracer.enabled = False
        tracer.reset()

    # Per-span record cost → the 1% budget.  A request's tracing work is
    # a handful of spans (queue-wait, prefill, TTFT, ~K TPOT intervals),
    # amortised over its max_tokens/window decode windows; with
    # SPANS_PER_REQUEST spans across the 32 windows this geometry runs,
    # the per-window tracing cost must stay under 1% of window time.
    bench = tracing.Tracer("bench", enabled=True, sampling=1.0,
                           max_spans_per_trace=8192)
    root = bench.start_span("r")
    n = 4000
    t1 = time.perf_counter()
    now = time.monotonic()
    for _ in range(n):
        bench.record_span("s", root, now, now)
    span_cost = (time.perf_counter() - t1) / n
    root.end()
    # Engine-process spans per request: queue-wait + prefill + TTFT,
    # recorded once at first token.  (The frontend's capped TPOT spans
    # ride the frontend event loop, not the decode window — its own
    # budget is the reported span cost × 32 per request, trivially off
    # the engine's critical path.)
    SPANS_PER_REQUEST = 3
    windows_per_request = 64 // 2       # max_tokens / decode_window
    per_window = t_off / 20
    overhead_frac = (SPANS_PER_REQUEST * span_cost
                     / max(windows_per_request * per_window, 1e-9))
    return {
        "tracing_extra_host_syncs": d_on["host_syncs"] - d_off["host_syncs"],
        "tracing_zero_extra_syncs":
            d_on["host_syncs"] == d_off["host_syncs"]
            and d_on["xla_cache_misses"] == d_off["xla_cache_misses"],
        "tracing_steady_window_spans": steady_spans,
        "tracing_zero_steady_spans": steady_spans == 0,
        "tracing_span_cost_us": round(span_cost * 1e6, 2),
        "tracing_wall_ratio": round(t_on / t_off, 3) if t_off else None,
        "tracing_overhead_frac": round(overhead_frac, 6),
        "tracing_overhead_within_1pct": overhead_frac <= 0.01,
    }


def telemetry_overhead_checks() -> dict:
    """KV/HBM telemetry must be free where it matters: a steady decode
    window with the memory-plane collectors sampling EVERY step (far
    hotter than the real scrape cadence) pays 0 extra host syncs and 0
    extra dispatches vs telemetry disabled — the same
    EngineStepCounters.delta pinning discipline as the tracing check."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime.metrics import (
        HbmPoller, KvCacheMetrics, MetricsRegistry)

    def steady_run(observe: bool):
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            enable_prefix_cache=True, decode_window=2,
            window_pipeline_depth=2,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(16, 128))))
        kvm = KvCacheMetrics(MetricsRegistry())
        poller = HbmPoller(kvm)
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
            if observe:
                kvm.observe_engine(core)
        if observe:
            poller.poll_once()
        return core.counters.delta(base)

    d_off = steady_run(False)
    d_on = steady_run(True)
    dispatch_keys = ("window_dispatches", "single_step_dispatches",
                     "prefill_dispatches", "h2d_uploads")
    return {
        "kv_telemetry_extra_host_syncs":
            d_on["host_syncs"] - d_off["host_syncs"],
        "kv_telemetry_zero_extra_syncs":
            d_on["host_syncs"] == d_off["host_syncs"],
        "kv_telemetry_extra_dispatches":
            sum(d_on[k] - d_off[k] for k in dispatch_keys),
        "kv_telemetry_zero_extra_dispatches":
            all(d_on[k] == d_off[k] for k in dispatch_keys),
    }


def flight_recorder_overhead_checks() -> dict:
    """ISSUE 14: the flight recorder must be free where it matters — a
    steady decode window with the ring ENABLED produces EngineStepCounters
    deltas byte-identical to recorder-off (0 extra host syncs, 0 extra
    dispatches, 0 recompiles) and stays inside the per-window ring-write
    budget: at most one ring write per window dispatch plus one periodic
    counters breadcrumb.  A fabricated chatty recorder (several writes
    per step — the regression this gate exists to catch) must FAIL the
    budget check."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime import flight_recorder

    rec = flight_recorder.get_recorder()

    def steady_run(chatty: int = 0):
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            enable_prefix_cache=False, decode_window=2,
            window_pipeline_depth=2,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(16, 128))))
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        writes0 = rec.events_written
        for _ in range(20):
            core.step()
            for _ in range(chatty):   # fabricated chatty recorder
                rec.record("chatty", x=1)
        return (core.counters.delta(base),
                rec.events_written - writes0)

    def budget_ok(ring_writes: int, delta: dict) -> bool:
        # One write per window dispatch + one periodic counters
        # breadcrumb (cadence 64 ⇒ ≤ 1 over a 20-step window).
        return ring_writes <= delta["window_dispatches"] + 1

    try:
        rec.reset()
        rec.enabled = False
        d_off, _ = steady_run()
        rec.configure(enabled=True, ring_size=4096)
        d_on, writes_on = steady_run()
        _, writes_chatty = steady_run(chatty=3)
    finally:
        # Never leak an enabled recorder into the other smoke checks.
        rec.enabled = False
        rec.reset()

    return {
        "flight_extra_host_syncs":
            d_on["host_syncs"] - d_off["host_syncs"],
        "flight_zero_extra_syncs":
            d_on["host_syncs"] == d_off["host_syncs"]
            and d_on["xla_cache_misses"] == d_off["xla_cache_misses"],
        "flight_counters_byte_identical": d_on == d_off,
        "flight_ring_writes": writes_on,
        "flight_window_budget_ok": budget_ok(writes_on, d_on),
        # The budget check must actually have teeth: a recorder writing
        # several events per steady step blows it.
        "flight_chatty_run_fails": not budget_ok(writes_chatty, d_on),
    }


def device_truth_checks() -> dict:
    """ISSUE 20: the device-truth plane must be FREE and HONEST.

    Free — a steady decode window with the profiler ENABLED produces
    EngineStepCounters deltas byte-identical to profiler-off: the
    cost-analysis harvest rides first-seen shapes only (the compile
    event), never the steady window.  Honest — the harvest lands real
    programs in the cost registry, the drift audit's modeled-vs-measured
    ratios sit INSIDE the one-sided band on the CPU tiny model (modeled
    KV bytes are a component of XLA's totals, so the honest ratio is
    well under 1), and a FABRICATED 2x modeled over-claim must drive the
    auditor to PAGE after its strike budget — the gate this plane exists
    to provide."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime import device_profiler

    prof = device_profiler.get_profiler()

    def steady_run():
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            enable_prefix_cache=False, decode_window=2,
            window_pipeline_depth=2,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(16, 128))))
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup (harvests land)
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
        return core, core.counters.delta(base)

    try:
        prof.reset()
        prof.enabled = False
        _, d_off = steady_run()
        prof.configure(enabled=True)
        core_on, d_on = steady_run()
        registry_size = prof.registry.size()
        ratios = prof.audit_engine(core_on)
        states = prof.auditor.states()
        in_band = bool(ratios) and all(
            st["state"] == "ok" for st in states.values())
        # The drift band must have teeth: an accounting bug that
        # over-claims modeled bytes 2x (the PR-16 int8 scale-pack
        # double-count class) must strike out and PAGE.
        fab = device_profiler.DriftAuditor()
        for _ in range(device_profiler.PAGE_STRIKES):
            fab.observe("kv_decode", modeled=2.0, measured=1.0)
    finally:
        # Never leak an enabled profiler into the other smoke checks.
        prof.enabled = False
        prof.reset()

    return {
        "device_truth_counters_byte_identical": d_on == d_off,
        "device_truth_registry_programs": registry_size,
        "device_truth_registry_nonempty": registry_size > 0,
        "device_truth_ratios": {k: round(v, 4)
                                for k, v in sorted(ratios.items())},
        "device_truth_ratios_in_band": in_band,
        "device_truth_overclaim_pages": fab.paged(),
    }


def ledger_checks() -> dict:
    """ISSUE 18: the request ledger must be HONEST and FREE.

    Honest — a mocker fleet's assembled ledgers must explain >= 90% of
    each request's measured TTFT (no dark time), and a FABRICATED
    ledger claiming more phase time than the wall-clock envelope must
    FAIL `coverage_ok` (a ledger that can over-claim can hide anything).
    Free — steady-decode `EngineStepCounters` deltas are byte-identical
    ledger-on vs ledger-off (the same pinning discipline as the
    tracing/flight-recorder checks: zero added host syncs, dispatches or
    recompiles)."""
    import asyncio
    import time

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.llm.mocker.engine import MockEngine, MockEngineArgs
    from dynamo_tpu.llm.preprocessor import PreprocessedRequest
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime import ledger as ledger_mod

    async def fleet_coverage():
        """3 concurrent requests against a mocker whose prefill budget
        forces multi-step (really-sleeping) prefills, so TTFT is real
        wall time the queue/prefill stamps must account for."""
        eng = MockEngine(MockEngineArgs(
            block_size=32, num_blocks=4096, max_batched_tokens=64,
            speedup_ratio=1.0))
        try:
            async def one(i: int) -> float:
                req = PreprocessedRequest(
                    request_id=f"led{i}", model="smoke",
                    token_ids=list(range(1, 257)),
                    sampling=SamplingParams(max_tokens=2))
                led = ledger_mod.begin(req)
                t0 = time.monotonic()
                ttft = 0.0
                async for d in eng.generate(req):
                    if d.token_ids:
                        ttft = time.monotonic() - t0
                        break
                return ledger_mod.ttft_coverage(led, ttft)
            return await asyncio.gather(*(one(i) for i in range(3)))
        finally:
            await eng.stop()

    ratios = asyncio.run(asyncio.wait_for(fleet_coverage(), 120))

    # Fabricated over-claim: a ledger whose phases sum past the
    # wall-clock envelope must FAIL the coverage check.
    fab = ledger_mod.RequestLedger("fabricated")
    fab.stamp("prefill", dur=2.0)
    fabricated_fails = not ledger_mod.coverage_ok(fab, 1.0)

    def steady_run(on: bool):
        ledger_mod.set_enabled(on)
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=128,
            enable_prefix_cache=False, decode_window=2,
            window_pipeline_depth=2,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=32,
                max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(16, 128))))
        core.add_request("a", list(range(1, 71)),
                         SamplingParams(max_tokens=64))
        for _ in range(8):   # prefill + window warmup
            core.step()
        base = core.counters.snapshot()
        for _ in range(20):
            core.step()
        return core.counters.delta(base)

    try:
        d_off = steady_run(False)
        d_on = steady_run(True)
    finally:
        ledger_mod.set_enabled(True)  # the process default

    return {
        "ledger_fleet_ttft_coverage": round(min(ratios), 4),
        "ledger_coverage_ok": all(
            ledger_mod.COVERAGE_FLOOR <= r <= ledger_mod.COVERAGE_CEIL
            for r in ratios),
        "ledger_fabricated_overclaim_fails": fabricated_fails,
        "ledger_extra_host_syncs":
            d_on["host_syncs"] - d_off["host_syncs"],
        "ledger_counters_byte_identical": d_on == d_off,
    }


def decode_wall_checks() -> dict:
    """ISSUE 6 smoke: the decode-bandwidth-wall features measured on CPU
    with the tiny model —

    - int8-KV traffic model at SERVING geometry (llama-3-1b, head_dim
      64): ratio <= 0.55 (the floor TPU rounds gate on; the formula is
      the same bytes_per_block accounting the block manager reports);
    - greedy quality pin: tiny-model greedy decode token-exact between
      bf16 and int8 KV caches;
    - speculative decoding on the repetitive workload: acceptance >=
      0.6, modeled sweep speedup >= 1.3, and output byte-identical to
      the non-spec baseline (lossless by construction, measured here)."""
    from dynamo_tpu.bench.decode_wall import (
        kv_quant_traffic, measure_spec_acceptance)
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg

    serving = kv_quant_traffic(mcfg.get_config("llama-3-1b"))

    def greedy_tokens(kv_quant: str):
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=64,
            kv_quant=kv_quant, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=8, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16, decode_buckets=(1, 2, 4, 8),
                prefill_buckets=(8, 16))))
        core.add_request("q", list(range(1, 30)),
                         SamplingParams(max_tokens=24))
        out = []
        for _ in range(500):
            for d in core.step():
                out.extend(d.token_ids)
            if not core._requests:
                break
        return out

    pin_bf16 = greedy_tokens("none")
    pin_int8 = greedy_tokens("int8")

    spec = measure_spec_acceptance(mcfg.get_config("tiny-test"))

    return {
        "kv_quant_traffic_ratio": serving["traffic_ratio"],
        "kv_quant_ratio_ok": serving["traffic_ratio"] <= 0.55,
        "kv_quant_greedy_pin": pin_bf16 == pin_int8 and len(pin_bf16) == 24,
        "spec_acceptance_rate": spec["acceptance_rate"],
        "spec_acceptance_ok": spec["acceptance_rate"] >= 0.6,
        "spec_modeled_speedup": spec["modeled_decode_speedup"],
        "spec_speedup_ok": spec["modeled_decode_speedup"] >= 1.3,
        "spec_output_identical": spec["output_identical_to_baseline"],
    }


def sharded_decode_checks() -> dict:
    """ISSUE 9 + 12 smoke: the sharded fast-decode plane measured on the
    CPU mesh rig — tp2 fused window/greedy step, the pp2 all-in-one
    stage program vs its unfused loop, the sp2 mode, int8 on all three,
    and the compose_matrix summary (no cell may read "rejected"; the
    declared-impossible cells must quote the capability table).

    The CPU ratios are NOT gated: host-process sharding overhead at tiny
    geometry swamps them; only presence + plumbing are asserted here,
    the 0.8 / 1.2 floors bind on TPU rounds."""
    import jax

    from dynamo_tpu.bench.sharded_decode import run_sharded_decode
    from dynamo_tpu.models import config as mcfg

    out = run_sharded_decode(
        mcfg.get_config("tiny-test"), batch=4, ctx=16, block=8, width=4,
        window=2, modes=("tp2", "sp2", "pp2"), with_int8=True)
    tp2 = out.get("tp2", {})
    pp2 = out.get("pp2", {})
    sp2 = out.get("sp2", {})
    matrix = out.get("compose_matrix", {})
    statuses = [c.get("status", "") for c in matrix.values()]
    ran = "tok_s_per_chip" in tp2
    return {
        "sharded_decode_devices": out["devices"],
        "sharded_decode_ran_tp2": ran,
        "sharded_decode_ratio": out.get("tok_s_per_chip_ratio"),
        "sharded_decode_pp_fused_vs_single": out.get(
            "pp_fused_vs_single"),
        "sharded_decode_section_ok": (
            ran and isinstance(out.get("tok_s_per_chip_ratio"), float)
            and out["tok_s_per_chip_ratio"] > 0
            and tp2.get("single_step_ms", 0) > 0
            and tp2.get("window_step_ms_int8", 0) > 0
            and len(jax.devices()) >= 2),
        # ISSUE 12: pp2/sp2 measured through the real stage programs,
        # fused-vs-unfused reported, int8 composing on every mode.
        # Presence checks only — tiny-geometry CPU slopes can clamp to 0
        # under machine load, so >0 would flake; the gated ratios bind
        # on TPU where slope timing is real.
        "sharded_decode_pp_ok": all(
            isinstance(pp2.get(k), (int, float))
            for k in ("single_step_ms", "single_unfused_ms",
                      "window_step_ms", "window_step_ms_int8",
                      "fused_vs_unfused")),
        "sharded_decode_sp_ok": all(
            isinstance(sp2.get(k), (int, float))
            for k in ("single_step_ms", "fused_vs_unfused",
                      "window_step_ms_int8")),
        "sharded_decode_matrix_no_rejects": (
            len(matrix) > 0
            and not any(s.startswith("rejected") for s in statuses)),
        "sharded_decode_matrix_declares_impossible": any(
            s.startswith("declared") for s in statuses),
    }


def ring_plane_checks() -> dict:
    """ISSUE 19 smoke: the ring-attention plane measured on the CPU mesh
    rig — the flash ring kernel (interpret mode) must agree with the XLA
    ppermute ring numerically, the section must carry the gated ratio
    and both modeled per-hop payload figures, and the tiny sp2+pallas
    engine must serve token-identical output with EVERY sp prefill
    attributed to the kernel path (ring_kernel_prefills — an XLA-ring
    fallback can't pass silently).

    The CPU ratio itself is NOT gated (interpret-mode kernel cost swamps
    it); the 1.15 floor binds on TPU rounds and is fabricated-failure-
    checked in run_smoke."""
    from dynamo_tpu.bench.ring_plane import run_tiny_ring_plane

    out = run_tiny_ring_plane()
    eng = out.get("engine", {})
    return {
        "ring_plane_ratio": out.get("kernel_vs_xla"),
        "ring_plane_numeric_parity": out.get("numeric_parity"),
        "ring_plane_section_ok": all(
            isinstance(out.get(k), (int, float))
            for k in ("kernel_ms", "xla_ring_ms", "meshless_ms",
                      "kernel_vs_xla", "per_hop_bytes",
                      "per_hop_bytes_int8_modeled")),
        # int8 exchange modeled payload must be smaller than bf16's —
        # the scales-ride-with-rows accounting, not a forked formula.
        "ring_plane_int8_payload_smaller": (
            out.get("per_hop_bytes_int8_modeled", 0)
            < out.get("per_hop_bytes", 0)),
        "ring_plane_engine_token_parity": eng.get("tokens_match"),
        "ring_plane_kernel_path_counted": (
            eng.get("ring_kernel_prefills", 0) > 0
            and eng.get("ring_kernel_prefills")
            == eng.get("sp_prefill_count")),
    }


def moe_decode_checks() -> dict:
    """ISSUE 17 smoke: the MoE fast-decode plane measured on CPU with
    tiny-moe — the grouped kernel (interpret mode) must be BITWISE equal
    to the moe_dense oracle in both plain and int8-weight form, the
    [E+1] stats must account every assignment with zero drops, and the
    section must carry the gated ratio.

    The CPU ratio itself is NOT gated (interpret-mode kernel cost swamps
    it); the 1.5 floor binds on TPU rounds and is fabricated-failure-
    checked in run_smoke."""
    from dynamo_tpu.bench.moe_decode import run_moe_decode
    from dynamo_tpu.models import config as mcfg

    cfg = mcfg.get_config("tiny-moe")
    out = run_moe_decode(cfg, batch=4)
    k = cfg.num_experts_per_token
    return {
        "moe_decode_ratio": out.get("grouped_vs_dense"),
        "moe_decode_token_parity": out.get("token_parity"),
        "moe_decode_int8_parity": out.get("int8_parity"),
        "moe_decode_load_accounted": (
            sum(out.get("expert_load", [])) == 4 * k
            and out.get("dropped_tokens") == 0),
        "moe_decode_section_ok": all(
            isinstance(out.get(key), (int, float))
            for key in ("dense_step_ms", "grouped_step_ms",
                        "grouped_int8_step_ms", "grouped_vs_dense",
                        "grouped_expert_weight_bytes")),
    }


def prefill_plane_checks() -> dict:
    """ISSUE 10 smoke: the packed ragged prefill plane measured on CPU
    with the tiny model — both planes serve the same ragged prompt set
    through real EngineCores (packed runs the Pallas flash-prefill
    kernel in interpret mode), the section must carry the gated ratio,
    and the first tokens must be byte-identical plane-to-plane.

    The CPU ratio itself is NOT gated: interpret-mode kernel cost
    swamps it; only presence + parity + packed-dispatch plumbing are
    asserted here, the 1.2 floor binds on TPU rounds."""
    from dynamo_tpu.bench.prefill_plane import run_tiny_prefill_plane

    out = run_tiny_prefill_plane()
    ratio = out.get("packed_vs_padded_tok_s_ratio")
    return {
        "prefill_plane_ratio": ratio,
        "prefill_plane_section_ok": (
            isinstance(ratio, float) and ratio > 0
            and out["packed"]["packed_dispatches"] > 0
            and out["padded"]["packed_dispatches"] == 0),
        "prefill_plane_token_parity": out["token_parity"],
    }


def transfer_plane_checks() -> dict:
    """ISSUE 13 smoke: the KV transfer planes measured on CPU between
    two real tiny engines — host-staged, device-direct, and streamed
    all land the full prefix with BYTE parity, the device plane really
    pulled blocks (the local device fabric on this jax build), and the
    plane-choice counters recorded the device pulls.  The CPU GB/s
    values are NOT gated (localhost wire); the 2x floor binds on TPU
    rounds and is fabricated-failure-checked in run_smoke."""
    import asyncio

    from dynamo_tpu.bench.transfer_plane import run_tiny_transfer_plane
    from dynamo_tpu.llm.block_manager.device_transfer import plane_counts

    before = sum(n for (plane, _), n in plane_counts().items()
                 if plane == "device")
    out = asyncio.run(asyncio.wait_for(run_tiny_transfer_plane(), 180))
    device_delta = sum(n for (plane, _), n in plane_counts().items()
                       if plane == "device") - before
    return {
        "transfer_transport": out["transport"],
        "transfer_host_gbs": out["host_staged_gbs"],
        "transfer_device_gbs": out["device_direct_gbs"],
        "transfer_streamed_gbs": out["streamed_gbs"],
        "transfer_section_ok": all(
            isinstance(out[k], (int, float)) and out[k] > 0
            for k in ("host_staged_gbs", "device_direct_gbs",
                      "streamed_gbs", "device_vs_host_ratio")),
        "transfer_device_plane_used": (out["device_blocks_pulled"] > 0
                                       and out["streamed_device_blocks"]
                                       > 0),
        "transfer_plane_counters_recorded": device_delta > 0,
        "transfer_byte_parity": out["byte_parity"],
    }


def prefix_fleet_checks() -> dict:
    """ISSUE 7 smoke: fleet-wide prefix reuse measured on CPU — the real
    router must hand out remote-prefix hints on the shared-prefix
    workload (remote_hit_rate >= 0.2, the TPU gate floor), remote reuse
    must beat local-only modeled TTFT, and the real PrefixFetcher must
    pull + inject the full context over the mocked wire with zero
    fallbacks."""
    import asyncio

    from dynamo_tpu.bench.prefix_fleet import run_prefix_fleet

    out = asyncio.run(asyncio.wait_for(run_prefix_fleet(), 120))
    measured = out["measured"]
    return {
        "prefix_fleet_remote_hit_rate": out["remote_hit_rate"],
        "prefix_fleet_hit_rate_ok": out["remote_hit_rate"] >= 0.2,
        "prefix_fleet_ttft_speedup": out["modeled_ttft_speedup"],
        "prefix_fleet_reuse_beats_local": out["modeled_ttft_speedup"] > 1.0,
        "prefix_fleet_pull_wall_ms": round(
            measured["pull_wall_s"] * 1e3, 1),
        "prefix_fleet_pull_complete": (measured["all_blocks_injected"]
                                       and measured["fallbacks"] == 0),
    }


def drain_migration_checks() -> dict:
    """ISSUE 15 smoke: the KV-carrying drain-migration resume (real
    PrefixFetcher over the modeled wire) must beat cold re-prefill —
    blip_ratio < 1.0 with blocks actually carried and zero re-prefill
    fallbacks — and the FABRICATED drop-the-KV donor (serves nothing)
    must FAIL that same claim: a gate that can't catch the KV silently
    not moving isn't a gate."""
    import asyncio

    from dynamo_tpu.bench.drain import run_drain_migration_model

    out = asyncio.run(asyncio.wait_for(run_drain_migration_model(), 120))
    dropped = asyncio.run(asyncio.wait_for(
        run_drain_migration_model(drop_kv=True), 120))
    return {
        "drain_migration_blip_ratio": out["blip_ratio"],
        "drain_migration_kv_carried_blocks": out["kv_carried_blocks"],
        "drain_migration_beats_reprefill": out["migration_beats_reprefill"],
        # The happy path took zero re-prefill fallbacks (acceptance pin).
        "drain_migration_no_fallbacks": out["reprefill_fallbacks"] == 0,
        # Fabricated drop-the-KV run: carried nothing, so the
        # beats-reprefill claim must come out False.
        "drain_fabricated_drop_kv_fails": (
            not dropped["migration_beats_reprefill"]
            and dropped["kv_carried_blocks"] == 0),
    }


def sla_profiler_checks() -> dict:
    """ISSUE 11 smoke: the SLA profiler + capacity frontier on CPU —
    the deterministic mocker-cell sweep must emit a profile SlaPlanner
    loads unchanged, the capacity model must name the PINNED cheapest
    fleet for the smoke (SLO, traffic-mix) fixture, a fabricated
    over-SLO requirement must make it REFUSE (naming every rejected
    config), and a mocker fleet cell driven through real MockEngines +
    status servers must agree with the modeled TTFT/TPOT when scraped
    through dynamo_top's collector (the documented factor-2/10ms
    tolerance)."""
    from benchmarks.sla_profiler import (
        CellConfig,
        SloTarget,
        find_knee,
        plan_capacity,
        run_smoke as profiler_smoke,
        validate_fleet_model,
    )
    from dynamo_tpu.planner.interpolation import (
        DecodeInterpolator,
        PrefillInterpolator,
    )
    from dynamo_tpu.planner.sla import SlaObservation, SlaPlanner

    res = profiler_smoke(None)
    plan = res["plan"]
    moe_plan = res["moe_plan"]
    profile = res["profile"]

    # The planner consumes the profiler's profile UNCHANGED (meta and
    # all), and a loaded interval produces a real scaling decision.
    planner_ok = True
    try:
        PrefillInterpolator(profile)
        DecodeInterpolator(profile)

        class _Conn:
            n = 1

            def replicas(self):
                return self.n

        planner = SlaPlanner(profile, observe=lambda: SlaObservation(),
                             decode_connector=_Conn())
        d = planner.decide(SlaObservation(
            num_requests=100, avg_isl=216, avg_osl=16,
            ttft_s=0.05, itl_s=0.008))
        planner_ok = d.num_decode >= 1
    except Exception:
        planner_ok = False

    # Fabricated over-SLO requirement: no profiled config can hold a
    # 1ms TTFT / 0.1ms TPOT SLO — the model must refuse, not deploy.
    refused = plan_capacity(res["frontiers"],
                            SloTarget(ttft_p99_s=0.001,
                                      tpot_p99_s=0.0001), 40.0)

    # Mocker fleet cell: real MockEngines + per-worker /metrics +
    # /debug/slo scraped via dynamo_top's collector, vs the model.
    fleet = validate_fleet_model(
        CellConfig("base"), "agentic", 30.0, num_workers=4,
        num_requests=32, slo=SloTarget(ttft_p99_s=0.25,
                                       tpot_p99_s=0.012))

    # Kneedle flags the max-deviation-below-the-chord point — the middle
    # of the bend (idx 4 = load 16 here), not its onset.
    knee = find_knee([1, 2, 4, 8, 16, 32],
                     [10.0, 10.5, 11.0, 12.0, 80.0, 400.0])
    return {
        "sla_profile_loads_in_planner": planner_ok,
        "sla_plan_feasible": plan.feasible,
        # Pinned fixture (SMOKE_SLO at SMOKE_RPS on the agentic mix):
        # the sweep is a pure virtual clock, so the cheapest fleet is
        # byte-stable — any drift is a model change and must be looked
        # at, not averaged away.
        "sla_plan_cell": (plan.cell or {}).get("name"),
        "sla_plan_pinned": ((plan.cell or {}).get("name")
                            == "int8+spec+packed"
                            and plan.replicas == 3
                            and plan.total_chips == 3),
        # Pinned MoE fixture (ISSUE 17): the MoE grid is swept under
        # its own mix and answered as its own plan, so the dense pin
        # above cannot drift.  At the shared smoke SLO the dense-MoE
        # oracle can't hold TPOT at ANY load (the E/k weight-traffic
        # wall the grouped kernel exists for) — the only feasible
        # fleet composes grouped + ep2 + every serving plane.
        "sla_moe_plan_cell": (moe_plan.cell or {}).get("name"),
        "sla_moe_plan_pinned": (
            (moe_plan.cell or {}).get("name")
            == "moe-grouped-ep2+int8+spec+packed"
            and moe_plan.replicas == 10
            and moe_plan.total_chips == 20),
        "sla_moe_dense_rejected": any(
            r["cell"] == "moe-dense" for r in moe_plan.rejected),
        "sla_over_slo_refused": (not refused.feasible
                                 and len(refused.rejected) > 0),
        "sla_fleet_ttft_agree": fleet["ttft_p50_agree"],
        "sla_fleet_tpot_agree": fleet["tpot_p50_agree"],
        # Boolean, not the raw count: the gate only fails on literal
        # False, so a partial scrape (3/4, or None) must not slip by.
        "sla_fleet_all_workers_scraped": (
            fleet["scraped"].get("workers") == 4),
        "sla_knee_detected_at_bend": knee == 4,
    }


def disagg_topology_checks() -> dict:
    """ISSUE 16 smoke: the slice topology plane measured end to end — a
    heterogeneous disagg cell (ring-SP int8 prefill slice → head-sharded
    tp int8 decode slice) serves byte-identical greedy output vs the
    meshless oracle with the KV crossing the DEVICE plane and landing
    resharded on the decode mesh (reshard_pulls pinned), and the
    fabricated mesh-blind planner decision — decode role deployed onto
    the prefill-only slice — must be REFUSED by `validate_placement`."""
    import asyncio

    from dynamo_tpu.bench.disagg_topology import run_disagg_topology

    out = asyncio.run(asyncio.wait_for(run_disagg_topology(), 300))
    return {
        "disagg_topology_prefill_slice": out["prefill_slice"],
        "disagg_topology_decode_slice": out["decode_slice"],
        "disagg_topology_token_parity": out["token_parity"],
        "disagg_topology_remote_prefills": out["remote_prefills"],
        "disagg_topology_no_fallbacks": out["local_fallbacks"] == 0,
        "disagg_topology_device_plane_used": (
            out["device_pulls"] > 0 and out["pulled_blocks"] > 0),
        "disagg_topology_reshard_pulls": out["reshard_pulls"],
        "disagg_topology_kv_resharded": out["reshard_pulls"] > 0,
        "disagg_topology_onboarded_blocks": out["onboarded_blocks"],
        "disagg_topology_mesh_blind_placement_refused":
            out["placement_guard_refuses_mesh_blind"],
    }


def run_smoke(args) -> int:
    """Mocker-backed smoke of the whole measurement loop — CPU-only, no
    JAX device work, fast enough for tier-1.

    1. synthesize a prefix-heavy trace;
    2. analyze it (predicted hit rate);
    3. replay against one MockEngine, compare measured vs predicted;
    4. gate a fabricated regressed run and a fabricated invalid run —
       both must FAIL the gate; an honest run must pass;
    5. bound tracing overhead: steady decode with sampling=1.0 adds no
       host syncs, no per-window spans, and ≤1% modeled wall time;
    6. measure the modeled disagg-TTFT benchmark (real EagerPuller over
       a mocked seal timeline + wire): eager streaming must hide >= half
       the transfer behind prefill (transfer_overlap_ratio >= 0.5) and
       land TTFT near max(prefill, transfer) + tail, not their sum;
    7. bound KV/HBM telemetry overhead: per-step memory-plane sampling
       adds 0 host syncs and 0 dispatches to the steady decode window;
    7b. bound flight-recorder overhead (ISSUE 14): recorder-on steady
       decode keeps EngineStepCounters deltas byte-identical to
       recorder-off (0 extra host syncs) and within the one-ring-write-
       per-window budget; a fabricated chatty recorder must fail it;
    7c. request-ledger honesty + overhead (ISSUE 18): a mocker fleet's
       assembled ledgers explain >= 90% of each measured TTFT, a
       fabricated ledger claiming more time than the wall-clock
       envelope FAILS coverage_ok, and ledger-on steady decode keeps
       EngineStepCounters deltas byte-identical to ledger-off;
    7d. device-truth plane (ISSUE 20): profiler-on steady decode keeps
       EngineStepCounters deltas byte-identical to profiler-off, the
       compile-time harvest lands a non-empty XLA cost registry, the
       drift audit's modeled-vs-measured ratios sit inside the band on
       CPU, a fabricated 2x modeled over-claim drives the auditor to
       PAGE, and the new TPU floor fails a fabricated over-claiming run;
    8. decode-bandwidth-wall features (ISSUE 6): int8-KV traffic ratio
       <= 0.55 at serving geometry, tiny-model greedy pin bf16 == int8,
       spec-decode acceptance >= 0.6 + modeled sweep speedup >= 1.3 on
       the repetitive workload with byte-identical output, and the new
       gate floors verified to fail fabricated bad runs;
    9. sharded fast-decode plane (ISSUE 9 + 12): tp2/sp2/pp2 fused
       windows + fused greedy steps + int8 measured on the CPU mesh rig
       through the real stage programs, the compose_matrix carrying no
       rejected cells, and the tok_s_per_chip_ratio /
       pp_fused_vs_single floors plus the rejected-cell check verified
       to fail fabricated bad runs;
    9b. MoE fast-decode plane (ISSUE 17): the grouped expert kernel
        bitwise equal to the moe_dense oracle (plain and int8-weight,
        interpret mode) with every assignment accounted and zero drops,
        and the grouped_vs_dense floor verified to fail a fabricated
        slower-than-dense run;
    9c. ring-attention plane (ISSUE 19): the Pallas flash ring kernel
        (interpret mode) numerically equal to the XLA ppermute ring at
        sp2, the tiny sp2+pallas engine token-identical with every sp
        prefill attributed to the kernel path, and the kernel_vs_xla
        floor verified to fail a fabricated slower-than-XLA kernel run;
    10. prefill plane (ISSUE 10): packed ragged vs padded prefill on the
        tiny model with byte-identical first tokens, and the
        packed_vs_padded_tok_s_ratio floor verified to fail a
        fabricated slow-packed run;
    11a. transfer plane (ISSUE 13): host-staged vs device-direct vs
        streamed KV pulls between two real tiny engines with byte
        parity, the device plane demonstrably used (plane counters),
        and the device_vs_host_ratio floor verified to fail a
        fabricated slower-than-host device run;
    11. SLA profiler + capacity frontier (ISSUE 11): the deterministic
        mocker-cell sweep emits a profile SlaPlanner loads unchanged,
        the capacity model names the pinned cheapest fleet and REFUSES
        a fabricated over-SLO requirement, and a mocker fleet cell
        scraped through dynamo_top agrees with the model within the
        documented tolerance;
    12. drain migration (ISSUE 15): the KV-carrying drain resume (real
        PrefixFetcher over the modeled wire) beats cold re-prefill
        (blip_ratio < 1, blocks carried, zero fallbacks), and the
        fabricated drop-the-KV donor must FAIL the same claim;
    13. slice topology (ISSUE 16): a heterogeneous disagg cell
        (sp-prefill slice → tp+int8 decode slice) serves byte-identical
        greedy output vs the meshless oracle with the KV resharded on
        the device plane (reshard_pulls > 0), and the fabricated
        mesh-blind placement (decode role on the prefill-only slice)
        must be refused by the topology guard.
    """
    # The sharded checks need a multi-device rig: force the 8-way
    # virtual-CPU platform BEFORE anything imports jax (this smoke is
    # CPU-only by contract — the module docstring and the tier-1 test
    # both pin JAX_PLATFORMS=cpu).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ("xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    import asyncio

    from benchmarks.data_generator.prefix_analyzer import analyze_trace
    from benchmarks.data_generator.synthesizer import (
        synthesize_prefix_heavy,
        tokens_for_record,
    )
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.llm.mocker.engine import MockEngine, MockEngineArgs
    from dynamo_tpu.llm.preprocessor import PreprocessedRequest

    block = 32
    records = synthesize_prefix_heavy(
        40, num_roots=4, context_blocks=6, suffix_tokens=16,
        output_tokens=4, interval_ms=1.0, block_size=block)
    report = analyze_trace(records, block)
    predicted = report.theoretical_hit_rate

    async def replay() -> float:
        eng = MockEngine(MockEngineArgs(
            block_size=block, num_blocks=4096, speedup_ratio=1000.0))
        hit_tokens = input_tokens = 0
        try:
            for i, rec in enumerate(records):
                toks = tokens_for_record(rec, block, unique_seed=i)
                input_tokens += len(toks)
                async for d in eng.generate(PreprocessedRequest(
                        request_id=f"s{i}", model="smoke", token_ids=toks,
                        sampling=SamplingParams(
                            max_tokens=rec.output_length))):
                    if d.finished:
                        break
            hit_tokens = eng.kv.hit_blocks * block
        finally:
            await eng.stop()
        return hit_tokens / input_tokens if input_tokens else 0.0

    measured = asyncio.run(asyncio.wait_for(replay(), 120))
    hit_delta = abs(measured - predicted)

    good = {"value": 100.0, "serving_tok_s": 50.0, "prefill_tok_s": 200.0,
            "itl_ms": 6.0, "calibration_ok": True, "run_health": "ok"}
    regressed = dict(good, serving_tok_s=50.0 * 0.7)       # 30% drop
    invalid = dict(good, calibration_ok=False,
                   run_health="invalid", vs_baseline=None)
    # Absolute TPU floors: a run below the MBU / interference floor fails
    # even against a baseline that already regressed there.
    tpu_good = dict(good, device="TPU v5 lite0", mbu=0.82,
                    mixed_prefill_decode={"interference_ratio": 0.88},
                    kv_quant={"traffic_ratio": 0.531},
                    spec_decode={"acceptance_rate": 0.9,
                                 "modeled_decode_speedup": 1.9},
                    prefix_fleet={"remote_hit_rate": 0.34},
                    sharded_decode={
                        "tok_s_per_chip_ratio": 0.91,
                        "pp_fused_vs_single": 1.6,
                        "compose_matrix": {
                            "fused_decode × pp2": {"status": "ok"},
                            "spec × multihost": {
                                "status": "declared: lockstep"}}},
                    prefill_plane={
                        "packed_vs_padded_tok_s_ratio": 1.45},
                    moe_decode={"grouped_vs_dense": 2.7,
                                "token_parity": True},
                    ring_plane={"kernel_vs_xla": 1.6,
                                "numeric_parity": True},
                    transfer={"device_vs_host_ratio": 3.4},
                    device_truth={"modeled_vs_measured_kv": 0.95})
    tpu_low_mbu = dict(tpu_good, mbu=0.60)
    tpu_interfered = dict(
        tpu_good, mixed_prefill_decode={"interference_ratio": 0.70})
    # New ISSUE-6 floors: a fat quantized cache (scales forgotten or
    # stored wide) and a collapsed acceptance rate must each fail.
    tpu_fat_quant = dict(tpu_good, kv_quant={"traffic_ratio": 0.80})
    tpu_low_accept = dict(
        tpu_good, spec_decode={"acceptance_rate": 0.3,
                               "modeled_decode_speedup": 1.9})
    # ISSUE-7 floor: a fleet that stopped handing out remote-prefix
    # hints (remote_hit_rate collapsed) must fail.
    tpu_no_remote = dict(tpu_good,
                         prefix_fleet={"remote_hit_rate": 0.05})
    # ISSUE-9 floor: a sharded engine that fell back to the slow gather
    # path (per-chip throughput collapsed vs meshless) must fail.
    tpu_sharded_slow = dict(
        tpu_good, sharded_decode=dict(
            tpu_good["sharded_decode"], tok_s_per_chip_ratio=0.5))
    # ISSUE-12 floor: a fused pp stage program that stopped beating the
    # unfused 3-dispatch loop (the r5 cliff back) must fail.
    tpu_pp_cliff = dict(
        tpu_good, sharded_decode=dict(
            tpu_good["sharded_decode"], pp_fused_vs_single=1.0))
    # ISSUE-12 matrix: a fabricated STILL-REJECTING cell — a combo the
    # capability table says composes but whose builder raised — must
    # fail the gate even with every headline number healthy.
    tpu_rejected_cell = dict(
        tpu_good, sharded_decode=dict(
            tpu_good["sharded_decode"],
            compose_matrix={"int8 × sp2": {
                "status": "rejected: ValueError: kv_quant=int8 is not "
                          "wired for ring-SP"}}))
    # ISSUE-10 floor: a packed prefill plane that stopped beating the
    # padded one (regressed to the gather path) must fail.
    tpu_slow_prefill = dict(
        tpu_good, prefill_plane={"packed_vs_padded_tok_s_ratio": 0.9})
    # ISSUE-17 floor: a grouped MoE kernel SLOWER than the dense
    # all-experts path (regressed to dense-ish weight streaming) must
    # fail — as must a parity failure, which zeroes the ratio at the
    # bench.
    tpu_moe_slow = dict(
        tpu_good, moe_decode={"grouped_vs_dense": 0.9,
                              "token_parity": True})
    # ISSUE-19 floor: a flash ring kernel that stopped beating the XLA
    # ppermute ring (RDMA no longer overlapping the fold, or a silent
    # fallback) must fail — as must a parity failure, which zeroes the
    # ratio at the bench.
    tpu_ring_slow = dict(
        tpu_good, ring_plane={"kernel_vs_xla": 1.05,
                              "numeric_parity": True})
    # ISSUE-13 floor: a device plane slower than the host-staged wire
    # (regressed to host staging under the covers, or double-copying on
    # inject) must fail — as must a parity failure, which zeroes the
    # ratio at the bench.
    tpu_slow_transfer = dict(
        tpu_good, transfer={"device_vs_host_ratio": 0.8})
    # ISSUE-20 floor: a modeled series claiming 2x the bytes XLA says
    # the decode programs actually touch (the accounting-over-claim bug
    # class the drift auditor pages on) must fail.
    tpu_drift_overclaim = dict(
        tpu_good, device_truth={"modeled_vs_measured_kv": 2.0})

    from dynamo_tpu.bench.disagg import run_disagg_ttft_model

    disagg = asyncio.run(asyncio.wait_for(run_disagg_ttft_model(), 120))

    checks = {
        "predicted_hit_rate": round(predicted, 4),
        "measured_hit_rate": round(measured, 4),
        "hit_rate_delta": round(hit_delta, 4),
        "hit_rate_within_5pts": hit_delta <= 0.05,
        "honest_run_passes": gate.compare(good, good).ok,
        "regression_fails": not gate.compare(regressed, good).ok,
        "invalid_run_fails": not gate.compare(invalid, good).ok,
        "tpu_floors_pass": gate.compare(tpu_good, tpu_good).ok,
        "low_mbu_fails": not gate.compare(tpu_low_mbu, tpu_low_mbu).ok,
        "interference_fails": not gate.compare(tpu_interfered,
                                               tpu_interfered).ok,
        "fat_quant_fails": not gate.compare(tpu_fat_quant,
                                            tpu_fat_quant).ok,
        "low_acceptance_fails": not gate.compare(tpu_low_accept,
                                                 tpu_low_accept).ok,
        "no_remote_hits_fails": not gate.compare(tpu_no_remote,
                                                 tpu_no_remote).ok,
        "sharded_floor_fails": not gate.compare(tpu_sharded_slow,
                                                tpu_sharded_slow).ok,
        "pp_cliff_fails": not gate.compare(tpu_pp_cliff,
                                           tpu_pp_cliff).ok,
        "rejected_cell_fails": not gate.compare(tpu_rejected_cell,
                                                tpu_rejected_cell).ok,
        "slow_prefill_plane_fails": not gate.compare(tpu_slow_prefill,
                                                     tpu_slow_prefill).ok,
        "slow_moe_grouped_fails": not gate.compare(tpu_moe_slow,
                                                   tpu_moe_slow).ok,
        "slow_ring_kernel_fails": not gate.compare(tpu_ring_slow,
                                                   tpu_ring_slow).ok,
        "slow_device_transfer_fails": not gate.compare(
            tpu_slow_transfer, tpu_slow_transfer).ok,
        "drift_overclaim_fails": not gate.compare(
            tpu_drift_overclaim, tpu_drift_overclaim).ok,
        "disagg_ttft_serial_ms": round(disagg["ttft_serial_s"] * 1e3, 1),
        "disagg_ttft_streamed_ms": round(
            disagg["ttft_streamed_s"] * 1e3, 1),
        "transfer_overlap_ratio": disagg["overlap_ratio"],
        "transfer_overlap_ok": disagg["overlap_ratio"] >= 0.5,
        "disagg_streamed_beats_serial": disagg["streamed_beats_serial"],
        "disagg_ttft_near_max_bound": disagg["ttft_near_max_bound"],
        **tracing_overhead_checks(),
        **telemetry_overhead_checks(),
        **flight_recorder_overhead_checks(),
        **device_truth_checks(),
        **ledger_checks(),
        **decode_wall_checks(),
        **moe_decode_checks(),
        **ring_plane_checks(),
        **prefill_plane_checks(),
        **transfer_plane_checks(),
        **prefix_fleet_checks(),
        **sharded_decode_checks(),
        **sla_profiler_checks(),
        **drain_migration_checks(),
        **disagg_topology_checks(),
    }
    ok = all(v is not False for v in checks.values())
    print(json.dumps({"smoke": "pass" if ok else "fail", **checks},
                     indent=2))
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser("tools/bench_gate.py",
                                description=__doc__.splitlines()[0])
    p.add_argument("new", nargs="?", default=None,
                   help="fresh bench JSON (bare output or BENCH_rNN form)")
    p.add_argument("--baseline", default=None,
                   help="baseline JSON to compare against (required "
                        "outside --smoke)")
    p.add_argument("--threshold", type=float,
                   default=gate.DEFAULT_THRESHOLD,
                   help="fractional regression that fails (default 0.2)")
    p.add_argument("--smoke", action="store_true",
                   help="CPU-only synthesize→analyze→mocker→gate smoke")
    args = p.parse_args(argv)
    if args.smoke:
        return run_smoke(args)
    if not args.new:
        p.error("pass a bench JSON or --smoke")
    if not args.baseline:
        # The repo records no measured round to default to: BENCH_r01-r05
        # predated PR 1 and went with the backend they were taken on.
        p.error("pass --baseline (the recorded run to compare against)")
    return run_gate(args)


if __name__ == "__main__":
    sys.exit(main())
