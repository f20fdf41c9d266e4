"""Request latency ledger e2e + contracts (ISSUE 18).

The acceptance spine: a 2-worker disaggregated cell (device KV plane)
fronted by KV routing must assemble ONE request's ledger out of every
hop — route / queue / prefill / kv_transfer(plane=device) / first_token
— with the TTFT-path phase durations summing to the measured TTFT
within tolerance, and byte-identical output to an aggregated reference.
Plus the tolerance contract (garbage wire ledgers drop the LEDGER,
never the request) and the overhead contract (steady-decode
EngineStepCounters byte-identical ledger-on vs ledger-off).
"""

import asyncio
import time

from dynamo_tpu.engine.engine import (
    EngineConfig,
    EngineCore,
    InferenceEngine,
    TokenDelta,
)
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.block_manager.transfer import (
    KV_BLOCKS_ENDPOINT,
    make_kv_blocks_handler,
)
from dynamo_tpu.llm.discovery import (
    delta_from_wire,
    delta_to_wire,
    engine_wire_handler,
)
from dynamo_tpu.llm.preprocessor import PreprocessedRequest
from dynamo_tpu.llm.service import LocalEngineClient
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.runtime import ledger as ledger_mod
from dynamo_tpu.runtime import logutil
from dynamo_tpu.runtime.control_plane import InProcessControlPlane
from dynamo_tpu.runtime.ledger import (
    LedgerSink,
    RequestLedger,
    decode_wire,
)
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.rpc import RpcServer

TINY = mcfg.get_config("tiny-test")
BS = 8
NS = "test-ledger"


def _core():
    return EngineCore(EngineConfig(
        model=TINY, num_blocks=64,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=BS, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))


class _Worker:
    async def start(self):
        self.engine = InferenceEngine(_core())
        await self.engine.start()
        self.client = LocalEngineClient(self.engine)
        self.rpc = RpcServer()
        self.rpc.register(KV_BLOCKS_ENDPOINT,
                          make_kv_blocks_handler(self.engine))
        self.address = await self.rpc.start()
        return self

    async def stop(self):
        await self.rpc.stop()
        await self.engine.stop()


def _req(rid, tokens, max_tokens=4):
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=list(tokens),
        sampling=SamplingParams(max_tokens=max_tokens))


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


# ---------------------------------------------------------------------------
# The acceptance e2e: KV-routed frontend → wire hop → disagg decode
# worker (device KV plane) → prefill worker; one ledger explains TTFT.


def test_ledger_e2e_disagg_device_cell_explains_ttft():
    from dynamo_tpu.llm.block_manager.device_transfer import (
        KV_OFFER_ENDPOINT, KV_PULLED_ENDPOINT, KvTransferPlane)
    from dynamo_tpu.llm.disagg import (
        DisaggDecodeClient, disagg_config_key, prefill_worker_loop)
    from dynamo_tpu.llm.kv_router.client import KvRoutedEngineClient
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    async def main():
        cp = InProcessControlPlane()
        await cp.start()
        await cp.put(disagg_config_key(NS), {"max_local_prefill_length": 12})

        prefill = await _Worker().start()
        prefill_plane = KvTransferPlane(prefill.engine)
        prefill_plane.start()
        prefill.rpc.register(KV_OFFER_ENDPOINT,
                             prefill_plane.make_offer_handler())
        prefill.rpc.register(KV_PULLED_ENDPOINT,
                             prefill_plane.make_pulled_handler())
        decode = await _Worker().start()
        decode_plane = KvTransferPlane(decode.engine)
        decode_plane.start()
        ploop = asyncio.create_task(prefill_worker_loop(
            cp, NS, prefill.client, prefill.address))

        dec = DisaggDecodeClient(decode.client, decode.engine, cp, NS, BS,
                                 transfer_plane=decode_plane)
        await dec.start()

        # The worker leg of the wire: the disagg client served behind a
        # runtime endpoint, exactly how dynamo_tpu.worker exposes it.
        runtime = DistributedRuntime(cp)
        ep = (runtime.namespace("dyn").component("backend")
              .endpoint("generate"))
        await ep.serve(engine_wire_handler(dec))
        client = await (runtime.namespace("dyn").component("backend")
                        .endpoint("generate").client())
        await client.wait_for_instances()
        kv = KvRoutedEngineClient(client, runtime, block_size=BS)
        await kv.start()

        async def collect(req):
            """(tokens, measured ttft) through the routed front."""
            t0 = time.monotonic()
            ttft = None
            out = []
            async for d in kv.generate(req):
                if d.token_ids and ttft is None:
                    ttft = time.monotonic() - t0
                out.extend(d.token_ids)
                if d.finished:
                    break
            return out, ttft

        try:
            long_prompt = list(range(1, 28))    # 3 sealed blocks + tail

            # Reference output: same prompt, aggregated on a fresh
            # engine.  The ledger must be observation-only: the routed
            # disagg cell's bytes must match exactly.
            ref = await _Worker().start()
            want = []
            async for d in ref.client.generate(_req("ref", long_prompt)):
                want.extend(d.token_ids)
                if d.finished:
                    break
            await ref.stop()

            # Warm every path (jit compiles, remote-prefill machinery)
            # before the measured request.
            warm = _req("warm", list(range(200, 227)))
            ledger_mod.begin(warm)
            await collect(warm)

            req = _req("r1", long_prompt)
            led = ledger_mod.begin(req)
            got, ttft = await collect(req)

            assert got == want                       # byte-identical
            assert dec.device_pulls >= 1             # device plane used
            totals = led.phase_totals()
            for phase in ("route", "queue", "prefill", "first_token",
                          "prefill_remote", "kv_transfer"):
                assert phase in totals, (phase, totals)
            planes = [a.get("plane") for p, _t, _d, a in led.stamps
                      if p == "kv_transfer" and a]
            assert "device" in planes, led.stamps
            # The assembled TTFT-path phases must explain the measured
            # TTFT: no giant dark time, no over-claim (loose bounds —
            # CI wall clocks wobble).
            covered = sum(d for p, _t, d, _a in led.stamps
                          if p in ledger_mod.TTFT_PHASES)
            assert ttft is not None and ttft > 0
            assert 0.5 <= covered / ttft <= 1.15, (covered, ttft, totals)
        finally:
            ploop.cancel()
            await kv.stop()
            await client.stop()
            await dec.stop()
            await runtime.shutdown()
            await prefill.stop()
            await decode.stop()
            await cp.close()

    _run(main())


# ---------------------------------------------------------------------------
# Tolerance contract: garbage wire ledgers drop the LEDGER, never the
# request (rate-limited warn), through the real delta codec.


def test_garbage_wire_ledger_drops_ledger_never_request(caplog):
    logutil.reset()
    garbage = [
        "not-a-dict",
        ["a", "list"],
        {"stamps": "nope"},
        {"stamps": [["prefill", "NaN-ish", "x"]]},
        {"anchor": "z", "stamps": []},
        {"stamps": [[42, 0.0, 0.1]]},          # non-string phase
        {"stamps": [["p", 0.0, 0.1, [1, 2]]]},  # attrs not a dict
    ]
    for bad in garbage:
        assert decode_wire(bad, where="test") is None

    req = _req("tol", [1, 2, 3])
    led = ledger_mod.begin(req)
    led.stamp("receive", dur=0.001)
    for i, bad in enumerate(garbage):
        wire = delta_to_wire(TokenDelta(
            request_id="tol", token_ids=[5 + i], finished=(i == 0),
            ledger=bad))
        delta = delta_from_wire(wire)
        ledger_mod.absorb_delta(req, delta, where="test")
        # The delta (the request path) is untouched; only the ledger
        # payload was dropped, and it never merges garbage stamps.
        assert delta.token_ids == [5 + i]
        assert delta.ledger is None
    assert [p for p, *_ in led.stamps] == ["receive"]

    # Non-scalar attr VALUES inside an otherwise-valid payload are
    # filtered per-key, not fatal.
    ok = decode_wire({"anchor": 1.0, "stamps": [
        ["kv_transfer", 0.5, 0.2, {"plane": "device", "bad": [1, 2]}]]})
    assert ok is not None
    _anchor, stamps, _dropped = ok
    assert stamps[0][3] == {"plane": "device"}


def test_hop_ledger_wire_round_trip_and_gating():
    # begin_hop only fires for requests that opted in via annotation.
    bare = _req("h0", [1])
    assert ledger_mod.begin_hop(bare) is None

    front = _req("h1", [1, 2])
    fled = ledger_mod.begin(front)         # sets the wire annotation
    assert front.annotations[ledger_mod.LEDGER_ANNOTATION]
    fled.stamp("route", dur=0.010)

    # Worker side: fresh hop ledger, own anchor; rides the final delta.
    hop_req = _req("h1", [1, 2])
    hop_req.annotations = dict(front.annotations)
    hop = ledger_mod.begin_hop(hop_req)
    assert hop is not None
    hop.stamp("queue", dur=0.002)
    hop.stamp("prefill", dur=0.030, prompt_tokens=2)
    wire = delta_to_wire(TokenDelta(
        request_id="h1", token_ids=[9], finished=True,
        ledger=hop.to_wire()))
    delta = delta_from_wire(wire)
    ledger_mod.absorb_delta(front, delta, where="test")
    assert delta.ledger is None            # consumed exactly once
    totals = fled.phase_totals()
    assert totals["route"] == 0.010
    assert abs(totals["prefill"] - 0.030) < 1e-6
    assert any(a == {"prompt_tokens": 2}
               for p, _t, _d, a in fled.stamps if p == "prefill")

    # Disabled plane: begin() is a no-op end to end.
    ledger_mod.set_enabled(False)
    try:
        off = _req("h2", [1])
        assert ledger_mod.begin(off) is None
        assert ledger_mod.ledger_of(off) is None
    finally:
        ledger_mod.set_enabled(True)

    # Runaway stamper degrades to a drop counter, never unbounded wire.
    led = RequestLedger("cap")
    for i in range(ledger_mod.MAX_STAMPS + 6):
        led.stamp("p", dur=0.001)
    assert len(led.stamps) == ledger_mod.MAX_STAMPS
    assert led.dropped == 6


# ---------------------------------------------------------------------------
# Frontend fold: goodput attribution + /debug/requests payload.


def test_ledger_sink_goodput_and_dominant_phase():
    sink = LedgerSink(MetricsRegistry(), slo_ttft=0.5, slo_tpot=0.1)

    slow = RequestLedger("slow")
    slow.stamp("queue", dur=0.1)
    slow.stamp("prefill", dur=1.5)
    slow.stamp("decode", dur=30.0, n=100)   # excluded from attribution
    sink.fold(slow, ttft=1.6, tpot=0.02, output_tokens=100)

    fast = RequestLedger("fast")
    fast.stamp("prefill", dur=0.2)
    sink.fold(fast, ttft=0.2, tpot=0.01, output_tokens=50)

    err = RequestLedger("err")
    err.stamp("prefill", dur=0.1)
    sink.fold(err, ttft=0.1, tpot=0.01, output_tokens=10, ok=False)

    assert sink.goodput_total.value() == 160.0
    assert sink.goodput_good.value() == 50.0          # fast only
    assert abs(sink.goodput_ratio() - 50.0 / 160.0) < 1e-9
    # Burn attribution: decode excluded by default, prefill dominates.
    assert sink.dominant_phase() == "prefill"

    payload = sink.debug_payload(n=2)
    assert payload["folded"] == 3
    assert payload["dominant_phase"] == "prefill"
    assert [e["request_id"] for e in payload["slowest"]] == ["slow", "fast"]
    assert payload["slowest"][0]["slo_good"] is False  # blew TTFT SLO
    assert payload["ledger_enabled"] is True


# ---------------------------------------------------------------------------
# Overhead contract: steady-decode EngineStepCounters byte-identical
# ledger-on vs ledger-off (same pinning discipline as tracing/flight
# recorder — zero added host syncs, dispatches or recompiles).


def test_mocker_ledgers_explain_their_ttft():
    """The mocker stamps queue, prefill and first_token like a real
    engine, on the wall clock it really sleeps: three concurrent requests
    whose prefill takes several steps each get a ledger that explains
    their measured TTFT, neither dark time nor more than the envelope."""
    from dynamo_tpu.llm.mocker.engine import MockEngine, MockEngineArgs

    async def measured():
        eng = MockEngine(MockEngineArgs(
            block_size=32, num_blocks=4096, max_batched_tokens=64,
            speedup_ratio=1.0))

        async def one(i):
            req = _req(f"led{i}", range(1, 257), max_tokens=2)
            led = ledger_mod.begin(req)
            t0 = time.monotonic()
            async for d in eng.generate(req):
                if d.token_ids:
                    return led, time.monotonic() - t0
            raise AssertionError("no token")

        try:
            return await asyncio.gather(*(one(i) for i in range(3)))
        finally:
            await eng.stop()

    for led, ttft in _run(measured()):
        assert ledger_mod.coverage_ok(led, ttft), \
            ledger_mod.ttft_coverage(led, ttft)
    # A ledger that claims more time than the wall clock had is refused.
    fabricated = RequestLedger("fabricated")
    fabricated.stamp("prefill", dur=2.0)
    assert not ledger_mod.coverage_ok(fabricated, 1.0)


def test_steady_decode_counters_byte_identical_on_vs_off():
    def steady_run(on: bool):
        ledger_mod.set_enabled(on)
        core = EngineCore(EngineConfig(
            model=TINY, num_blocks=64, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16, decode_buckets=(1, 2, 4),
                prefill_buckets=(8, 16))))
        core.add_request("s", list(range(1, 15)),
                         SamplingParams(max_tokens=32))
        for _ in range(4):   # prefill + settle
            core.step()
        base = core.counters.snapshot()
        for _ in range(12):
            core.step()
        return core.counters.delta(base)

    try:
        d_off = steady_run(False)
        d_on = steady_run(True)
    finally:
        ledger_mod.set_enabled(True)
    assert d_on == d_off, (d_on, d_off)


# ---------------------------------------------------------------------------
# The engine's stamps come from the request-state clock (PR 39): `queue`
# is arrival -> admission alone, `budget_wait` admission -> first chunk
# planned, and the stream's end brings `cohort_wait` (and `preempted`).


def test_engine_stamps_tile_ttft_with_queue_narrowed_to_admission():
    """One slot, two requests: the second waits for the slot, and its
    ledger says so under `queue` (not admitted), not under `budget_wait`
    (admitted, no chunk planned) or `prefill`; the four TTFT stamps tile
    its measured TTFT; the last delta brings `cohort_wait`."""
    async def main():
        engine = InferenceEngine(EngineCore(EngineConfig(
            model=TINY, num_blocks=64, enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=1, block_size=BS, max_pages_per_seq=8,
                max_prefill_chunk=16, decode_buckets=(1,),
                prefill_buckets=(8, 16)))))
        await engine.start()
        client = LocalEngineClient(engine)

        async def one(rid, n_out):
            req = _req(rid, range(1, 30), max_tokens=n_out)
            led = ledger_mod.begin(req)
            t0, ttft = time.monotonic(), None
            async for d in client.generate(req):
                assert d.timings is None or d.token_ids
                if d.token_ids and ttft is None:
                    ttft = time.monotonic() - t0
            return led, ttft

        try:
            await one("warm", 6)                 # compiles out of the way
            return await asyncio.gather(one("a", 32), one("b", 3))
        finally:
            await engine.stop()

    (led_a, ttft_a), (led_b, ttft_b) = _run(main())
    for led, ttft in ((led_a, ttft_a), (led_b, ttft_b)):
        phases = [p for p, _t, _d, _a in led.stamps]
        assert phases[:4] == ["queue", "budget_wait", "prefill",
                              "first_token"], phases
        assert "cohort_wait" in phases and "preempted" not in phases
        assert phases.index("cohort_wait") > phases.index("first_token")
        covered = sum(d for p, _t, d, _a in led.stamps
                      if p in ("queue", "budget_wait", "prefill",
                               "first_token"))
        assert all(d >= 0 for _p, _t, d, _a in led.stamps)
        # Stamped on the engine thread, measured on the loop: the hops
        # between them (a loaded test host's) are what the stamps miss.
        # (no floor: how much of a few milliseconds they are is the host's)
        assert 0 < covered <= ttft + 0.005, (covered, ttft)
        # The four tile: each begins where the one before ended, on the
        # ledger's own clock, from after the request was begun to before
        # its first token was seen.
        four = led.stamps[:4]
        for (_p, t_prev, _d, _a), (_q, t, d, _b) in zip(four, four[1:]):
            assert abs((t - d) - t_prev) < 1e-6, four
        assert 0 <= four[0][1] - four[0][2] and four[-1][1] <= ttft
        attrs = next(a for p, _t, _d, a in led.stamps if p == "prefill")
        assert attrs["prompt_tokens"] == 29 and attrs["preempts"] == 0
    b = led_b.phase_totals()
    a = led_a.phase_totals()
    # b stood in the queue while a held the one slot.
    assert b["queue"] > 2 * (b["budget_wait"] + b["prefill"]), b
    assert b["queue"] > 4 * a["queue"], (a, b)


def test_timings_ride_the_first_and_the_last_delta_and_never_the_wire():
    core = _core()
    core.add_request("x", list(range(1, 20)), SamplingParams(max_tokens=5))
    deltas = []
    while core.has_work:
        deltas.extend(core.step())
    with_tokens = [d for d in deltas if d.token_ids]
    first, last = with_tokens[0], with_tokens[-1]
    assert set(first.timings) == {
        "arrival", "admitted", "prefill_start", "prefill_end",
        "first_token", "prompt_tokens", "cached_tokens", "preempts"}
    t = first.timings
    assert (t["arrival"] <= t["admitted"] <= t["prefill_start"]
            <= t["prefill_end"] <= t["first_token"] <= time.monotonic())
    assert last.finished and set(last.timings) == {
        "cohort_wait_s", "preempted_s"}
    assert last.timings["cohort_wait_s"] >= 0
    assert last.timings["preempted_s"] == 0
    assert all(d.timings is None for d in with_tokens[1:-1])
    assert "timings" not in delta_to_wire(first)
    assert delta_from_wire(delta_to_wire(first)).timings is None
    # One token in all: both halves on one delta.
    core.add_request("y", list(range(1, 20)), SamplingParams(max_tokens=1))
    only = [d for d in _drain(core) if d.token_ids]
    assert len(only) == 1 and {"first_token", "cohort_wait_s"} <= set(
        only[0].timings)
    # Ledger off, no tracer: nothing is built.
    ledger_mod.set_enabled(False)
    try:
        core.add_request("z", list(range(1, 20)),
                         SamplingParams(max_tokens=3))
        assert all(d.timings is None for d in _drain(core))
    finally:
        ledger_mod.set_enabled(True)


def _drain(core):
    out = []
    while core.has_work:
        out.extend(core.step())
    return out


def test_budget_wait_is_a_ttft_phase_and_can_be_the_dominant_one():
    """'The pool is full' (`queue`) and 'the mixed-prefill budget starves
    prompts' (`budget_wait`) are different alarms."""
    assert "budget_wait" in ledger_mod.TTFT_PHASES
    assert "cohort_wait" not in ledger_mod.TTFT_PHASES
    assert "preempted" not in ledger_mod.TTFT_PHASES
    sink = LedgerSink(MetricsRegistry())
    timings = {"arrival": 10.0, "admitted": 10.1, "prefill_start": 12.6,
               "prefill_end": 13.0, "first_token": 13.05,
               "prompt_tokens": 700, "cached_tokens": 64, "preempts": 0}
    led = RequestLedger("starved", anchor=10.0)
    LocalEngineClient._stamp_engine_timings(led, timings)
    LocalEngineClient._stamp_engine_timings(
        led, {"cohort_wait_s": 0.04, "preempted_s": 0.0})
    totals = led.phase_totals()
    assert abs(totals["queue"] - 0.1) < 1e-9
    assert abs(totals["budget_wait"] - 2.5) < 1e-9
    assert abs(totals["prefill"] - 0.4) < 1e-9
    assert abs(totals["first_token"] - 0.05) < 1e-9
    assert abs(totals["cohort_wait"] - 0.04) < 1e-9
    assert "preempted" not in totals
    assert ledger_mod.coverage_ok(led, 3.05)
    sink.fold(led, ttft=3.05, tpot=0.01, output_tokens=8)
    assert sink.dominant_phase() == "budget_wait"
    led2 = RequestLedger("shed", anchor=0.0)
    LocalEngineClient._stamp_engine_timings(
        led2, {"cohort_wait_s": 0.01, "preempted_s": 7.0})
    sink.fold(led2, ttft=0.1, tpot=0.01, output_tokens=8)
    assert sink.dominant_phase() == "preempted"
