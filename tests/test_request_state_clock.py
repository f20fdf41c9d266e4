"""The request-state clock (EngineStepCounters.request_state): where each
live request's seconds go between `add_request` and its last token, the
admission block and the prefill chances that share its file, and its sinks
on the worker's `/metrics`.

Engine-backed tests share test_phase_clock's tiny geometry (and so its
compiled programs)."""

import threading
import time
import types

import pytest

from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    BlockAllocator, Request, Scheduler, SchedulerConfig)
from dynamo_tpu.runtime import ledger, tracing
from dynamo_tpu.runtime.metrics import (
    ADMIT_BLOCKED,
    BLOCKED_PAGES,
    BLOCKED_SLOTS,
    CHANCE_DISPATCHED,
    CHANCE_DUTY_SKIPPED,
    CHANCE_NO_BUDGET,
    CHANCE_NO_WINDOW,
    PREFILL_CHANCES,
    REQUEST_STATES,
    RS_BUDGET_WAIT,
    RS_COHORT_WAIT,
    RS_DECODE,
    RS_FIRST_TOKEN,
    RS_NONE,
    RS_PREEMPTED,
    RS_PREFILL,
    RS_WAITING,
    EngineStepCounters,
)
from tests.test_phase_clock import _burst, _served, _tiny_engine


def _spy(counters):
    """Log every transition as (request id, state, clock reading)."""
    log, real = [], counters.request_state

    def request_state(req, state, now=0):
        moved = req.clock_state != state
        now = real(req, state, now)
        if moved:
            log.append((req.request_id, state, now))
        return now

    counters.request_state = request_state
    return log


def _device_clock(core, window_s=1.0, chunk_s=0.5):
    """Put `core` on an injected clock that moves as a device queue would:
    every window dispatched takes `window_s` seconds there and every
    prefill batch `chunk_s`, in the order they were dispatched, and the
    read of a window comes back when the device has finished it.  Returns
    the device's log, in that order: ("w", live rows) and ("c", tokens,
    backlog), the prompt tokens that waited when the chunk was planned."""
    dev = types.SimpleNamespace(t=0.0, now=0.0, done=[], seen=0, log=[])
    sync, prefill = core._sync_one_window, core._run_prefill_batch

    def windows_so_far():
        while dev.seen < core.counters.window_dispatches:
            dev.seen += 1
            dev.t += window_s
            dev.done.append(dev.t)
            dev.log.append(("w", len(core._inflight[-1]["rows"])))

    def sync_one_window():
        windows_so_far()
        dev.now = max(dev.now, dev.done.pop(0))
        return sync()

    def run_prefill_batch(batch, **kw):
        windows_so_far()
        dev.t += chunk_s
        dev.log.append((
            "c", sum(w.length for w in batch.items),
            sum(len(r.prompt_tokens) - r.prefilled
                for r in core.scheduler.running + core.scheduler.waiting
                if r.state.value in ("waiting", "prefill"))))
        return prefill(batch, **kw)

    core._sync_one_window = sync_one_window
    core._run_prefill_batch = run_prefill_batch
    core._clock = lambda: dev.now
    return dev.log


def _mixed_burst(core):
    """Chunked prefill (prompts over the 128-token chunk), a prefix-cache
    hit (a prompt served twice), a capacity preemption (the pool holds 23
    pages and the two long generations outgrow it) and a cancel; Σ
    `req_state_n` against the scheduler's live requests after every
    `step()`.  Returns the requests by id."""
    c, reqs = core.counters, {}

    def add(rid, prompt, n):
        core.add_request(rid, prompt, SamplingParams(max_tokens=n))
        reqs[rid] = core._requests[rid]

    def step():
        core.step()
        assert sum(c.req_state_n) == core.scheduler.num_active, (
            c.req_state_n, core.scheduler.num_active)
        assert all(n >= 0 for n in c.req_state_n)

    shared = list(range(1, 41))
    add("first", shared, 3)
    while core.has_work:
        step()
    add("hit", shared, 3)                       # every whole block cached
    add("long-a", [7] * 60, 60)
    add("long-b", [9] * 50, 60)
    add("doomed", list(range(50, 80)), 40)
    for _ in range(6):
        step()
    core.cancel("doomed")
    for _ in range(4000):
        if not core.has_work:
            break
        step()
    assert not core.has_work
    assert sum(c.req_state_n) == 0
    return reqs


def test_states_are_exclusive_and_tile_every_request_from_arrival_to_finish():
    core = _tiny_engine(enable_prefix_cache=True, num_blocks=24)
    c = core.counters
    log = _spy(c)
    reqs = _mixed_burst(core)
    assert reqs["long-a"].preempts + reqs["long-b"].preempts >= 1
    assert reqs["hit"].cached_prompt_tokens >= 32
    assert c.req_state_entries[RS_PREEMPTED] >= 1
    # (b) each request's intervals tile arrival -> finish: consecutive
    # transitions of one request, by state, are its `state_ns`, and the
    # requests' shares sum to the clock's integral exactly.
    total = [0] * len(REQUEST_STATES)
    for rid, req in reqs.items():
        mine = [(s, t) for r, s, t in log if r == rid]
        assert mine[0][0] == RS_WAITING and mine[-1][0] == RS_NONE, mine
        assert mine[0][1] == req.state_entry_ns[RS_WAITING]
        by_state = [0] * len(REQUEST_STATES)
        for (s, t), (_s, t_next) in zip(mine, mine[1:]):
            assert t_next >= t
            by_state[s] += t_next - t
        assert by_state == req.state_ns, (rid, by_state, req.state_ns)
        assert sum(req.state_ns) == mine[-1][1] - mine[0][1]
        assert req.clock_state == RS_NONE
        total = [a + b for a, b in zip(total, req.state_ns)]
    assert total == c.req_state_ns
    # What each kind of request passed through.
    states = {rid: [s for r, s, _t in log if r == rid] for rid in reqs}
    assert states["first"] == [RS_WAITING, RS_BUDGET_WAIT, RS_PREFILL,
                               RS_FIRST_TOKEN, RS_COHORT_WAIT, RS_DECODE,
                               RS_NONE]
    # The prefix hit prefills its last token alone (a causal admission
    # always owes the logits of one).
    assert states["hit"][:4] == [RS_WAITING, RS_BUDGET_WAIT, RS_PREFILL,
                                 RS_FIRST_TOKEN]
    victim = "long-a" if reqs["long-a"].preempts else "long-b"
    tail = states[victim][states[victim].index(RS_PREEMPTED):]
    assert tail[:2] == [RS_PREEMPTED, RS_DECODE]     # requeue, re-prefill
    assert RS_NONE in states["doomed"]                # and re-admission inside
    assert c.request_first_tokens == sum(
        1 for r in reqs.values() if r.first_token_ns)
    assert c.request_output_tokens >= 3 + 3 + 60 + 60


def test_scraped_seconds_sum_to_the_integral_of_live_requests():
    """Between any two reads, from another thread, ΣΔseconds over states is
    ∫ live requests dt: here over a served burst cut by reads at instants
    of their own, against the integral the transition log gives."""
    async def run(engine, core):
        c = core.counters
        log = _spy(c)
        await _burst(engine, "warm", n_requests=2, max_tokens=4)
        t0, s0 = time.perf_counter_ns(), c.request_state_seconds()[0]
        import asyncio

        burst = asyncio.ensure_future(_burst(engine, "run"))
        reads = []
        while not burst.done():
            await asyncio.sleep(0.01)
            s, t = c.request_state_seconds()[0], time.perf_counter_ns()
            reads.append((t, s))
        assert await burst == 4 * 24
        return log, t0, s0, reads

    log, t0, s0, reads = _served(run)
    assert len(reads) >= 3

    def live_integral(a, b):
        """∫ live requests dt over [a, b] from the log's arrivals and
        departures."""
        total, live, last = 0, 0, a
        events = []
        for _rid, state, t in log:
            if state == RS_WAITING:
                events.append((t, 1))
            elif state == RS_NONE:
                events.append((t, -1))
        for t, d in sorted(events):
            if t <= a:
                live += d
                continue
            if t >= b:
                break
            total += live * (t - last)
            live, last = live + d, t
        return (total + live * (b - last)) / 1e9

    prev_t, prev_s = t0, s0
    for t, s in reads:
        d = {k: s[k] - prev_s[k] for k in REQUEST_STATES}
        assert all(v >= -1e-9 for v in d.values()), d
        # The two clock reads of one scrape are not one instant: a
        # millisecond covers the few requests live between them.
        assert sum(d.values()) == pytest.approx(
            live_integral(prev_t, t), abs=1e-3)
        prev_t, prev_s = t, s


def test_a_scrape_inside_a_transition_counts_no_second_twice():
    """A read that lands after the clock has charged the closed interval
    but before the transition is whole must not add that interval again as
    the open part: it waits the transition out (test_phase_clock's
    method)."""
    c = EngineStepCounters()
    req = Request("r", [1], SamplingParams())
    mid, read = threading.Event(), {}

    class StallsAfterTheAdd(list):
        def __setitem__(self, i, v):
            super().__setitem__(i, v)
            mid.set()
            time.sleep(0.005)            # the scrape runs into this

    def scrape():
        mid.wait(5)
        read["secs"] = c.request_state_seconds()[0]
        read["at"] = time.perf_counter_ns()

    start = c.request_state(req, RS_WAITING)
    c.req_state_ns = StallsAfterTheAdd(c.req_state_ns)
    scraper = threading.Thread(target=scrape)
    scraper.start()
    time.sleep(0.05)                     # 50 ms in the queue
    c.request_state(req, RS_BUDGET_WAIT)
    scraper.join(5)
    wall = (read["at"] - start) / 1e9
    assert read["secs"]["waiting"] == pytest.approx(0.05, abs=0.02)
    assert wall - 0.002 <= sum(read["secs"].values()) <= wall


def test_block_diffusion_enters_decode_at_its_first_block_and_never_waits():
    from dynamo_tpu.models import config as mcfg

    core = _tiny_engine(
        model=mcfg.get_config("tiny-sdar"), decode_window=1,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=32,
            max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 128)))
    c = core.counters
    log = _spy(c)
    block_calls = []
    real = core._run_block_decode

    def run_block_decode(rows):
        before = len(log)
        call = real(rows)
        block_calls.append([(r, s) for r, s, _t in log[before:]])
        return call

    core._run_block_decode = run_block_decode
    for i in range(3):
        core.add_request(f"r{i}", [3 + i] * (9 + 8 * i),
                         SamplingParams(max_tokens=12))
    # A prompt shorter than a block owes no prefill at all.
    core.add_request("short", [5, 6, 7], SamplingParams(max_tokens=12))
    short = core._requests["short"]
    while core.has_work:
        core.step()
        assert sum(c.req_state_n) == core.scheduler.num_active
    assert c.req_state_entries[RS_COHORT_WAIT] == 0
    assert c.req_state_ns[RS_COHORT_WAIT] == 0
    assert c.req_state_entries[RS_DECODE] == 4
    assert c.request_first_tokens == 4
    assert c.request_output_tokens == 4 * 12
    # `decode` begins inside a block dispatch and nowhere else, straight
    # from `first_token`; the first token comes with that call's read.
    marked = [m for call in block_calls for m in call]
    assert sorted(marked) == [(f"r{i}", RS_DECODE) for i in range(3)] + [
        ("short", RS_DECODE)]
    for i in range(3):
        states = [s for r, s, _t in log if r == f"r{i}"]
        assert states == [RS_WAITING, RS_BUDGET_WAIT, RS_PREFILL,
                          RS_FIRST_TOKEN, RS_DECODE, RS_NONE], states
    # A state passed through in no time is entered and left at one clock
    # reading: the entry counts, the seconds do not.
    mine = [(s, t) for r, s, t in log if r == "short"]
    assert [s for s, _t in mine][:4] == [RS_WAITING, RS_BUDGET_WAIT,
                                         RS_PREFILL, RS_FIRST_TOKEN]
    assert mine[1][1] == mine[2][1] == mine[3][1]
    assert short.state_ns[RS_BUDGET_WAIT] == short.state_ns[RS_PREFILL] == 0
    assert short.state_ns[RS_FIRST_TOKEN] > 0
    assert c.req_state_entries[RS_PREFILL] == 4
    # The first token is appended at the read, inside `decode`.
    assert short.first_token_ns > short.state_entry_ns[RS_DECODE]


def test_counter_deltas_identical_with_ledger_and_tracer_on_and_off():
    """The clock is in neither's guard and moves no integer counter with
    them: `to_dict()` deltas over a steady decode are the same, and the
    clock is not among its keys."""
    def steady_run(on: bool):
        ledger.set_enabled(on)
        tracer = tracing.get_tracer()
        was = tracer.enabled
        tracer.configure(enabled=on, sampling=1.0)
        try:
            core = _tiny_engine()
            core.add_request("a", list(range(1, 71)),
                             SamplingParams(max_tokens=64))
            for _ in range(8):
                core.step()
            base = core.counters.snapshot()
            clock0 = list(core.counters.req_state_entries)
            for _ in range(20):
                core.step()
            return (core.counters.delta(base), clock0,
                    list(core.counters.req_state_entries),
                    core.counters.phase_entries)
        finally:
            tracer.configure(enabled=was)
            ledger.set_enabled(True)

    d_off, before_off, after_off, phases_off = steady_run(False)
    d_on, before_on, after_on, phases_on = steady_run(True)
    assert d_on == d_off, (d_on, d_off)
    assert d_on["window_dispatches"] == 20
    assert not any("state" in k or "chance" in k or "blocked" in k
                   for k in d_on)
    # Steady decode: no transition at all, on or off, and no phase entry
    # that the parent's loop did not have.
    assert before_on == after_on == before_off == after_off
    assert before_on[RS_DECODE] == 1
    assert phases_on == phases_off


def _bare_scheduler(num_blocks, max_seqs, **kw):
    cfg = SchedulerConfig(max_seqs=max_seqs, block_size=8,
                          max_pages_per_seq=32, max_prefill_chunk=128,
                          decode_buckets=(1, 2, 4, 8),
                          prefill_buckets=(16, 128), **kw)
    return Scheduler(cfg, BlockAllocator(num_blocks))


@pytest.mark.parametrize("limit", ["pages", "slots", "held"])
def test_admit_blocked_charges_the_reason_the_queue_stands_for(limit):
    if limit == "pages":
        sched = _bare_scheduler(num_blocks=9, max_seqs=8)
        prompts = [[1] * 40, [2] * 40]         # 6 pages each of 8 usable
    elif limit == "slots":
        sched = _bare_scheduler(num_blocks=64, max_seqs=1)
        prompts = [[1] * 10, [2] * 10]
    else:
        sched = _bare_scheduler(num_blocks=64, max_seqs=8)
        sched.qos_pressure_fn = lambda: 2.0     # the budget burns
        prompts = [[1] * 10]
    c = sched.counters
    for i, p in enumerate(prompts):
        req = Request(f"r{i}", p, SamplingParams(max_tokens=4))
        if limit == "held":
            req.priority = 0
        sched.add_request(req)
    sched.plan()
    assert len(sched.waiting) == 1
    time.sleep(0.03)
    secs = c.request_state_seconds()[1]
    assert set(secs) == set(ADMIT_BLOCKED)
    assert secs[limit] >= 0.03
    assert sum(secs.values()) == secs[limit]
    # The one that runs finishes: the queue empties, nothing blocks, and
    # the seconds stop.
    if limit == "held":
        sched.qos_pressure_fn = None
    else:
        sched.finish(sched.running[0], None)
    sched.plan()
    assert not sched.waiting
    assert c._admit_blocked == RS_NONE
    stood = c.request_state_seconds()[1][limit]
    time.sleep(0.01)
    assert c.request_state_seconds()[1][limit] == stood
    assert (BLOCKED_PAGES, BLOCKED_SLOTS) == (1, 0)


def test_each_prefill_chance_outcome_occurs_where_the_step_loop_says():
    """`dispatched` with the chunk; `duty_skipped` where a window went out
    and the rule of mixed prefill passed the planned chunk over (the
    credit did not cover it yet); `no_budget` where decode work went out
    and the plan held no chunk; `no_window` where nothing was dispatched
    at all."""
    core = _tiny_engine()
    _device_clock(core, window_s=1.0, chunk_s=0.5)
    c = core.counters

    def stepped():
        before = list(c.prefill_chances)
        prefills, windows = c.prefill_dispatches, c.window_dispatches
        core.step()
        d = [a - b for a, b in zip(c.prefill_chances, before)]
        assert sum(d) <= 1
        return (PREFILL_CHANCES[d.index(1)] if 1 in d else None,
                c.prefill_dispatches - prefills,
                c.window_dispatches - windows)

    core.add_request("a", list(range(1, 41)), SamplingParams(max_tokens=96))
    assert stepped() == ("dispatched", 1, 0)
    seen = []
    for _ in range(6):
        seen.append(stepped())
    assert all(s[0] is None for s in seen)       # no backlog: no chance
    assert c.window_s == 1.0                     # plain windows measured
    # A long prompt arrives behind the decoding row: a chunk of half a
    # window's seconds rides when 15 / 85 of the windows' seconds cover it.
    core.add_request("b", [5] * 250, SamplingParams(max_tokens=4))
    outcomes = []
    while core._requests["b"].clock_state in (
            RS_WAITING, RS_BUDGET_WAIT, RS_PREFILL):
        outcomes.append(stepped())
    kinds = [o[0] for o in outcomes]
    assert "duty_skipped" in kinds and "dispatched" in kinds
    for kind, prefills, windows in outcomes:
        assert (kind == "dispatched") == (prefills > 0)
        if kind == "duty_skipped":
            assert windows == 1 and prefills == 0
    stepped()
    assert c.chunk_s == {128: 0.5}               # read two windows on
    # No budget: the scheduler is handed a zero budget while rows decode.
    core.add_request("c", [6] * 100, SamplingParams(max_tokens=4))
    lifted = core.scheduler.mixed_budget_override
    core.scheduler.mixed_budget_override = 0
    kind, prefills, windows = stepped()
    assert (kind, prefills) == ("no_budget", 0) and windows >= 1
    core.scheduler.mixed_budget_override = lifted
    while core.has_work:
        core.step()
    # Nothing dispatched at all: the plan comes back empty with a request
    # admitted and unplanned.
    idle = _tiny_engine()
    req = Request("z", [1] * 20, SamplingParams(max_tokens=4))
    idle._requests["z"] = req
    idle.scheduler.add_request(req)
    idle.scheduler.config = idle.scheduler.config.__class__(
        **dict(idle.scheduler.config.__dict__, max_batched_tokens=0))
    before = list(idle.counters.prefill_chances)
    idle.step()
    d = [a - b for a, b in zip(idle.counters.prefill_chances, before)]
    assert d[CHANCE_NO_WINDOW] == 1 and sum(d) == 1
    assert (CHANCE_DISPATCHED, CHANCE_DUTY_SKIPPED, CHANCE_NO_BUDGET) == (
        0, 1, 2)


@pytest.mark.parametrize("chunk_s", [1.0, 0.5, 3.0])
def test_chunks_keep_to_their_share_of_a_cohort_that_stays(chunk_s):
    """The bound, in the shape of `long-context` (one row decodes, chunks
    about as long as a window, a backlog that does not end): over any 50
    consecutive windows the chunks' seconds are at most 15 / 85 of the
    windows' seconds plus one chunk, and no two chunks stand between two
    consecutive windows."""
    core = _tiny_engine(scheduler=SchedulerConfig(
        max_seqs=8, block_size=16, max_pages_per_seq=512,
        max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
        prefill_buckets=(16, 128)), num_blocks=1100)
    log = _device_clock(core, window_s=1.0, chunk_s=chunk_s)
    core.add_request("row", list(range(1, 9)), SamplingParams(max_tokens=400))
    for _ in range(8):
        core.step()
    assert core.counters.window_s == 1.0
    del log[:]
    for i in range(2):
        core.add_request(f"long{i}", [7] * 8000, SamplingParams(max_tokens=2))
    while sum(1 for e in log if e[0] == "w") < 100:
        core.step()
    assert all(e == ("w", 1) for e in log if e[0] == "w")   # it stayed
    kinds = "".join(e[0] for e in log)
    assert "cc" not in kinds and kinds.count("c") >= 5
    share = (1 - 0.85) / 0.85
    windows = [i for i, k in enumerate(kinds) if k == "w"]
    for first, last in zip(windows, windows[49:]):
        chunks = kinds[first:last + 1].count("c")
        assert chunks * chunk_s <= 50 * 1.0 * share + chunk_s + 1e-9
    # ... and they get that share: the rule does not starve a backlog.
    assert kinds.count("c") * chunk_s >= 0.8 * len(windows) * share - chunk_s
    line = [ln for ln in core.counters.request_state_metrics_lines()
            if ln.startswith("dynamo_worker_prefill_chunk_seconds_share ")]
    assert len(line) == 1 and 0.05 < float(line[0].split()[1]) <= 0.16


def test_clock_series_in_prometheus_text_and_snapshot_copies_them():
    core = _tiny_engine()
    core.add_request("a", list(range(1, 30)), SamplingParams(max_tokens=9))
    while core.has_work:
        core.step()
    c = core.counters
    lines = c.request_state_metrics_lines()
    page = dict(ln.rsplit(" ", 1) for ln in lines)
    for s in REQUEST_STATES:
        assert f'dynamo_worker_request_state_seconds_total{{state="{s}"}}' \
            in page
        assert f'dynamo_worker_request_state_entries_total{{state="{s}"}}' \
            in page
    for r in ADMIT_BLOCKED:
        assert f'dynamo_worker_admit_blocked_seconds_total{{reason="{r}"}}' \
            in page
    for o in PREFILL_CHANCES:
        assert f'dynamo_worker_prefill_chances_total{{outcome="{o}"}}' \
            in page
    assert page["dynamo_worker_request_first_tokens_total"] == "1"
    assert page["dynamo_worker_request_output_tokens_total"] == "9"
    assert float(page[
        'dynamo_worker_request_state_seconds_total{state="decode"}']) > 0
    assert page["dynamo_worker_prefill_chunk_seconds_share"] == "0.000000"
    assert not any("mixed_prefill" in name for name in page)
    for v in page.values():
        float(v)
    snap = c.snapshot()
    assert snap.req_state_ns == c.req_state_ns
    assert snap.req_state_ns is not c.req_state_ns
    assert snap.prefill_chances is not c.prefill_chances
    assert snap.chunk_s == c.chunk_s and snap.chunk_s is not c.chunk_s
    assert "req_state_ns" not in c.to_dict()
