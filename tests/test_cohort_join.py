"""A finished prompt joins the decode cohort at the first dispatch after its
last chunk (EngineCore.step / _window_work): where the joining rows would
force a merge as soon as their first token settled, the window pipeline
holds at the completing chunk, reads what is in flight, waits for the token
and dispatches the merged cohort; a large cohort with prompts still queued
keeps its windows going and batches the rows.  `cohort_joins` says which.

Engine-backed tests share test_phase_clock's tiny geometry (and so its
compiled programs); windows of 2 tokens, 2 in flight."""

import types

import pytest

from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import RequestState, SchedulerConfig
from dynamo_tpu.runtime import metrics
from dynamo_tpu.runtime.metrics import (
    COHORT_JOINS,
    JOIN_AT_CHUNK,
    JOIN_AT_SETTLE,
    RS_COHORT_WAIT,
    RS_DECODE,
    RS_FIRST_TOKEN,
)
from tests.test_phase_clock import _tiny_engine
from tests.test_request_state_clock import _spy


class _NotDoneYet:
    """A first-token fetch as the chip gives it: not done when the next
    iteration looks (`done()` False), there for the one that waits."""

    def __init__(self, fut):
        self._fut = fut

    def done(self):
        return False

    def result(self):
        return self._fut.result()


def _mixed_engine(slow_first_token=True, **kw):
    """Window mode with a prefill chunk behind every window, sized by the
    scheduler's static cap: these tests are about what follows a chunk,
    not about when one may ride (`EngineCore._chunk_rides`)."""
    core = _tiny_engine(**kw)
    core._chunk_rides = lambda batch, windows=1.0: batch is not None
    core.scheduler.mixed_budget_override = None
    if slow_first_token:
        real = core._sample_rows

        def sample_rows(logits, reqs, async_fetch=False):
            out = real(logits, reqs, async_fetch=async_fetch)
            return _NotDoneYet(out) if async_fetch else out

        core._sample_rows = sample_rows
    core.window_log = []              # the row set of every window dispatched
    real_dispatch = core._dispatch_window

    def dispatch_window(work):
        out = real_dispatch(work)
        if out is not None:
            core.window_log.append([r.request_id for r in work.requests])
        return out

    core._dispatch_window = dispatch_window
    return core


def _collect(deltas, outputs, finished):
    for d in deltas:
        outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if d.finished:
            finished.setdefault(d.request_id, []).append(d.finish_reason)


def _decoding_alone(core, rid="a", prompt=None, max_tokens=64, sampling=None,
                    outputs=None, finished=None):
    """`rid` decodes in windows with the pipeline full."""
    core.add_request(rid, prompt or list(range(1, 41)),
                     sampling or SamplingParams(max_tokens=max_tokens))
    for _ in range(4):
        deltas = core.step()
        if outputs is not None:
            _collect(deltas, outputs, finished)
    assert len(core._inflight) == 2


def _step_to_completing_chunk(core, rid, outputs=None, finished=None):
    """Step until the chunk that completes `rid`'s prompt has gone out;
    returns that step's (windows, prefills) dispatched."""
    c = core.counters
    req = core._requests[rid]
    for _ in range(50):
        w0, p0 = c.window_dispatches, c.prefill_dispatches
        deltas = core.step()
        if outputs is not None:
            _collect(deltas, outputs, finished)
        if req.state is RequestState.DECODE:
            return c.window_dispatches - w0, c.prefill_dispatches - p0
    raise AssertionError("prompt never completed")


def test_a_row_whose_last_chunk_rides_behind_a_window_joins_at_the_next_dispatch():  # noqa: E501
    core = _mixed_engine()
    c = core.counters
    _decoding_alone(core)
    assert c.cohort_joins == [1, 0]            # `a` itself: prefill, window
    core.add_request("b", [5] * 100, SamplingParams(max_tokens=16))
    b = core._requests["b"]
    windows, prefills = _step_to_completing_chunk(core, "b")
    # The completing chunk went out behind a window of the old cohort, its
    # first token is in flight and the host has not read it.
    assert (windows, prefills) == (1, 1)
    assert core.window_log[-1] == ["a"]
    assert "b" in core._pending_first and not b.output_tokens
    assert b.clock_state == RS_FIRST_TOKEN
    in_flight = len(core._inflight)
    assert in_flight == 2
    at_chunk = (c.window_dispatches, c.window_syncs, c.host_syncs)
    deltas = core.step()
    # ONE iteration: the windows in flight are read (their tokens handed
    # out), the token is waited for (one counted sync), and the next
    # window dispatched is the merged cohort's: none of the old cohort's
    # stands between the chunk and it.
    assert c.window_dispatches - at_chunk[0] == 1
    assert core.window_log[-1] == ["a", "b"]
    assert c.window_syncs - at_chunk[1] == in_flight
    assert c.host_syncs - at_chunk[2] == in_flight + 1
    assert len(core._inflight) == 1 and not core._pending_first
    assert b.clock_state == RS_DECODE and len(b.output_tokens) == 1
    assert b.decode_dispatches_at_prefill == at_chunk[0]
    got = {}
    _collect(deltas, got, {})
    assert len(got["a"]) == 2 * in_flight and len(got["b"]) == 1
    assert c.cohort_joins == [2, 0]
    # No window ever held `a` alone again, and none was compiled anew for
    # the join (the merged window is a warmed (rows, pages) shape on the
    # chip; here: nothing but windows of 1 and 2 rows ran).
    assert ["a"] not in core.window_log[core.window_log.index(["a", "b"]):]
    while core.has_work:
        core.step()
    assert c.cohort_joins == [2, 0]


def _streams(decode_window, sampling_a, sampling_b):
    core = _mixed_engine(decode_window=decode_window)
    outputs, finished = {}, {}
    core.add_request("a", list(range(1, 41)), sampling_a)
    for _ in range(4):
        _collect(core.step(), outputs, finished)
    core.add_request("b", [5] * 100, sampling_b)
    core.add_request("c", [9] * 30, sampling_b)
    for _ in range(2000):
        if not core.has_work:
            break
        _collect(core.step(), outputs, finished)
    assert not core.has_work
    return core, outputs, finished


@pytest.mark.parametrize("sampling", [
    (SamplingParams(max_tokens=40), SamplingParams(max_tokens=21)),
    (SamplingParams(max_tokens=40, temperature=0.8, seed=11),
     SamplingParams(max_tokens=21, temperature=0.9, top_k=20, seed=7)),
], ids=["greedy", "seeded"])
def test_streams_through_a_join_equal_the_single_step_engines(sampling):
    """Old and new rows get the tokens a step-by-step engine gives them:
    the merged window is built from host bookkeeping after a drain."""
    held, out, fin = _streams(2, *sampling)
    stepped, want, want_fin = _streams(1, *sampling)
    assert out == want and fin == want_fin
    assert [len(out[r]) for r in "abc"] == [40, 21, 21]
    # The rows did join at their chunks, through holds; the step-by-step
    # engine has no cohort to join.
    assert held.counters.cohort_joins == [3, 0]
    assert stepped.counters.cohort_joins == [0, 0]
    assert any(set(w) >= {"a", "b"} for w in held.window_log)


def test_a_large_cohort_with_a_backlog_batches_the_row_and_keeps_its_windows():
    core = _mixed_engine(scheduler=SchedulerConfig(
        max_seqs=16, block_size=8, max_pages_per_seq=32,
        max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8, 16),
        prefill_buckets=(16, 128)))
    c = core.counters
    cohort = [f"r{i}" for i in range(8)]
    for i, rid in enumerate(cohort):
        core.add_request(rid, [3 + i] * (9 + i), SamplingParams(max_tokens=64))
    for _ in range(4):
        core.step()
    assert core.window_log[-1] == cohort and c.cohort_joins == [8, 0]
    # One short prompt and one that takes several chunks behind it: while
    # the long one is a backlog, one row is under the quarter of 8.
    core.add_request("short", [5] * 20, SamplingParams(max_tokens=12))
    core.add_request("long", [6] * 200, SamplingParams(max_tokens=12))
    _step_to_completing_chunk(core, "short")
    long_req = core._requests["long"]
    assert long_req.state is RequestState.PREFILL
    assert "short" in core._pending_first
    at_chunk = c.window_dispatches
    while long_req.state is RequestState.PREFILL:
        core.step()
        # Windows of the old cohort keep going, none of them drained for
        # the one row.
        assert core.window_log[-1] == cohort
        assert len(core._inflight) == 2
    between = c.window_dispatches - at_chunk
    assert between >= 2
    assert c.cohort_joins == [8, 0]
    # The long prompt's last chunk makes two joining rows and leaves no
    # backlog: the pipeline holds, and both rows merge at one drain.
    assert "long" in core._pending_first
    core.step()
    assert core.window_log[-1] == cohort + ["short", "long"]
    assert c.cohort_joins == [9, 1]            # `long` at its chunk
    assert c.window_dispatches - at_chunk == between + 1


@pytest.mark.parametrize("ends_by", ["max_tokens", "stop_token"])
def test_a_first_token_that_ends_its_request_through_a_hold(ends_by):
    if ends_by == "stop_token":
        # What the prompt's first token is, from an engine of its own.
        probe = _mixed_engine()
        probe.add_request("b", [5] * 100, SamplingParams(max_tokens=1))
        first = [t for d in probe.step() for t in d.token_ids]
        while probe.has_work:
            probe.step()
        sampling = SamplingParams(max_tokens=16, stop_token_ids=first)
    else:
        sampling = SamplingParams(max_tokens=1)
    core = _mixed_engine()
    c = core.counters
    outputs, finished = {}, {}
    _decoding_alone(core, outputs=outputs, finished=finished)
    free = core.scheduler.allocator.free_blocks
    held_by_a = len(core._requests["a"].pages)
    core.add_request("b", [5] * 100, sampling)
    _step_to_completing_chunk(core, "b", outputs, finished)
    assert "b" in core._pending_first
    at_chunk = c.window_dispatches
    _collect(core.step(), outputs, finished)
    # The hold read the token, the token ended the request: one finished
    # delta with the one token, no decode dispatch ever held the row, and
    # the cohort goes on as it was.
    assert len(outputs["b"]) == 1 and len(finished["b"]) == 1
    assert finished["b"][0].value == (
        "stop" if ends_by == "stop_token" else "length")
    assert not core.has_request("b") and not core._pending_first
    assert c.window_dispatches - at_chunk == 1
    assert core.window_log[-1] == ["a"]
    assert all("b" not in w for w in core.window_log)
    assert c.cohort_joins == [1, 0]
    grown = len(core._requests["a"].pages) - held_by_a
    assert core.scheduler.allocator.free_blocks == free - grown
    while core.has_work:
        _collect(core.step(), outputs, finished)
    assert len(outputs["a"]) == 64 and len(finished["b"]) == 1
    assert c.req_state_entries[RS_DECODE] == 1


def test_the_request_state_clock_through_a_hold(monkeypatch):
    """`cohort_wait` begins when the token is appended, after the windows
    in flight were read, and ends at the merged dispatch: on a clock that
    moves a second with every window read and a nanosecond otherwise, the
    row's wait holds no window's time."""
    now = [1_000]

    def perf_counter_ns():
        now[0] += 1
        return now[0]

    monkeypatch.setattr(metrics, "time", types.SimpleNamespace(
        perf_counter_ns=perf_counter_ns, sleep=metrics.time.sleep,
        monotonic=metrics.time.monotonic,
        perf_counter=metrics.time.perf_counter, time=metrics.time.time))
    core = _mixed_engine()
    c = core.counters
    real_sync = core._sync_one_window

    def sync_one_window():
        now[0] += 1_000_000_000              # a window's time on the device
        return real_sync()

    core._sync_one_window = sync_one_window
    log = _spy(c)
    _decoding_alone(core)
    core.add_request("b", [5] * 100, SamplingParams(max_tokens=16))
    b = core._requests["b"]
    _step_to_completing_chunk(core, "b")
    entries = list(c.req_state_entries)
    syncs = c.window_syncs
    core.step()
    assert c.window_syncs - syncs == 2       # two windows' seconds passed
    mine = [s for r, s, _t in log if r == "b"]
    assert mine[-3:] == [RS_FIRST_TOKEN, RS_COHORT_WAIT, RS_DECODE]
    assert c.req_state_entries[RS_COHORT_WAIT] - entries[RS_COHORT_WAIT] == 1
    assert c.req_state_entries[RS_DECODE] - entries[RS_DECODE] == 1
    # The drain's seconds were waited in `first_token` (the chunk ran
    # behind those windows); the wait for the cohort is the host's
    # re-plan and rebuild, here a few clock readings.
    assert b.state_ns[RS_FIRST_TOKEN] >= 2_000_000_000
    assert 0 < b.state_ns[RS_COHORT_WAIT] < 1_000
    while core.has_work:
        core.step()
    assert mine.count(RS_COHORT_WAIT) == mine.count(RS_DECODE) == 1
    assert b.state_ns[RS_COHORT_WAIT] < 1_000
    assert c.req_state_ns[RS_COHORT_WAIT] < 2_000      # `a`'s and `b`'s


def test_a_drain_hands_each_window_over_as_it_is_read():
    """With a serving loop attached the hold's drain does not keep the old
    rows' tokens to the iteration's end: every window read goes out before
    the next is waited for, in order, and step() returns the rest (here the
    joining row's first token, read after the drain)."""
    core = _mixed_engine()
    c = core.counters
    outputs, handed = {}, []                  # handed: (window syncs, ids)

    def deliver(deltas):
        handed.append((c.window_syncs, [d.request_id for d in deltas]))
        _collect(deltas, outputs, {})

    serve = core.step
    core.step = lambda: serve(deliver)        # as InferenceEngine's loop does
    _decoding_alone(core, outputs=outputs, finished={})
    core.add_request("b", [5] * 100, SamplingParams(max_tokens=16))
    _step_to_completing_chunk(core, "b", outputs, {})
    assert handed == [] and len(core._inflight) == 2    # no drain yet
    syncs = c.window_syncs
    rest = core.step()
    assert handed == [(syncs + 1, ["a", "a"]), (syncs + 2, ["a", "a"])]
    assert [(d.request_id, len(d.token_ids)) for d in rest] == [("b", 1)]
    _collect(rest, outputs, {})
    while core.has_work:
        _collect(core.step(), outputs, {})
    # The same tokens in the same order as an engine nobody listens to.
    quiet, want = _mixed_engine(), {}
    _decoding_alone(quiet, outputs=want, finished={})
    quiet.add_request("b", [5] * 100, SamplingParams(max_tokens=16))
    while quiet.has_work:
        _collect(quiet.step(), want, {})
    assert outputs == want


def test_a_join_after_a_single_step_of_the_old_cohort_counts_at_the_settle():
    core = _mixed_engine(slow_first_token=False)
    c = core.counters
    # `a` has one token left after its first: under half a window, so the
    # iteration that prefills `b` runs `a` through the single-step path.
    core.add_request("a", list(range(1, 11)), SamplingParams(max_tokens=2))
    core.step()
    core.add_request("b", [5] * 20, SamplingParams(max_tokens=20))
    core.step()
    assert c.single_step_dispatches == 1 and c.window_dispatches == 0
    assert core._requests["b"].decode_dispatches_at_prefill == 0
    core.step()
    assert core.window_log == [["b"]]
    assert c.cohort_joins == [0, 1]
    while core.has_work:
        core.step()


def test_the_joins_on_the_metrics_page_and_in_a_snapshot():
    core = _mixed_engine(slow_first_token=False)
    _decoding_alone(core, max_tokens=9)
    while core.has_work:
        core.step()
    c = core.counters
    page = dict(ln.rsplit(" ", 1) for ln in c.request_state_metrics_lines())
    assert COHORT_JOINS == ("chunk", "settle")
    assert (JOIN_AT_CHUNK, JOIN_AT_SETTLE) == (0, 1)
    assert page['dynamo_worker_cohort_joins_total{at="chunk"}'] == "1"
    assert page['dynamo_worker_cohort_joins_total{at="settle"}'] == "0"
    snap = c.snapshot()
    assert snap.cohort_joins == c.cohort_joins
    assert snap.cohort_joins is not c.cohort_joins
    assert "cohort_joins" not in c.to_dict()
    assert c.decode_dispatches == (c.window_dispatches + c.spec_dispatches
                                   + c.single_step_dispatches)
