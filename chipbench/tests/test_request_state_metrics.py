"""The layer metrics that read the engine's request-state clock, the tallies
beside it and the two TTFT histograms: each reader over a hand-made pair of
scrapes with known seconds, entries and tokens, and None on a program that
has none of the series (the parent of the PR that added them), so that its
result line leaves the metric out."""

import json
import os
import types

import pytest

from chipbench import request_readers, run

SECONDS = 'dynamo_worker_request_state_seconds_total{state="%s"}'
ENTRIES = 'dynamo_worker_request_state_entries_total{state="%s"}'
BLOCKED = 'dynamo_worker_admit_blocked_seconds_total{reason="%s"}'
CHANCES = 'dynamo_worker_prefill_chances_total{outcome="%s"}'
STATES = ("waiting", "budget_wait", "prefill", "first_token", "cohort_wait",
          "decode", "preempted")
# Over a 40 s window 50 requests arrive; request-seconds by state:
STEP_S = {"waiting": 0.5, "budget_wait": 25.0, "prefill": 100.0,
          "first_token": 1.5, "cohort_wait": 3.0, "decode": 56.0,
          "preempted": 1.0}
STEP_N = {"waiting": 50, "budget_wait": 50, "prefill": 50, "first_token": 50,
          "cohort_wait": 40, "decode": 42, "preempted": 2}


def _worker(k: float, t: float, causal: bool = True) -> dict:
    """The worker's page after `k` windows' worth of traffic."""
    page = {"_t": t}
    for s in STATES:
        wait = s == "cohort_wait" and not causal
        page[SECONDS % s] = 7.0 + (0.0 if wait else k * STEP_S[s])
        page[ENTRIES % s] = 3 + (0 if wait else k * STEP_N[s])
    page["dynamo_worker_request_first_tokens_total"] = 10 + k * 48
    page["dynamo_worker_request_output_tokens_total"] = 100 + k * 5048
    page.update({BLOCKED % "slots": 1.0 + k * 0.8, BLOCKED % "pages": k * 0.4,
                 BLOCKED % "held": 0.0})
    page.update({CHANCES % "dispatched": 20 + k * 60,
                 CHANCES % "duty_skipped": 100 + k * 400,
                 CHANCES % "no_budget": k * 15, CHANCES % "no_window": k * 5})
    page.update({"dynamo_request_ttft_seconds_sum": 2.0 + k * 120.0,
                 "dynamo_request_ttft_seconds_count": 10 + k * 48,
                 'dynamo_request_ttft_seconds_bucket{le="1.0"}': 10 + k * 9})
    return page


def _frontend(k: float, t: float) -> dict:
    """Two models' labelled series, to be summed."""
    sum_, count = 'dynamo_request_ttft_seconds_sum{model="%s"}', \
        'dynamo_request_ttft_seconds_count{model="%s"}'
    return {"_t": t, sum_ % "a": 1.0 + k * 100.0, count % "a": 5 + k * 40,
            sum_ % "b": 1.5 + k * 20.96, count % "b": 5 + k * 8,
            'dynamo_request_ttft_seconds_bucket{model="a",le="1.0"}': 4.0}


def _records():
    """50 judged requests: TTFT 2.6 s each, then 100 tokens after the first
    at 12.5 ms each."""
    out = []
    for i in range(50):
        due = 100.0 + i
        first = due + 2.6
        chunks = [[first, 1]] + [[first + 0.1 * (j + 1), 8]
                                 for j in range(12)] + [[first + 1.25, 4]]
        out.append({"ok": True, "due": due, "first": first, "chunks": chunks})
    return out


def _ctx(worker=_worker, frontend=_frontend, records=None, **kw):
    scrapes = {"window_start": {"worker": worker(1, 10.0, **kw),
                                "frontend": frontend(1, 10.0)},
               "window_end": {"worker": worker(2, 50.0, **kw),
                              "frontend": frontend(2, 50.0)}}

    def delta(source, key, scope="window"):
        x = scrapes.get(f"{scope}_start", {}).get(source)
        y = scrapes.get(f"{scope}_end", {}).get(source)
        if not x or not y or key not in x or key not in y:
            return None
        return y[key] - x[key]

    return types.SimpleNamespace(
        scrapes=scrapes, delta=delta,
        records=_records() if records is None else records)


def _old_program():
    """The parent: neither page holds a request-state series, both hold the
    TTFT histograms."""
    def worker(k, t):
        w = _worker(k, t)
        return {key: v for key, v in w.items()
                if key == "_t" or key.startswith("dynamo_request_ttft")}

    return _ctx(worker=worker)


EXPECTED = {
    "req_waiting_ms.mean": 10.0,                  # 0.5 s over 50 requests
    "req_budget_wait_ms.mean": 500.0,             # 25 s over 50
    "req_prefill_ms.mean": 2000.0,                # 100 s over 50
    "prefill_chances_per_chunk": 8.0,             # 480 chances, 60 taken
    "admit_blocked_share": 3.0,                   # 1.2 s of 40
    "req_first_token_ms.mean": 30.0,              # 1.5 s over 50
    "req_cohort_wait_ms.mean": 75.0,              # 3 s over 40
    "itl_inside_ms.mean": 12.0,                   # 60 s over 5048 - 48 tokens
    "itl_cohort_wait_share": 5.0,                 # 3 s of 60
    "itl_inside_over_client": 0.96,               # 12 ms of the client's 12.5
    "ttft_engine_over_client": 127.0 / 48 / 2.6,  # 2,646 ms of 2,600
    "frontend_ttft_overhead_ms.mean": 19.9999,    # 2.52 s less 2.5 s
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_known_scrapes(name):
    read = run.load_reader("layer_metrics", name).read
    assert read(_ctx()) == pytest.approx(EXPECTED[name], rel=1e-4)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_series_reads_nothing(name):
    read = run.load_reader("layer_metrics", name).read
    value = read(_old_program())
    if name == "frontend_ttft_overhead_ms.mean":
        # The one that reads series the parent has too.
        assert value == pytest.approx(EXPECTED[name], rel=1e-4)
    else:
        assert value is None
    # A run that lost a scrape, or a page, reads nothing and does not raise.
    lost = _ctx()
    lost.scrapes["window_end"]["worker"] = None
    lost.scrapes["window_start"]["frontend"] = None
    assert read(lost) is None


def test_an_engine_without_the_cohort_state_leaves_its_mean_out():
    """A block-diffusion engine never enters `cohort_wait`: the state's mean
    has no request to be a mean over, its share of the engine's own
    inter-token time reads 0, and the rest reads as ever."""
    ctx = _ctx(causal=False)
    assert request_readers.ms_per_entry(ctx, "cohort_wait") is None
    assert request_readers.cohort_wait_share(ctx) == 0.0
    assert request_readers.itl_inside_ms(ctx) == pytest.approx(57.0 / 5.0)
    assert request_readers.ms_per_entry(ctx, "decode") is not None


def test_ratios_to_the_client_need_a_client_that_finished():
    ctx = _ctx()
    assert request_readers.client_ttft_ms(ctx) == pytest.approx(2600.0)
    ctx.records[3]["ok"] = False               # one miss: the pooled ITL is
    assert request_readers.itl_inside_over_client(ctx) is None  # infinite
    assert request_readers.ttft_inside_over_client(ctx) is not None
    assert request_readers.itl_inside_over_client(_ctx(records=[])) is None
    assert request_readers.ttft_inside_over_client(_ctx(records=[])) is None


def test_the_window_with_no_first_token_or_no_chunk_reads_nothing():
    def still(k, t):
        return _worker(1, t)                   # nothing moved in 40 s

    ctx = _ctx(worker=still)
    for name in sorted(EXPECTED):
        if name in ("admit_blocked_share", "frontend_ttft_overhead_ms.mean"):
            continue
        assert run.load_reader("layer_metrics", name).read(ctx) is None, name
    assert request_readers.admit_blocked_share(ctx) == 0.0


def test_new_metrics_are_in_the_manifest_under_their_layers():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert list(entries)[-len(EXPECTED):] == [
        "req_waiting_ms.mean", "req_budget_wait_ms.mean",
        "req_prefill_ms.mean", "prefill_chances_per_chunk",
        "admit_blocked_share", "req_first_token_ms.mean",
        "req_cohort_wait_ms.mean", "itl_inside_ms.mean",
        "itl_cohort_wait_share", "itl_inside_over_client",
        "ttft_engine_over_client", "frontend_ttft_overhead_ms.mean"]
    for name in EXPECTED:
        m = entries[name]
        assert m["source"] == "program_counter" and m["moves"] == "itl_ms.mean"
        listed = [c["name"] for c in bench["workloads"]
                  if c["name"] in m.get("workloads", cells)]
        # Every cell reads them, but the one state a block-diffusion engine
        # has not: its mean lists the cells of the causal configurations.
        if name == "req_cohort_wait_ms.mean":
            assert len(listed) == len(cells) - 1
        else:
            assert "workloads" not in m and listed == cells
    assert entries["frontend_ttft_overhead_ms.mean"]["layer"] == "frontend"
    assert {entries[n]["layer"] for n in EXPECTED} == {
        "scheduler", "EngineCore", "frontend"}
