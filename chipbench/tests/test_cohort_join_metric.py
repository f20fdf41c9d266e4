"""The share of a window cohort's joins that happened at the completing
chunk, on recorded counters: the reader's arithmetic by hand over two
scrapes, None on a program that lacks the series (the parent of the PR that
added it), so that its result line leaves the metric out, and None, not a
division, where no row joined between the scrapes."""

import json
import os

import pytest

from chipbench import run
from chipbench.tests.test_request_state_metrics import _ctx, _old_program

NAME = "cohort_joins_at_chunk_share"
JOINS = 'dynamo_worker_cohort_joins_total{at="%s"}'
CELLS = ["mistral-7b.chat-steady", "glm-4.7-flash.long-context"]


def _read(ctx):
    return run.load_reader("layer_metrics", NAME).read(ctx)


def _join_ctx(chunk, settle, before=(7, 3)):
    """The request-state tests' window with `chunk` and `settle` joins
    between its scrapes, on top of what the lead-in counted."""
    ctx = _ctx()
    for key, at, moved in (("chunk", before[0], chunk),
                           ("settle", before[1], settle)):
        ctx.scrapes["window_start"]["worker"][JOINS % key] = at
        ctx.scrapes["window_end"]["worker"][JOINS % key] = at + moved
    return ctx


@pytest.mark.parametrize("chunk,settle,want", [
    (45, 5, 90.0),           # 48 requests and two preempted rows rejoin
    (50, 0, 100.0),
    (0, 4, 0.0),             # every join rode a batch: 0, not nothing
    (1, 2, 100.0 / 3)])
def test_the_share_from_two_scrapes(chunk, settle, want):
    assert _read(_join_ctx(chunk, settle)) == pytest.approx(want)


def test_it_reads_nothing_without_the_series():
    assert _read(_ctx()) is None             # PR 39's pages: no such series
    assert _read(_old_program()) is None     # nor the parent's
    half = _join_ctx(3, 1)
    del half.scrapes["window_end"]["worker"][JOINS % "settle"]
    assert _read(half) is None
    lost = _join_ctx(3, 1)
    lost.scrapes["window_end"]["worker"] = None
    assert _read(lost) is None


def test_no_join_between_the_scrapes_is_nothing_and_no_division():
    assert _read(_join_ctx(0, 0)) is None
    assert _read(_join_ctx(0, 0, before=(0, 0))) is None


def test_the_reader_says_what_its_manifest_entry_will():
    """The entry itself waits for a `benchmark` PR (PERF.md, Open
    questions): until then the reader's constants are what is pinned, and
    its cells are the causal ones the manifest holds."""
    mod = run.load_reader("layer_metrics", NAME)
    assert (mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT) == (
        "EngineCore", "program_counter", "itl_ms.mean", "%")
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}
    moved = {m["name"]: m for m in bench["end_to_end"]}[mod.MOVES]
    assert set(CELLS) <= set(moved.get("workloads", CELLS))
