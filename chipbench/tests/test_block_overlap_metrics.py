"""The two layer metrics of reading one block call behind, on recorded
counters: each reader's arithmetic by hand, and None on a program that lacks
the series (the parent of the PR that added them has the block tallies and
neither of these), so that its result line just leaves the metric out."""

import json
import os

import pytest

from chipbench import run
from chipbench.tests.test_block_metrics import _ctx, _read

OVERLAP = ("block_calls_overlapped_share", "block_rows_dropped_share")


def _overlap_ctx(with_series=True):
    """A window of 100 block calls, 97 of them dispatched while the one
    before was unread, 2,940 blocks committed to streams and 60 dropped."""
    ctx = _ctx()
    extra = {"dynamo_worker_block_calls_overlapped_total": 97,
             "dynamo_worker_diffusion_rows_dropped_total": 60,
             "dynamo_worker_diffusion_blocks_committed_total": 2940}
    if with_series:
        for key, page in ctx.scrapes.items():
            page["worker"].update(
                extra if key == "window_end" else dict.fromkeys(extra, 0))
    return ctx


def test_the_overlap_metrics_by_hand():
    ctx = _overlap_ctx()
    assert _read("block_calls_overlapped_share", ctx) == pytest.approx(97.0)
    assert _read("block_rows_dropped_share", ctx) \
        == pytest.approx(100 * 60 / 3000)
    # Nothing dropped is 0, not nothing to read.
    ctx.scrapes["window_end"]["worker"][
        "dynamo_worker_diffusion_rows_dropped_total"] = 0
    assert _read("block_rows_dropped_share", ctx) == 0.0


@pytest.mark.parametrize("name", OVERLAP)
def test_the_overlap_metrics_read_nothing_on_the_parents_series(name):
    """The parent has the block tallies and neither new series; an engine
    that generates no blocks has none of them."""
    assert _read(name, _overlap_ctx(with_series=False)) is None
    assert _read(name, _ctx(with_series=False)) is None


def test_the_overlap_metrics_list_the_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, better in zip(OVERLAP, ("higher", "lower")):
        m = by_name[name]
        assert m["workloads"] == ["sdar-30b-a3b.block-gen"]
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            "EngineCore", "itl_ms.mean", better, "program_counter")
