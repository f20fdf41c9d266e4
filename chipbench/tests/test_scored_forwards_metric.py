"""The layer metric of a commit that stops at its last K/V write, on
recorded counters: the reader's arithmetic by hand, and None on a program
that lacks the series (the parent of the PR that added it has the block
tallies and not this one), so that its result line just leaves it out."""

import json
import os

import pytest

from chipbench import run
from chipbench.tests.test_block_metrics import _ctx, _read

NAME = "scored_forwards_share"
SERIES = "dynamo_worker_diffusion_scored_forwards_total"


def _scored_ctx(scored):
    """`test_block_metrics`'s window (100 block calls, 390 denoising
    forwards) with `scored` of its 490 forwards scored; None = a program
    without the series."""
    ctx = _ctx()
    if scored is not None:
        for key, page in ctx.scrapes.items():
            page["worker"][SERIES] = scored if key == "window_end" else 0
    return ctx


def test_four_of_five_forwards_scored_read_80():
    ctx = _scored_ctx(0)
    for key, page in ctx.scrapes.items():
        end = key == "window_end"
        page["worker"].update({
            'dynamo_worker_diffusion_forwards_total{kind="denoise"}':
                4 if end else 0,
            'dynamo_worker_diffusion_forwards_total{kind="commit"}':
                1 if end else 0,
            SERIES: 4 if end else 0})
    assert _read(NAME, ctx) == pytest.approx(80.0)


@pytest.mark.parametrize("scored,want", [
    (390, 100 * 390 / 490),      # every commit stopped short
    (490, 100.0),                # every forward ran whole
    (0, 0.0)])                   # nothing scored is 0, not nothing to read
def test_the_share_by_hand(scored, want):
    assert _read(NAME, _scored_ctx(scored)) == pytest.approx(want)


def test_it_reads_nothing_without_the_series():
    """The parent has the block tallies and not this series; an engine
    that generates no blocks has none of them."""
    assert _read(NAME, _scored_ctx(None)) is None
    assert _read(NAME, _ctx(with_series=False)) is None


def test_the_metric_lists_the_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        m = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    assert m["workloads"] == ["sdar-30b-a3b.block-gen"]
    assert (m["layer"], m["moves"], m["better"], m["source"], m["unit"]) == (
        "step programs", "itl_ms.mean", "lower", "program_counter", "%")
