"""The fill of the grouped expert kernel's packed buffers in a causal
engine, on recorded counters: the reader's arithmetic by hand at the shapes
of a one-row decode step over 64 experts of 4 a token, and None on a program
that lacks the series (the parent of the PR that added it has the other
expert tallies and not this one), so that its result line leaves it out."""

import json
import os

import pytest

from chipbench import run
from chipbench.tests.test_latent_block import CELL, _ctx, _read

NAME = "moe_pack_fill_share.causal"
ASSIGNED = "dynamo_worker_moe_assignments_total"
PACKED = "dynamo_worker_moe_packed_rows_total"


def _fill_ctx(assigned, packed):
    """`test_latent_block`'s window with `assigned` assignments in `packed`
    rows of packed buffer; `packed` None = a program without the series.
    (Its window and its capture share their pages.)"""
    ctx = _ctx()
    for key, page in ctx.scrapes.items():
        end = key.endswith("_end")
        page["worker"][ASSIGNED] = assigned if end else 0
        if packed is not None:
            page["worker"][PACKED] = packed if end else 0
    return ctx


@pytest.mark.parametrize("assigned,packed,want", [
    (4, 32, 12.5),           # one row: 4 tiles of 8, a row in each
    (4, 448, 100 / 112),     # the same step in 56 tiles
    (2048, 6080, 100 * 2048 / 6080),   # a chunk of 512 tokens, tile 64
    (0, 32, 0.0)])           # nothing assigned is 0, not nothing to read
def test_the_share_by_hand(assigned, packed, want):
    assert _read(NAME, _fill_ctx(assigned, packed)) == pytest.approx(want)


def test_it_reads_nothing_without_the_series():
    assert _read(NAME, _fill_ctx(4, None)) is None
    assert _read(NAME, _ctx(with_series=False)) is None
    assert _read(NAME, _fill_ctx(4, 0)) is None      # no layer ran


def test_the_metric_lists_the_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        m = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    assert m["workloads"] == [CELL] and m["moves"] == "itl_ms.mean"
