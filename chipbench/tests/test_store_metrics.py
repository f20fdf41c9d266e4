"""The two layer metrics that read the program store's series: each reader
on a synthetic page, and None on a program that has no such series (the
parent of the PR that added the store), so that its result line just leaves
the metric out."""

import json
import os
import types

import pytest

from chipbench import run

STORE = "dynamo_worker_program_store_%s_total"
BUILD = 'dynamo_worker_program_build_seconds_total{stage="%s"}'


def _ctx(page):
    scrapes = {"window_start": {"worker": page, "frontend": {}},
               "window_end": {"worker": page, "frontend": {}}}
    return types.SimpleNamespace(scrapes=scrapes, trace=None)


WARM = {STORE % "hits": 107, STORE % "misses": 0, STORE % "errors": 0,
        BUILD % "store_read": 31.5, BUILD % "trace": 6.0}
COLD = {STORE % "hits": 0, STORE % "misses": 107, STORE % "errors": 0,
        BUILD % "store_read": 0.0}
MIXED = {STORE % "hits": 80, STORE % "misses": 27, STORE % "errors": 1,
         BUILD % "store_read": 20.25}
# The parent: build accounting, no store.  An engine-less or store-less
# process: the series at zero.
PARENT = {BUILD % "trace": 97.0, BUILD % "cache_read": 34.5}
UNUSED = {STORE % "hits": 0, STORE % "misses": 0, STORE % "errors": 0,
          BUILD % "store_read": 0.0}

CASES = [
    ("program_store_hit_share", WARM, 100.0),
    ("program_store_hit_share", COLD, 0.0),
    ("program_store_hit_share", MIXED, 100.0 * 80 / 107),
    ("program_store_hit_share", PARENT, None),
    ("program_store_hit_share", UNUSED, None),
    ("program_store_hit_share", {}, None),
    ("build_store_read_s", WARM, 31.5),
    ("build_store_read_s", COLD, 0.0),
    ("build_store_read_s", MIXED, 20.25),
    ("build_store_read_s", PARENT, None),
    ("build_store_read_s", {}, None),
]


@pytest.mark.parametrize("name,page,want", CASES)
def test_reader_on_a_synthetic_page(name, page, want):
    got = run.load_reader("layer_metrics", name).read(_ctx(page))
    assert got == (None if want is None else pytest.approx(want))


def test_a_run_without_the_scrape_reads_nothing():
    ctx = types.SimpleNamespace(scrapes={}, trace=None)
    for name in ("program_store_hit_share", "build_store_read_s"):
        assert run.load_reader("layer_metrics", name).read(ctx) is None


def test_both_metrics_are_in_the_manifest_and_move_setup():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("program_store_hit_share", "build_store_read_s"):
        assert listed[name]["moves"] == "setup_s"
        assert listed[name]["layer"] == "step programs"
    # The share has something to read only where an engine's step programs
    # go through the store (meshless, one process): it lists its cells.
    assert listed["program_store_hit_share"]["workloads"]
    assert "workloads" not in listed["build_store_read_s"]
