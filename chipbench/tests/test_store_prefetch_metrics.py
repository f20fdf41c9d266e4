"""The two layer metrics that read the program store's read-ahead: each
reader on a synthetic page, and None on a program that has no such series
(the parent of the PR that added the read-ahead), so that its result line
just leaves the metric out."""

import json
import os
import types

import pytest

from chipbench import run

STORE = "dynamo_worker_program_store_%s_total"
BUILD = 'dynamo_worker_program_build_seconds_total{stage="%s"}'
NAMES = ("program_store_prefetched_share", "build_store_wait_s")


def _ctx(page):
    scrapes = {"window_start": {"worker": page, "frontend": {}},
               "window_end": {"worker": page, "frontend": {}}}
    return types.SimpleNamespace(scrapes=scrapes, trace=None)


def _page(hits, misses, prefetched, wait_s, read_s=30.0):
    return {STORE % "hits": hits, STORE % "misses": misses,
            STORE % "errors": 0, STORE % "prefetched": prefetched,
            STORE % "prefetch_unclaimed": 0,
            BUILD % "store_read": read_s, BUILD % "store_wait": wait_s}


AHEAD = _page(112, 0, 112, 0.4)
PARTLY = _page(112, 0, 84, 7.5)          # the first calls overtook the loads
COLD = _page(0, 107, 0, 0.02, read_s=0.0)
MIXED = _page(80, 27, 60, 3.25)
# The parent: a store and its accounting, no read-ahead.  An engine-less
# process: the series at zero.
PARENT = {STORE % "hits": 112, STORE % "misses": 0, STORE % "errors": 0,
          BUILD % "store_read": 33.9, BUILD % "trace": 0.3}
UNUSED = _page(0, 0, 0, 0.0, read_s=0.0)

CASES = [
    ("program_store_prefetched_share", AHEAD, 100.0),
    ("program_store_prefetched_share", PARTLY, 75.0),
    ("program_store_prefetched_share", COLD, 0.0),
    ("program_store_prefetched_share", MIXED, 75.0),
    ("program_store_prefetched_share", PARENT, None),
    ("program_store_prefetched_share", UNUSED, None),
    ("program_store_prefetched_share", {}, None),
    ("build_store_wait_s", AHEAD, 0.4),
    ("build_store_wait_s", PARTLY, 7.5),
    ("build_store_wait_s", COLD, 0.02),
    ("build_store_wait_s", PARENT, None),
    ("build_store_wait_s", {}, None),
]


@pytest.mark.parametrize("name,page,want", CASES)
def test_reader_on_a_synthetic_page(name, page, want):
    got = run.load_reader("layer_metrics", name).read(_ctx(page))
    assert got == (None if want is None else pytest.approx(want))


def test_a_run_without_the_scrape_reads_nothing():
    ctx = types.SimpleNamespace(scrapes={}, trace=None)
    for name in NAMES:
        assert run.load_reader("layer_metrics", name).read(ctx) is None


def test_both_metrics_are_in_the_manifest_and_move_setup():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NAMES:
        assert listed[name]["moves"] == "setup_s"
        assert listed[name]["layer"] == "step programs"
        assert listed[name]["source"] == "program_counter"
        # Every cell's engine has a store: neither lists its cells.
        assert "workloads" not in listed[name]


def test_the_readers_name_the_series_the_worker_prints():
    """The program's side of the contract: `metrics_lines` prints the
    series these readers look for, under these names."""
    from dynamo_tpu.runtime import compile_cache

    compile_cache._listen()
    names = {line.split(" ")[0] for line in compile_cache.metrics_lines()}
    assert {STORE % "prefetched", STORE % "prefetch_unclaimed",
            STORE % "hits", BUILD % "store_wait"} <= names
