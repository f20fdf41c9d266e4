"""The data-driven layout: every name in BENCHMARK.json resolves to a file
of its own, reader constants agree with the manifest, and the harness holds
no cell, configuration or metric name in its code."""

import json
import os
import re

import pytest

from chipbench import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_files(cell):
    _bench, c, cfg_entry, config, mix, params = run.load_cell(cell)
    assert params["rate_rps"] > 0
    assert config["vocab_size"] > 0 and cfg_entry["file"].startswith("chipbench/")
    for key in ("input_tokens", "output_tokens"):
        assert key in mix
    for kind in ("end_to_end", "per_layer"):
        assert run.metrics_for(BENCH, cell, kind)
    assert any(m["name"] == "setup_s"
               for m in run.metrics_for(BENCH, cell, "end_to_end"))


def test_a_metric_may_list_the_cells_it_exists_in():
    """The manifest's optional `workloads` key on a metric: how a later PR
    adds a cell with a metric of its own without touching the others."""
    bench = {"per_layer": [{"name": "a"},
                           {"name": "b", "workloads": ["cell-2"]}]}
    assert [m["name"] for m in run.metrics_for(bench, "cell-1", "per_layer")] \
        == ["a"]
    assert [m["name"] for m in run.metrics_for(bench, "cell-2", "per_layer")] \
        == ["a", "b"]


@pytest.mark.parametrize("kind,folder", [("end_to_end", "end_to_end"),
                                         ("per_layer", "layer_metrics")])
def test_every_metric_has_a_reader_that_agrees(kind, folder):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH[kind]:
        mod = run.load_reader(folder, m["name"])
        assert callable(mod.read)
        assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
        if kind == "per_layer":
            assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
            moved = e2e[m["moves"]]
            # The moved metric is reported in every cell this one is in.
            cells = m.get("workloads") or [w["name"]
                                           for w in BENCH["workloads"]]
            assert all("workloads" not in moved or c in moved["workloads"]
                       for c in cells)


def test_harness_code_names_no_cell_config_or_metric():
    names = ([w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                if m["name"] != "setup_s"])
    for mod in ("run.py", "traffic.py", "loadgen.py", "serve_child.py",
                "trace_reduce.py", "check.py", "stats.py"):
        with open(os.path.join(run.HERE, mod)) as f:
            code = f.read()
        for name in names:
            assert not re.search(r"[\"']" + re.escape(name) + r"[\"']", code), \
                f"{mod} holds the name {name!r}"


def test_configs_state_source_cuts_and_deployment():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in ("assumed", "deployment", "engine_flags", "programs"):
            assert key in cfg
        # Every width as published.
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"]) \
            == (4096, 14336, 32, 8)
