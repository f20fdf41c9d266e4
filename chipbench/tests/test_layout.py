"""The data-driven layout: every name in BENCHMARK.json resolves to a file
of its own, reader constants agree with the manifest, and the harness holds
no cell, configuration, metric, reference, comparison or warm-up name in
its code."""

import json
import os
import re

import pytest

from chipbench import pieces, run

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


def _piece_names():
    """Every reference, comparison and warm-up the benchmark holds."""
    return sorted(f[:-3] for kind in pieces.CONFIG_PIECES.values()
                  for f in os.listdir(os.path.join(run.HERE, kind))
                  if f.endswith(".py"))


HARNESS_FILES = sorted(f for f in os.listdir(run.HERE) if f.endswith(".py"))


@pytest.mark.parametrize("mod", HARNESS_FILES)
def test_harness_code_names_no_cell_config_or_metric(mod):
    """No file of the harness (every module directly under the benchmark's
    directory) holds the name of a cell, configuration, mix, metric,
    reference, comparison or warm-up: all of those are found by name."""
    names = ([w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                if m["name"] != "setup_s"]
             + _piece_names())
    assert len(_piece_names()) >= 6
    with open(os.path.join(run.HERE, mod)) as f:
        code = f.read()
    for name in names:
        assert not re.search(r"[\"'`/]" + re.escape(name) + r"(\.py)?[\"'`]",
                             code), f"{mod} holds the name {name!r}"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configs_name_their_pieces(entry):
    """A configuration names its reference, its comparison and its warm-ups,
    and each is a file with what the harness calls."""
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    names = pieces.named(cfg)
    assert callable(pieces.load("references", names["reference"]).forward)
    comparison = pieces.load("comparisons", names["comparison"])
    assert callable(comparison.run) and len(comparison.LENGTHS) > 0
    assert names["warmups"]
    for name in names["warmups"]:
        mod = pieces.load("warmups", name)
        assert callable(mod.warm) and isinstance(mod.STEP_PROGRAMS, bool)


# Widths the repository has held a configuration to since it was added; a
# later configuration is held by what its own file says was published.
HELD_WIDTHS = {"mistral-7b-v0.3-d16": (4096, 14336, 32, 8)}


def _is_width(key: str) -> bool:
    return (key.endswith(("_dim", "_rank", "_size")) and key != "vocab_size"
            or key in ("num_attention_heads", "num_key_value_heads",
                       "num_experts_per_tok"))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configs_state_source_cuts_and_deployment(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in ("assumed", "deployment", "engine_flags", "programs"):
        assert key in cfg
    published = cfg["published"]
    # Each cut states what the source has, and is a cut.
    for key in cfg["reduced"]:
        assert key in published and published[key] != cfg[key], key
        assert not _is_width(key), f"{key} is a width: never reduced"
    # Everything else that sets a shape is as published, every width among
    # them.
    for key, value in published.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key, value in cfg.items():
        if _is_width(key) and isinstance(value, (int, float)):
            assert key in published, f"{key} states no published value"
    if entry["name"] in HELD_WIDTHS:
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"]) \
            == HELD_WIDTHS[entry["name"]]
