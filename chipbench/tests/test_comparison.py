"""The comparison a configuration names, run as a worker runs it (found by
name, through `check.run_check`) over a real engine of the program at tiny
widths on the CPU: a sound engine is `ok` with every prompt compared, and an
engine whose timed path is broken underneath -- a token altered where it is
produced, a prompt dropped, a layer left out on one side -- is not.  jax is
imported inside the fixture: collecting this file touches no accelerator
library."""

import json
import os
import shutil

import pytest

from chipbench import check, run

LENGTHS = (5, 17, 40)


def _configs():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return [c for c in json.load(f)["configs"]]


@pytest.fixture(scope="module", params=_configs(), ids=lambda c: c["name"])
def served(request):
    """(hf, a built EngineCore) at the configuration's rehearsal widths."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models.loader import config_from_hf

    with open(os.path.join(run.ROOT, request.param["file"])) as f:
        hf = json.load(f)
    if "cpu_rehearsal" not in hf:
        pytest.skip("the configuration states no rehearsal widths")
    hf.update(hf["cpu_rehearsal"])
    cfg = config_from_hf(hf, "t").replace(dtype=jnp.float32)
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=64, enable_prefix_cache=False, decode_window=8,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=8,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32))))
    return hf, core


def test_a_sound_engine_is_ok_with_every_prompt_compared(served):
    hf, core = served
    out = check.run_check(core, hf, 7, LENGTHS)
    assert out["ok"] is True, out["problems"]
    assert out["prompts"] == out["compared"] == len(LENGTHS)
    assert (out["reference"], out["comparison"]) \
        == (hf["reference"], hf["comparison"])
    # float32 on both sides: far inside every limit.
    assert out["limits"] and all(i["value"] <= 1e-3 for i in out["limits"])


def test_a_token_altered_where_it_is_produced_is_not_ok(served):
    hf, core = served
    step = core.step

    def altered():
        deltas = step()
        for d in deltas:
            if d.request_id.endswith("-1") and d.token_ids:
                d.token_ids[-1] = (d.token_ids[-1] + 1) % hf["vocab_size"]
        return deltas

    core.step = altered
    try:
        out = check.run_check(core, hf, 7, LENGTHS)
    finally:
        del core.step
    assert out["ok"] is False
    over = [i["name"] for i in out["limits"] if i["value"] > i["limit"]]
    assert over, out["problems"]


def test_a_layer_left_out_on_one_side_is_not_ok(served, tmp_path):
    """The same comparison, by name, against a reference laid beside it that
    leaves the last layer out: the logits limits catch it."""
    hf, core = served
    root = str(tmp_path)
    for kind, name in (("comparisons", hf["comparison"]),
                       ("references", hf["reference"])):
        os.makedirs(os.path.join(root, kind))
        shutil.copy(os.path.join(run.HERE, kind, name + ".py"),
                    os.path.join(root, kind))
    with open(os.path.join(root, "references", "a_layer_short.py"), "w") as f:
        f.write("from chipbench import pieces\n\n\n"
                "def forward(hf, params, tokens, **kw):\n"
                f"    whole = pieces.load('references', {hf['reference']!r},"
                f" {root!r})\n"
                "    short = dict(params, layers=params['layers'][:-1])\n"
                "    return whole.forward(hf, short, tokens, **kw)\n")
    os.makedirs(os.path.join(root, "warmups"))
    for name in hf["warmups"]:
        shutil.copy(os.path.join(run.HERE, "warmups", name + ".py"),
                    os.path.join(root, "warmups"))
    assert check.run_check(core, hf, 7, LENGTHS, root=root)["ok"] is True
    out = check.run_check(core, dict(hf, reference="a_layer_short"), 7,
                          LENGTHS, root=root)
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    over = {i["name"] for i in out["limits"] if i["value"] > i["limit"]}
    assert len(over) >= 2, out["limits"]


def test_fewer_prompts_served_than_asked_is_not_ok(served):
    hf, core = served
    add = core.add_request

    def drop_one(rid, *a, **kw):
        if not rid.endswith("-2"):
            add(rid, *a, **kw)

    core.add_request = drop_one
    try:
        out = check.run_check(core, hf, 7, LENGTHS)
    finally:
        del core.add_request
    assert out["ok"] is False and out["compared"] == len(LENGTHS) - 1
