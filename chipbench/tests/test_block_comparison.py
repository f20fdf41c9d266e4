"""The block-diffusion comparison over a real tiny engine of the program on
the CPU, sound and broken underneath in the three ways its own checks name:
a token altered where the block program hands it back, a block left
uncommitted, an expert dropped on one side.  (`test_comparison.py` drives
the same comparison by name through a sound engine, an altered stream, a
dropped prompt and a reference a layer short.)  jax is imported inside the
fixture: collecting this file touches no accelerator library."""

import dataclasses
import json
import os
import shutil

import pytest

from chipbench import check, run

LENGTHS = (5, 17, 40)
CONFIG = "chipbench/configs/sdar-30b-a3b-chat-d7.json"


def _engine(hf, prefix_cache=False, **model):
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models.loader import config_from_hf

    cfg = config_from_hf(hf, "t").replace(dtype=jnp.float32, **model)
    return EngineCore(EngineConfig(
        model=cfg, num_blocks=64, enable_prefix_cache=prefix_cache,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=8,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32))))


@pytest.fixture(scope="module")
def block_served():
    with open(os.path.join(run.ROOT, CONFIG)) as f:
        hf = json.load(f)
    hf.update(hf["cpu_rehearsal"])
    return hf, _engine(hf)


def _over(out):
    return {i["name"] for i in out["limits"] if i["value"] > i["limit"]}


def test_every_forward_of_every_block_is_compared(block_served):
    hf, core = block_served
    out = check.run_check(core, hf, 11, LENGTHS)
    assert out["ok"] is True, out["problems"]
    # 7 tokens from prompts of 5, 17 and 40 (n % 4 = 1, 1, 0): two block
    # calls each, 4 + 1 forwards a fresh block, 3 + 1 after a tail of 1.
    assert out["forwards_compared"] == (4 + 5) + (4 + 5) + (5 + 5)
    assert core.block_record is None      # recording is off again
    # The served pass: the same prompts through the programs without the
    # logits, rows of block calls that hold this engine's five fillers
    # beside them; every forward of theirs is held by its decisions.
    assert out["served_rows_min"] >= 6
    # (a call runs the forwards its slowest row needs: 5 with a filler's
    # fresh block in it, also for a row that needed 4)
    assert out["served_forwards_compared"] == 3 * (5 + 5)
    assert {"max_served_margin", "max_served_order"} <= {
        i["name"] for i in out["limits"]}
    assert core.block_record_logits is True


def test_a_prefix_cache_hides_nothing_from_either_pass(block_served):
    """As served: with the prefix cache on (pages of 16, so the prompts of
    17 and 40 leave full pages behind).  Both passes prefill from the first
    token: the served pass has prompts of its own, and so has another seed
    on the same engine."""
    hf, _ = block_served
    core = _engine(hf, prefix_cache=True)
    for seed in (11, 12):
        out = check.run_check(core, hf, seed, LENGTHS)
        assert out["ok"] is True, out["problems"]
        assert out["served_forwards_compared"] == 3 * (5 + 5)


@pytest.mark.parametrize("fault", ["token", "length"])
def test_a_fault_of_the_served_program_alone_is_not_ok(block_served, fault):
    """The twin that hands out logits sound and the program the window
    drives not: a token altered in every row of the served block program
    (its trail says another token was fed to the commit), or a served
    stream cut short."""
    hf, core = block_served
    block_fn = core._block_fn

    def served_altering(greedy, record=False):
        fn = block_fn(greedy, record)
        if record:
            return fn

        def run_then_alter(*args):
            out = list(fn(*args))
            out[1] = out[1].at[:, -1].add(1)      # every row's last token
            return tuple(out)
        return run_then_alter

    add = core.add_request

    def cutting(rid, prompt, sampling, *a, **kw):
        if rid.startswith("chipbench-served-"):   # one short of the asked
            sampling = dataclasses.replace(
                sampling, max_tokens=sampling.max_tokens - 1)
        return add(rid, prompt, sampling, *a, **kw)

    if fault == "token":
        core._block_fn = served_altering
    else:
        core.add_request = cutting
    try:
        out = check.run_check(core, hf, 11, LENGTHS)
    finally:
        core.__dict__.pop("_block_fn", None)
        core.__dict__.pop("add_request", None)
    assert out["ok"] is False
    # The recorded pass was held to the reference and found sound.
    assert all(r["forwards"] > 0 for r in out["rows"])
    assert not {"max_abs_logit_diff", "max_body_logit_diff"} & _over(out)
    assert "bookkeeping_faults" in _over(out), out["problems"]
    assert all(p.startswith("chipbench-served-") for p in out["problems"]
               if p.startswith("chipbench-"))


@pytest.mark.parametrize("rule,steps,block", [
    ("low_confidence_static", 4, 8), ("low_confidence_dynamic", 2, 4),
    ("low_confidence_dynamic", 4, 8)])
def test_other_blocks_and_both_rules_agree_with_the_reference(block_served, rule,
                                                              steps, block):
    hf = dict(block_served[0], remasking=rule, denoising_steps=steps,
              diffusion_block_length=block, confidence_threshold=0.004)
    out = check.run_check(_engine(hf), hf, 5, (3, 16, 29))
    assert out["ok"] is True, out["problems"]
    assert all(i["value"] <= 1e-3 for i in out["limits"])


def test_a_token_altered_where_it_is_unmasked_is_not_ok(block_served):
    hf, core = block_served
    block_fn = core._block_fn

    def altering(greedy, record=False):
        fn = block_fn(greedy, record)

        def run_then_alter(*args):
            out = list(fn(*args))
            out[1] = out[1].at[0, -1].add(1)      # row 0's last token
            return tuple(out)
        return run_then_alter

    core._block_fn = altering
    try:
        out = check.run_check(core, hf, 11, LENGTHS)
    finally:
        del core._block_fn
    assert out["ok"] is False
    assert "bookkeeping_faults" in _over(out), out["problems"]
    assert any("other tokens than the call returned" in p
               for p in out["problems"])


def test_a_block_left_uncommitted_is_not_ok(block_served):
    """The block program's K and V thrown away after every call: the next
    block's forwards no longer see what the reference sees."""
    import jax
    import jax.numpy as jnp

    hf, core = block_served
    block_fn = core._block_fn

    def forgetting(greedy, record=False):
        fn = block_fn(greedy, record)

        def run_then_forget(params, cache, *rest):
            before = jax.tree.map(jnp.copy, cache)
            return (before,) + tuple(fn(params, cache, *rest))[1:]
        return run_then_forget

    core._block_fn = forgetting
    try:
        out = check.run_check(core, hf, 11, LENGTHS)
    finally:
        del core._block_fn
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    assert "max_abs_logit_diff" in _over(out), out["limits"]


def test_an_expert_dropped_on_one_side_is_not_ok(block_served, tmp_path):
    """The same comparison against a reference laid beside it whose expert
    0 gives nothing: the logits limits catch it."""
    hf, core = block_served
    root = str(tmp_path)
    for kind, names in (("comparisons", [hf["comparison"]]),
                        ("references", [hf["reference"]]),
                        ("warmups", hf["warmups"])):
        os.makedirs(os.path.join(root, kind))
        for name in names:
            shutil.copy(os.path.join(run.HERE, kind, name + ".py"),
                        os.path.join(root, kind))
    with open(os.path.join(root, "references", "an_expert_short.py"),
              "w") as f:
        f.write("from chipbench import pieces\n\n\n"
                "def forward(hf, params, tokens, **kw):\n"
                f"    whole = pieces.load('references', {hf['reference']!r},"
                f" {root!r})\n"
                "    layers = [dict(l, moe=dict(l['moe'], w_down=l['moe']"
                "['w_down'].at[0].set(0))) for l in params['layers']]\n"
                "    return whole.forward(hf, dict(params, layers=layers),"
                " tokens, **kw)\n")
    out = check.run_check(core, dict(hf, reference="an_expert_short"), 11,
                          LENGTHS, root=root)
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    assert {"max_abs_logit_diff", "max_body_logit_diff"} <= _over(out)


@pytest.mark.parametrize("lower", ["bfloat16", "int8_kv"])
def test_a_lower_precision_than_stated_fails_a_limit(block_served, lower):
    """The rehearsal configuration states float32: the same engine in
    bfloat16, or with an int8 KV cache, is refused by the median limit (on
    the chip, where bfloat16 is what is stated, the int8 cache is the
    control and fails both logits limits: PERF.md section 6, PR 27)."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models.loader import config_from_hf

    hf, _ = block_served
    cfg = config_from_hf(hf, "t").replace(
        dtype=jnp.bfloat16 if lower == "bfloat16" else jnp.float32)
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=64, enable_prefix_cache=False,
        packed_prefill=True,
        kv_quant="int8" if lower == "int8_kv" else "none",
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=8,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32))))
    out = check.run_check(core, hf, 11, LENGTHS)
    assert out["ok"] is False and out["compared"] == len(LENGTHS)
    assert "max_body_logit_diff" in _over(out), out["limits"]
    assert "bookkeeping_faults" not in _over(out)
