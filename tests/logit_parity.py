"""A mesh engine against the meshless oracle that shares its cache
precision: first-token logits under a stated tolerance, and greedy
tokens equal wherever the oracle's own margin between the two candidates
is outside twice that tolerance.  It is what `chip_smoke.py`'s `parity`
phase compares on the chip, so it is that phase's code.

Greedy *tokens* of a random-weight model are no oracle across cache
precisions: the int8 cache moves tiny-test's logits by 0.03 where the
best and the second token lie 0.005 apart, so an int8 engine parts from
the bf16 oracle however it is sharded.  Sharding the same int8 program
moves a logit by 2e-6 (the order of f32 sums).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# Same program, same cache precision, another partition: only the order
# of f32 reductions differs (measured 0 to 1.7e-6 over the eight int8
# cells).  chip_smoke's int8 tolerance on the chip is 0.2.
SHARDING_ATOL = 1e-3
assert SHARDING_ATOL <= chip_smoke.LOGIT_ATOL_INT8


def greedy(core, prompts, max_tokens=12):
    """({"r<i>": tokens}, {"r<i>": the f32 logits row that chose the
    first token}) for `prompts` through `core`."""
    return chip_smoke._run_engine(core, prompts, max_tokens)


def assert_logit_parity(name, ref_core, ref, got, prompts,
                        atol=SHARDING_ATOL):
    """`ref` and `got` are `greedy` results; `ref_core` re-derives the
    oracle's margin where the token streams part."""
    try:
        chip_smoke._compare(name, ref, got, ref_core, prompts, atol)
    except chip_smoke.SmokeFailure as e:
        raise AssertionError(str(e)) from None
