"""`window=None` leaves every accepted configuration's step programs as they
were: one greedy decode window (the decode kernel) and one packed prefill
step (the prefill kernel) of the tiny dense, routed, state and pattern presets
lower to the text the tree had before window layers existed.

The digests in `tests/data/lowered_text_before_window.json` were written by
`tests/lowered_text.py` from the parent commit of the PR that added window
layers (lowered by the same script from the same place as this tree).  A
later PR that means to change these programs writes them anew the same way,
from its own parent, and says so.  PR 54 meant to change the two routed
presets' four (their expert kernel fetches its weights through a ring of its
own) and wrote those from its own tree; the dense and the state presets' four
are still the digests from before window layers."""

import json
import os

import pytest

from tests import lowered_text

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def digests():
    return lowered_text.digests()


with open(os.path.join(HERE, "data", "lowered_text_before_window.json")) as f:
    BEFORE = json.load(f)


@pytest.mark.parametrize("program", sorted(BEFORE))
def test_a_program_without_a_window_lowers_to_the_parents_text(
        digests, program):
    assert digests[program] == BEFORE[program], (
        f"{program} lowers to other text than before window layers: a "
        "kernel's or the layer walk's `window is None` path has moved")


def test_every_preset_is_held():
    assert sorted(BEFORE) == sorted(
        f"{name}.{what}" for name in lowered_text.PRESETS
        for what in ("decode_window", "packed_prefill"))
