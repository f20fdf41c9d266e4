"""The seeded generator: same seed, same schedule; another seed, the same
work in another order; every drawn length as the mix states it."""

import os
from collections import Counter

import pytest

from chipbench import traffic

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))


def _mix(name=MIXES[0]):
    return traffic.load_mix(TRAFFIC_DIR, name)


def test_same_seed_same_schedule():
    a = traffic.schedule(_mix(), 3.0, 2**31 + 5, 10.0, 40.0)
    b = traffic.schedule(_mix(), 3.0, 2**31 + 5, 10.0, 40.0)
    assert a == b
    assert traffic.prompt_ids(a[3], 32768) == traffic.prompt_ids(b[3], 32768)


@pytest.mark.parametrize("rate,lead,seconds", [(1.2, 10.0, 40.0),
                                               (3.0, 10.0, 400.0)])
def test_other_seed_same_work_other_order(rate, lead, seconds):
    a = traffic.schedule(_mix(), rate, 1, lead, seconds)
    b = traffic.schedule(_mix(), rate, 2**31 + 7, lead, seconds)
    assert [r.n_in for r in a] != [r.n_in for r in b]
    assert traffic.prompt_ids(a[0], 999) != traffic.prompt_ids(b[0], 999)
    for judged in (True, False):
        sa = [r for r in a if (r.due_s >= 0) == judged]
        sb = [r for r in b if (r.due_s >= 0) == judged]
        # The same requests, prompt tokens, output tokens and gaps, in the
        # lead-in and in the judged window, whatever the seed.
        assert len(sa) == len(sb) == round(rate * (seconds if judged else lead))
        assert Counter(r.n_in for r in sa) == Counter(r.n_in for r in sb)
        assert Counter(r.n_out for r in sa) == Counter(r.n_out for r in sb)
        gaps = [sorted(round(y.due_s - x.due_s, 9) for x, y in zip(s, s[1:]))
                for s in (sa, sb)]
        # All but the closing gap of a segment are between its requests.
        assert len(set(gaps[0]) ^ set(gaps[1])) <= 2


@pytest.mark.parametrize("mix_name", MIXES)
def test_lengths_follow_the_mix_unaligned(mix_name):
    mix = _mix(mix_name)
    reqs = traffic.schedule(mix, 5.0, 7, 10.0, 400.0)
    assert len(reqs) == 2050
    i, o = mix["input_tokens"], mix["output_tokens"]
    for r in reqs:
        assert i["min"] <= r.n_in <= i["max"]
        assert o["min"] <= r.n_out <= o["max"]
    assert min(r.n_in for r in reqs) == i["min"]
    assert max(r.n_in for r in reqs) == i["max"]
    med = sorted(r.n_out for r in reqs)[len(reqs) // 2]
    assert 0.9 * o["median"] < med < 1.1 * o["median"]
    # Nothing is snapped to the engine's decode window: every residue of 8
    # is drawn about as often as any other.
    residues = Counter(r.n_out % 8 for r in reqs if r.n_out < o["max"])
    assert len(residues) == 8 and min(residues.values()) > 0.08 * len(reqs)


def test_arrivals_fill_lead_in_and_window_at_the_rate():
    reqs = traffic.schedule(_mix(), 4.0, 3, 10.0, 40.0)
    assert reqs[0].due_s == -10.0 and reqs[40].due_s == 0.0
    assert -1.5 < reqs[39].due_s < 0 and 38.5 < reqs[-1].due_s < 40.0
    assert all(a.due_s < b.due_s for a, b in zip(reqs, reqs[1:]))
    assert [r.index for r in reqs] == list(range(200))
    # Poisson-like gaps: about a third under a third of the mean gap.
    gaps = [b.due_s - a.due_s for a, b in zip(reqs[40:], reqs[41:])]
    assert 0.2 < sum(g < 0.25 / 3 for g in gaps) / len(gaps) < 0.4


def test_no_lead_in_and_bad_rate():
    assert traffic.schedule(_mix(), 2.0, 1, 0.0, 10.0)[0].due_s == 0.0
    with pytest.raises(ValueError):
        traffic.schedule(_mix(), 0.0, 1, 10.0, 10.0)


# sha256 over (index, due_s, n_in, n_out, prompt_seed, prompt ids) of every
# request of the schedule, lead-in included, as the generator of the commit
# before `reserved_token_ids` existed (0b39598) drew them: mix chat-steady at
# 1.2 req/s, lead-in 10 s, window 40 s, vocabulary 32768.
PARENT_DRAWS = {
    1: "d23c53a1d56d89134cf5ac63881410a4514067a6df6e7b5f112913537490ee5b",
    13: "b6bf5e4f953aaf33d33a73c356f871fe0b5f629352dfcd214a64650b085035a9",
    2147484001:
        "607a6f8f934e4f0c3aab64a2fd5048af6a9b2e527d644b83e6781547540050a3",
}


@pytest.mark.parametrize("seed", sorted(PARENT_DRAWS))
def test_without_reserved_ids_the_draw_is_the_parents_bit_for_bit(seed):
    import hashlib
    import json

    reqs = traffic.schedule(_mix("chat-steady"), 1.2, seed, 10.0, 40.0)
    assert len(reqs) == 60
    h = hashlib.sha256()
    for r in reqs:
        h.update(json.dumps([r.index, r.due_s, r.n_in, r.n_out, r.prompt_seed,
                             traffic.prompt_ids(r, 32768)]).encode())
    assert h.hexdigest() == PARENT_DRAWS[seed]


def test_reserved_ids_are_never_drawn_and_the_rest_keeps_its_order():
    req = traffic.Request(0, 0.0, 4000, 8, 2**31 - 5)
    plain = traffic.prompt_ids(req, 64)
    reserved = {3, 17, 63}
    assert reserved <= set(plain)          # a small vocabulary: all are hit
    got = traffic.prompt_ids(req, 64, reserved)
    assert len(got) == req.n_in and not reserved & set(got)
    assert 1 <= min(got) and max(got) < 64
    # The reserved draws are struck and made up from the same stream: what
    # is kept of the plain draw comes first, in its order.
    kept = [t for t in plain if t not in reserved]
    assert got[:len(kept)] == kept
    assert traffic.prompt_ids(req, 64, ()) == plain
    assert traffic.prompt_ids(req, 64, reserved) == got
