"""The one traffic generator.  A mix is a data file under `traffic/`; a
cell adds its rate.  Nothing here knows a mix, a cell or a model by name.

A schedule is drawn up front from the seed: due times, prompt and output
lengths, prompt contents.  Every seed gets the SAME work in another order.
The lead-in and the measured window are drawn apart, each holding
round(rate x its length) requests whose lengths and inter-arrival gaps are
the quantiles of the stated distributions at (i + 0.5) / N, shuffled by the
seed.  A segment's first request is due at its start, each gap leads to the
next and the last gap closes the segment, scaled so the gaps fill it: two
seeds offer the judged window the same requests, prompt tokens, output
tokens and gaps, and differ only in how these are interleaved."""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

_NORMAL = NormalDist()


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # relative to the start of the measured window
    n_in: int
    n_out: int
    prompt_seed: int


def load_mix(traffic_dir: str, name: str) -> dict:
    with open(os.path.join(traffic_dir, name + ".json")) as f:
        return json.load(f)


def _length(spec: dict, u: float) -> int:
    """Inverse CDF of the clipped lognormal at quantile u in (0, 1)."""
    x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    return int(round(min(max(x, spec["min"]), spec["max"])))


def _segment(mix: dict, rate_rps: float, rng: random.Random, start: float,
             length: float, first_index: int) -> List[Request]:
    n = max(1, int(round(rate_rps * length)))

    def quantiles() -> List[float]:
        us = [(i + 0.5) / n for i in range(n)]
        rng.shuffle(us)
        return us

    ins = [_length(mix["input_tokens"], u) for u in quantiles()]
    outs = [_length(mix["output_tokens"], u) for u in quantiles()]
    gaps = [-math.log(1.0 - u) for u in quantiles()]      # Poisson arrivals
    scale = length / sum(gaps)
    reqs, t = [], start
    for i in range(n):
        reqs.append(Request(first_index + i, t, ins[i], outs[i],
                            rng.getrandbits(31)))
        t += gaps[i] * scale
    return reqs


def schedule(mix: dict, rate_rps: float, seed: int, lead_in_s: float,
             seconds: float) -> List[Request]:
    """Requests due in [-lead_in_s, seconds).  Those due before 0 bring the
    system to its steady state and are not judged."""
    if rate_rps <= 0:
        raise ValueError("rate must be positive")
    rng = random.Random(seed)
    lead = (_segment(mix, rate_rps, rng, -lead_in_s, lead_in_s, 0)
            if lead_in_s > 0 else [])
    return lead + _segment(mix, rate_rps, rng, 0.0, seconds, len(lead))


def prompt_ids(req: Request, vocab_size: int, reserved=()) -> List[int]:
    """Seeded unshared prompt: token ids in [1, vocab), none of them one of
    the configuration's `reserved` ids (a word of the vocabulary the model
    gives a meaning of its own, such as a mask token).  The reserved ones are
    struck from the draw and made up from the same stream, so with none
    reserved the ids, and the time it takes to draw them, are what they
    always were."""
    rng = random.Random(req.prompt_seed)
    ids = [rng.randrange(1, vocab_size) for _ in range(req.n_in)]
    if reserved:
        reserved = frozenset(reserved)
        ids = [t for t in ids if t not in reserved]
        while len(ids) < req.n_in:
            token = rng.randrange(1, vocab_size)
            if token not in reserved:
                ids.append(token)
    return ids
