"""What decides `correct`, found by name, and the contract every comparison
is held to.  Runs in the worker process, on the engine it is about to serve
with, before it serves.

A configuration names a `reference` (`references/<name>.py`: `forward(hf,
params, tokens, **kw) -> float32 logits`, plain `jax.numpy` at "highest"
matmul precision) and a `comparison` (`comparisons/<name>.py`: `LENGTHS`, and
`run(core, hf, seed, lengths, reference) -> dict`).  The comparison drives
the engine and the reference and says what it read; this file loads the two
and believes no `ok` it cannot check.  A result has

- `ok` (bool) and `problems` (list of str);
- `prompts`, how many it was asked to compare, and `compared`, how many it
  did;
- `limits`: a non-empty list of {`name`, `value`, `limit`}, every number
  compared beside the tolerance it was read against (`value <= limit` is the
  sound side; a quantity that must stay high is compared as its shortfall).

`hold` turns a result to not `ok`, with the reason among its `problems`,
where it compared fewer prompts than were asked, names no limit, holds a
value over its own limit or a number that is not finite, or says `ok` beside
problems.  Whatever else a comparison reports (per-prompt rows, timings, its
limits again under names of its own) is passed through."""

from __future__ import annotations

import math

from chipbench import pieces


def hold(result, asked: int) -> dict:
    """The comparison's result, `ok` only where the contract bears it out."""
    if not isinstance(result, dict):
        return {"ok": False, "prompts": asked, "compared": 0, "limits": [],
                "problems": [f"the comparison returned {type(result).__name__}"
                             ", not a dict"]}
    out = dict(result)
    problems = [str(p) for p in out.get("problems") or []]
    if out.get("ok") is not True and not problems:
        problems.append("the comparison says neither ok nor why not")
    if out.get("prompts") != asked:
        problems.append(f"asked to compare {asked} prompts, the comparison "
                        f"reports {out.get('prompts')!r}")
    if out.get("compared") != asked:
        problems.append(f"{out.get('compared')!r} of {asked} prompts compared")
    limits = out.get("limits")
    if not isinstance(limits, list) or not limits:
        problems.append("the comparison names no limit")
        limits = []
    for item in limits:
        try:
            name, value, limit = item["name"], item["value"], item["limit"]
            finite = math.isfinite(value) and math.isfinite(limit)
        except (TypeError, KeyError):
            problems.append(f"a limit without name, value and limit: {item!r}")
            continue
        if not finite:
            problems.append(f"{name}: {value!r} against {limit!r} is not "
                            "finite")
        elif value > limit:
            problems.append(f"{name}: {value!r} is over its limit {limit!r}")
    out["problems"] = problems
    out["ok"] = not problems
    return out


def run_check(core, hf: dict, seed: int, lengths=None,
              root: str = pieces.HERE) -> dict:
    """Load the reference and the comparison `hf` names from under `root`,
    run the comparison over `lengths` (its own `LENGTHS` if none are given)
    and hold what it returns to the contract."""
    names = pieces.named(hf, root)
    reference = pieces.load("references", names["reference"], root,
                            needs=("forward",))
    comparison = pieces.load("comparisons", names["comparison"], root,
                             needs=("run", "LENGTHS"))
    lengths = tuple(lengths) if lengths else tuple(comparison.LENGTHS)
    out = hold(comparison.run(core, hf, seed, lengths, reference),
               len(lengths))
    out.update(reference=names["reference"], comparison=names["comparison"])
    return out
