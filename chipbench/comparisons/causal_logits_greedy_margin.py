"""Last-prompt-position logits against a full causal forward pass, then
every greedy token the engine decodes through its cache: the comparison of a
configuration whose engine yields one token a sequence a step under a causal
mask.  The reference is the one the configuration names; `chipbench/check.py`
loads both and holds the result to its contract.

A seeded sample of ragged prompts runs prefill and then a few decode steps
through the very EngineCore the server is about to use (same params, same
cache pool, same kernel planes, the fused decode window).  Compared:

- the logits at the last prompt position (all of the vocabulary) of EVERY
  prompt with the reference's full causal forward pass: max |difference|
  <= ATOL_LOGITS, and the median over the vocabulary <= ATOL_BODY.  A
  prompt whose logits the engine did not hand over is a failure, not a
  skip;
- every greedy token the engine then decodes through its paged cache: the
  reference, fed the prompt plus the engine's earlier tokens, must rate that
  token within MARGIN_LOGITS of its own best one (2 x ATOL_LOGITS: if no
  logit is further than A from the reference, the engine's best token is at
  most 2A under the reference's best).

Tolerances.  The engine computes in bfloat16 (8 significant bits) with
float32 accumulation; the reference in float32.  With seeded N(0, 1/fan_in)
weights the logits are ~N(0, 1), |max| near 4.5.  Measured on a TPU v5 lite
over 14 runs of 8 prompts, 5 to 700 tokens, 16 layers (my chip runs, PR 23):
worst |difference| 0.060 to 0.072, worst median over the vocabulary 0.009,
a decoded token at most 0.035 under the reference's best.  ATOL_LOGITS is
1.25 x the worst measured; with an int8 KV cache the program's own kernels
read 0.0967 against 0.0763 in bf16 (PR 21's parity run, CHANGES.md), which
this refuses.  ATOL_BODY is twice its worst measured.  A bound this close
can refuse a seed whose weights read a little worse than the 14 seen: then
the run says `correct: false` with the figures, which is the safe side.

Prompt ids are drawn from [1, vocab) and none is one of the configuration's
`reserved_token_ids` (absent = none: the draw is then what it always was)."""

from __future__ import annotations

import time

import numpy as np

ATOL_LOGITS = 0.09           # 1.25 x the worst of 14 chip runs (0.072)
ATOL_BODY = 0.02             # 2 x the worst median over the vocabulary
MARGIN_LOGITS = 2 * ATOL_LOGITS   # the engine's best is at most 2A under
LENGTHS = (5, 17, 64, 100, 129, 300, 511, 700)
DECODE_TOKENS = 9            # 1 from prefill + one 8-step window


def _drive(core, prompts, max_tokens):
    """Greedy-generate each prompt; returns ({rid: tokens}, {rid: f32 logits
    row that chose the first token})."""
    from dynamo_tpu.engine.sampling import SamplingParams

    logits = {}
    finish = core._finish_prefill_items     # moved internal: fail, not skip

    def capture(items, rows, *a, **kw):
        host = np.asarray(rows, dtype=np.float32)
        for i, work in enumerate(items):
            if work.start + work.length == len(work.request.prompt_tokens):
                logits[work.request.request_id] = host[i]
        return finish(items, rows, *a, **kw)

    core._finish_prefill_items = capture
    try:
        for i, p in enumerate(prompts):
            core.add_request(f"chipbench-check-{i}", p,
                             SamplingParams(max_tokens=max_tokens))
        tokens = {f"chipbench-check-{i}": [] for i in range(len(prompts))}
        while core.has_work:
            for delta in core.step():
                tokens[delta.request_id].extend(delta.token_ids)
    finally:
        core._finish_prefill_items = finish
    return tokens, logits


def _prompts(rng, vocab: int, lengths, reserved) -> list:
    """One seeded prompt per length, ids in [1, vocab) and none reserved."""
    reserved = np.asarray(sorted(reserved), dtype=np.int64)
    prompts = []
    for n in lengths:
        ids = rng.integers(1, vocab, size=n)
        bad = np.isin(ids, reserved)
        while bad.any():
            ids[bad] = rng.integers(1, vocab, size=int(bad.sum()))
            bad = np.isin(ids, reserved)
        prompts.append(ids.tolist())
    return prompts


def run(core, hf: dict, seed: int, lengths, reference,
        decode_tokens: int = DECODE_TOKENS) -> dict:
    import jax

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    vocab = hf["vocab_size"]
    prompts = _prompts(rng, vocab, lengths, hf.get("reserved_token_ids", ()))
    tokens, logits = _drive(core, prompts, decode_tokens)
    t_engine = time.monotonic() - t0
    pad_to = -(-(max(lengths) + decode_tokens) // 128) * 128
    rows, problems = [], []
    for i, prompt in enumerate(prompts):
        rid = f"chipbench-check-{i}"
        got = tokens[rid]
        if len(got) != decode_tokens:
            problems.append(f"{rid}: {len(got)} tokens, wanted {decode_tokens}")
            continue
        if rid not in logits:
            problems.append(f"{rid}: the engine handed over no prefill logits")
            continue
        seq = prompt + got[:-1]
        ref = reference.forward(hf, core.params,
                                seq + [0] * (pad_to - len(seq)))
        ref = np.asarray(jax.device_get(ref))[:len(seq)]
        n = len(prompt)
        got_row = logits[rid]
        if not np.isfinite(ref).all() or got_row.shape != ref[n - 1].shape \
                or not np.isfinite(got_row).all():
            problems.append(f"{rid}: logits misshapen or not finite")
            continue
        d = np.abs(got_row - ref[n - 1])
        rows.append({
            "len": n, "logit_diff_max": float(d.max()),
            "logit_diff_median": float(np.median(d)),
            "decode": [float(ref[n - 1 + j].max() - ref[n - 1 + j][tok])
                       for j, tok in enumerate(got)]})

    worst_logit = max((r["logit_diff_max"] for r in rows), default=0.0)
    worst_body = max((r["logit_diff_median"] for r in rows), default=0.0)
    worst_margin = max((m for r in rows for m in r["decode"]), default=0.0)
    if len(rows) != len(prompts):
        problems.append(f"{len(rows)} of {len(prompts)} prompts compared")
    if worst_logit > ATOL_LOGITS:
        problems.append(f"prefill logits differ by {worst_logit:.4f} > "
                        f"{ATOL_LOGITS}")
    if worst_body > ATOL_BODY:
        problems.append("a row's median |logit difference| over the "
                        f"vocabulary is {worst_body:.4f} > {ATOL_BODY}")
    if worst_margin > MARGIN_LOGITS:
        problems.append(f"a decoded token sits {worst_margin:.4f} under the "
                        f"reference's best (> {MARGIN_LOGITS})")
    return {"ok": not problems, "problems": problems,
            "prompts": len(prompts), "lengths": list(lengths), "rows": rows,
            "prefill_logits_compared": len(rows), "compared": len(rows),
            "limits": [
                {"name": "max_abs_logit_diff", "value": worst_logit,
                 "limit": ATOL_LOGITS},
                {"name": "max_body_logit_diff", "value": worst_body,
                 "limit": ATOL_BODY},
                {"name": "max_decode_margin", "value": worst_margin,
                 "limit": MARGIN_LOGITS}],
            "max_abs_logit_diff": worst_logit, "atol_logits": ATOL_LOGITS,
            "max_body_logit_diff": worst_body, "atol_body": ATOL_BODY,
            "max_decode_margin": worst_margin, "margin_logits": MARGIN_LOGITS,
            "engine_s": t_engine, "total_s": time.monotonic() - t0}
