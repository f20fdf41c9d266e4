"""Last-prompt-position logits against a full causal forward pass, then every
greedy token the engine decodes through its cache, for a causal engine over
ROUTED experts at prompts up to the cell's longest: the comparison of a
configuration whose engine yields one token a sequence a step, chooses
experts by top-k, and serves contexts of thousands of tokens.  The reference
is the one the configuration names; `chipbench/check.py` loads both and holds
the result to its contract.

It is `causal_logits_greedy_margin` with three things added.

- The engine's expert choices go to the reference.  bfloat16 flips which
  expert is the k-th largest on seeded weights (a seventh of the
  token-layers here by arithmetic: the 4th and 5th of 64 scores lie 0.02
  apart, bfloat16 activations move a score by 0.003), and a float32 forward
  that chose for itself would measure the flips and not the arithmetic.  The
  engine's recording (`EngineCore.block_record`) holds, for every prefill
  chunk and for every step of every decode window or single step, the
  experts each token chose in each expert layer; every position of prompt +
  decoded tokens must be covered, or the prompt is a failure, not a skip.
  The scores, the weights and all arithmetic are the reference's own.
- Taking the engine's choices must not hide a router that chooses wrongly
  (the correction bias left out, a choice by `s` and not by `s + b`: with
  the same choices on both sides the logits agree).  So the reference also
  says how far each expert it was given lies under ITS k-th best float32
  `s + b` for that token (`forward(..., shortfall=True)`), 0 where it is
  among its own k best; the largest over layers, tokens and prompts is held
  to SHORTFALL_CHOICE.
- LENGTHS reach 12,000 tokens: 24 chunks of prefill through the paged cache,
  then decoding behind 188 pages.  The reference computes such a sequence in
  blocks and hands back the logits of the positions asked for.

A seeded sample of ragged prompts runs prefill and then a few decode steps
through the very EngineCore the server is about to use (same params, same
cache pool, same kernels, the fused decode window).  Compared:

- the logits at the last prompt position (all of the vocabulary) of EVERY
  prompt: max |difference| <= ATOL_LOGITS, and the median over the
  vocabulary <= ATOL_BODY;
- every greedy token the engine then decodes through its cache: the
  reference, fed the prompt plus the engine's earlier tokens, must rate that
  token within MARGIN_LOGITS of its own best one (2 x ATOL_LOGITS);
- every expert the engine chose at every position of every expert layer:
  its shortfall under the reference's k-th best `s + b` <= SHORTFALL_CHOICE.

Tolerances.  The engine computes in bfloat16 with float32 accumulation, the
reference in float32.  With seeded N(0, 1/fan_in) weights the logits are
~N(0, 1).  Read on a TPU v5 lite at the published widths, eight layers, six
prompts of 5-12,000 tokens a seed (my chip runs, PR 36; PERF.md section 6):

                      sound, 21 seeds     control (latent rows   limit
                                          through 8 bits), 2 seeds
  max |difference|    0.0900 - 0.1291     0.4520 - 0.4747        0.16
  median over vocab   0.0136 - 0.0158     0.0640 - 0.0666        0.025
  decode margin       0 - 0.0471          0.1166 - 0.1906        0.32

The control is the same engine with each latent row rounded through
float8_e4m3 as it is written to the cache: the nearest precision below the
bfloat16 the configuration states for the part this configuration adds.
Each of the first two limits lies between its two readings with room on both
sides (the largest sound reading is 81 % and 63 % of its limit, the smallest
control reading 2.8 and 2.6 times it); the control is refused by both.  The
margin is a bound that follows from ATOL_LOGITS, not a read limit.

The shortfall has a control of its own, because the rounded cache moves no
choice's rank by more than bfloat16 does: the same engine with the router's
correction bias left out (it then chooses by `s`).  On the chip that engine
meets the three limits above (0.101, 0.0143, 0.043) and is refused by this
one alone.  Read at the published widths, eight layers, bfloat16 (my runs,
PR 36; the limit was set from the CPU readings, before the chip's):

                      sound                          control     limit
  choice shortfall    chip, 8 seeds, six prompts     chip, one   0.08
                      of 5 - 12,000 tokens:          seed:
                      0.0154 - 0.0225                0.4363
                      CPU, one prompt a reading:     (0.294 at 5
                      0.0020 - 0.0054 (5 tokens)     tokens to
                      0.0122 - 0.0131 (700)          0.436 at
                      0.0176 (3,000)                 12,000);
                      0.0186 (12,000)                CPU 0.334 -
                                                     0.339

It is a maximum over every token-layer and grows with their number, so the
limit is not "a few bfloat16 ulps of a score" (0.01 - 0.02), which a sound
engine passes at length: the largest sound reading is 28 % of the limit,
the control 5.5 times it.

Prompt ids are drawn from [1, vocab) and none is one of the configuration's
`reserved_token_ids`."""

from __future__ import annotations

import time

import numpy as np

# Set from the chip runs of PR 36 (the tables above).
ATOL_LOGITS = 0.16
ATOL_BODY = 0.025
MARGIN_LOGITS = 2 * ATOL_LOGITS   # the engine's best is at most 2A under
SHORTFALL_CHOICE = 0.08      # its own readings: the docstring's last table
LENGTHS = (5, 17, 129, 700, 3000, 12000)
DECODE_TOKENS = 9            # 1 from prefill + one 8-step window
# Reference sequences are padded to a multiple of this (the reference's token
# block): six prompts then show it three lengths, and a first start builds a
# dozen reference programs and not three dozen.
PAD_TO = 1024


def _drive(core, prompts, max_tokens):
    """Greedy-generate each prompt with the engine's recording on; returns
    ({rid: tokens}, {rid: f32 logits row that chose the first token}, the
    record)."""
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
    core.block_record = record = []
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
        core.block_record = None
    return tokens, logits, record


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


def _choices(record, lengths: dict) -> dict:
    """{rid: [L, n, k] the experts the engine chose at each of the first n
    positions of the request, -1 where the record holds none}.  A prefill
    entry covers a chunk's positions, a decode entry the position each step
    of the call fed."""
    import jax

    out = {}

    def room(rid, routing_shape):
        if rid not in out:
            layers, k = routing_shape
            out[rid] = np.full((layers, lengths[rid], k), -1, np.int32)
        return out[rid]

    for entry in record:
        if entry.get("prefill"):
            for rid, start, routing in zip(entry["rids"], entry["starts"],
                                           entry["routing"]):
                if rid not in lengths:
                    continue
                r = np.asarray(routing, np.int32)          # [L, n, k]
                dst = room(rid, (r.shape[0], r.shape[2]))
                end = min(start + r.shape[1], dst.shape[1])
                if end > start:
                    dst[:, start:end] = r[:, : end - start]
        elif entry.get("decode"):
            r = np.asarray(jax.device_get(entry["routing"]), np.int32)
            for rid, row, first in zip(entry["rids"], entry["rows"],
                                       entry["starts"]):     # [K, L, rows, k]
                if rid not in lengths:
                    continue
                dst = room(rid, (r.shape[1], r.shape[3]))
                for step in range(r.shape[0]):
                    if 0 <= first + step < dst.shape[1]:
                        dst[:, first + step] = r[step, :, row]
    return out


def run(core, hf: dict, seed: int, lengths, reference,
        decode_tokens: int = DECODE_TOKENS) -> dict:
    import jax

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    vocab = hf["vocab_size"]
    prompts = _prompts(rng, vocab, lengths, hf.get("reserved_token_ids", ()))
    tokens, logits, record = _drive(core, prompts, decode_tokens)
    t_engine = time.monotonic() - t0
    rids = [f"chipbench-check-{i}" for i in range(len(prompts))]
    # The reference is fed prompt + all decoded tokens but the last.
    chosen = _choices(record, {rid: len(p) + decode_tokens - 1
                               for rid, p in zip(rids, prompts)})
    rows, problems = [], []
    for rid, prompt in zip(rids, prompts):
        got = tokens[rid]
        if len(got) != decode_tokens:
            problems.append(f"{rid}: {len(got)} tokens, wanted {decode_tokens}")
            continue
        if rid not in logits:
            problems.append(f"{rid}: the engine handed over no prefill logits")
            continue
        seq = prompt + got[:-1]
        n = len(prompt)
        mine = chosen.get(rid)
        if mine is None or (mine < 0).any():
            missing = (len(seq) if mine is None
                       else int((mine < 0).any(axis=(0, 2)).sum()))
            problems.append(f"{rid}: the recording holds no expert choices "
                            f"for {missing} of {len(seq)} positions")
            continue
        pad = -(-len(seq) // PAD_TO) * PAD_TO
        full = np.full((mine.shape[0], pad, mine.shape[2]), -1, np.int32)
        full[:, :len(seq)] = mine
        ref, short = reference.forward(
            hf, core.params, seq + [0] * (pad - len(seq)), choices=full,
            positions=list(range(n - 1, len(seq))), shortfall=True)
        ref = np.asarray(jax.device_get(ref), dtype=np.float32)
        got_row = logits[rid]
        if not np.isfinite(ref).all() or got_row.shape != ref[0].shape \
                or not np.isfinite(got_row).all():
            problems.append(f"{rid}: logits misshapen or not finite")
            continue
        d = np.abs(got_row - ref[0])
        rows.append({
            "len": n, "logit_diff_max": float(d.max()),
            "logit_diff_median": float(np.median(d)),
            "choice_shortfall": float(short),
            "decode": [float(ref[j].max() - ref[j][tok])
                       for j, tok in enumerate(got)]})

    worst_logit = max((r["logit_diff_max"] for r in rows), default=0.0)
    worst_body = max((r["logit_diff_median"] for r in rows), default=0.0)
    worst_margin = max((m for r in rows for m in r["decode"]), default=0.0)
    worst_short = max((r["choice_shortfall"] for r in rows), default=0.0)
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
    if not worst_short <= SHORTFALL_CHOICE:
        problems.append(f"an expert the engine chose lies {worst_short:.4f} "
                        "under the reference's k-th best score + bias (> "
                        f"{SHORTFALL_CHOICE})")
    return {"ok": not problems, "problems": problems,
            "prompts": len(prompts), "lengths": list(lengths), "rows": rows,
            "compared": len(rows),
            "limits": [
                {"name": "max_abs_logit_diff", "value": worst_logit,
                 "limit": ATOL_LOGITS},
                {"name": "max_body_logit_diff", "value": worst_body,
                 "limit": ATOL_BODY},
                {"name": "max_decode_margin", "value": worst_margin,
                 "limit": MARGIN_LOGITS},
                {"name": "max_choice_shortfall", "value": worst_short,
                 "limit": SHORTFALL_CHOICE}],
            "engine_s": t_engine, "total_s": time.monotonic() - t0}
