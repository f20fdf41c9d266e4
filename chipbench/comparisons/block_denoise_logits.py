"""Every forward of every block the engine denoises, against the reference's
block-causal forward over the same tokens: the comparison of a configuration
whose engine generates by diffusion over blocks.  The reference is the one
the configuration names; `chipbench/check.py` loads both and holds the
result to its contract.

A seeded sample of ragged prompts (lengths with `n % B` zero and not) is
generated through the very EngineCore the server is about to use (same
params, same cache pool, same kernels, the same block program from the
program store's builder with its recording output on).  The engine hands
over, for each forward of each block call: the tokens it fed, which positions
were still masked, the logits at all B positions, and the experts every
token chose in every layer; and for each prefill chunk the experts chosen
there.  Compared, for every forward (denoising and commit):

- the logits at the B positions of the block with the reference's forward
  over prompt + committed blocks + the block as fed, under the engine's
  expert choices (bfloat16 flips the k-th expert on seeded weights; the
  gates and all arithmetic are the reference's own float32): max
  |difference| <= ATOL_LOGITS and the median over the vocabulary
  <= ATOL_BODY;
- every token a denoising forward decides: the reference rates it within
  MARGIN_LOGITS of its own best token at that position (2 x ATOL_LOGITS:
  if no logit is further than A from the reference, the engine's best is at
  most 2A under the reference's best), the mask token excluded on both
  sides (its logit is -inf before the argmax: the one departure from the
  published sampler, so that a generated token is never the mask);
- the order of unmasking: by the reference's log-confidences
  (`log softmax(logits)[argmax]` at the masked positions), no position left
  masked is more than MARGIN_LOGITS more confident than one that was
  decided, and the number decided is what the rule says
  (`low_confidence_static`: B / denoising_steps, or all that are left);
- the bookkeeping: each block call starts where the last one committed, its
  last forward (the commit) is fed no mask and exactly the tokens the call
  returned, and the stream is the blocks' tokens in order, cut at
  `max_tokens`.

The programs that hand out logits are twins of the served ones (the same
builder; a trail of logits is 0.8 GB at 64 rows, so six prompts in the
smallest row bucket).  So six more prompts of the same lengths are generated
through the block programs the window drives (and its prefill: nothing of
them is in the prefix cache), behind SERVED_ROWS - 6 seeded filler
prompts that are still decoding when they arrive: the compared rows are the
last of block calls at the cell's rows (row bucket 64, the expert kernel's
larger tiles).  A served program's trail has everything above but the
logits (the tokens fed, the positions masked, the experts chosen), so every
forward of the served pass is held to the reference in the same way, less
the two logits limits: each token it decides and the order it decides them
in by the reference's own logits (`max_served_margin`, `max_served_order`,
the same MARGIN_LOGITS), and the bookkeeping.  (Holding a served stream to
its recorded twin's was tried first and is not sound: bfloat16 at another
batch size flips near ties, most often which *position* of a block is the
most confident, after which the two blocks are denoised under other
contexts and differ freely: 1-4 of 6 streams parted in every seed on the
chip, PERF.md section 6.)  A prompt counts as `compared` only if both passes
held it.

Tolerances.  The engine computes in bfloat16 with float32 accumulation
(kernels, grouped experts), the reference in float32.  With seeded
N(0, 1/fan_in) weights the logits are ~N(0, 1).  Read on a TPU v5 lite at the
published widths, seven layers, six prompts of 5-511 tokens and 70 forwards a
seed and a pass (my chip runs, PR 27; PERF.md section 6):

                      sound, 49 seeds    control (int8 KV), 6 seeds   limit
  max |difference|    0.0373 - 0.0503    0.0673 - 0.0831              0.058
  median over vocab   0.0051 - 0.0058    0.0091 - 0.0099              0.0074
  unmask margin       0 - 0.028          0 - 0.048                    0.116
  unmask order        0 - 0.023          0 - 0.022                    0.116
  served margin       0 - 0.014 (17 seeds, 43-47 rows a call)         0.116
  served order        0 - 0.014                                       0.116

Each of the first two limits lies between its two readings with room on both
sides (15 % and 27 % over the largest sound reading; the control's smallest
is 16 % and 23 % over the limit): the int8 cache is refused by both.  The
precision below the configuration's separates from it by 1.34 x on the
maximum and 1.57 x on the median, not by the 3 x a contract would like: with
seven layers the cache's rounding is a smaller part of the whole than the
weights' and activations'.  The margins are bounds that follow from
ATOL_LOGITS, not read limits."""

from __future__ import annotations

import time

import numpy as np

# Set from the chip runs of PR 27 (the table above): the largest the sound
# seeds gave beside the smallest the int8-KV control gave.
ATOL_LOGITS = 0.058
ATOL_BODY = 0.0074
MARGIN_LOGITS = 2 * ATOL_LOGITS   # the engine's best is at most 2A under
LENGTHS = (5, 17, 64, 130, 300, 511)    # n % 4 = 1, 1, 0, 2, 0, 3
GEN_TOKENS = 7       # no multiple of the block length: the last block is cut
PAD_TO = 128         # reference sequences are padded to a multiple of this
# The served pass: rows a block call of the cell holds (40-50 at 0.8 x knee,
# PERF.md section 4), as many as the engine admits at most.  Fillers are
# short, generate until the compared prompts are through (or the context
# ends) and are cancelled then.
SERVED_ROWS = 48
FILLER_LENGTHS = (24, 40, 56)
FILLER_TOKENS = 256


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


def _generate(core, requests, behind=()):
    """Greedy-generate [(rid, prompt, max_tokens)]; returns ({rid: tokens},
    {rid: the fewest requests that got tokens in a step in which it did}:
    the rows of the smallest block call it was part of).  `behind` are
    requests to keep decoding around them: added first, the others once
    every one of these streams, and cancelled when the others are through."""
    from dynamo_tpu.engine.sampling import SamplingParams

    def add(batch):
        for rid, prompt, max_tokens in batch:
            core.add_request(rid, prompt,
                             SamplingParams(max_tokens=max_tokens))

    tokens = {rid: [] for rid, _p, _n in list(behind) + list(requests)}
    company = {}
    waiting, around = list(requests), [rid for rid, _p, _n in behind]
    add(behind)
    while core.has_work or waiting:
        if waiting and (not core.has_work
                        or all(tokens[rid] for rid in around)):
            add(waiting)
            waiting = []
            continue
        deltas = core.step()
        got = {d.request_id for d in deltas if d.token_ids}
        for delta in deltas:
            tokens[delta.request_id].extend(delta.token_ids)
        for rid in got:
            company[rid] = min(company.get(rid, len(got)), len(got))
        if around and not waiting and all(
                len(tokens[rid]) >= n for rid, _p, n in requests):
            for rid in around:
                core.cancel(rid)
            around = []
    return tokens, company


def _drive(core, prompts, max_tokens):
    """Greedy-generate each prompt with the engine's recording on; returns
    ({rid: tokens}, the record)."""
    core.block_record = record = []
    try:
        tokens, _ = _generate(core, [
            (f"chipbench-check-{i}", p, max_tokens)
            for i, p in enumerate(prompts)])
    finally:
        core.block_record = None
    return tokens, record


def _drive_served(core, rng, prompts, max_tokens, vocab, reserved):
    """`prompts` through the served programs (their trail has no logits),
    the last rows of block calls that hold SERVED_ROWS rows where the
    engine admits as many; returns `_generate`'s pair and the record."""
    rows = min(SERVED_ROWS, core.scheduler.config.max_seqs)
    n_fill = max(0, rows - len(prompts))
    fill = _prompts(rng, vocab, [FILLER_LENGTHS[i % len(FILLER_LENGTHS)]
                                 for i in range(n_fill)], reserved)
    sched = core.scheduler.config
    room = sched.max_pages_per_seq * sched.block_size - max(FILLER_LENGTHS)
    record = core.block_record = []
    core.block_record_logits = False
    try:
        tokens, company = _generate(
            core,
            [(f"chipbench-served-{i}", p, max_tokens)
             for i, p in enumerate(prompts)],
            behind=[(f"chipbench-fill-{i}", p, min(FILLER_TOKENS, room))
                    for i, p in enumerate(fill)])
    finally:
        core.block_record, core.block_record_logits = None, True
    return tokens, company, record


def _ref_logits(reference, hf, params, tokens, choices, start, block):
    """The reference's logits at [start, start + block) of `tokens`, padded
    behind (a later position changes nothing before it)."""
    import jax

    n = len(tokens)
    pad = -(-n // PAD_TO) * PAD_TO
    kw = {}
    if choices is not None:
        full = np.full((choices.shape[0], pad, choices.shape[2]), -1,
                       np.int32)
        full[:, :n] = choices
        kw["choices"] = full
    out = reference.forward(hf, params, list(tokens) + [0] * (pad - n),
                            positions=list(range(start, start + block)),
                            **kw)
    return np.asarray(jax.device_get(out), dtype=np.float32)


def run(core, hf: dict, seed: int, lengths, reference,
        gen_tokens: int = GEN_TOKENS) -> dict:
    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    vocab = hf["vocab_size"]
    block = int(hf["diffusion_block_length"])
    steps = int(hf.get("denoising_steps", block))
    per_step = max(1, block // steps)
    dynamic = hf.get("remasking") == "low_confidence_dynamic"
    threshold = float(hf.get("confidence_threshold", 0.9))
    mask_id = int(hf["mask_token_id"])
    reserved = hf.get("reserved_token_ids", ())
    prompts = _prompts(rng, vocab, lengths, reserved)
    streamed, record = _drive(core, prompts, gen_tokens)
    # Other prompts of the same lengths: the recorded ones now sit in the
    # prefix cache, and a served prefill that starts behind cached pages
    # would show nothing of the experts chosen there.
    served_prompts = _prompts(rng, vocab, lengths, reserved)
    served, company, served_record = _drive_served(
        core, rng, served_prompts, gen_tokens, vocab, reserved)
    t_engine = time.monotonic() - t0

    problems = []
    faults = 0           # bookkeeping faults: each is a problem, limit 0

    def hold(record, rids, prompts, streamed):
        """One pass's record against the reference: ({rid: what was read},
        the rids that failed).  A record without logits (the served
        programs') is held by its decisions and its bookkeeping alone."""
        prompt_of = dict(zip(rids, prompts))
        committed = {r: [] for r in rids}      # tokens in the cache, in order
        chosen = {r: None for r in rids}       # [L, len(committed), k] or None
        decided_tokens = {r: [] for r in rids}  # what the blocks produced
        rows = {r: {"len": len(prompt_of[r]), "forwards": 0,
                    "logit_diff_max": 0.0, "logit_diff_median": 0.0,
                    "margin": 0.0, "order": 0.0} for r in rids}
        bad = set()

        def fail(rid, msg):
            nonlocal faults
            faults += 1
            problems.append(f"{rid}: {msg}")
            bad.add(rid)

        def extend(rid, toks, routing):
            committed[rid] = committed[rid] + [int(t) for t in toks]
            if routing is not None:
                r = np.asarray(routing, np.int32)
                chosen[rid] = r if chosen[rid] is None else np.concatenate(
                    [chosen[rid], r], axis=1)

        for entry in record:
            if entry.get("prefill"):
                for rid, start, routing in zip(entry["rids"], entry["starts"],
                                               entry["routing"]):
                    if rid not in committed:
                        continue
                    if start != len(committed[rid]):
                        fail(rid, f"a prefill chunk starts at {start}, the "
                                  f"cache holds {len(committed[rid])}")
                        continue
                    n = routing.shape[1]
                    extend(rid, prompt_of[rid][start: start + n], routing)
                continue
            for i, rid in enumerate(entry["rids"]):
                if rid not in committed or rid in bad:
                    continue
                start, known = entry["starts"][i], entry["known"][i]
                if start != len(committed[rid]):
                    fail(rid, f"a block starts at {start}, the cache holds "
                              f"{len(committed[rid])}: a block was left "
                              "uncommitted or committed twice")
                    continue
                n_fwd = entry["forwards"]
                final = entry["tokens"][i]
                routing = entry.get("routing")
                row = rows[rid]
                for f in range(n_fwd):
                    fed = entry["fed"][f, i]
                    masked = entry["masked"][f, i].astype(bool)
                    got = (entry["logits"][f, i] if "logits" in entry
                           else None)
                    r_f = (None if routing is None
                           else routing[f][:, i * block:(i + 1) * block])
                    pre = chosen[rid]
                    choices = None
                    if r_f is not None:
                        choices = r_f if pre is None else np.concatenate(
                            [pre, r_f], axis=1)
                    ref = _ref_logits(reference, hf, core.params,
                                      committed[rid] + [int(t) for t in fed],
                                      choices, start, block)
                    if not np.isfinite(ref).all() or (got is not None and (
                            got.shape != ref.shape
                            or not np.isfinite(got).all())):
                        fail(rid, "logits misshapen or not finite")
                        break
                    row["forwards"] += 1
                    if got is not None:
                        d = np.abs(got - ref)
                        row["logit_diff_max"] = max(row["logit_diff_max"],
                                                    float(d.max()))
                        row["logit_diff_median"] = max(
                            row["logit_diff_median"],
                            float(np.median(d, axis=-1).max()))
                    commit = f == n_fwd - 1
                    if commit:
                        if masked.any() or (fed == mask_id).any():
                            fail(rid, "the commit forward was fed a mask: the "
                                      "block was committed undecided")
                        if not np.array_equal(fed, final):
                            fail(rid, "the commit forward was fed other "
                                      "tokens than the call returned")
                        continue
                    nxt = entry["fed"][f + 1, i]
                    still = entry["masked"][f + 1, i].astype(bool)
                    decided = masked & ~still
                    want = min(per_step, int(masked.sum()))
                    ref_m = ref.copy()
                    ref_m[:, mask_id] = -np.inf
                    best = ref_m.max(axis=-1)
                    lse = np.log(np.exp(ref_m - best[:, None]).sum(-1)) + best
                    conf = best - lse                 # log-confidence, [B]
                    if dynamic:
                        over = int((np.exp(conf[masked]) > threshold).sum())
                        want = over if over >= per_step else want
                        # The engine counts by its own confidences: near the
                        # threshold the two may differ by a position.
                        if abs(int(decided.sum()) - want) > (over > 0):
                            fail(rid, f"forward {f} decided "
                                      f"{int(decided.sum())}, the rule says "
                                      f"{want}")
                    elif int(decided.sum()) != want:
                        fail(rid, f"forward {f} decided {int(decided.sum())} "
                                  f"positions, the rule says {want}")
                    if (still & ~masked).any() or (
                            nxt[~decided] != fed[~decided]).any():
                        fail(rid, f"forward {f} masked a decided position "
                                  "again or changed one it did not decide")
                    for pos in np.flatnonzero(decided):
                        row["margin"] = max(row["margin"], float(
                            best[pos] - ref_m[pos, int(nxt[pos])]))
                    if decided.any() and still.any():
                        row["order"] = max(row["order"], float(
                            conf[still].max() - conf[decided].min()))
                if rid in bad:
                    continue
                commit_routing = (None if routing is None else
                                  routing[n_fwd - 1][:, i * block:
                                                     (i + 1) * block])
                extend(rid, final, commit_routing)
                decided_tokens[rid].extend(int(t) for t in final[known:])

        for rid in rids:
            if rid in bad:
                continue
            want = decided_tokens[rid][:gen_tokens]
            if len(want) != gen_tokens or streamed[rid] != want:
                fail(rid, f"streamed {len(streamed[rid])} tokens "
                          f"{streamed[rid]!r}, the blocks hold {want!r}")
            elif rows[rid]["forwards"] == 0:
                fail(rid, "no forward of it was recorded")
        return rows, bad

    n = len(prompts)
    rows, bad = hold(record, [f"chipbench-check-{i}" for i in range(n)],
                     prompts, streamed)
    srows, sbad = hold(served_record,
                       [f"chipbench-served-{i}" for i in range(n)],
                       served_prompts, served)
    # One row a prompt: the recorded pass's readings, the served pass's
    # beside them, sound only if both held.
    merged, done = [], []
    for i in range(n):
        rid, twin = f"chipbench-check-{i}", f"chipbench-served-{i}"
        row = dict(rows[rid], served_forwards=srows[twin]["forwards"],
                   served_margin=srows[twin]["margin"],
                   served_order=srows[twin]["order"],
                   served_rows=company.get(twin, 0))
        merged.append(row)
        if rid not in bad and twin not in sbad:
            done.append(row)
    worst = {k: max((r[k] for r in done), default=0.0)
             for k in ("logit_diff_max", "logit_diff_median", "margin",
                       "order", "served_margin", "served_order")}
    if worst["logit_diff_max"] > ATOL_LOGITS:
        problems.append(f"block logits differ by "
                        f"{worst['logit_diff_max']:.4f} > {ATOL_LOGITS}")
    if worst["logit_diff_median"] > ATOL_BODY:
        problems.append("a position's median |logit difference| over the "
                        f"vocabulary is {worst['logit_diff_median']:.4f} > "
                        f"{ATOL_BODY}")
    if worst["margin"] > MARGIN_LOGITS:
        problems.append(f"an unmasked token sits {worst['margin']:.4f} "
                        f"under the reference's best (> {MARGIN_LOGITS})")
    if worst["order"] > MARGIN_LOGITS:
        problems.append("a position left masked was "
                        f"{worst['order']:.4f} more confident (log) than "
                        f"one decided (> {MARGIN_LOGITS})")
    if worst["served_margin"] > MARGIN_LOGITS:
        problems.append("a token a served program unmasked sits "
                        f"{worst['served_margin']:.4f} under the reference's"
                        f" best (> {MARGIN_LOGITS})")
    if worst["served_order"] > MARGIN_LOGITS:
        problems.append("a position a served program left masked was "
                        f"{worst['served_order']:.4f} more confident (log) "
                        f"than one it decided (> {MARGIN_LOGITS})")
    return {"ok": not problems, "problems": problems,
            "prompts": len(prompts), "lengths": list(lengths),
            "rows": merged, "compared": len(done),
            "forwards_compared": sum(r["forwards"] for r in done),
            "served_forwards_compared": sum(r["served_forwards"]
                                            for r in done),
            "served_rows_min": min((r["served_rows"] for r in done),
                                   default=0),
            "limits": [
                {"name": "max_abs_logit_diff",
                 "value": worst["logit_diff_max"], "limit": ATOL_LOGITS},
                {"name": "max_body_logit_diff",
                 "value": worst["logit_diff_median"], "limit": ATOL_BODY},
                {"name": "max_unmask_margin", "value": worst["margin"],
                 "limit": MARGIN_LOGITS},
                {"name": "max_unmask_order", "value": worst["order"],
                 "limit": MARGIN_LOGITS},
                {"name": "max_served_margin",
                 "value": worst["served_margin"], "limit": MARGIN_LOGITS},
                {"name": "max_served_order",
                 "value": worst["served_order"], "limit": MARGIN_LOGITS},
                {"name": "bookkeeping_faults", "value": faults,
                 "limit": 0}],
            "engine_s": t_engine, "total_s": time.monotonic() - t0}
