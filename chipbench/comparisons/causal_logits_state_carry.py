"""Last-prompt-position logits against a full causal forward pass, every
greedy token the engine then decodes, and the recurrent state a sequence
leaves in its slot: the comparison of a causal engine whose layers carry a
state-space mixer's state beside the paged cache.  The reference is the one
the configuration names; `chipbench/check.py` loads both and holds the result
to its contract.

Two phases through the very EngineCore the server is about to use (same
params, same pools, same kernels, the fused decode window):

A. A seeded prompt of each of LENGTHS, all offered at once: short prompts
   are packed several to a chunk, 511/513/700/1500 cross the prefill chunk
   of 512 (the scan then starts from the slot's state), 127/128/129 cross
   the scan's own chunk of 128.  DECODE_TOKENS greedy tokens each: one from
   the prefill, three windows of eight steps, two single steps.  Compared,
   in units of the reference's own spread at that position (SIGMA = the
   standard deviation of its logits over the vocabulary: `lm_head_multiplier`
   is 0.0078, so the logits are a hundredth of a dense model's and an
   absolute limit would pass anything):
   - the logits at the last prompt position: max |difference| / SIGMA
     <= REL_LOGITS, and the median over the vocabulary <= REL_BODY;
   - every greedy token: the reference, fed the prompt plus the engine's
     earlier tokens, rates it within REL_MARGIN x SIGMA of its own best one
     (2 x REL_LOGITS: what the first limit implies).
B. One prompt of STATE_PROMPT tokens (no longer than the longest asked for)
   alone, STATE_TOKENS greedy tokens: one
   from the prefill, seven windows, two single steps, and no step more (alone
   in the engine, a sequence is fed exactly its own tokens: no window of a
   cohort overshoots it).  Its slot of the first layer's `ssm` leaf then
   holds the state after prompt + tokens[:-1], which the reference hands
   over too (`forward(..., state_at=)`): the largest relative difference
   over the heads, ||engine - reference|| / ||reference||, <= REL_STATE.
   The first layer's, because its inputs are the embeddings themselves: what
   is read there is the state path's own error (the projection's bfloat16
   output, the update, the storage), not five layers of activations.

Tolerances.  The engine computes in bfloat16 with float32 accumulation and
keeps the state in float32; the reference is float32 throughout.  Two
controls, each the same engine with one thing changed (`run(...,
control=)`; `controls()` runs both):
- `bf16_state`: the `ssm` leaves stored in bfloat16, the nearest precision
  below the float32 the configuration states for the state.  A rounding of
  2**-9 a step moves no logit by more than the activations' own bfloat16
  does, so the logit limits cannot see it; the state's limit does: the
  roundings of 58 decode steps add up in the heads that decay slowly.
- `zero_ssm`: the mixer's output projection zeroed (the state-space branch
  left out).  The state itself is then still right; the logits are not.

Readings (TPU v5 lite, published widths, 6 layers, eight prompts of 5-1,500
tokens and the state prompt a seed; my chip runs, PR 44, PERF.md section 6;
CPU readings at tiny widths in chipbench/tests/test_state_block_reference.py):

                      sound, 17 seeds      bf16_state   zero_ssm   limit
  logits max / SIGMA  0.0355 - 0.0409      0.0397       5.72       0.15
  body median / SIGMA 0.0050 - 0.0052      0.0052       0.790      0.02
  decode margin       0.004 - 0.043        0.004        6.12       0.30
  state, of its norm  0.00032 - 0.00045    0.0286       0.00031    0.003

Each read limit lies between the largest sound reading and the smallest
reading of the control it is there for, with room on both sides (3.7 and 38
times, 3.8 and 40 times, 6.7 and 9.5 times); the margin's follows from the
first.  `bf16_state` is refused by the state's limit alone, `zero_ssm` by the
three logit limits alone: each limit binds.  SIGMA is 0.0078 here: the dense
configuration's absolute 0.09 would be 11.5 SIGMA and pass `zero_ssm`.

Prompt ids are drawn from [1, vocab) and none is one of the configuration's
`reserved_token_ids`."""

from __future__ import annotations

import time

import numpy as np

REL_LOGITS = 0.15            # x SIGMA: between the two readings above
REL_BODY = 0.02              # x SIGMA
REL_MARGIN = 2 * REL_LOGITS  # follows from REL_LOGITS
REL_STATE = 0.003            # of the state's norm
LENGTHS = (5, 127, 128, 129, 511, 513, 700, 1500)
DECODE_TOKENS = 27           # 1 from prefill + 3 windows of 8 + 2 steps
STATE_PROMPT = 129
STATE_TOKENS = 59            # 1 from prefill + 7 windows of 8 + 2 steps
CONTROLS = ("bf16_state", "zero_ssm")


def _drive(core, prompts, max_tokens, tag):
    """Greedy-generate each prompt; returns ({rid: tokens}, {rid: f32 logits
    row that chose the first token}, {rid: state slot})."""
    from dynamo_tpu.engine.sampling import SamplingParams

    logits, slots = {}, {}
    finish = core._finish_prefill_items     # moved internal: fail, not skip

    def capture(items, rows, *a, **kw):
        host = np.asarray(rows, dtype=np.float32)
        for i, work in enumerate(items):
            if work.start + work.length == len(work.request.prompt_tokens):
                logits[work.request.request_id] = host[i]
                slots[work.request.request_id] = work.request.slot
        return finish(items, rows, *a, **kw)

    core._finish_prefill_items = capture
    try:
        for i, p in enumerate(prompts):
            core.add_request(f"chipbench-{tag}-{i}", p,
                             SamplingParams(max_tokens=max_tokens))
        tokens = {f"chipbench-{tag}-{i}": [] for i in range(len(prompts))}
        while core.has_work:
            for delta in core.step():
                tokens[delta.request_id].extend(delta.token_ids)
    finally:
        core._finish_prefill_items = finish
    return tokens, logits, slots


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


def _apply(core, control):
    """Change the engine as `control` says; returns what undoes it."""
    import jax.numpy as jnp

    if control is None:
        return lambda: None
    if control == "bf16_state":
        def leaves(dtype):
            core.cache = dict(core.cache, ssm=[
                jnp.zeros(a.shape, dtype) for a in core.cache["ssm"]])

        leaves(jnp.bfloat16)
        return lambda: leaves(jnp.float32)
    if control == "zero_ssm":
        kept = core.params

        def layer(p):
            return dict(p, ssm=dict(p["ssm"],
                                    w_out=jnp.zeros_like(p["ssm"]["w_out"])))

        core.params = dict(kept, layers=[layer(p) for p in kept["layers"]])

        def undo():
            core.params = kept

        return undo
    raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")


def _row(ref_rows, got_row, got_tokens):
    """One prompt's numbers from the reference's logits at its last prompt
    position and after, the engine's logits there and its tokens."""
    sigma = float(ref_rows[0].std())
    d = np.abs(got_row - ref_rows[0]) / sigma
    return {"sigma": sigma, "logit_rel_max": float(d.max()),
            "logit_rel_median": float(np.median(d)),
            "decode": [float((ref_rows[j].max() - ref_rows[j][tok])
                             / ref_rows[j].std())
                       for j, tok in enumerate(got_tokens)]}


def run(core, hf: dict, seed: int, lengths, reference,
        decode_tokens: int = DECODE_TOKENS, state_tokens: int = STATE_TOKENS,
        control=None) -> dict:
    import jax

    if "ssm" not in core.cache:
        # A program that maps this configuration without its mixer (it
        # would serve a plain dense decoder) is not this comparison's to
        # measure: say so before anything is compiled.
        raise RuntimeError(
            "the engine holds no recurrent state (no `ssm` leaf in its "
            "cache): this program does not build the state-space mixer "
            "the configuration states")
    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    vocab = hf["vocab_size"]
    reserved = hf.get("reserved_token_ids", ())
    prompts = _prompts(rng, vocab, lengths, reserved)
    state_prompt = _prompts(
        rng, vocab, (min(STATE_PROMPT, max(lengths)),), reserved)[0]
    params = core.params                      # the reference's: unchanged
    undo = _apply(core, control)
    before = core.counters.snapshot()
    try:
        tokens, logits, _ = _drive(core, prompts, decode_tokens, "check")
        s_tokens, s_logits, s_slots = _drive(core, [state_prompt],
                                             state_tokens, "state")
        s_rid = "chipbench-state-0"
        engine_state = None
        if s_rid in s_slots:
            engine_state = np.asarray(jax.device_get(
                core.cache["ssm"][0][s_slots[s_rid]]), dtype=np.float32)
    finally:
        undo()
    ran = core.counters.delta(before)
    t_engine = time.monotonic() - t0
    rows, problems = [], []
    pad_to = -(-(max(lengths) + decode_tokens) // 128) * 128
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
        n = len(prompt)
        ref = np.asarray(jax.device_get(reference.forward(
            hf, params, seq + [0] * (pad_to - len(seq)),
            positions=list(range(n - 1, len(seq))))))
        got_row = logits[rid]
        if not np.isfinite(ref).all() or got_row.shape != ref[0].shape \
                or not np.isfinite(got_row).all():
            problems.append(f"{rid}: logits misshapen or not finite")
            continue
        rows.append(dict(_row(ref, got_row, got), len=n))

    # Phase B: the state the lone sequence left in its slot.
    state_rel = float("inf")
    got = s_tokens["chipbench-state-0"]
    if len(got) != state_tokens or engine_state is None:
        problems.append(f"state prompt: {len(got)} tokens, wanted "
                        f"{state_tokens}, or no slot seen")
    else:
        seq = state_prompt + got[:-1]
        n = len(state_prompt)
        pad = -(-len(seq) // 128) * 128
        ref, ref_state = reference.forward(
            hf, params, seq + [0] * (pad - len(seq)),
            positions=list(range(n - 1, len(seq))), state_at=len(seq))
        ref = np.asarray(jax.device_get(ref))
        ref_state = np.asarray(jax.device_get(ref_state))
        per_head = np.sqrt(((engine_state - ref_state) ** 2).sum((1, 2))
                           / (ref_state ** 2).sum((1, 2)))
        state_rel = float(per_head.max())
        rows.append(dict(_row(ref, s_logits[s_rid], got), len=n,
                         state_rel_by_head=[float(v) for v in per_head]))

    asked = len(prompts)
    compared = sum(1 for r in rows if "state_rel_by_head" not in r)
    worst_logit = max((r["logit_rel_max"] for r in rows), default=0.0)
    worst_body = max((r["logit_rel_median"] for r in rows), default=0.0)
    worst_margin = max((m for r in rows for m in r["decode"]), default=0.0)
    if len(rows) != asked + 1:
        problems.append(f"{compared} of {asked} prompts and "
                        f"{len(rows) - compared} of 1 state prompt compared")
    if not ran["window_dispatches"] or not ran["single_step_dispatches"]:
        problems.append("the check ran no decode window or no single step: "
                        f"{ran['window_dispatches']} and "
                        f"{ran['single_step_dispatches']}")
    if worst_logit > REL_LOGITS:
        problems.append(f"prefill logits differ by {worst_logit:.4f} of "
                        f"their spread > {REL_LOGITS}")
    if worst_body > REL_BODY:
        problems.append("a row's median |logit difference| over the "
                        f"vocabulary is {worst_body:.4f} of its spread > "
                        f"{REL_BODY}")
    if worst_margin > REL_MARGIN:
        problems.append(f"a decoded token sits {worst_margin:.4f} of the "
                        f"spread under the reference's best (> {REL_MARGIN})")
    if not state_rel <= REL_STATE:
        problems.append(f"the state left in the slot differs by "
                        f"{state_rel:.5f} of its norm > {REL_STATE}")
    return {"ok": not problems, "problems": problems, "control": control,
            "prompts": asked, "lengths": list(lengths), "rows": rows,
            "prefill_logits_compared": compared, "compared": compared,
            "limits": [
                {"name": "max_rel_logit_diff", "value": worst_logit,
                 "limit": REL_LOGITS},
                {"name": "max_rel_body_logit_diff", "value": worst_body,
                 "limit": REL_BODY},
                {"name": "max_rel_decode_margin", "value": worst_margin,
                 "limit": REL_MARGIN},
                {"name": "max_rel_state_diff",
                 "value": state_rel if np.isfinite(state_rel) else 1e9,
                 "limit": REL_STATE}],
            "windows": ran["window_dispatches"],
            "single_steps": ran["single_step_dispatches"],
            "engine_s": t_engine, "total_s": time.monotonic() - t0}


def controls(core, hf: dict, seed: int, reference, lengths=LENGTHS,
             **kw) -> dict:
    """The sound engine and each control over the same seed: {name: result}.
    Every control must come out not ok (`chipbench/check.hold` says why)."""
    from chipbench import check

    return {str(c): check.hold(run(core, hf, seed, lengths, reference,
                                   control=c, **kw), len(lengths))
            for c in (None,) + CONTROLS}


def main(argv=None) -> int:
    """`python -m chipbench.comparisons.causal_logits_state_carry
    --config-file <configs/x.json> --seed n`: build the engine as the
    benchmark's worker would and print the sound check and both controls,
    one JSON line each (what PERF.md's readings are taken from)."""
    import argparse
    import json

    from chipbench import pieces

    p = argparse.ArgumentParser()
    p.add_argument("--config-file", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--override", default="{}")
    p.add_argument("--lengths", default="")
    args = p.parse_args(argv)
    with open(args.config_file) as f:
        hf = json.load(f)
    hf.update(json.loads(args.override))
    import os

    for k, v in (hf.get("env") or {}).items():
        os.environ.setdefault(k, v)
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import loader

    cfg = loader.config_from_hf(hf, "check")
    if hf.get("torch_dtype") == "float32":
        cfg = cfg.replace(dtype=jnp.float32)
    flags = dict(zip(hf["engine_flags"][::2], hf["engine_flags"][1::2]))
    block = int(flags.get("--block-size", 64))
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=int(flags.get("--num-blocks", 512)),
        seed=args.seed % (2 ** 31),
        scheduler=SchedulerConfig(
            block_size=block, max_pages_per_seq=-(-int(
                flags.get("--max-context", 8192)) // block))))
    reference = pieces.load("references", hf["reference"],
                            needs=("forward",))
    lengths = (tuple(int(x) for x in args.lengths.split(","))
               if args.lengths else LENGTHS)
    bad = 0
    for name, out in controls(core, hf, args.seed, reference,
                              lengths).items():
        print("chipbench: control", name, json.dumps(
            {k: out[k] for k in ("ok", "limits", "problems", "windows",
                                 "single_steps", "total_s")}), flush=True)
        bad += (out["ok"] is not True) if name == "None" else (
            out["ok"] is True)
    return 1 if bad else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
