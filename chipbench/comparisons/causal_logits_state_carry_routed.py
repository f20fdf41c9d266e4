"""Last-prompt-position logits against a full causal forward pass, every
greedy token's logits, the recurrent state a sequence leaves in its slot and
the experts the engine chose: the comparison of a causal engine whose layers
differ by a pattern (a state-space mixer, attention, or routed experts of
which this chip holds a share).  The reference is the one the configuration
names; `chipbench/check.py` loads both and holds the result to its contract.

It is `causal_logits_state_carry` (phases A and B, limits relative to the
reference's own spread SIGMA at a position) with what
`causal_logits_long_routed` adds for routed experts:

- the engine's expert choices go to the reference (`EngineCore.block_record`
  holds, for every prefill chunk and every step of every decode call, the
  experts each token chose in each expert layer).  bfloat16 flips which
  expert is the 22nd of 512 on seeded weights, and a float32 forward that
  chose for itself would measure the flips and not the arithmetic.  Every
  position of prompt + decoded tokens must be covered, or the prompt is a
  failure.  The scores, the gates, which of the chosen experts are held here
  and all arithmetic are the reference's own;
- so that taking the choices cannot hide a router that chooses wrongly, the
  reference says how far each expert it was given lies under ITS k-th best
  float32 `s + b` (`forward(..., shortfall=True)`): held to
  SHORTFALL_CHOICE.

A. A seeded prompt of each of LENGTHS, all offered at once: short prompts
   packed several to a chunk, 511/513/700/1500 crossing the prefill chunk
   of 512, 127/128/129 the scan's chunk of 128.  DECODE_TOKENS greedy tokens
   each: one from the prefill, three windows of eight steps, two single
   steps (a window's edge is crossed three times).  Compared, in units of
   SIGMA: the last-prompt logits (max and median over the vocabulary) and
   every greedy token's margin under the reference's best.
B. One prompt of STATE_PROMPT tokens alone, STATE_TOKENS greedy tokens; its
   slot of the first state layer's `ssm` leaf against the reference's state
   after the same tokens (`state_at=`): the relative difference,
   ||engine - reference|| / ||reference|| a head, at the MEDIAN OF THE
   QUARTER OF THE HEADS THAT DECAY MOST SLOWLY (`_slow_heads`).  The first layer of the
   pattern is a state layer whose inputs are the embeddings themselves:
   what is read is the state path's own error.  Why the slow heads: this
   family puts no multiplier on dt, so with seeded N(0, 1/fan_in) weights
   the time step's pre-activation has unit variance, dt swings by e^+-1 a
   token, and a fast head's state is its last token's dt * x (outer) B
   alone: the bfloat16 rounding of that one token's inputs (0.4 % a
   factor) IS its state's error, 0.6-1.0 % of its norm on a sound engine
   (my chip run, PR 47, call 1: the largest over all 128 heads on six
   seeds, beside 1.5 % with the state stored in bfloat16: no room for a
   limit; the largest over the slow quarter 0.33-0.67 % beside 1.5 %, call
   2: one head with a large dt at the last token is enough).  A slow head
   averages its inputs' roundings over the tens of tokens it remembers,
   while the roundings of a state STORED in bfloat16 add up over the same
   tokens, and the median of 32 such heads is not moved by the one whose
   last token swung: there the two readings part (0.18-0.20 % beside
   0.87 %).

Controls (`run(..., control=)`; `controls()` runs them), each the same
engine with one thing changed; each must be refused by at least one limit:
- `bf16_state`: the `ssm` leaves stored in bfloat16 (the nearest precision
  below the float32 the configuration states): the state's limit;
- `zero_ssm`: the mixers' output projections zeroed: the logit limits;
- `zero_routed`: the held experts' part of every expert layer zeroed (the
  latent map behind their sum: the same result as every held expert's down
  projection zeroed; the routed quarter must be visible beside the shared
  expert): the logit limits;
- `route_over_held`: the router cut to the experts held here (the
  correction bias of every other expert at -1e9, so the 22 are chosen among
  the held 128: a different model that a loose limit would pass): the
  shortfall's limit.
`bf16_router` (scores rounded to bfloat16 before the choice) needs another
program, so `main(--bf16-router)` patches the program's router before it
builds the engine; it is reported (PERF.md section 6).

Readings (TPU v5 lite, published widths, 11 layers, eight prompts of 5-1,500
tokens and the state prompt a seed; my chip runs, PR 47, PERF.md section 6;
CPU readings at tiny widths in chipbench/tests/test_pattern_block_reference.py)
are in the table under LIMITS below.

Prompt ids are drawn from [1, vocab) and none is one of the configuration's
`reserved_token_ids`."""

from __future__ import annotations

import time

import numpy as np

# The two comparisons this one joins: one seeded prompt a length and the
# engine's recorded expert choices by position from the routed one, a
# prompt's numbers in units of the reference's spread from the state one.
from chipbench.comparisons.causal_logits_long_routed import (
    _choices, _prompts)
from chipbench.comparisons.causal_logits_state_carry import _row

# LIMITS.  Readings on the chip at the published widths, 11 layers, eight
# prompts of 5-1,500 tokens and the state prompt a seed (my chip runs, PR 47,
# calls 1 and 2: six prompt seeds on one seed of weights; the cell's runs in
# PERF.md section 6 add a seed of weights each), each control over one seed:
#
#                      sound            the control it is there for      limit
#  logits max / SIGMA  0.049 - 0.065    zero_ssm 6.42, zero_routed 3.74  0.15
#  body median / SIGMA 0.0077 - 0.0088  zero_ssm 0.861, zero_routed 0.518 0.02
#  decode margin       0.017 - 0.059    zero_ssm 6.12, zero_routed 2.97  0.30
#  state, slow median  0.0018 - 0.0020  bf16_state 0.0087                0.004
#  choice shortfall    0.0061 - 0.0114  route_over_held 0.267            0.08
#
# Each read limit lies between the largest sound reading and the smallest
# reading of the control it is there for, with room on both sides (2.3 and
# 25 times, 2.3 and 26 times, 2.0 and 2.2 times, 7.0 and 3.3 times); the
# margin's follows from the first.  `bf16_state` is refused by the state's
# limit alone, `route_over_held` by the shortfall's alone (with the engine's
# choices handed over its logits agree: 0.059 / 0.0088), `zero_ssm` and
# `zero_routed` by the three logit limits (and by the shortfall's: a layer
# left out moves the later routers' inputs).  The three logit limits and the
# shortfall's are PR 44's and PR 36's values: this block's sound readings
# sit at the same place under them.
REL_LOGITS = 0.15            # x SIGMA
REL_BODY = 0.02              # x SIGMA
REL_MARGIN = 2 * REL_LOGITS  # follows from REL_LOGITS
REL_STATE = 0.004            # of a head's norm, the slow quarter's median
SHORTFALL_CHOICE = 0.08      # of a score + bias (scores lie in 0..1)
LENGTHS = (5, 127, 128, 129, 511, 513, 700, 1500)
DECODE_TOKENS = 27           # 1 from prefill + 3 windows of 8 + 2 steps
STATE_PROMPT = 129
STATE_TOKENS = 59            # 1 from prefill + 7 windows of 8 + 2 steps
CONTROLS = ("bf16_state", "zero_ssm", "zero_routed", "route_over_held")
PAD_TO = 512                 # reference sequences are padded to its multiple:
# nine sequences then show the reference three lengths, and a first start
# builds two dozen reference programs and not five dozen


def _drive(core, prompts, max_tokens, tag):
    """Greedy-generate each prompt with the engine's recording on; returns
    ({rid: tokens}, {rid: f32 logits row that chose the first token},
    {rid: state slot}, the record)."""
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
    core.block_record = record = []
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
        core.block_record = None
    return tokens, logits, slots, record


def _map_layers(params, kind, change):
    """`params` with `change(part)` in place of every layer's `kind` part."""
    return dict(params, layers=[
        dict(p, **{kind: change(p[kind])}) if kind in p else p
        for p in params["layers"]])


def _apply(core, hf, control):
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
    kept = core.params

    def undo():
        core.params = kept

    if control == "zero_ssm":
        core.params = _map_layers(kept, "ssm", lambda s: dict(
            s, w_out=jnp.zeros_like(s["w_out"])))
    elif control == "zero_routed":
        # The map behind the routed sum zeroed: what every held expert's
        # down projection zeroed gives, without 3.5 GB of zeros beside the
        # served weights.
        core.params = _map_layers(kept, "moe", lambda m: dict(
            m, latent_out=jnp.zeros_like(m["latent_out"])))
    elif control == "route_over_held":
        held = hf.get("routed_experts_held")
        if not held:
            raise ValueError("route_over_held needs a configuration that "
                             "holds a share (`routed_experts_held`)")
        inside = np.zeros((int(held["of"]),), bool)
        inside[int(held["first"]):int(held["first"]) + int(held["count"])] \
            = True

        def cut(m):
            return dict(m, router_bias=jnp.where(
                jnp.asarray(inside), m["router_bias"], -1e9))

        core.params = _map_layers(kept, "moe", cut)
    else:
        raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")
    return undo


def _slow_heads(params) -> np.ndarray:
    """The quarter of the first state layer's heads whose state decays most
    slowly: the smallest `exp(A_log) * softplus(dt_bias)`, the decay's
    exponent a token at a time step of the bias alone.  Weights are data
    here, like the prompt."""
    ssm = next(p["ssm"] for p in params["layers"] if "ssm" in p)
    a = np.exp(np.asarray(ssm["A_log"], np.float64))
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"], np.float64)))
    return np.argsort(a * dt)[:max(1, len(a) // 4)]


def _reference(reference, hf, params, seq, n, chosen, problems, rid,
               state_at=None):
    """The reference over `seq` with the engine's choices: (logits at
    positions n - 1 .., shortfall[, state]) or None with a problem noted."""
    import jax

    if chosen is None or (chosen < 0).any():
        missing = (len(seq) if chosen is None
                   else int((chosen < 0).any(axis=(0, 2)).sum()))
        problems.append(f"{rid}: the recording holds no expert choices for "
                        f"{missing} of {len(seq)} positions")
        return None
    pad = -(-len(seq) // PAD_TO) * PAD_TO
    full = np.full((chosen.shape[0], pad, chosen.shape[2]), -1, np.int32)
    full[:, :len(seq)] = chosen
    out = reference.forward(
        hf, params, seq + [0] * (pad - len(seq)), choices=full,
        positions=list(range(n - 1, len(seq))), shortfall=True,
        state_at=state_at)
    return [np.asarray(jax.device_get(o), dtype=np.float32) for o in out]


def run(core, hf: dict, seed: int, lengths, reference,
        decode_tokens: int = DECODE_TOKENS, state_tokens: int = STATE_TOKENS,
        control=None) -> dict:
    import jax

    if "ssm" not in core.cache:
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
    undo = _apply(core, hf, control)
    before = core.counters.snapshot()
    try:
        tokens, logits, _, record = _drive(core, prompts, decode_tokens,
                                           "check")
        s_tokens, s_logits, s_slots, s_record = _drive(
            core, [state_prompt], state_tokens, "state")
        s_rid = "chipbench-state-0"
        engine_state = None
        if s_rid in s_slots:
            engine_state = np.asarray(jax.device_get(
                core.cache["ssm"][0][s_slots[s_rid]]), dtype=np.float32)
    finally:
        undo()
    ran = core.counters.delta(before)
    t_engine = time.monotonic() - t0
    rids = [f"chipbench-check-{i}" for i in range(len(prompts))]
    chosen = _choices(record, {rid: len(p) + decode_tokens - 1
                               for rid, p in zip(rids, prompts)})
    chosen.update(_choices(s_record, {
        s_rid: len(state_prompt) + state_tokens - 1}))
    rows, problems = [], []
    for rid, prompt in zip(rids, prompts):
        got = tokens[rid]
        if len(got) != decode_tokens:
            problems.append(f"{rid}: {len(got)} tokens, wanted {decode_tokens}")
            continue
        if rid not in logits:
            problems.append(f"{rid}: the engine handed over no prefill logits")
            continue
        n = len(prompt)
        out = _reference(reference, hf, params, prompt + got[:-1], n,
                         chosen.get(rid), problems, rid)
        if out is None:
            continue
        ref, short = out
        got_row = logits[rid]
        if not np.isfinite(ref).all() or got_row.shape != ref[0].shape \
                or not np.isfinite(got_row).all():
            problems.append(f"{rid}: logits misshapen or not finite")
            continue
        rows.append(dict(_row(ref, got_row, got), len=n,
                         choice_shortfall=float(short)))

    # Phase B: the state the lone sequence left in its slot.
    state_rel = float("inf")
    got = s_tokens[s_rid]
    if len(got) != state_tokens or engine_state is None:
        problems.append(f"state prompt: {len(got)} tokens, wanted "
                        f"{state_tokens}, or no slot seen")
    else:
        seq = state_prompt + got[:-1]
        n = len(state_prompt)
        out = _reference(reference, hf, params, seq, n, chosen.get(s_rid),
                         problems, s_rid, state_at=len(seq))
        if out is not None:
            ref, ref_state, short = out
            per_head = np.sqrt(((engine_state - ref_state) ** 2).sum((1, 2))
                               / (ref_state ** 2).sum((1, 2)))
            slow = _slow_heads(params)
            state_rel = float(np.median(per_head[slow]))
            rows.append(dict(_row(ref, s_logits[s_rid], got), len=n,
                             choice_shortfall=float(short),
                             state_rel_by_head=[float(v) for v in per_head],
                             slow_heads=[int(h) for h in slow]))

    asked = len(prompts)
    compared = sum(1 for r in rows if "state_rel_by_head" not in r)
    worst_logit = max((r["logit_rel_max"] for r in rows), default=0.0)
    worst_body = max((r["logit_rel_median"] for r in rows), default=0.0)
    worst_margin = max((m for r in rows for m in r["decode"]), default=0.0)
    worst_short = max((r["choice_shortfall"] for r in rows), default=0.0)
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
    if not worst_short <= SHORTFALL_CHOICE:
        problems.append(f"an expert the engine chose lies {worst_short:.4f} "
                        "under the reference's k-th best score + bias (> "
                        f"{SHORTFALL_CHOICE})")
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
                 "limit": REL_STATE},
                {"name": "max_choice_shortfall", "value": worst_short,
                 "limit": SHORTFALL_CHOICE}],
            "windows": ran["window_dispatches"],
            "single_steps": ran["single_step_dispatches"],
            "engine_s": t_engine, "total_s": time.monotonic() - t0}


def controls(core, hf: dict, seed: int, reference, lengths=LENGTHS,
             which=(None,) + CONTROLS, **kw) -> dict:
    """The sound engine and each control over the same seed: {name: result}.
    Every control must come out not ok (`chipbench/check.hold` says why)."""
    from chipbench import check

    return {str(c): check.hold(run(core, hf, seed, lengths, reference,
                                   control=c, **kw), len(lengths))
            for c in which}


def main(argv=None) -> int:
    """`python -m chipbench.comparisons.causal_logits_state_carry_routed
    --config-file <configs/x.json> --seed n [--seeds a,b,c] [--bf16-router]`:
    build the engine as the benchmark's worker would and print the sound
    check and every control, one JSON line each (what PERF.md's readings
    are taken from).  `--seeds`: the sound check alone over more seeds.
    `--bf16-router`: the program's router scores rounded to bfloat16 before
    the choice (patched before the engine is built), the check alone."""
    import argparse
    import json
    import os

    from chipbench import pieces

    p = argparse.ArgumentParser()
    p.add_argument("--config-file", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seeds", default="")
    p.add_argument("--bf16-router", action="store_true")
    p.add_argument("--heads", action="store_true",
                   help="print the state's relative error head by head")
    p.add_argument("--override", default="{}")
    p.add_argument("--lengths", default="")
    args = p.parse_args(argv)
    with open(args.config_file) as f:
        hf = json.load(f)
    hf.update(json.loads(args.override))
    for k, v in (hf.get("env") or {}).items():
        os.environ.setdefault(k, v)
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import loader
    from dynamo_tpu.ops import moe as moe_ops

    if args.bf16_router:
        topk = jax.lax.top_k

        def rounded_topk(cfg, p_moe, x):
            # As `router_topk`'s sigmoid branch, the scores through bfloat16.
            # `reduce_precision`, not a pair of casts: XLA on the TPU may
            # keep the excess precision of a cast down and up again (it did:
            # call 1's reading was the sound engine's to the last digit).
            scores = jax.lax.reduce_precision(jax.nn.sigmoid(jnp.dot(
                x, p_moe["router"], preferred_element_type=jnp.float32)),
                exponent_bits=8, mantissa_bits=7)
            _, idx = topk(scores + p_moe["router_bias"],
                          cfg.num_experts_per_token)
            chosen = jnp.take_along_axis(scores, idx, axis=-1)
            gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
            return idx, (gates * cfg.routed_scaling_factor).astype(x.dtype)

        moe_ops.router_topk = rounded_topk

    cfg = loader.config_from_hf(hf, "check")
    if hf.get("torch_dtype") == "float32":
        cfg = cfg.replace(dtype=jnp.float32)
    flags = dict(zip(hf["engine_flags"][::2], hf["engine_flags"][1::2]))
    block = int(flags.get("--block-size", 64))
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=int(flags.get("--num-blocks", 512)),
        seed=args.seed % (2 ** 31),
        # The expert path the worker is told to time, not `auto` whatever
        # the flags say: the controls are read on the path that is timed.
        moe_mode=flags.get("--moe-mode", "auto"),
        scheduler=SchedulerConfig(
            block_size=block, max_pages_per_seq=-(-int(
                flags.get("--max-context", 8192)) // block))))
    reference = pieces.load("references", hf["reference"],
                            needs=("forward",))
    lengths = (tuple(int(x) for x in args.lengths.split(","))
               if args.lengths else LENGTHS)
    keys = ("ok", "limits", "problems", "windows", "single_steps", "total_s")

    def heads(out):
        row = next((r for r in out["rows"] if "state_rel_by_head" in r), {})
        return {"state_rel_by_head": row.get("state_rel_by_head"),
                "slow_heads": row.get("slow_heads")} if args.heads else {}

    bad = 0
    which = (None,) if args.bf16_router else (None,) + CONTROLS
    for name, out in controls(core, hf, args.seed, reference, lengths,
                              which=which).items():
        tag = "bf16_router" if args.bf16_router else name
        print("chipbench: control", tag, json.dumps(
            dict({k: out[k] for k in keys}, **heads(out))), flush=True)
        bad += (out["ok"] is not True) if name == "None" else (
            out["ok"] is True)
    for seed in (int(s) for s in args.seeds.split(",") if s):
        out = controls(core, hf, seed, reference, lengths,
                       which=(None,))["None"]
        print(f"chipbench: seed {seed}", json.dumps(
            dict({k: out[k] for k in keys}, **heads(out))), flush=True)
        bad += out["ok"] is not True
    return 1 if bad and not args.bf16_router else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
