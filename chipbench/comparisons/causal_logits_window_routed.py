"""Last-prompt-position logits against a full forward pass, then every greedy
token the engine decodes through its cache, for a causal engine whose layers
are WINDOW or full attention over routed experts, at prompts that cross the
window and a page release: the comparison of a configuration whose window
layers keep their pages in a group of their own and give a block back once it
lies wholly behind the window.

It is `causal_logits_long_routed` (whose three helpers it uses: the engine's
expert choices go to the reference, and their shortfall under the
reference's own is held) with two things of its own.

- LENGTHS cross the window and a release: one prompt under the window (the
  window layers are then plain causal layers), one a few blocks past it that
  DECODES ACROSS A RELEASE (its 27 decoded tokens carry the context over a
  block edge, so a window-group block goes back to its pool between two
  decode windows and the steps after it read tables with one more null
  entry), one near the traffic mix's longest (32 blocks, 13 of them released
  during its prefill).  A program that kept no window would pass the first
  and fail the others; one that released a block a query still sees, or
  fetched a released one, fails the second and third.
- Two controls that must fail (`run(..., control=)`; `controls()` runs them,
  `main()` on the chip):
  * `f8_weights`: the engine serves with every attention projection rounded
    through float8_e4m3 (the nearest precision below the bfloat16 the
    configuration states), the reference keeps the weights as they were;
  * `no_window`: the sound engine against the reference run with no window
    (`sliding_window` past every context): what a program whose kernels took
    no window, or a reference that forgot it, would read.

Compared, as in `causal_logits_long_routed`: the logits at the last prompt
position of every prompt (max |difference| <= ATOL_LOGITS, median over the
vocabulary <= ATOL_BODY), every greedy token's margin under the reference's
best (<= MARGIN_LOGITS = 2 x ATOL_LOGITS), every chosen expert's shortfall
under the reference's k-th best score (<= SHORTFALL_CHOICE).

Tolerances.  The engine computes in bfloat16 with float32 accumulation, the
reference in float32; seeded N(0, 1/fan_in) weights, logits ~N(0, 1).  Read on
a TPU v5 lite at the published widths, four layers, three prompts a seed (my
chip runs, PR 53: the sound engine in calls 1-5, eleven seeds: 2000000123,
...531, ...653 at lengths 700 / 4,860 / 16,000, 2000001153 and 2000000901-907
at 700 / 4,860 / 8,000; the controls in call 1; PERF.md section 6):

                      sound, 11 seeds    f8_weights   no_window   limit
  max |difference|    0.0206 - 0.0258    0.6093       3.1533      0.10
  median over vocab   0.0034 - 0.0039    0.1029       0.5545      0.015
  decode margin       0 - 0.0226         0            0.8037      0.20
  choice shortfall    0.0033 - 0.0043    0.0947       0.6028      0.04

Each of the first two limits lies between its two readings with room on both
sides (the largest sound reading is 26 % of either limit, the smaller control
6.1 and 6.9 times it), and so does the shortfall's (11 %; 2.4 and 15 times);
each control is refused by three limits.  The margin is a bound that follows
from ATOL_LOGITS, not a read limit.

Prompt ids are drawn from [1, vocab) and none is one of the configuration's
`reserved_token_ids`."""

from __future__ import annotations

import time

import numpy as np

from chipbench.comparisons.causal_logits_long_routed import (
    _choices, _drive, _prompts)

# Set from the chip runs of PR 53 (the table above).
ATOL_LOGITS = 0.10
ATOL_BODY = 0.015
MARGIN_LOGITS = 2 * ATOL_LOGITS   # the engine's best is at most 2A under
SHORTFALL_CHOICE = 0.04      # of a score (scores lie in 0..1)
# Under the window (4,096); 19 blocks of 256 less 4 tokens, so that the
# decoded tokens cross position 4,864 and a block is released under them;
# near the mix's longest prompt (8,192).
LENGTHS = (700, 4860, 8000)
DECODE_TOKENS = 27           # 1 from prefill + 3 windows of 8 + 2 steps
CONTROLS = ("f8_weights", "no_window")
# Reference sequences are padded to a multiple of this (the reference's token
# block), so that a first start builds few reference programs.
PAD_TO = 1024


def _round_attention_weights(core):
    """Every attention projection through float8_e4m3; returns what undoes
    it and the weights as they were (which the reference keeps)."""
    import jax.numpy as jnp

    sound = core.params
    layers = [dict(layer, attn={
        name: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        for name, w in layer["attn"].items()}) for layer in sound["layers"]]
    core.params = dict(sound, layers=layers)

    def undo():
        core.params = sound

    return undo, sound


def run(core, hf: dict, seed: int, lengths, reference,
        decode_tokens: int = DECODE_TOKENS, control=None) -> dict:
    import jax

    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}: one of {CONTROLS}")
    if not getattr(core.config.model, "has_window", False):
        # A program that does not map this configuration's `model_type`
        # builds some other decoder from its keys: say so at once, before
        # thousands of tokens are driven through it.
        raise ValueError(
            "causal_logits_window_routed compares an engine whose model has "
            "window layers (`ModelConfig.layer_windows`); the engine handed "
            "over states none: the program does not serve this "
            "configuration")
    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    prompts = _prompts(rng, hf["vocab_size"], lengths,
                       hf.get("reserved_token_ids", ()))
    released0 = core.scheduler.window_released
    undo, ref_params = (lambda: None), core.params
    if control == "f8_weights":
        undo, ref_params = _round_attention_weights(core)
    ref_hf = dict(hf, sliding_window=10 ** 9) if control == "no_window" \
        else hf
    try:
        tokens, logits, record = _drive(core, prompts, decode_tokens)
    finally:
        undo()
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
            ref_hf, ref_params, seq + [0] * (pad - len(seq)), choices=full,
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

    def worst(values):
        return max(values, default=0.0)

    limits = [
        {"name": "max_abs_logit_diff", "limit": ATOL_LOGITS,
         "value": worst(r["logit_diff_max"] for r in rows)},
        {"name": "max_body_logit_diff", "limit": ATOL_BODY,
         "value": worst(r["logit_diff_median"] for r in rows)},
        {"name": "max_decode_margin", "limit": MARGIN_LOGITS,
         "value": worst(m for r in rows for m in r["decode"])},
        {"name": "max_choice_shortfall", "limit": SHORTFALL_CHOICE,
         "value": worst(r["choice_shortfall"] for r in rows)}]
    if len(rows) != len(prompts):
        problems.append(f"{len(rows)} of {len(prompts)} prompts compared")
    problems += [f"{item['name']}: {item['value']:.4f} > {item['limit']}"
                 for item in limits if not item["value"] <= item["limit"]]
    return {"ok": not problems, "problems": problems, "control": control,
            "prompts": len(prompts), "lengths": list(lengths), "rows": rows,
            "compared": len(rows), "limits": limits,
            "window_blocks_released":
                core.scheduler.window_released - released0,
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
    """`python -m chipbench.comparisons.causal_logits_window_routed
    --config-file <configs/x.json> --seed n [--seeds a,b,c]`: build the
    engine as the benchmark's worker would and print the sound check and
    every control, one JSON line each (what PERF.md's readings are taken
    from).  `--seeds`: the sound check alone over more seeds of prompts.
    Exit 0: the sound engine passes everywhere and every control fails."""
    import argparse
    import json
    import os

    from chipbench import pieces

    p = argparse.ArgumentParser()
    p.add_argument("--config-file", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seeds", default="")
    p.add_argument("--override", default="{}")
    p.add_argument("--lengths", default="")
    args = p.parse_args(argv)
    with open(args.config_file) as f:
        hf = json.load(f)
    hf.update(json.loads(args.override))
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
        seed=args.seed % (2 ** 31), moe_mode=flags.get("--moe-mode", "auto"),
        scheduler=SchedulerConfig(
            block_size=block, max_pages_per_seq=-(-int(
                flags.get("--max-context", 8192)) // block))))
    reference = pieces.load("references", hf["reference"],
                            needs=("forward",))
    lengths = (tuple(int(x) for x in args.lengths.split(","))
               if args.lengths else LENGTHS)
    keys = ("ok", "limits", "problems", "window_blocks_released", "total_s")
    bad = 0
    for name, out in controls(core, hf, args.seed, reference,
                              lengths).items():
        print("chipbench: control", name, json.dumps(
            {k: out[k] for k in keys}), flush=True)
        bad += (out["ok"] is not True) if name == "None" else (
            out["ok"] is True)
    for seed in (int(s) for s in args.seeds.split(",") if s):
        out = controls(core, hf, seed, reference, lengths,
                       which=(None,))["None"]
        print(f"chipbench: seed {seed}", json.dumps(
            {k: out[k] for k in keys}), flush=True)
        bad += out["ok"] is not True
    return 1 if bad else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
