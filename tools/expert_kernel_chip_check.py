#!/usr/bin/env python3
"""On the chip: the grouped expert kernel (ops/pallas/moe_grouped.py), what
its time is made of, and its fetch ring against the grid pipeline it took
the place of.  No benchmark cell runs this; it is the check that interpret
mode cannot give (PERF.md section 6, PR 54).

    chiprun --timeout 900 -- python tools/expert_kernel_chip_check.py

At the four served geometries (SDAR 128 experts of 2048 x 768 at 1,024
assignments in tiles of 16, about 100 experts touched, evenly, with a few
experts holding many tiles each, and as a block call's bucket packs them:
its padding rows all on the same 8 experts; GLM-4.7-Flash 64 of
2048 x 1536, two F blocks, at 4 and at 2,048 assignments; Command A+ 16 held
of 128, 4096 x 4096, eight F blocks; Nemotron-3-Super's two-matrix form, 128
held of 512, 1024 x 2688), bf16, routings drawn from `--seed` and packed as
`ops/moe.py::moe_grouped` packs them, microseconds a call over `--iters`
calls in one program:
(a) the kernel of `--kernel-file`: by default the one that stood before the
    ring (`tests/grouped_ffn_before_ring.py`: the weight blocks through the
    grid's own two buffers);
(b) that kernel with its body cut to a touch of each block: the grid steps
    and the DMAs alone;
(c) this tree's ring at depth 2, 3 and 4, at the depth it picks itself, and
    at that depth with the body cut as in (b).
`floor_us` is the touched experts' bytes over the HBM peak, `moved_us` the
bytes the kernel moves (with more than one F block every live tile fetches
its expert again); `roofline_*` is `floor_us` as a share of a timing.  Every
output of (c) is held to (a)'s bit for bit over the live tiles' rows.  One
JSON line, the last of standard output; exit 1 where no TPU is found or an
output differs.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from dynamo_tpu.ops.pallas import moe_grouped as ring         # noqa: E402

BEFORE = os.path.join(ROOT, "tests", "grouped_ffn_before_ring.py")
HBM_BYTES_PER_S = 819e9          # one TPU v5e (chipbench/peaks.json)
# name: (held, of, H, F, matrices, assignments, how they spread: None evenly
# over `of`; a share of `held` touched, evenly; -a: expert i as 1 / i**a;
# ("pad", live): a block call's bucket of which `live` rows in 32 are real,
# 4 positions a row, and every padding row goes where the others go)
GEOMETRIES = {
    "sdar-1024": (128, 128, 2048, 768, 3, 1024, 0.79),
    "sdar-1024-zipf": (128, 128, 2048, 768, 3, 1024, -1.2),
    "sdar-1024-pad18": (128, 128, 2048, 768, 3, 1024, ("pad", 18)),
    "sdar-512-pad10": (128, 128, 2048, 768, 3, 512, ("pad", 10)),
    "glm-4": (64, 64, 2048, 1536, 3, 4, None),
    "glm-2048": (64, 64, 2048, 1536, 3, 2048, None),
    "command-a-plus-256": (16, 128, 4096, 4096, 3, 256, None),
    "nemotron-352": (128, 512, 1024, 2688, 2, 352, None),
}
INTERPRET = False                # --rehearse-cpu: the kernel interpreted


def routing(held: int, of: int, assignments: int, touched, seed: int):
    """[held (+ 1)] rows a group: `assignments` spread evenly over `of`
    experts (over `touched` of the held ones where that is given, each at
    least once); what falls on another chip's experts is one more group
    behind the held ones, as `moe_grouped` packs it."""
    rng = np.random.default_rng(seed)
    if isinstance(touched, tuple):
        # What the engine's block call packs (`_run_block_decode`): the
        # padding rows are one token at one position, so all of them take
        # the same 8 experts, several tiles each that fetch nothing.
        rows, k = assignments // 32, 8
        real = touched[1] * assignments // rows
        counts = rng.multinomial(real, np.full((held,), 1 / held))
        counts[rng.permutation(held)[:k]] += (assignments - real) // k
        return counts
    if touched is not None and touched < 0:
        # A few experts with many tiles each: tiles that fetch nothing.
        p = rng.permutation(1 / np.arange(1, held + 1) ** -touched)
        return rng.multinomial(assignments, p / p.sum())
    if touched is not None:
        n = round(touched * held)
        counts = np.zeros((held,), np.int64)
        counts[np.sort(rng.permutation(held)[:n])] = 1 + rng.multinomial(
            assignments - n, np.full((n,), 1 / n))
        return counts
    counts = rng.multinomial(assignments, np.full((of,), 1 / of))
    if held == of:
        return counts
    return np.concatenate([counts[:held], [counts[held:].sum()]])


def operands(name: str, seed: int):
    """(args of the kernel, its static arguments, what it has to move)."""
    held, of, H, F, matrices, S, touched = GEOMETRIES[name]
    counts = routing(held, of, S, touched, seed)
    bm = ring.grouped_block_rows(S, of, held)
    T = ring.packed_rows(S, len(counts), bm) // bm
    tiles = -(-counts // bm)
    live = int(tiles[:held].sum())
    te = np.repeat(np.arange(len(counts)), tiles)
    te = np.minimum(np.concatenate(
        [te, np.full((T - len(te),), held - 1)])[:T], held - 1)
    te[live:] = te[max(live - 1, 0)]
    k = jax.random.split(jax.random.key(seed), matrices + 1)
    rows = np.zeros((T * bm,), bool)
    at = np.cumsum(tiles * bm) - tiles * bm
    for g, c in enumerate(counts):
        rows[at[g]:at[g] + c] = True
    x = jnp.where(jnp.asarray(rows)[:, None],
                  jax.random.normal(k[0], (T * bm, H), jnp.bfloat16), 0)
    ws = [jax.random.normal(k[1 + i], (held, F, H) if i == matrices - 1
                            else (held, H, F), jnp.bfloat16) * H ** -0.5
          for i in range(matrices)]
    nf = F // ring.auto_block_f(H, F, 2, matrices=matrices)
    expert_bytes = matrices * H * F * 2
    n_touched = int((counts[:held] > 0).sum())
    shape = {"geometry": name, "experts": held, "of": of, "H": H, "F": F,
             "matrices": matrices, "assignments": S, "tile": bm, "tiles": T,
             "live_tiles": live, "touched": n_touched, "f_blocks": nf,
             "floor_us": round(
                 1e6 * n_touched * expert_bytes / HBM_BYTES_PER_S, 1),
             "moved_us": round(1e6 * (n_touched if nf == 1 else live)
                               * expert_bytes / HBM_BYTES_PER_S, 1)}
    args = (x, jnp.asarray(te, jnp.int32), *ws,
            jnp.asarray([live], jnp.int32))
    return args, bm, shape


def call(module, matrices: int, bm: int):
    """The kernel of `module` traced anew at every use (the jitted wrappers
    would keep a body that has since been patched)."""
    fn = (module.grouped_expert_ffn if matrices == 3
          else module.grouped_expert_ffn_relu2).__wrapped__
    return lambda x, te, *rest: fn(x, te, *rest[:-1], live_tiles=rest[-1],
                                   block_rows=bm, interpret=INTERPRET)


def _touch(x_ref, blocks, o_ref):
    """A tile's body cut to nothing: one vreg of each weight block into the
    output, so that no block's fetch is dead."""
    got = jnp.zeros((8, 128), jnp.float32)
    for ref in blocks:
        got += ref[0, 0:8, 0:128].astype(jnp.float32)
    o_ref[0:8, 0:128] = got.astype(o_ref.dtype)


def _cut(module):
    """`module`'s tile bodies replaced by `_touch`; returns the undo."""
    names = ("_ffn_tile", "_ffn2_tile", "_ffn2_kernel")
    kept = {n: getattr(module, n) for n in names if hasattr(module, n)}

    def gated(nf, quant, f, x_ref, wg, wu, wd, o_ref, acc):
        _touch(x_ref, (wg, wu, wd), o_ref)

    def two(nf, f, x_ref, wu, wd, o_ref, acc):
        _touch(x_ref, (wu, wd), o_ref)

    def two_whole(nf, te_ref, live_ref, x_ref, wu, wd, o_ref, acc):
        from jax.experimental import pallas as pl

        @pl.when(pl.program_id(0) < live_ref[0])
        def _():
            _touch(x_ref, (wu, wd), o_ref)

    module._ffn_tile = gated
    for name, body in (("_ffn2_tile", two), ("_ffn2_kernel", two_whole)):
        if name in kept:
            setattr(module, name, body)
    return lambda: [setattr(module, n, v) for n, v in kept.items()]


def timed(fn, args, iters: int) -> float:
    """Microseconds a call of `fn` over `iters` calls in one program, each
    call's tile map read off the one before (so none is hoisted)."""
    def run(x, te, *rest):
        def step(_, carry):
            te, total = carry
            got = fn(x, te, *rest)[0, 0].astype(jnp.float32)
            return jnp.minimum(te + (got != got), te), total + got
        return jax.lax.fori_loop(0, iters, step, (te, jnp.float32(0)))[1]

    program = jax.jit(run)
    program(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        program(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return round(1e6 * best / iters, 1)


def at_depth(depth):
    """This tree's ring held to `depth` slots (None: what it picks)."""
    kept = ring.ring_depth
    if depth is not None:
        ring.ring_depth = lambda block_bytes: depth
    return lambda: setattr(ring, "ring_depth", kept)


def check(name: str, before, iters: int, seed: int) -> dict:
    args, bm, line = operands(name, seed)
    matrices, live = line["matrices"], line["live_tiles"] * line["tile"]
    want = np.asarray(jax.jit(call(before, matrices, bm))(*args))[:live]
    line["a_before_us"] = timed(call(before, matrices, bm), args, iters)
    undo = _cut(before)
    line["b_before_cut_us"] = timed(call(before, matrices, bm), args, iters)
    undo()
    differs = []
    for depth in (2, 3, 4, None):
        undo = at_depth(depth)
        key = f"c_ring{depth or ''}_us"
        got = np.asarray(jax.jit(call(ring, matrices, bm))(*args))[:live]
        if not np.array_equal(got.view(np.uint16), want.view(np.uint16)):
            differs.append(key)
        line[key] = timed(call(ring, matrices, bm), args, iters)
        undo()
    line["ring_depth"] = ring.ring_depth(0)
    undo = _cut(ring)
    line["c_ring_cut_us"] = timed(call(ring, matrices, bm), args, iters)
    undo()
    for key in ("a_before_us", "b_before_cut_us", "c_ring2_us", "c_ring3_us",
                "c_ring4_us", "c_ring_us", "c_ring_cut_us"):
        line["roofline_" + key[:-3]] = round(
            100 * line["floor_us"] / line[key], 1)
    line["differs"] = differs
    print(json.dumps(line), flush=True)
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-file", default=BEFORE,
                    help="time this moe_grouped.py (a parent's) as (a) and "
                         "(b), and hold the ring's outputs to its own")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow alone, here: small widths, the "
                         "kernel interpreted, 2 calls a timing; nothing it "
                         "prints is a device number and the exit code is 1")
    args = ap.parse_args()
    device = jax.devices()[0]
    global INTERPRET
    if args.rehearse_cpu:
        INTERPRET, args.iters = True, 2
        for name, g in list(GEOMETRIES.items()):
            held = min(g[0], 8)
            GEOMETRIES[name] = (held, held * (g[1] // g[0]), 256, 256, g[4],
                                min(g[5], 64), g[6])
    elif device.platform != "tpu":
        print(f"no TPU: the first device is {device.platform}")
        return 1
    spec = importlib.util.spec_from_file_location("kernel_before",
                                                  args.kernel_file)
    before = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(before)
    lines = [check(name, before, args.iters, args.seed + n)
             for n, name in enumerate(GEOMETRIES)]
    result = {"device": device.device_kind, "iters": args.iters,
              "seed": args.seed, "before": os.path.relpath(
                  args.kernel_file, ROOT),
              "differs": [f"{ln['geometry']}: {d}" for ln in lines
                          for d in ln["differs"]],
              "timings": lines}
    if args.rehearse_cpu:
        result = {"cpu_rehearsal": result}
    print(json.dumps(result))
    return 1 if result.get("differs") or args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
