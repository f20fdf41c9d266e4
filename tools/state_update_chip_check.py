#!/usr/bin/env python3
"""On the chip: the state-update kernel (ops/pallas/ssm.py) against the plain
XLA form, and what its time is made of.  No benchmark cell runs this; it is
the check that interpret mode cannot give (PERF.md section 7, PR 48).

    chiprun --timeout 900 -- python tools/state_update_chip_check.py

At both published geometries (Nemotron-3-Super 128 heads x 64 x 128 with 8
groups, Falcon-H1 32 x 128 x 256 with 2), on a leaf of 64 slots and the
scratch slot filled with noise, over rows with padding scattered between the
live ones, one live row of 16, every row live, one row, and no live row:
every live row's `y` and stepped slot within 1e-6 of the plain form's (of the
largest value compared), every padding row's `y` zero, every slot no live row
names, the scratch slot aside, bit for bit what it was.  Exit 1 on any of
these, or where no TPU is found.

Then three timings a geometry and bucket (16 rows of which 13 live, 24 of
which 18; microseconds a call, `--iters` calls in one program):
(a) the kernel as it stands; (b) its body cut to a copy of the block, which
leaves the grid steps and the DMA; (c) the padding rows taken out of R, which
is what skipping them can give at most.  `roofline_a` is the live rows' bytes
(each state once in, once out) over the HBM peak as a share of (a).  One JSON
line, the last of standard output.  `--kernel-file` times another tree's
`ops/pallas/ssm.py` (a parent's) in place of this one's.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from dynamo_tpu.ops import ssm as ssm_ops                     # noqa: E402
from dynamo_tpu.ops.pallas import ssm as kernel               # noqa: E402

GEOMETRIES = {"nemotron-3-super": (128, 64, 128, 8),
              "falcon-h1": (32, 128, 256, 2)}
SLOTS = 64                       # and the scratch slot, the leaf's last
HBM_BYTES_PER_S = 819e9          # one TPU v5e (chipbench/peaks.json)
LIMIT = 1e-6
INTERPRET = False                # --rehearse-cpu: the kernel interpreted


def places(rows: int, live: int, seed: int) -> np.ndarray:
    """[rows] slots: `live` rows on scattered places and scattered slots,
    the rest on the scratch slot."""
    rng = np.random.default_rng(seed)
    slots = np.full((rows,), SLOTS, np.int32)
    at = np.sort(rng.permutation(rows)[:live])
    slots[at] = rng.permutation(SLOTS)[:live]
    return slots


def operands(geometry, slots: np.ndarray, seed: int):
    H, P, N, G = geometry
    R = len(slots)
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (SLOTS + 1, H, P, N)), jnp.asarray(slots),
            jax.random.normal(k[1], (R, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (R, H))),
            -jnp.exp(jax.random.normal(k[3], (H,))),
            jax.random.normal(k[4], (R, G, N)),
            jax.random.normal(k[5], (R, G, N)))


def compare(name: str, geometry, slots: np.ndarray, seed: int) -> list:
    """What differs between the kernel and the plain form; [] when nothing."""
    args = operands(geometry, slots, seed)
    y, out = ssm_ops.ssm_state_update(*args, interpret=INTERPRET)
    want_y, want = jax.jit(ssm_ops.state_update_plain)(*args)
    y, out, want_y, want, before = (np.asarray(v) for v in
                                    (y, out, want_y, want, args[0]))
    live = slots != SLOTS
    wrong, err_y, err_s = [], 0.0, 0.0
    if not (np.isfinite(y).all() and np.isfinite(out).all()):
        wrong.append("a value that is not finite")
    if live.any():
        err_y = np.abs(y[live] - want_y[live]).max() \
            / np.abs(want_y[live]).max()
        named = slots[live]
        err_s = np.abs(out[named] - want[named]).max() \
            / np.abs(want[named]).max()
        if err_y > LIMIT:
            wrong.append(f"y differs by {err_y:.3g}")
        if err_s > LIMIT:
            wrong.append(f"a stepped slot differs by {err_s:.3g}")
    if (~live).any() and np.abs(y[~live]).max() != 0.0:
        wrong.append("a padding row's y is not zero")
    for slot in sorted(set(range(SLOTS)) - set(slots[live].tolist())):
        if not np.array_equal(out[slot], before[slot]):
            wrong.append(f"slot {slot}, which no live row names, changed")
            break
    print(f"{name}: rows {len(slots)} live {int(live.sum())}: "
          + ("; ".join(wrong) or "equal")
          + f" (y within {err_y:.2g}, stepped slots within {err_s:.2g})",
          flush=True)
    return [f"{name}: {w}" for w in wrong]


def _copy_body(*refs, **_):
    """The kernel's body cut to a copy of the block: the grid steps and the
    DMA alone (a padding row's steps copy inside VMEM, which moves nothing
    through the HBM either)."""
    s_ref, y_ref, s_out_ref = refs[-3:]
    y_ref[...] = jnp.zeros_like(y_ref)
    s_out_ref[...] = s_ref[...]


def timed(module, args, iters: int, copy: bool = False) -> float:
    """Microseconds a call of `module.state_update_kernel` over `iters`
    calls in one program, the leaf donated and stepped where it lies."""
    body = module._update_kernel
    if copy:
        module._update_kernel = _copy_body
    try:
        def run(ssm, *rest):
            def step(_, carry):
                ssm, acc = carry
                y, ssm = module.state_update_kernel(ssm, *rest,
                                                    interpret=INTERPRET)
                return ssm, acc + y
            return jax.lax.fori_loop(
                0, iters, step, (ssm, jnp.zeros(rest[1].shape, jnp.float32)))

        fn = jax.jit(run, donate_argnums=(0,))
        ssm, rest = args[0], args[1:]
        ssm, acc = fn(ssm + 0.0, *rest)       # compiles; a copy is donated
        acc.block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ssm, acc = fn(ssm, *rest)
            acc.block_until_ready()
            best = min(best, time.perf_counter() - t0)
    finally:
        module._update_kernel = body
    return round(1e6 * best / iters, 2)


def timings(module, iters: int) -> list:
    lines = []
    for name, geometry in GEOMETRIES.items():
        H, P, N, _ = geometry
        for rows, live in ((16, 13), (24, 18)):
            slots = places(rows, live, rows)
            args = operands(geometry, slots, rows)
            keep = np.flatnonzero(slots != SLOTS)
            alone = (args[0], args[1][keep], args[2][keep], args[3][keep],
                     args[4], args[5][keep], args[6][keep])
            a = timed(module, args, iters)
            floor = 1e6 * live * 2 * H * P * N * 4 / HBM_BYTES_PER_S
            lines.append({
                "geometry": name, "rows": rows, "live": live,
                "a_as_it_stands_us": a,
                "b_copy_body_us": timed(module, args, iters, copy=True),
                "c_live_rows_alone_us": timed(module, alone, iters),
                "floor_us": round(floor, 2),
                "roofline_a": round(100 * floor / a, 1)})
            print(json.dumps(lines[-1]), flush=True)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--kernel-file", default=None,
                    help="time this ops/pallas/ssm.py in place of the tree's")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow alone, here: 16 slots, the kernel "
                         "interpreted, 2 calls a timing, nothing it prints "
                         "is a device number and the exit code is 1")
    args = ap.parse_args()
    device = jax.devices()[0]
    global SLOTS, INTERPRET
    cases = ((16, 13), (24, 18), (16, 1), (16, 16), (1, 1), (64, 40), (16, 0))
    if args.rehearse_cpu:
        SLOTS, INTERPRET, args.iters, cases = 24, True, 2, cases[:3]
    elif device.platform != "tpu":
        print(f"no TPU: the first device is {device.platform}")
        return 1
    wrong = []
    for name, geometry in GEOMETRIES.items():
        for n, (rows, live) in enumerate(cases):
            wrong += compare(name, geometry, places(rows, live, 7 * n + rows),
                             100 + n)
    module = kernel
    if args.kernel_file:
        spec = importlib.util.spec_from_file_location("timed_ssm_kernel",
                                                      args.kernel_file)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    result = {"device": device.device_kind, "iters": args.iters,
              "kernel": args.kernel_file or "this tree's",
              "wrong": wrong, "timings": timings(module, args.iters)}
    if args.rehearse_cpu:
        result = {"cpu_rehearsal": result}
    print(json.dumps(result))
    return 1 if wrong or args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
