"""Sharded fast-decode plane benchmark (ISSUE 9 leg 4; pp/sp + the
composition matrix added by ISSUE 12).

Measures whether tok/s/chip on a sharded engine approaches the meshless
number — the composition claim of the fast decode plane (int8 KV, Pallas
paged decode, fused greedy steps all working UNDER a mesh).  Before this
PR every multi-chip engine decoded on the slow bf16 GSPMD-gather path
with the r5 single-step cliff; this section is what keeps that from
silently coming back.

ISSUE 12 additions:

- pp2 / sp2 modes with FUSED-vs-UNFUSED slope timings: `single_unfused_ms`
  is the r5-cliff dispatch shape (step returning [B, V] logits + a
  separate argmax dispatch + feedback) and `fused_vs_unfused` the ratio
  the fused program must win; the headline `pp_fused_vs_single` (pp2's
  ratio) carries a TPU gate floor >= 1.2 — the all-in-one stage program
  must measurably kill the pp half of the cliff.
- `compose_matrix`: one status per (feature x mesh) cell — "ok" with
  tok/s/chip when measured, "declared: <reason>" when the capability
  table (parallel.sharding.plane_capability) declares it impossible,
  "skipped: ..." on small rigs, and "rejected: <error>" when a builder
  that should compose raises — which FAILS the gate (bench/gate.py), so
  a regressing cell can't hide behind a pretty headline number.

Per mesh mode (tp2 / dp2 / sp2 / pp2) the section reports:

- `window_step_ms` / `tok_s` / `tok_s_per_chip` — the fused K-token
  decode window through parallel.sharding.make_sharded_window, exactly
  the program a served sharded engine dispatches;
- `single_step_ms` and `single_vs_window` — the fused greedy
  forward+argmax single step (make_sharded_greedy_step) against the
  per-token window cost; ≤ ~1.2 means the sharded single-step cliff is
  dead (acceptance criterion);
- `mbu_per_chip` (TPU, when hbm_bw/weight_bytes given) — per-chip bytes
  (weights/tp + KV/shards) over the window step time vs nominal HBM
  bandwidth, consistent with the engine's per-chip
  `kv_read_bytes_modeled` accounting;
- `window_step_ms_int8` (tp2) — the same window with the int8 quantized
  cache, scales sharded with their kv heads.

The headline gate number is `tok_s_per_chip_ratio` = tp2 tok/s/chip ÷
meshless tok/s (one chip): `bench_gate` holds it ≥ 0.8 on TPU rounds
(tools/bench_gate.py TPU_FLOORS rationale).  Fewer than 2 visible
devices skips the sharded modes (the section still appears, ratio
absent → floor skipped, never silently passed).

All timings are slope-timed with forced completion (the bench.py
honesty rules); CPU runs use tiny geometries through the same code
paths (`bench_gate --smoke`).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _sync(x) -> None:
    jax.device_get(jax.tree.leaves(x)[0].ravel()[0])


def _slope(fn, n1: int, n2: int) -> float:
    """Trimmed-median slope (bench.harness.measure_slope, repeats=3):
    this number feeds a hard gate floor, so a single pause
    inside one run window must not define it."""
    from dynamo_tpu.bench import harness

    fn(1)  # warm / compile
    return harness.measure_slope(fn, n1, n2, repeats=3).per_call_s


def _block_tables(batch: int, width: int) -> jnp.ndarray:
    from dynamo_tpu.bench.harness import sequential_block_tables

    return jnp.asarray(sequential_block_tables(batch, width))


def _window_loop(win, params, fresh, batch, ctx, bt, window):
    z = jnp.zeros((batch,), jnp.float32)
    zi = jnp.zeros((batch,), jnp.int32)
    ones = jnp.ones((batch,), jnp.float32)
    keys = jnp.zeros((batch, 2), jnp.uint32)

    def run(n):
        cache, last = fresh()
        t0 = time.perf_counter()
        for _ in range(n):
            out = win(params, cache, last,
                      jnp.full((batch,), ctx, jnp.int32),
                      jnp.full((batch,), ctx + 1, jnp.int32),
                      bt, z, zi, ones, keys, zi)
            cache, toks = out[0], out[1]
            last = toks[window - 1]
        _sync(last)
        return time.perf_counter() - t0

    return _slope(run, 2, 6) / window  # seconds per token-step


def _single_loop(fused, params, fresh, batch, ctx, bt):
    zi = jnp.zeros((batch,), jnp.int32)

    def run(n):
        cache, last = fresh()
        toks = last[:, None]
        t0 = time.perf_counter()
        for i in range(n):
            res = fused(params, cache,
                        toks,
                        jnp.full((batch, 1), ctx - 1 + i, jnp.int32),
                        jnp.full((batch,), ctx + i, jnp.int32),
                        bt, zi)
            toks_flat, cache = res[0], res[1]
            toks = toks_flat[:, None]
        _sync(toks)
        return time.perf_counter() - t0

    return _slope(run, 3, 9)


def _measure_meshless(cfg, params, batch, ctx, block, width, window,
                      num_blocks):
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models.llama import make_decode_window, make_forward_step

    on_tpu = jax.default_backend() == "tpu"
    win = jax.jit(make_decode_window(cfg, block, window,
                                     use_pallas_decode=on_tpu,
                                     greedy_only=True),
                  donate_argnums=(1,))
    bt = _block_tables(batch, width)

    def fresh():
        return (kvc.init_cache(kvc.KvCacheConfig.for_model(
                    cfg, num_blocks=num_blocks, block_size=block)),
                jnp.ones((batch,), jnp.int32))

    win_s = _window_loop(win, params, fresh, batch, ctx, bt, window)

    fwd = make_forward_step(cfg, block, use_pallas_decode=on_tpu)

    def fused_fn(p, cache, toks, pos, sl, bts, sp):
        logits, cache = fwd(p, cache, toks, pos, sl, bts, sp)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    fused = jax.jit(fused_fn, donate_argnums=(1,))
    single_s = _single_loop(fused, params, fresh, batch, ctx, bt)
    return win_s, single_s


def _measure_mesh(cfg, params, mesh, batch, ctx, block, width, window,
                  num_blocks, kv_quant=False, with_unfused=False,
                  with_single=True):
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.parallel.sharding import (
        cache_pspecs, make_sharded_greedy_step, make_sharded_window,
        param_pspecs, shard_pytree)

    from dynamo_tpu.ops.pallas import mosaic_geometry_ok

    on_tpu = jax.default_backend() == "tpu"
    # Pallas per-shard geometry: heads split over tp, so the per-shard
    # feature width must still satisfy Mosaic tiling (the engine's own
    # auto rule, one shared predicate).
    tp = mesh.shape["tp"]
    feat = cfg.num_kv_heads * cfg.head_dim // max(tp, 1)
    pallas = on_tpu and mosaic_geometry_ok(feat, block)
    win = make_sharded_window(cfg, block, mesh, window, greedy_only=True,
                              use_pallas_decode=pallas,
                              kv_quant=kv_quant)
    sparams = shard_pytree(params, param_pspecs(cfg), mesh)
    cache_specs = cache_pspecs(cfg.num_layers, kv_quant=kv_quant)
    bt = _block_tables(batch, width)

    def fresh():
        return (shard_pytree(
                    kvc.init_cache(kvc.KvCacheConfig.for_model(
                        cfg, num_blocks=num_blocks, block_size=block,
                        kv_quant="int8" if kv_quant else "none")),
                    cache_specs, mesh),
                jnp.ones((batch,), jnp.int32))

    win_s = _window_loop(win, sparams, fresh, batch, ctx, bt, window)
    if not with_single:
        # int8 re-pass keeps only the window time — don't compile two
        # more single-step programs to throw their timings away.
        return win_s, None, None
    fused = make_sharded_greedy_step(cfg, block, mesh,
                                     use_pallas_decode=pallas,
                                     kv_quant=kv_quant)
    single_s = _single_loop(fused, sparams, fresh, batch, ctx, bt)
    unfused_s = None
    if with_unfused:
        from dynamo_tpu.parallel.sharding import make_sharded_step

        step = make_sharded_step(cfg, block, mesh,
                                 use_pallas_decode=pallas,
                                 kv_quant=kv_quant)
        argmax = jax.jit(lambda l: jnp.argmax(l, -1).astype(jnp.int32))

        def unfused(p, cache, toks, pos, sl, bts, sp):
            # The r5-cliff dispatch shape: full [B, V] f32 logits out of
            # the step, then a SEPARATE argmax dispatch — what every
            # sharded single-step decode paid before the fused program.
            logits, cache = step(p, cache, toks, pos, sl, bts, sp)
            return argmax(logits), cache

        unfused_s = _single_loop(unfused, sparams, fresh, batch, ctx, bt)
    return win_s, single_s, unfused_s


def _measure_pp(cfg, params, mesh, batch, ctx, block, width, window,
                num_blocks, n_microbatches=2, kv_quant=False,
                with_single=True):
    """pp2 mode (ISSUE 12 leg 3): the schedule-looping decode window,
    the all-in-one fused greedy stage program, and the UNFUSED loop it
    replaces (pp step → [B, V] logits → separate argmax → feedback).
    `with_single=False` builds/times ONLY the window (the int8 re-pass
    keeps just w8_s — compiling two more stage programs to discard
    their timings would burn bench/smoke wall-clock for nothing)."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.parallel.pipeline import (
        init_pp_cache, make_pp_decode_window, make_pp_greedy_step,
        make_pp_step, pp_cache_pspecs, pp_param_pspecs,
        stack_layer_params)
    from dynamo_tpu.parallel.sharding import shard_pytree

    sparams = shard_pytree(stack_layer_params(params),
                           pp_param_pspecs(cfg), mesh)
    cache_specs = pp_cache_pspecs(kv_quant)

    def fresh():
        return (shard_pytree(
                    init_pp_cache(kvc.KvCacheConfig.for_model(
                        cfg, num_blocks=num_blocks, block_size=block,
                        kv_quant="int8" if kv_quant else "none")),
                    cache_specs, mesh),
                jnp.ones((batch,), jnp.int32))

    bt = _block_tables(batch, width)
    win = make_pp_decode_window(cfg, block, mesh, n_microbatches, window,
                                greedy_only=True, kv_quant=kv_quant)
    win_s = _window_loop(win, sparams, fresh, batch, ctx, bt, window)
    if not with_single:
        return win_s, None, None
    fused = make_pp_greedy_step(cfg, block, mesh, n_microbatches,
                                kv_quant=kv_quant)
    step = make_pp_step(cfg, block, mesh, n_microbatches,
                        kv_quant=kv_quant)
    argmax = jax.jit(lambda l: jnp.argmax(l, -1).astype(jnp.int32))

    def unfused(p, cache, toks, pos, sl, bts, sp):
        logits, cache = step(p, cache, toks, pos, sl, bts, sp)
        return argmax(logits), cache

    single_s = _single_loop(fused, sparams, fresh, batch, ctx, bt)
    unfused_s = _single_loop(unfused, sparams, fresh, batch, ctx, bt)
    return win_s, single_s, unfused_s


def run_sharded_decode(cfg, params=None, *, batch: int = 64,
                       ctx: int = 512, block: int = 64, width: int = 16,
                       window: int = 8,
                       hbm_bw: Optional[float] = None,
                       weight_bytes: Optional[int] = None,
                       modes=("tp2", "dp2", "sp2", "pp2"),
                       with_int8: bool = True,
                       meshless_window_step_s: Optional[float] = None,
                       meshless_single_step_s: Optional[float] = None,
                       seed: int = 0) -> Dict:
    """The `sharded_decode` BENCH section (see module docstring).

    `meshless_window_step_s` / `meshless_single_step_s`: bench.py already
    slope-times the meshless window and the fused raw single step at
    this exact geometry — pass them in to skip the duplicate compile +
    measurement (standalone callers, e.g. the smoke, omit them and this
    function measures its own baseline)."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models.llama import init_params
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    if params is None:
        params = init_params(cfg, jax.random.key(seed))
    devices = jax.devices()
    num_blocks = 1 + batch * width
    out: Dict = {"devices": len(devices), "batch": batch, "ctx": ctx,
                 "window": window}

    if meshless_window_step_s and meshless_single_step_s:
        win_s, single_s = meshless_window_step_s, meshless_single_step_s
    else:
        win_s, single_s = _measure_meshless(cfg, params, batch, ctx,
                                            block, width, window,
                                            num_blocks)
    meshless_tok_s = batch / win_s
    out["meshless"] = {
        "window_step_ms": round(win_s * 1e3, 4),
        "single_step_ms": round(single_s * 1e3, 4),
        "tok_s": round(meshless_tok_s, 2),
        "single_vs_window": round(single_s / win_s, 3),
    }

    kv_bytes = (batch * ctx
                * kvc.KvCacheConfig.for_model(
                    cfg, num_blocks=2, block_size=block)
                .bytes_per_context_token)
    mesh_cfgs = {"tp2": MeshConfig(tp=2), "dp2": MeshConfig(dp=2),
                 "sp2": MeshConfig(sp=2), "pp2": MeshConfig(pp=2)}
    matrix: Dict = {}
    for mode in modes:
        mcfg_ = mesh_cfgs[mode]
        if mcfg_.size > len(devices):
            out[mode] = {"skipped": f"needs {mcfg_.size} devices, "
                                    f"have {len(devices)}"}
            matrix[f"fused_decode × {mode}"] = {
                "status": f"skipped: needs {mcfg_.size} devices"}
            continue
        if mode == "pp2" and cfg.num_layers % 2:
            out[mode] = {"skipped": f"pp2 needs an even layer count, "
                                    f"model has {cfg.num_layers}"}
            matrix[f"fused_decode × {mode}"] = {
                "status": "skipped: odd layer count"}
            continue
        mesh = make_mesh(mcfg_, devices[:mcfg_.size])
        try:
            if mode == "pp2":
                w_s, s_s, u_s = _measure_pp(
                    cfg, params, mesh, batch, ctx, block, width, window,
                    num_blocks)
            else:
                w_s, s_s, u_s = _measure_mesh(
                    cfg, params, mesh, batch, ctx, block, width, window,
                    num_blocks, with_unfused=(mode == "sp2"))
        except Exception as e:  # a composing cell that raises must FAIL
            # the gate (bench/gate.py rejects "rejected" statuses) —
            # never silently vanish from the section.
            out[mode] = {"rejected": str(e)}
            matrix[f"fused_decode × {mode}"] = {
                "status": f"rejected: {e}"}
            continue
        entry = {
            "window_step_ms": round(w_s * 1e3, 4),
            "single_step_ms": round(s_s * 1e3, 4),
            "tok_s": round(batch / w_s, 2),
            "tok_s_per_chip": round(batch / w_s / mcfg_.size, 2),
            # The cliff criterion: the fused sharded single step must sit
            # near the windowed per-token cost, not 2x over it.
            "single_vs_window": round(s_s / w_s, 3),
        }
        if u_s is not None:
            # Fused-vs-unfused: the fused program against the r5-cliff
            # dispatch shape it replaces (ISSUE 12).
            entry["single_unfused_ms"] = round(u_s * 1e3, 4)
            entry["fused_vs_unfused"] = round(u_s / s_s, 3)
        if hbm_bw and weight_bytes:
            # Per-chip moved bytes: tp shards weights AND KV tp-ways; dp
            # replicates weights but each chip serves batch/dp rows of
            # the (replicated-slot) cache; a pp stage streams its layer
            # slice of both; sp replicates decode entirely (the honest
            # per-chip mbu does NOT divide by sp — the win is prefill).
            if mode == "tp2":
                per_chip = (weight_bytes + kv_bytes) / mcfg_.size
            elif mode == "pp2":
                per_chip = (weight_bytes + kv_bytes) / mcfg_.size
            elif mode == "sp2":
                per_chip = weight_bytes + kv_bytes
            else:
                per_chip = weight_bytes + kv_bytes / mcfg_.size
            entry["mbu_per_chip"] = round(per_chip / w_s / hbm_bw, 4)
        if (mode in ("tp2", "sp2", "pp2") and with_int8
                and cfg.num_kv_heads >= 2):
            try:
                if mode == "pp2":
                    w8_s, _, _ = _measure_pp(
                        cfg, params, mesh, batch, ctx, block, width,
                        window, num_blocks, kv_quant=True,
                        with_single=False)
                else:
                    w8_s, _, _ = _measure_mesh(
                        cfg, params, mesh, batch, ctx, block, width,
                        window, num_blocks, kv_quant=True,
                        with_single=False)
                entry["window_step_ms_int8"] = round(w8_s * 1e3, 4)
                matrix[f"int8 × {mode}"] = {"status": "ok"}
            except Exception as e:
                matrix[f"int8 × {mode}"] = {"status": f"rejected: {e}"}
        out[mode] = entry
        matrix[f"fused_decode × {mode}"] = {
            "status": "ok", "tok_s_per_chip": entry["tok_s_per_chip"]}
    # Declared-impossible cells come from the ONE capability table, so
    # the matrix summary and the engine's pointed errors can never
    # drift (the README Notes line quotes the same reasons).
    from dynamo_tpu.parallel.sharding import PlaneSpec, plane_capability

    if len(devices) >= 2:
        any2 = make_mesh(MeshConfig(tp=2), devices[:2])
        pp2 = make_mesh(MeshConfig(pp=2), devices[:2])
        for cell, (mesh_, plane, mh) in {
            "spec × multihost": (any2, PlaneSpec(spec=True), True),
            "spec × pp": (pp2, PlaneSpec(spec=True), False),
            "pallas × dp_attention(non-local)": (
                any2, PlaneSpec(use_pallas=True, dp_attention=True),
                False),
            "pallas × pp": (pp2, PlaneSpec(use_pallas=True), False),
            "pallas × multihost": (any2, PlaneSpec(use_pallas=True),
                                   True),
        }.items():
            cap = plane_capability(mesh_, plane, multihost=mh)
            matrix[cell] = {"status": ("ok" if cap.ok
                                       else f"declared: {cap.reason}")}
    out["compose_matrix"] = matrix
    tp2 = out.get("tp2", {})
    if "tok_s_per_chip" in tp2 and meshless_tok_s:
        out["tok_s_per_chip_ratio"] = round(
            tp2["tok_s_per_chip"] / meshless_tok_s, 4)
    pp2_entry = out.get("pp2", {})
    if "fused_vs_unfused" in pp2_entry:
        # Gate floor sharded_decode.pp_fused_vs_single >= 1.2 (TPU).
        out["pp_fused_vs_single"] = pp2_entry["fused_vs_unfused"]
    return out
