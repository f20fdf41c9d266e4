"""Decode-step component profiler with a per-phase breakdown.

Round-4 built the first version (isolated window/kernel slopes); round 6
extends it into the serving-path diagnosis tool the r5 regression lacked:
one JSON artifact that splits a decode step into

  - kernel        — the Pallas paged-decode kernel alone x num_layers
  - weights       — window at ctx=1 (attention reads ~nothing; cost =
                    weight streaming + elementwise + lm_head)
  - non_attention — window minus kernel (RoPE/norm/MLP/lm_head/sampling
                    inside the fused program, plus loop fixed costs)
  - sampling      — argmax over [B, V] logits alone
  - host_sync     — blocking device→host fetch of one window's [K, B]
                    token block (what _sync_one_window pays per window)
  - scheduler     — host-side Scheduler.plan() cost per step at this
                    batch (pure CPU; the engine pays it every iteration)

All device timings are slope-timed with forced completion.  Runs on CPU with a tiny
model for tests (`--model tiny-test --no-probes --json`); on TPU the
default geometry matches bench.py's serving shape (b64/ctx512).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _prescan_mesh() -> None:
    """`--tp/--sp/--pp N` on a CPU host needs N visible devices, and the
    XLA flag must land before jax initialises (same discipline as
    worker/__main__.py's prescan).  Harmless under a real TPU backend:
    the flag only multiplies the HOST platform's device count."""
    argv = sys.argv[1:]
    need = 1
    for flag in ("--tp", "--sp", "--pp"):
        deg = 0
        for i, a in enumerate(argv):
            if a == flag and i + 1 < len(argv):
                deg = int(argv[i + 1])
            elif a.startswith(flag + "="):
                deg = int(a.split("=", 1)[1])
        need *= max(deg, 1)
    if need > 1 and ("xla_force_host_platform_device_count"
                     not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(need, 8)}"
        ).strip()


_prescan_mesh()

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import (
    BlockAllocator,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
)
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params, make_decode_window
from dynamo_tpu.ops.pallas import paged_decode_attention
from dynamo_tpu.runtime.compile_cache import enable_compile_cache

BATCH = 64
CTX = 512
BLOCK = 64
WIDTH = 16
WINDOW = 8


def _sync(x):
    jax.device_get(jax.tree.leaves(x)[0].ravel()[0])


def slope(fn, n1=3, n2=9):
    """fn(n) runs n dependent iterations and syncs; returns per-iter secs."""
    fn(1)  # warm
    t1 = fn(n1)
    t2 = fn(n2)
    return max((t2 - t1) / (n2 - n1), 1e-9)


def _block_tables(batch, width):
    from dynamo_tpu.bench.harness import sequential_block_tables

    return jnp.asarray(sequential_block_tables(batch, width))


def window_time(cfg, params, use_pallas, *, batch=BATCH, ctx=CTX,
                block=BLOCK, width=WIDTH, window=WINDOW,
                kv_quant="none", mesh=None):
    """Per-token device time inside the fused K-step decode window.
    With `mesh`, the SHARDED window (parallel.sharding.make_sharded_window
    — exactly the program a `--tp N` worker dispatches) with params and
    cache laid out over it."""
    num_blocks = 1 + batch * width
    quant = kv_quant != "none"
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        # Fused pp stage programs (ISSUE 12): the schedule-looping
        # decode window over the STACKED layer/cache layout — exactly
        # what a `--pp N` worker dispatches per steady window.
        from dynamo_tpu.parallel.pipeline import (
            init_pp_cache, make_pp_decode_window, pp_cache_pspecs,
            pp_param_pspecs, stack_layer_params)
        from dynamo_tpu.parallel.sharding import shard_pytree

        win = make_pp_decode_window(cfg, block, mesh, 2, window,
                                    greedy_only=True, kv_quant=quant)
        params = shard_pytree(stack_layer_params(params),
                              pp_param_pspecs(cfg), mesh)
        pp_specs = pp_cache_pspecs(quant)

        def make_cache(c):
            del c
            return shard_pytree(
                init_pp_cache(kvc.KvCacheConfig.for_model(
                    cfg, num_blocks=num_blocks, block_size=block,
                    kv_quant=kv_quant)), pp_specs, mesh)
    elif mesh is not None:
        from dynamo_tpu.parallel.sharding import (
            cache_pspecs, make_sharded_window, param_pspecs, shard_pytree)

        win = make_sharded_window(cfg, block, mesh, window,
                                  greedy_only=True,
                                  use_pallas_decode=use_pallas,
                                  kv_quant=quant)
        params = shard_pytree(params, param_pspecs(cfg), mesh)
        cache_specs = cache_pspecs(cfg.num_layers, kv_quant=quant)

        def make_cache(c):
            return shard_pytree(c, cache_specs, mesh)
    else:
        win = jax.jit(
            make_decode_window(cfg, block, window,
                               use_pallas_decode=use_pallas,
                               greedy_only=True),
            donate_argnums=(1,))

        def make_cache(c):
            return c
    bt = _block_tables(batch, width)
    z = jnp.zeros((batch,), jnp.float32)
    zi = jnp.zeros((batch,), jnp.int32)
    ones = jnp.ones((batch,), jnp.float32)
    keys = jnp.zeros((batch, 2), jnp.uint32)

    def fresh():
        return (make_cache(kvc.init_cache(kvc.KvCacheConfig.for_model(
                    cfg, num_blocks=num_blocks, block_size=block,
                    kv_quant=kv_quant))),
                jnp.ones((batch,), jnp.int32))

    def run(n):
        cache, last = fresh()
        t0 = time.perf_counter()
        for _ in range(n):
            cache, out, _, _, _ = win(
                params, cache, last,
                jnp.full((batch,), ctx, jnp.int32),
                jnp.full((batch,), ctx + 1, jnp.int32),
                bt, z, zi, ones, keys, zi)
            last = out[window - 1]
        _sync(last)
        return time.perf_counter() - t0

    per = slope(run, 2, 6)
    return per / window


def kernel_time(cfg, *, batch=BATCH, ctx=CTX, block=BLOCK, width=WIDTH,
                layers=None, interpret=None, tp=1):
    """Pallas paged-decode kernel alone, chained x num_layers per 'step'.
    `tp` > 1 profiles the PER-SHARD geometry a head-sharded engine hands
    the kernel inside shard_map (Hq/tp query heads over an [S, F/tp]
    cache slice) — the honest per-chip kernel cost under tensor
    parallelism."""
    L = layers or cfg.num_layers
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S = (1 + batch * width) * block
    F = cfg.num_kv_heads * cfg.head_dim // tp
    k_cache = jnp.ones((S, F), jnp.bfloat16)
    v_cache = jnp.ones((S, F), jnp.bfloat16)
    bt = _block_tables(batch, width)
    sl = jnp.full((batch,), ctx, jnp.int32)

    @jax.jit
    def step(q):
        for _ in range(L):
            q = paged_decode_attention(q, k_cache, v_cache, bt, sl,
                                       block_size=block,
                                       interpret=interpret)
        return q

    q0 = jnp.ones((batch, cfg.num_heads // tp, cfg.head_dim),
                  jnp.bfloat16)

    def run(n):
        q = q0
        t0 = time.perf_counter()
        for _ in range(n):
            q = step(q)
        _sync(q)
        return time.perf_counter() - t0

    return slope(run)


def sampling_time(cfg, *, batch=BATCH):
    """Greedy sampling alone: argmax over [B, V] f32 logits."""
    logits = jnp.ones((batch, cfg.vocab_size), jnp.float32)

    @jax.jit
    def step(x, i):
        return jnp.argmax(x + i[None, :].astype(jnp.float32), -1)

    def run(n):
        i = jnp.zeros((cfg.vocab_size,), jnp.int32)
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = step(logits, i)
            i = i.at[0].set(out[0].astype(jnp.int32))  # dependency chain
        _sync(out)
        return time.perf_counter() - t0

    return slope(run)


def host_sync_time(*, batch=BATCH, window=WINDOW, reps=5):
    """Blocking device→host fetch of one window's [K, B] token block —
    the cost _sync_one_window pays when the pipeline can't hide it.
    Fixed cost (median of reps), NOT slope-timed: the fetch itself is
    the number."""
    x = jnp.ones((window, batch), jnp.int32)
    _sync(x)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(x))
        samples.append(time.perf_counter() - t0)
    return sorted(samples)[len(samples) // 2]


def scheduler_time(*, batch=BATCH, ctx=CTX, block=BLOCK, iters=200):
    """Host-side Scheduler.plan() per step with `batch` sequences in
    steady decode — pure CPU, the engine pays it every iteration."""
    pages_per = (ctx + block - 1) // block + 1
    alloc = BlockAllocator(1 + batch * pages_per)
    sched = Scheduler(SchedulerConfig(
        max_seqs=max(batch, 64), block_size=block,
        max_pages_per_seq=pages_per + 1), alloc)
    for i in range(batch):
        req = Request(request_id=f"r{i}", prompt_tokens=list(range(ctx)),
                      sampling=SamplingParams(max_tokens=64))
        sched.add_request(req)
    sched.plan()  # admit
    for req in sched.running:
        req.prefilled = len(req.prompt_tokens)
        req.state = RequestState.DECODE
    t0 = time.perf_counter()
    for _ in range(iters):
        sched.plan()
    return (time.perf_counter() - t0) / iters


def phase_breakdown(cfg, params, *, batch=BATCH, ctx=CTX, block=BLOCK,
                    width=WIDTH, window=WINDOW, use_pallas=None,
                    with_kernel=True, mesh=None):
    """The per-phase decode-step split, all values in ms.

    `non_attention` is derived (window - kernel) and only meaningful
    when both run on the real device; on CPU the kernel runs in
    interpret mode and the subtraction is reported as None.

    `mesh` (ISSUE 9 satellite): the window/weights phases run the
    SHARDED programs, so a `--tp N` gap vs meshless is attributable to a
    phase instead of being one opaque number; the kernel phase profiles
    the per-shard geometry."""
    from dynamo_tpu.ops.pallas import mosaic_geometry_ok

    on_tpu = jax.default_backend() == "tpu"
    tp = mesh.shape["tp"] if mesh is not None else 1
    if use_pallas is None:
        feat = cfg.num_kv_heads * cfg.head_dim // max(tp, 1)
        use_pallas = on_tpu and mosaic_geometry_ok(feat, block)
    win_ms = window_time(cfg, params, use_pallas, batch=batch, ctx=ctx,
                         block=block, width=width, window=window,
                         mesh=mesh) * 1e3
    weights_ms = window_time(cfg, params, use_pallas, batch=batch, ctx=1,
                             block=block, width=width,
                             window=window, mesh=mesh) * 1e3
    # 6 decimals: tiny-model CPU smokes can slope-clamp to 1e-6 ms under
    # machine load, and 4-decimal rounding flattened that to a 0.0 that
    # reads as "not measured".
    phases = {
        "window_ms_per_tok": round(win_ms, 6),
        "weights_ms": round(weights_ms, 6),
        "sampling_ms": round(sampling_time(cfg, batch=batch) * 1e3, 6),
        "host_sync_ms": round(
            host_sync_time(batch=batch, window=window) * 1e3, 6),
        "scheduler_ms": round(
            scheduler_time(batch=batch, ctx=ctx, block=block) * 1e3, 6),
        "kernel_ms": None,
        "non_attention_ms": None,
    }
    if with_kernel and cfg.num_heads % max(tp, 1) == 0 \
            and cfg.num_kv_heads % max(tp, 1) == 0:
        k_ms = kernel_time(cfg, batch=batch, ctx=ctx, block=block,
                           width=width, tp=tp) * 1e3
        phases["kernel_ms"] = round(k_ms, 6)
        # Interpret-mode kernel times are not comparable to compiled
        # window times — the subtraction only means something on TPU.
        if on_tpu:
            phases["non_attention_ms"] = round(win_ms - k_ms, 4)
    return phases


def transfer_phase(cfg, block, batch_sizes=(1, 4, 8, 16),
                   n_blocks=32, kv_quant="none"):
    """Pure-transport GB/s of the device plane per pull batch size:
    stage `bsz` wire blocks on a KvTransferPlane, pull them, wall-clock
    the round.  Measures the fabric + staging cost the batched pull
    pipelines amortise — no engines, no RPC, so the number isolates the
    transport itself (pjrt service where the build has it, the local
    device_put fabric otherwise)."""
    import asyncio

    from dynamo_tpu.engine.kv_cache import KvCacheConfig
    from dynamo_tpu.llm.block_manager.device_transfer import (
        KvTransferPlane)

    cache_cfg = KvCacheConfig.for_model(cfg, num_blocks=n_blocks + 1,
                                        block_size=block,
                                        kv_quant=kv_quant)
    shape = cache_cfg.block_wire_shape
    dtype = cache_cfg.block_wire_dtype
    blocks = {h: jnp.zeros(shape, dtype) for h in range(1, n_blocks + 1)}
    jax.block_until_ready(list(blocks.values()))
    block_bytes = cache_cfg.bytes_per_block
    plane = KvTransferPlane(offer_ttl_s=30.0)
    plane.start()

    async def pull_all(bsz: int) -> float:
        order = sorted(blocks)
        t0 = time.perf_counter()
        for lo in range(0, n_blocks, bsz):
            meta = plane.stage(blocks, order[lo:lo + bsz],
                               peer_fabric=plane.fabric)
            assert meta is not None, plane.last_refusal
            pulled = await plane.pull(meta)
            plane.mark_pulled(meta["uuid"])
            assert len(pulled) == len(order[lo:lo + bsz])
        return time.perf_counter() - t0

    per_batch = {}
    for bsz in batch_sizes:
        asyncio.run(pull_all(min(bsz, n_blocks)))    # warm
        wall = asyncio.run(pull_all(min(bsz, n_blocks)))
        per_batch[str(bsz)] = round(
            n_blocks * block_bytes / wall / 1e9, 4) if wall else 0.0
    transport = plane.transport_kind
    plane.stop()
    return {
        "transport": transport,
        "kv_quant": kv_quant,
        "block_bytes": block_bytes,
        "n_blocks": n_blocks,
        "gbs_per_batch_size": per_batch,
    }


def main(argv=None):
    p = argparse.ArgumentParser("tools/profile_decode.py")
    p.add_argument("--model", default="llama-3-1b")
    p.add_argument("--batch", type=int, default=BATCH)
    p.add_argument("--ctx", type=int, default=CTX)
    p.add_argument("--block", type=int, default=BLOCK)
    p.add_argument("--width", type=int, default=WIDTH)
    p.add_argument("--window", type=int, default=WINDOW)
    p.add_argument("--tp", type=int, default=1,
                   help="profile a SHARDED engine's decode phases: the "
                        "window/weights phases run under a tp-degree "
                        "mesh (CPU hosts get virtual devices forced "
                        "before jax init), the kernel phase profiles "
                        "the per-shard geometry — so the sharded gap "
                        "is attributable per phase")
    p.add_argument("--pp", type=int, default=1,
                   help="profile the fused pp stage programs (ISSUE 12):"
                        " window/weights phases run the schedule-looping"
                        " pp decode window over the stacked layout; "
                        "modeled bytes divide by pp (each stage streams "
                        "its layer slice), matching the engine's "
                        "kv_traffic_shards.  Exclusive of --tp/--sp "
                        "(pipeline v1 composes with no other in-mesh "
                        "axis)")
    p.add_argument("--sp", type=int, default=1,
                   help="build the mesh with an sp axis (ring-SP "
                        "engines): decode phases run the sharded "
                        "programs under it.  Modeled decode bytes do "
                        "NOT divide by sp — the sp axis replicates "
                        "decode (its win is ring prefill), and "
                        "dividing would flatter the per-chip numbers "
                        "(the engine's kv_traffic_shards makes the "
                        "same call).  Also grows a ring-kernel phase: "
                        "flash-ring vs XLA-ring vs meshless slopes at "
                        "this geometry + modeled per-hop ICI bytes "
                        "(skip with --no-kernel)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON object instead of the text report")
    p.add_argument("--no-probes", action="store_true",
                   help="skip the HBM-bandwidth / peak-FLOPs probes "
                        "(slow; pointless off-TPU)")
    p.add_argument("--no-kernel", action="store_true",
                   help="skip the Pallas kernel phase (interpret mode "
                        "is slow on CPU at real geometries)")
    p.add_argument("--kv-quant", choices=("none", "int8"), default="none",
                   help="also measure the fused window with the "
                        "quantized KV cache (modeled int8 rooflines are "
                        "always reported)")
    p.add_argument("--transfer", action="store_true",
                   help="also profile the device-transfer plane: pure "
                        "transport GB/s of staged wire-block pulls per "
                        "batch size (ISSUE 13; CPU-runnable — the local "
                        "device fabric on builds without "
                        "jax.experimental.transfer), at this model's "
                        "wire-block geometry in both cache modes")
    p.add_argument("--moe", action="store_true",
                   help="also profile the MoE fast-decode plane (ISSUE "
                        "17): dense-oracle vs grouped-kernel slope "
                        "timing at decode shape plus modeled expert-"
                        "weight bytes (and their HBM floors when probes "
                        "run).  A dense --model profiles an 8-expert "
                        "top-2 variant at its dims; interpret mode "
                        "off-TPU — times then show plumbing, not "
                        "silicon")
    p.add_argument("--prefill-attn", action="store_true",
                   help="also slope-time prefill attention: the Pallas "
                        "paged flash-prefill kernel vs the gather_kv "
                        "path at this geometry (ISSUE 10; interpret "
                        "mode off-TPU — times then show plumbing, not "
                        "silicon)")
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = mcfg.get_config(args.model)
    params = init_params(cfg, jax.random.key(0))
    mesh = None
    if args.pp > 1 and (args.tp > 1 or args.sp > 1):
        p.error("--pp is exclusive of --tp/--sp (pipeline v1 composes "
                "with no other in-mesh axis)")
    mesh_need = max(args.tp, 1) * max(args.sp, 1) * max(args.pp, 1)
    if mesh_need > 1:
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        devices = jax.devices()
        if len(devices) < mesh_need:
            p.error(f"--tp {args.tp} --sp {args.sp} --pp {args.pp} "
                    f"needs {mesh_need} devices; have {len(devices)}")
        mesh = make_mesh(MeshConfig(tp=args.tp, sp=args.sp, pp=args.pp),
                         devices[:mesh_need])
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    # PER-CHIP modeled bytes (same honesty rule as the engine's
    # kv_traffic_shards and the bench's mbu_per_chip): the measured
    # window/kernel times below are per-chip sharded times, so a
    # whole-model byte count would inflate any mbu/roofline derived
    # from this JSON.  Weights and KV both split tp-ways under
    # head-sharded tensor parallelism and pp-ways under the stacked
    # stage layout (each stage streams its layer slice for all rows);
    # sp REPLICATES decode, so it is deliberately NOT a divisor —
    # exactly the engine's kv_traffic_shards discipline.
    shards = max(args.pp, 1) if args.pp > 1 else max(args.tp, 1)
    w_bytes = n_params * 2 // shards
    # True per-context-token KV bytes (incl. int8 scales) from the ONE
    # accounting everything else gates on (bench.py BENCH JSON, the
    # bench_gate traffic-ratio floor) — no forked formula here.
    from dynamo_tpu.bench.decode_wall import kv_quant_traffic

    traffic = kv_quant_traffic(cfg, block_size=args.block,
                               batch=args.batch, ctx=args.ctx)
    kv_bytes = traffic["kv_bytes_per_step_bf16"] // shards
    kv_bytes_int8 = traffic["kv_bytes_per_step_int8"] // shards

    out = {
        "model": args.model,
        "batch": args.batch,
        "ctx": args.ctx,
        "window": args.window,
        "tp": args.tp,
        "pp": args.pp,
        "sp": args.sp,
        "modeled_byte_shards": shards,
        "device": str(jax.devices()[0]),
        "weight_bytes": w_bytes,
        "kv_bytes_per_step": kv_bytes,
        # The decode-bandwidth-wall phase (ISSUE 6): modeled KV bytes
        # each emitted token costs in HBM sweeps, both cache modes — the
        # "move half the bytes" claim as arithmetic a CPU can check
        # (per chip under --tp, like every other figure here).
        "effective_bytes_per_token": {
            "bf16": args.ctx * traffic["bytes_per_context_token_bf16"]
            // shards,
            "int8": args.ctx * traffic["bytes_per_context_token_int8"]
            // shards,
            "traffic_ratio": traffic["traffic_ratio"],
        },
    }
    if not args.no_probes:
        # Peak/bandwidth probes live in bench.py (ONE methodology —
        # VERDICT r3 weak #2); import rather than fork them.
        from bench import calibrate_peak_flops, measure_hbm_bw

        bw = measure_hbm_bw().measured
        pk = calibrate_peak_flops().measured
        out["hbm_bw_gbs"] = round(bw / 1e9, 1)
        out["peak_bf16_tflops"] = round(pk / 1e12, 1)
        out["weights_floor_ms"] = round(w_bytes / bw * 1e3, 4)
        out["kv_floor_ms"] = round(kv_bytes / bw * 1e3, 4)
        out["roofline_ms"] = round((w_bytes + kv_bytes) / bw * 1e3, 4)
        # Quantized-cache roofline: same weights, ~0.53x the KV bytes.
        out["kv_floor_ms_int8"] = round(kv_bytes_int8 / bw * 1e3, 4)
        out["roofline_ms_int8"] = round(
            (w_bytes + kv_bytes_int8) / bw * 1e3, 4)
    out["phases"] = phase_breakdown(
        cfg, params, batch=args.batch, ctx=args.ctx, block=args.block,
        width=args.width, window=args.window,
        with_kernel=not args.no_kernel, mesh=mesh)
    if args.kv_quant != "none":
        # Measured: the fused window's wall time with the quantized cache
        # (gather path dequant on CPU; kernel dequant on TPU) — lets a
        # TPU round report measured-vs-modeled for the int8 plane.
        # Composes with --tp: scales shard with their kv heads.
        from dynamo_tpu.ops.pallas import mosaic_geometry_ok

        feat = cfg.num_kv_heads * cfg.head_dim // max(args.tp, 1)
        use_pallas = (jax.default_backend() == "tpu"
                      and mosaic_geometry_ok(feat, args.block))
        out["phases"]["window_ms_per_tok_int8"] = round(window_time(
            cfg, params, use_pallas,
            batch=args.batch, ctx=args.ctx, block=args.block,
            width=args.width, window=args.window,
            kv_quant=args.kv_quant, mesh=mesh) * 1e3, 6)

    if args.sp > 1:
        # Ring-kernel phase (ISSUE 19): one measurement methodology with
        # the gated `ring_plane` bench section — import, don't fork.
        # Reports the flash-ring-kernel vs XLA-ppermute-ring vs meshless
        # slopes at this geometry plus the modeled per-hop ICI payload
        # in both cache modes (interpret mode off-TPU unless --no-kernel
        # — times then show plumbing, not silicon).
        if args.no_kernel:
            out["ring"] = {"skipped": "--no-kernel"}
        else:
            from dynamo_tpu.bench.ring_plane import run_ring_plane

            out["ring"] = run_ring_plane(
                cfg, batch=min(args.batch, 4), seq=args.ctx, sp=args.sp,
                with_engine=False)

    if args.transfer:
        # Device-transfer transport phase (ISSUE 13): per-batch-size
        # GB/s in both cache modes at this model's wire-block geometry.
        out["transfer"] = {
            "bf16": transfer_phase(cfg, args.block),
            "int8": transfer_phase(cfg, args.block, kv_quant="int8"),
        }

    if args.moe:
        # MoE fast-decode phase (ISSUE 17): one measurement methodology
        # with the gated `moe_decode` bench section — import, don't
        # fork.  Reports dense/grouped/int8 step slopes, bitwise parity,
        # the [E+1] expert-load histogram, and modeled per-step expert-
        # weight bytes (dense streams all E experts; grouped streams
        # only the active ones).
        from dynamo_tpu.bench.moe_decode import run_moe_decode

        moe_cfg = cfg if cfg.is_moe else cfg.replace(
            name=cfg.name + "-moe8", num_experts=8,
            num_experts_per_token=2)
        moe = run_moe_decode(moe_cfg, batch=args.batch)
        # Expert-weight HBM floors against the SAME measured bandwidth
        # the dense rooflines above use — the grouped kernel's claim
        # ("decode is weight-bytes-bound; stop streaming inactive
        # experts") as arithmetic next to the measured slopes.
        if "hbm_bw_gbs" in out and "dense_expert_weight_bytes" in moe:
            bw = out["hbm_bw_gbs"] * 1e9
            moe["dense_expert_weights_floor_ms"] = round(
                moe["dense_expert_weight_bytes"] / bw * 1e3, 4)
            moe["grouped_expert_weights_floor_ms"] = round(
                moe["grouped_expert_weight_bytes"] / bw * 1e3, 4)
        out["moe"] = moe

    if args.prefill_attn:
        # Prefill-plane attention phase (ISSUE 10): one measurement
        # methodology with the gated bench — import, don't fork.
        from dynamo_tpu.bench.prefill_plane import measure_prefill_attention

        out["prefill_attention"] = measure_prefill_attention(
            cfg, block_size=args.block,
            ctx=min(args.ctx, args.width * args.block),
            chunk=min(args.ctx, args.width * args.block),
            segments=4,
            interpret=jax.default_backend() != "tpu")

    if args.json:
        print(json.dumps(out))
        return out
    for k, v in out.items():
        if k == "phases":
            print("phases (ms):")
            for pk_, pv in v.items():
                print(f"  {pk_:22s} {pv}")
        else:
            print(f"{k:24s} {v}")
    return out


if __name__ == "__main__":
    main()
