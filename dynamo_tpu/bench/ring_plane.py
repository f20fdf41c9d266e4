"""Ring-attention plane benchmark (ISSUE 19): the Pallas flash ring vs
the XLA ppermute ring vs the meshless oracle at sp prefill shape.

The claim under measurement: the flash ring kernel
(ops/pallas/ring_attention.py) beats `ring_causal_attention` (the XLA
ppermute formulation) because its overlap is STRUCTURAL — the next
hop's K/V RDMA is issued before the local block's online-softmax fold,
and the per-hop `s`/`p` intermediates never round-trip HBM — where the
XLA path's overlap is scheduler-dependent.  Three slope timings at one
attention-layer shape:

- `meshless_ms`   — single-device blockwise attention over the full
                    sequence (the no-ring reference slope);
- `xla_ring_ms`   — `ring_causal_attention` under shard_map at sp;
- `kernel_ms`     — `ring_flash_attention` under the same shard_map
                    (compiled on TPU when `ring_geometry_ok` admits the
                    per-shard shape; interpret mode off-TPU, where the
                    time shows plumbing, not silicon).

`kernel_vs_xla` (= xla_ring_ms / kernel_ms) is PARITY-ZEROED: the two
rings' outputs must allclose first — a fast-but-wrong kernel zeroes the
ratio and fails the TPU gate floor `ring_plane.kernel_vs_xla >= 1.15`
(bench/gate.py TPU_FLOORS rationale).  CPU rigs report the interpret-
mode ratio but never gate it (`bench_gate --smoke` asserts presence,
parity, and the engine attribution only).

ICI accounting like transfer_mbu: `per_hop_bytes` is the modeled
payload one chip ships per hop (K+V rows at the exchange dtype, + the
absolute positions that ride with them; the int8 modeled figure adds
the f32 scales and drops the rows to one byte), `ring_ici_mbu` puts the
kernel's total shipped bytes over its measured wall time against the
v5e ICI datasheet — so a TPU round can say how much of the fabric the
overlap actually used.

`engine` subsection: the attribution check at tiny-engine scale — an
sp2+pallas EngineCore must serve token-identical output vs the meshless
engine with `ring_kernel_prefills` counting every sp prefill (the
counter and the trace-time dispatch share ONE predicate,
`ring_kernel_supported`, so this can't drift).  On TPU the tiny
geometry is compiled-ineligible and the engine honestly reports the
XLA-ring fallback (kernel count 0); the smoke gates these fields on the
CPU rig where interpret mode makes the kernel path real.

    python -m dynamo_tpu.bench.ring_plane     # tiny CPU run, JSON
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# v5e ICI datasheet peak — the SAME figure transfer_plane pins (one
# denominator per fabric, so ratios stay stable across runs).
V5E_ICI_BW = 1600e9 / 8      # 200 GB/s


def _slope(fn, n1: int = 2, n2: int = 6) -> float:
    """Trimmed-median slope (bench.harness.measure_slope, repeats=3) —
    these numbers feed a hard gate floor, so one pause must not
    define them."""
    from dynamo_tpu.bench import harness

    fn(1)  # warm / compile
    return harness.measure_slope(fn, n1, n2, repeats=3).per_call_s


def _timed_loop(jitted, *args):
    def run(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = jitted(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    return run


def _engine_attribution() -> Dict:
    """Tiny-engine attribution: sp2+pallas serving must be
    token-identical to meshless AND attribute every sp prefill to the
    ring implementation that actually ran (ring_kernel_prefills)."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    devices = jax.devices()
    if len(devices) < 4:
        return {"skipped": f"needs 4 devices, have {len(devices)}"}
    sched = SchedulerConfig(
        max_seqs=4, block_size=8, max_pages_per_seq=8,
        max_prefill_chunk=16, decode_buckets=(2, 4),
        prefill_buckets=(8, 16))
    prompts = {"a": [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
               "b": list(range(20, 34))}

    def run(mesh=None, **extra):
        kwargs = dict(enable_prefix_cache=False)
        if mesh is not None:
            kwargs.update(sp_prefill_threshold=8)
        kwargs.update(extra)
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=64, mesh=mesh,
            scheduler=sched, **kwargs))
        for rid, toks in prompts.items():
            core.add_request(rid, toks, SamplingParams(max_tokens=12))
        out: Dict = {}
        for _ in range(300):
            for d in core.step():
                out.setdefault(d.request_id, []).extend(d.token_ids)
            if not core._requests:
                break
        return core, out

    _, want = run()
    mesh = make_mesh(MeshConfig(sp=2, tp=2), devices[:4])
    core, got = run(mesh, use_pallas_decode=True)
    return {
        "tokens_match": got == want,
        "sp_prefill_count": core.sp_prefill_count,
        "ring_kernel_prefills": core.counters.ring_kernel_prefills,
        "ring_exchange_bytes_modeled":
            core.counters.ring_exchange_bytes_modeled,
    }


def run_ring_plane(cfg, *, batch: int = 2, seq: int = 512, sp: int = 2,
                   on_tpu: Optional[bool] = None,
                   with_engine: bool = True, seed: int = 0) -> Dict:
    """Measure the three ring slopes at one attention-layer shape and
    return the `ring_plane` BENCH section (see module docstring)."""
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.pallas.ring_attention import (
        ring_flash_attention, ring_kernel_supported)
    from dynamo_tpu.ops.ring_attention import ring_causal_attention
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    devices = jax.devices()
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    soft_cap = cfg.attn_soft_cap
    out: Dict = {"devices": len(devices), "batch": batch, "seq": seq,
                 "sp": sp, "heads": Hq, "kv_heads": Hkv, "head_dim": D}
    if len(devices) < sp:
        out["skipped"] = f"needs {sp} devices, have {len(devices)}"
        return out
    if seq % sp:
        out["skipped"] = f"seq {seq} not divisible by sp {sp}"
        return out

    mesh = make_mesh(MeshConfig(sp=sp), devices[:sp])
    t_loc = seq // sp
    feat = Hkv * D                      # sp-only mesh: no tp head split
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    interpret = not on_tpu

    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (batch, seq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (batch, seq, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (batch, seq, Hkv, D), dtype)
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))

    spec4 = P(None, "sp", None, None)
    spec2 = P(None, "sp")
    specs = (spec4, spec4, spec4, spec2)

    meshless = jax.jit(lambda qs, ks_, vs, ps: ring_causal_attention(
        qs, ks_, vs, ps, scale=cfg.query_scale, soft_cap=soft_cap))
    xla_ring = jax.jit(jax.shard_map(
        lambda qs, ks_, vs, ps: ring_causal_attention(
            qs, ks_, vs, ps, axis_name="sp", scale=cfg.query_scale,
            soft_cap=soft_cap),
        mesh=mesh, in_specs=specs, out_specs=spec4, check_vma=False))

    meshless_s = _slope(_timed_loop(meshless, q, k, v, pos))
    xla_s = _slope(_timed_loop(xla_ring, q, k, v, pos))
    out["meshless_ms"] = round(meshless_s * 1e3, 4)
    out["xla_ring_ms"] = round(xla_s * 1e3, 4)

    # Per-hop modeled ICI payload: one chip's resident K+V rows plus the
    # absolute positions that ride with them (causality survives any
    # interleaving); the int8 modeled figure is the quantized-exchange
    # payload (1-byte rows + f32 per-token-per-head scales).
    hop_tokens = batch * t_loc
    per_hop = hop_tokens * (2 * feat * jnp.dtype(dtype).itemsize + 4)
    per_hop_int8 = hop_tokens * (2 * (feat + 4 * Hkv) + 4)
    out["per_hop_bytes"] = int(per_hop)
    out["per_hop_bytes_int8_modeled"] = int(per_hop_int8)
    out["modeled_ici_bytes"] = int(per_hop) * (sp - 1)
    out["ici_bw_nominal_gbs"] = (round(V5E_ICI_BW / 1e9, 1)
                                 if on_tpu else None)

    # The eligibility discipline: compiled mode consults the SAME
    # geometry predicate the engine/model dispatch uses; a rejected
    # shape reports skipped (floor skipped, never silently passed).
    if not ring_kernel_supported(feat, t_loc, batch, Hq, D, interpret):
        out["kernel"] = {"skipped": f"ring geometry rejected: feat="
                                    f"{feat}, t_local={t_loc}"}
        if with_engine:
            out["engine"] = _engine_attribution()
        return out

    kernel = jax.jit(jax.shard_map(
        lambda qs, ks_, vs, ps: ring_flash_attention(
            qs, ks_, vs, ps, mesh=mesh, scale=cfg.query_scale,
            soft_cap=soft_cap, interpret=interpret),
        mesh=mesh, in_specs=specs, out_specs=spec4, check_vma=False))

    # Numeric parity BEFORE timing: both rings fold the same f32 flash
    # math, so they must agree to output-dtype resolution — a
    # fast-but-wrong kernel zeroes the gated ratio.
    got = np.asarray(kernel(q, k, v, pos), np.float32)
    want = np.asarray(xla_ring(q, k, v, pos), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    parity = bool(np.allclose(got, want, rtol=tol, atol=tol))

    kernel_s = _slope(_timed_loop(kernel, q, k, v, pos))
    out["kernel_ms"] = round(kernel_s * 1e3, 4)
    out["kernel_interpret"] = interpret
    out["numeric_parity"] = parity
    out["kernel_vs_xla"] = (round(xla_s / kernel_s, 3)
                            if kernel_s and parity else 0.0)
    out["kernel_vs_meshless"] = (round(meshless_s / kernel_s, 3)
                                 if kernel_s else 0.0)
    if on_tpu and kernel_s:
        out["ring_ici_mbu"] = round(
            int(per_hop) * (sp - 1) / kernel_s / V5E_ICI_BW, 4)
    if with_engine:
        out["engine"] = _engine_attribution()
    return out


def run_tiny_ring_plane() -> Dict:
    """CPU smoke variant: tiny model, tiny sequence, interpret-mode
    kernel — plumbing, parity and attribution are real; the slope
    values are interpret-mode numbers, not gated."""
    from dynamo_tpu.models import config as mcfg

    return run_ring_plane(mcfg.get_config("tiny-test"), batch=2, seq=32,
                          sp=2, on_tpu=False)


def main() -> int:
    import json

    out = run_tiny_ring_plane()
    print(json.dumps(out, indent=2))
    eng = out.get("engine", {})
    ok = (out.get("numeric_parity") is True
          and eng.get("tokens_match") is True
          and eng.get("ring_kernel_prefills", 0) > 0)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
