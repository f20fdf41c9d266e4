"""Transfer-plane benchmark: GB/s of the KV data planes over real endpoints.

Decode got a roofline (measured bytes/step vs the HBM datasheet —
`bench.py` mbu); ROADMAP item 3 says transfer gets one too.  This bench
moves a sealed prompt prefix between two REAL engines three ways and
reports wall-clock GB/s for each, against the interconnect datasheet:

  host-staged  — `pull_prefix` over the `kv_blocks` msgpack RPC wire
                 (extract → numpy → msgpack → numpy → inject: two host
                 hops per block);
  device-direct— `pull_prefix_device` over a real `KvTransferPlane`
                 pair (descriptor probe → device pull → ack, batched
                 double-buffered; no numpy ever materialises);
  streamed     — the `EagerPuller` device stream driven by seal
                 announcements (the disagg overlap path), announcements
                 issued back-to-back so the number isolates pipeline
                 throughput rather than prefill overlap (bench/disagg.py
                 measures the overlap itself).

`transfer_mbu` is the device-direct rate over the fabric datasheet —
the ICI figure when holder and puller share a host's chips (this
bench's topology), the DCN figure for cross-host pulls.  On the CPU rig
there is no datasheet (TCP/buffer-copy transports), so the roofline
fields are None and only presence/parity/ratio plumbing is gated
(`bench_gate --smoke`); TPU rounds gate
`transfer.device_vs_host_ratio >= 2.0` (gate.py TPU_FLOORS).

Byte parity is asserted, not assumed: after each pull the puller's
exported block bytes must equal the holder's — a fast-but-corrupting
plane zeroes the ratio and fails the floor.

    python -m dynamo_tpu.bench.transfer_plane     # tiny CPU run, JSON
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional

import numpy as np

from dynamo_tpu.llm.block_manager.device_transfer import (
    KV_OFFER_ENDPOINT,
    KV_PULLED_ENDPOINT,
    KvTransferPlane,
    pull_blocks_device,
    pull_prefix_device,
)
from dynamo_tpu.llm.block_manager.eager import EagerPuller
from dynamo_tpu.llm.block_manager.transfer import (
    KV_BLOCKS_ENDPOINT,
    make_kv_blocks_handler,
    pull_prefix,
    sealed_hashes,
)

# Interconnect datasheet peaks (the transfer_mbu denominators, fixed the
# same way bench.py pins the v5e HBM/FLOP figures so ratios are stable
# across runs): v5e inter-chip interconnect is 1,600 Gbit/s per chip
# (ICI; same-host chip-to-chip pulls), and the DCN path is bounded by a
# 200 Gbit/s NIC (cross-host pulls).
V5E_ICI_BW = 1600e9 / 8      # 200 GB/s
DCN_NIC_BW = 200e9 / 8       # 25 GB/s


def _build_engine(cfg, params, *, num_blocks, block_size, max_pages,
                  max_prefill_chunk):
    from dynamo_tpu.engine.engine import (
        EngineConfig, EngineCore, InferenceEngine)
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=num_blocks,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=block_size,
            max_pages_per_seq=max_pages,
            max_prefill_chunk=max_prefill_chunk,
            decode_buckets=(1, 2, 4),
            prefill_buckets=(max_prefill_chunk,))),
        params=params)
    return InferenceEngine(core)


async def _seal_prompt(engine, prompt) -> None:
    from dynamo_tpu.engine.sampling import SamplingParams

    async for _ in engine.generate("seal", prompt,
                                   SamplingParams(max_tokens=1)):
        pass


async def _parity(eng_holder, eng_puller, hashes: List[int]) -> bool:
    """Byte-identical inject: the puller's exported wire blocks must
    equal the holder's, hash for hash."""
    a = await eng_holder.export_blocks(hashes)
    b = await eng_puller.export_blocks(hashes)
    if set(a) != set(b) or len(a) != len(hashes):
        return False
    return all(np.array_equal(np.asarray(a[h]), np.asarray(b[h]))
               for h in hashes)


async def run_transfer_plane(cfg, *, params=None, n_blocks: int = 24,
                             block_size: int = 8,
                             batch_blocks: int = 4,
                             chunk_blocks: int = 4,
                             max_prefill_chunk: int = 128,
                             on_tpu: Optional[bool] = None) -> Dict:
    """Measure all three planes between two real engines in this
    process; returns the `transfer` BENCH section."""
    import jax

    from dynamo_tpu.models.llama import init_params
    from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

    if params is None:
        params = init_params(cfg, jax.random.key(0))
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"

    max_pages = n_blocks + 4
    mk = lambda: _build_engine(  # noqa: E731
        cfg, params, num_blocks=2 * n_blocks + 8, block_size=block_size,
        max_pages=max_pages, max_prefill_chunk=max_prefill_chunk)
    eng_a, eng_b = mk(), mk()
    await eng_a.start()
    await eng_b.start()
    plane_a = KvTransferPlane(eng_a)
    plane_a.start()
    plane_b = KvTransferPlane(eng_b)
    plane_b.start()

    server = RpcServer()
    server.register(KV_BLOCKS_ENDPOINT, make_kv_blocks_handler(eng_a))
    server.register(KV_OFFER_ENDPOINT, plane_a.make_offer_handler())
    server.register(KV_PULLED_ENDPOINT, plane_a.make_pulled_handler())
    addr = await server.start()
    rpc = RpcClient(addr)

    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size,
                          size=n_blocks * block_size + 3).tolist()
    hashes = sealed_hashes(prompt, block_size)
    cache_cfg = eng_a.core.cache_cfg
    block_bytes = cache_cfg.bytes_per_block   # wire bytes incl. scales
    total_bytes = n_blocks * block_bytes

    try:
        await _seal_prompt(eng_a, prompt)

        async def timed(coro_fn) -> float:
            # Run once warm (one-time jit lowerings — extract, the host-
            # vs device-input inject variants — plus transport dial-in
            # must not be charged to any one plane), once measured.
            for measured in (False, True):
                t0 = time.perf_counter()
                covered = await coro_fn()
                wall = time.perf_counter() - t0
                assert covered == n_blocks * block_size, (
                    f"pull covered {covered} of {n_blocks * block_size} "
                    "tokens — the comparison is void")
                if not measured:
                    await eng_b.clear_kv_blocks()
            return wall

        # Host-staged wire.
        host_s = await timed(lambda: pull_prefix(
            eng_b, rpc, prompt, block_size))
        parity_ok = await _parity(eng_a, eng_b, hashes)
        await eng_b.clear_kv_blocks()

        # Device-direct (batched double-buffered descriptor pulls).
        pulled0 = plane_b.pulled_blocks
        dev_s = await timed(lambda: pull_prefix_device(
            eng_b, plane_b, rpc, prompt, block_size,
            batch_blocks=batch_blocks))
        device_blocks = (plane_b.pulled_blocks - pulled0) // 2
        parity_ok = parity_ok and await _parity(eng_a, eng_b, hashes)
        await eng_b.clear_kv_blocks()

        # Streamed: the eager pipeline fed back-to-back announcements
        # (a puller is single-use — timed() builds one per run).
        last_puller = [None]

        async def streamed():
            puller = EagerPuller(eng_b, lambda a: rpc, prompt,
                                 block_size, plane=plane_b,
                                 max_inflight=2,
                                 batch_blocks=batch_blocks)
            last_puller[0] = puller
            for k in range(chunk_blocks, n_blocks + 1, chunk_blocks):
                puller.on_progress(k, addr)
                await asyncio.sleep(0)     # let pull tasks launch
            puller.on_progress(n_blocks, addr)
            return await puller.finish(addr)

        stream_s = await timed(streamed)
        puller = last_puller[0]
        parity_ok = parity_ok and await _parity(eng_a, eng_b, hashes)
        transport = plane_b.transport_kind
    finally:
        await rpc.close()
        await server.stop()
        plane_a.stop()
        plane_b.stop()
        await eng_a.stop()
        await eng_b.stop()

    def gbs(wall: float) -> float:
        return total_bytes / wall / 1e9 if wall > 0 else 0.0

    host_gbs, dev_gbs, stream_gbs = gbs(host_s), gbs(dev_s), gbs(stream_s)
    # A fast-but-wrong plane must fail the floor, same discipline as
    # prefill_plane's token_parity zeroing the gated ratio.
    ratio = (dev_gbs / host_gbs if host_gbs and parity_ok else 0.0)
    roofline = V5E_ICI_BW if on_tpu else None
    return {
        "n_blocks": n_blocks,
        "block_bytes": block_bytes,
        "total_mb": round(total_bytes / 1e6, 3),
        "kv_quant": cache_cfg.kv_quant,
        "transport": transport,
        "host_staged_gbs": round(host_gbs, 4),
        "device_direct_gbs": round(dev_gbs, 4),
        "streamed_gbs": round(stream_gbs, 4),
        "device_vs_host_ratio": round(ratio, 3),
        "streamed_vs_device_ratio": round(stream_gbs / dev_gbs, 3)
        if dev_gbs else 0.0,
        "device_blocks_pulled": int(device_blocks),
        "streamed_device_blocks": int(puller.device_blocks),
        "byte_parity": bool(parity_ok),
        "fabric_bw_nominal_gbs": round(roofline / 1e9, 1)
        if roofline else None,
        "dcn_bw_nominal_gbs": round(DCN_NIC_BW / 1e9, 1)
        if on_tpu else None,
        "transfer_mbu": round(dev_gbs * 1e9 / roofline, 4)
        if roofline else None,
    }


async def run_tiny_transfer_plane() -> Dict:
    """CPU smoke variant: the tiny model at tiny geometry — plumbing,
    parity and the plane split are real; the GB/s values are CPU-rig
    numbers (local device fabric / localhost RPC), not gated."""
    from dynamo_tpu.models import config as mcfg

    return await run_transfer_plane(
        mcfg.get_config("tiny-test"), n_blocks=12, block_size=8,
        batch_blocks=4, max_prefill_chunk=32, on_tpu=False)


def main() -> int:
    import json

    out = asyncio.run(asyncio.wait_for(run_tiny_transfer_plane(), 180))
    print(json.dumps(out, indent=2))
    ok = (out["byte_parity"] and out["device_blocks_pulled"] > 0
          and out["host_staged_gbs"] > 0)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
