"""Device-direct KV data plane v2 (the NIXL analog, device edition).

Same-process: worker A stages G1-resident device blocks, worker B pulls
them device-to-device and serves the prompt with prefill skipped — no
numpy hop on either side.  On jax builds without the PJRT transfer
service the plane rides the local device_put fabric, so these tests run
(and the plane-choice counters are pinned) on the plain CPU rig.

Two-process: a holder process stages blocks and prints its descriptor; a
puller process in a separate OS process pulls over localhost — the CPU
stand-in for the cross-host DCN path (the driver's multi-chip dryrun
model, SURVEY §7 'riskiest novel component').  PJRT-only: the local
fabric cannot cross processes, so those tests skip without the service.
"""

import asyncio
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore, InferenceEngine
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.llm.block_manager.device_transfer import (
    KV_OFFER_ENDPOINT,
    KV_PULLED_ENDPOINT,
    MAX_OUTSTANDING_OFFERS,
    KvTransferPlane,
    plane_counts,
    pull_prefix_device,
)
from dynamo_tpu.llm.block_manager.transfer import (
    KV_BLOCKS_ENDPOINT,
    make_kv_blocks_handler,
    sealed_hashes,
)
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

TINY = mcfg.get_config("tiny-test")
BS = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LONG_PROMPT = list(range(1, 36))   # 4 sealed blocks + 3-token tail

def _core(kv_quant="none"):
    return EngineCore(EngineConfig(
        model=TINY, num_blocks=64, kv_quant=kv_quant,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=BS, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))


class _Holder:
    """One in-process donor worker: engine + plane + RPC server with the
    offer/ack/kv_blocks endpoints (what worker/main.py registers)."""

    async def start(self, kv_quant="none"):
        self.engine = InferenceEngine(_core(kv_quant))
        await self.engine.start()
        self.plane = KvTransferPlane(self.engine)
        self.plane.start()
        self.rpc = RpcServer()
        self.rpc.register(KV_OFFER_ENDPOINT, self.plane.make_offer_handler())
        self.rpc.register(KV_PULLED_ENDPOINT,
                          self.plane.make_pulled_handler())
        self.rpc.register(KV_BLOCKS_ENDPOINT,
                          make_kv_blocks_handler(self.engine))
        self.address = await self.rpc.start()
        return self

    async def stop(self):
        await self.rpc.stop()
        self.plane.stop()
        await self.engine.stop()


async def _collect(engine, rid, prompt, n=4):
    out = []
    async for d in engine.generate(rid, list(prompt),
                                   SamplingParams(max_tokens=n)):
        out.extend(d.token_ids)
    return out


def _count(plane: str) -> int:
    return sum(n for (p, _), n in plane_counts().items() if p == plane)


def _reasons(plane: str) -> dict:
    return {r: n for (p, r), n in plane_counts().items() if p == plane}


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 180))


def test_device_pull_between_engines_same_process():
    prompt = list(range(40, 70))  # 3 sealed blocks + tail

    async def main():
        holder = await _Holder().start()
        eng_b = InferenceEngine(_core())
        await eng_b.start()
        plane_b = KvTransferPlane(eng_b)
        plane_b.start()
        client = RpcClient(holder.address)
        dev0 = _count("device")
        try:
            out_a = await _collect(holder.engine, "a", prompt)

            covered = await pull_prefix_device(eng_b, plane_b, client,
                                               prompt, BS)
            assert covered == 24  # 3 sealed blocks of 8
            assert holder.plane.offers == 1
            assert plane_b.pulled_blocks == 3
            assert _count("device") - dev0 == 1   # one batched round
            # The puller's ack (spawned off the pull's critical path)
            # retires the holder's offer accounting.
            for _ in range(200):
                if not holder.plane._outstanding:
                    break
                await asyncio.sleep(0.01)
            assert len(holder.plane._outstanding) == 0

            out_b = await _collect(eng_b, "b", prompt)
            assert out_b == out_a
            assert eng_b.core.allocator.manager.device.hits >= 3

            # Unknown hashes: refused offer ('not_resident'), puller
            # reports 0 — the fallback signal — and the reason is
            # counted against the host plane.
            covered = await pull_prefix_device(
                eng_b, plane_b, client, list(range(200, 216)), BS)
            assert covered == 0
            assert _reasons("host").get("not_resident", 0) >= 1
        finally:
            await client.close()
            await holder.stop()
            plane_b.stop()
            await eng_b.stop()

    _run(main())


def test_offer_ttl_and_refusal_split():
    """Stale-offer reclaim (ISSUE 13 satellite): offers carry a TTL;
    expired offers retire from the outstanding accounting (counted
    separately from cap refusals), so a puller that died between offer
    and pull cannot starve the cap forever."""
    import jax.numpy as jnp

    blocks = {h: jnp.zeros((2, 2, BS, 4), jnp.float32)
              for h in range(1, MAX_OUTSTANDING_OFFERS + 2)}

    # Default TTL: the cap refuses the 33rd offer.
    plane = KvTransferPlane()
    plane.start()
    first = plane.stage(blocks, [1])
    assert first is not None
    for h in range(2, MAX_OUTSTANDING_OFFERS + 1):
        assert plane.stage(blocks, [h]) is not None
    assert plane.stage(blocks, [MAX_OUTSTANDING_OFFERS + 1]) is None
    assert plane.last_refusal == "offer_cap"
    assert plane.refused_offers == 1 and plane.expired_offers == 0
    # An ack retires one slot and the next offer fits again.
    plane.mark_pulled(first["uuid"])
    assert plane.stage(blocks, [MAX_OUTSTANDING_OFFERS + 1]) is not None
    plane.stop()

    # TTL 0: hitting the cap expires the stale offers instead of
    # refusing — the cap stops lying about strandable memory.
    plane = KvTransferPlane(offer_ttl_s=0.0)
    plane.start()
    for h in range(1, MAX_OUTSTANDING_OFFERS + 1):
        assert plane.stage(blocks, [h]) is not None
    assert plane.stage(blocks, [MAX_OUTSTANDING_OFFERS + 1]) is not None
    assert plane.expired_offers == MAX_OUTSTANDING_OFFERS
    assert plane.refused_offers == 0
    assert len(plane._outstanding) == 1
    plane.stop()

    # Transport mismatch (a peer on a fabric this holder can't reach)
    # refuses with its own reason on every transport kind.
    plane = KvTransferPlane()
    plane.start()
    assert plane.stage(blocks, [1], peer_fabric="local:0") is None
    assert plane.last_refusal == "transport"
    assert plane.refused_offers == 1
    plane.stop()


@pytest.mark.slow
def test_int8_packed_block_device_pull_parity():
    """ISSUE 13 satellite: the packed int8 wire block [2, L, bs, F+4Hkv]
    crosses the device plane byte-identical to the host-staged path, and
    a mixed bf16<-int8 device offer is refused loudly at inject.

    Slow-marked (3 engine builds): tier-1 runs ~650-800 s against the
    870 s timeout, and its acceptance coverage (byte-identical outputs +
    pinned plane counters) stays in tier-1 via the bf16 eager/prefix
    e2e tests below; the int8 wire itself is also parity-checked by
    tests/test_kv_transfer.py on the host plane."""
    prompt = list(range(40, 70))

    async def main():
        holder = await _Holder().start("int8")
        eng_b = InferenceEngine(_core("int8"))
        await eng_b.start()
        plane_b = KvTransferPlane(eng_b)
        plane_b.start()
        eng_c = InferenceEngine(_core())          # bf16: must refuse
        await eng_c.start()
        plane_c = KvTransferPlane(eng_c)
        plane_c.start()
        client = RpcClient(holder.address)
        try:
            out_a = await _collect(holder.engine, "a", prompt)
            covered = await pull_prefix_device(eng_b, plane_b, client,
                                               prompt, BS)
            assert covered == 24

            hashes = sealed_hashes(prompt, BS)
            wire_shape = holder.engine.core.cache_cfg.block_wire_shape
            exp_a = await holder.engine.export_blocks(hashes)
            exp_b = await eng_b.export_blocks(hashes)
            assert set(exp_b) == set(hashes)
            for h in hashes:
                a, b = np.asarray(exp_a[h]), np.asarray(exp_b[h])
                assert a.dtype == b.dtype == np.int8
                assert a.shape == b.shape == wire_shape
                assert np.array_equal(a, b)   # byte-identical inject

            out_b = await _collect(eng_b, "b", prompt)
            assert out_b == out_a

            # Mixed-mode peer: the bf16 engine's inject must REFUSE the
            # packed int8 block — loudly, with nothing in the cache —
            # and the error propagates so the caller falls back to
            # LOCAL prefill (the host wire would refuse identically).
            with pytest.raises(ValueError, match="kv_quant"):
                await pull_prefix_device(eng_c, plane_c, client, prompt,
                                         BS)
            assert eng_c.core.allocator.manager.onboarded_blocks == 0
        finally:
            await client.close()
            await holder.stop()
            for plane, eng in ((plane_b, eng_b), (plane_c, eng_c)):
                plane.stop()
                await eng.stop()

    _run(main())


def test_eager_stream_rides_device_plane():
    """Acceptance e2e: eager streaming pulls sealed blocks
    device-to-device while 'prefill' announces progress — plane
    counters pinned, outputs byte-identical, zero host-staged blocks."""
    from dynamo_tpu.llm.block_manager.eager import EagerPuller

    async def main():
        holder = await _Holder().start()
        eng_b = InferenceEngine(_core())
        await eng_b.start()
        plane_b = KvTransferPlane(eng_b)
        plane_b.start()
        client = RpcClient(holder.address)
        dev0, host0 = _count("device"), _count("host")
        try:
            out_a = await _collect(holder.engine, "a", LONG_PROMPT)

            puller = EagerPuller(eng_b, lambda a: client, LONG_PROMPT,
                                 BS, plane=plane_b, batch_blocks=2)
            puller.on_progress(2, holder.address)
            await asyncio.sleep(0.05)      # first batch in flight
            puller.on_progress(4, holder.address)
            covered = await puller.finish(holder.address)

            assert covered == 4 * BS
            assert puller.covered_blocks == 4
            assert puller.device_blocks == 4       # ALL blocks device
            assert plane_b.pulled_blocks == 4
            assert _count("device") - dev0 >= 2    # two batched rounds
            assert _count("host") - host0 == 0     # never host-staged

            out_b = await _collect(eng_b, "b", LONG_PROMPT)
            assert out_b == out_a                  # byte-identical
            sched = eng_b.core.scheduler
            assert sched.prefix_hit_tokens == 4 * BS
        finally:
            await client.close()
            await holder.stop()
            plane_b.stop()
            await eng_b.stop()

    _run(main())


def test_prefix_fetcher_device_first_with_host_fallback():
    """Acceptance e2e: PrefixFetcher.pull probes the device plane first
    (counters pinned); a holder whose offer cap is exhausted degrades to
    the host-staged wire — same frontier accounting, request still
    lands."""
    from dynamo_tpu.llm.block_manager.prefix_share import PrefixFetcher

    async def main():
        holder = await _Holder().start()
        eng_b = InferenceEngine(_core())
        await eng_b.start()
        plane_b = KvTransferPlane(eng_b)
        plane_b.start()
        client = RpcClient(holder.address)
        try:
            out_a = await _collect(holder.engine, "a", LONG_PROMPT)

            dev0 = _count("device")
            fetcher = PrefixFetcher(eng_b, lambda a: client, BS,
                                    plane=plane_b, batch_blocks=2)
            covered = await fetcher.pull(LONG_PROMPT, holder.address,
                                         4 * BS)
            assert covered == 4 * BS
            assert fetcher.remote_hits == 1 and fetcher.fallbacks == 0
            assert fetcher.device_pulled_blocks == 4
            assert _count("device") - dev0 >= 2
            out_b = await _collect(eng_b, "b", LONG_PROMPT)
            assert out_b == out_a

            # Holder cap exhausted: every offer refused -> the SAME
            # pull covers everything over the host wire, reason counted.
            await eng_b.clear_kv_blocks()
            holder.plane._outstanding = {
                10_000 + i: (1, time.monotonic() + 999)
                for i in range(MAX_OUTSTANDING_OFFERS)}
            fetcher2 = PrefixFetcher(eng_b, lambda a: client, BS,
                                     plane=plane_b, batch_blocks=2)
            covered = await fetcher2.pull(LONG_PROMPT, holder.address,
                                          4 * BS)
            assert covered == 4 * BS               # request still lands
            assert fetcher2.device_pulled_blocks == 0
            assert fetcher2.fallbacks == 0
            assert _reasons("host").get("offer_cap", 0) >= 1
            out_b = await _collect(eng_b, "b2", LONG_PROMPT)
            assert out_b == out_a
        finally:
            await client.close()
            await holder.stop()
            plane_b.stop()
            await eng_b.stop()

    _run(main())


def test_mesh_pull_lands_on_inject_sharding():
    """ISSUE 13 bugfix: under a mesh, pulled blocks must land on the
    engine's inject sharding (replicated over the mesh), not pile onto
    jax.devices()[0] and double-copy at inject."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from dynamo_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=TINY, num_blocks=64, mesh=mesh,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=BS, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))
    sharding = core.block_inject_sharding
    assert isinstance(sharding, NamedSharding)
    assert len(sharding.device_set) == 2

    holder = KvTransferPlane()
    holder.start()
    puller = KvTransferPlane(InferenceEngine(core))
    puller.start()
    wire = core.cache_cfg.block_wire_shape
    blocks = {7: jnp.zeros(wire, core.cache_cfg.block_wire_dtype)}
    meta = holder.stage(blocks, [7], peer_fabric=puller.fabric)
    assert meta is not None
    pulled = _run(puller.pull(meta))
    assert set(pulled[7].sharding.device_set) == set(sharding.device_set)
    holder.stop()
    puller.stop()

    # Meshless engines land on the cache's own device (the pre-fix
    # single-device behavior, still correct there).
    core1 = _core()
    assert len(core1.block_inject_sharding.device_set) == 1


def test_plane_counters_sampled_into_metrics_and_top():
    """Plane-choice observability (ISSUE 13 satellite): note_plane
    tallies sample into dynamo_kv_transfer_plane_total without
    double-counting, and `dynamo top` renders the device/host split."""
    import importlib.util

    from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

    reg = MetricsRegistry()
    kv = KvCacheMetrics(reg)
    counts = {("device", "eager"): 3, ("host", "offer_cap"): 1}
    kv.observe_transfer_plane(counts=counts)
    kv.observe_transfer_plane(counts=counts)   # same cumulatives: no inc
    text = reg.expose()
    assert ('dynamo_kv_transfer_plane_total'
            '{plane="device",reason="eager"} 3') in text
    assert ('dynamo_kv_transfer_plane_total'
            '{plane="host",reason="offer_cap"} 1') in text

    spec = importlib.util.spec_from_file_location(
        "dynamo_top", os.path.join(REPO, "tools", "dynamo_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    samples = [("dynamo_kv_transfer_plane_total",
                {"plane": "device", "reason": "eager"}, 3.0),
               ("dynamo_kv_transfer_plane_total",
                {"plane": "host", "reason": "offer_cap"}, 1.0)]
    row = top.summarize("worker-both", "127.0.0.1:1", samples, None)
    assert row["device_pulls"] == 3.0
    assert row["host_pulls"] == 1.0
    table = top.render_table({"control_plane": "cp", "processes": [row]})
    assert "PLANE" in table.splitlines()[1]
    assert "d3/h1" in table


_HOLDER = r"""
import sys, json
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from dynamo_tpu.llm.block_manager.device_transfer import KvTransferPlane

plane = KvTransferPlane()
plane.start()
blocks = {{h: jnp.full((2, 2, 8, 16), h, jnp.float32) for h in (11, 22, 33)}}
meta = plane.stage(blocks, [11, 22, 33])
print("META " + json.dumps(meta), flush=True)
sys.stdin.readline()  # stay alive until the puller is done
"""

_PULLER = r"""
import sys, json, asyncio
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dynamo_tpu.llm.block_manager.device_transfer import KvTransferPlane

meta = json.loads(sys.argv[1])
plane = KvTransferPlane()
plane.start()
blocks = asyncio.run(plane.pull(meta))
ok = sorted(blocks) == [11, 22, 33] and all(
    np.allclose(np.asarray(v), h) for h, v in blocks.items())
print("PULL_OK" if ok else "PULL_BAD", flush=True)
"""


@pytest.mark.e2e
def test_device_pull_across_processes():
    """The DCN-path dryrun: holder and puller are separate OS processes;
    blocks cross via the PJRT transfer service over localhost."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    holder = subprocess.Popen(
        [sys.executable, "-c", _HOLDER.format(repo=REPO)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = holder.stdout.readline().strip()
        assert line.startswith("META "), line
        meta = json.loads(line[5:])
        assert meta["uuid"] and meta["hashes"] == [11, 22, 33]

        out = subprocess.run(
            [sys.executable, "-c", _PULLER.format(repo=REPO),
             json.dumps(meta)],
            capture_output=True, text=True, timeout=120, env=env)
        assert "PULL_OK" in out.stdout, (out.stdout, out.stderr[-2000:])
    finally:
        try:
            holder.stdin.write("\n")
            holder.stdin.flush()
        except Exception:
            pass
        holder.terminate()
        holder.wait(timeout=10)


@pytest.mark.e2e
@pytest.mark.parametrize("prefill_tp,decode_tp", [(1, 2), (2, 1)])
def test_disagg_reshards_kv_between_tp_degrees(prefill_tp, decode_tp,
                                               tmp_path):
    """VERDICT r4 next-5 'done': disagg moves KV device-direct between
    workers with DIFFERENT tp degrees — extract gathers the canonical
    block from the holder's sharding, inject scatters into the puller's
    (the block_copy.cu layout-transpose analog, `disagg_serving.md:96`)."""
    from aiohttp import ClientSession

    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    procs = []

    def spawn(name, extra):
        log = open(tmp_path / f"{name}.log", "w+")
        p = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.worker",
             "--model", "tiny-test", "--block-size", "8",
             "--decode-window", "4"] + extra,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
            cwd=REPO, stdout=log, stderr=subprocess.STDOUT, text=True)
        p._log = log
        procs.append(p)
        return p

    async def main():
        cp_server = ControlPlaneServer()
        cp_port = await cp_server.start()
        cp = ControlPlaneClient("127.0.0.1", cp_port)
        await cp.start()
        runtime = DistributedRuntime(cp)
        models = ModelManager()
        watcher = ModelWatcher(runtime, models, migration_limit=0)
        await watcher.start()
        svc = HttpService(models)
        http_port = await svc.start()

        cp_addr = f"127.0.0.1:{cp_port}"
        decode = spawn("decode", [
            "--control-plane", cp_addr, "--model-name", "reshard",
            "--role", "decode", "--max-local-prefill", "8",
            "--tp", str(decode_tp)])
        spawn("prefill", ["--control-plane", cp_addr,
                          "--role", "prefill",
                          "--tp", str(prefill_tp)])
        await watcher.wait_for_model("reshard", timeout=180)

        base = f"http://127.0.0.1:{http_port}"
        async with ClientSession() as s:
            async with s.post(f"{base}/v1/chat/completions", json={
                    "model": "reshard",
                    "messages": [{"role": "user",
                                  "content": "a prompt long enough to "
                                             "cross the remote prefill "
                                             "threshold easily"}],
                    "max_tokens": 8}) as r:
                body = await r.json()
                assert r.status == 200, body
                assert body["choices"][0]["message"]["content"]

        # The SUCCESS markers are "... onboarded from HOST
        # (device-direct)" / "(device-stream)"; the failure path logs
        # "device... pull ... failed" — assert the parenthesised success
        # marker so a broken plane can't pass.
        deadline = time.monotonic() + 15
        log = ""
        while time.monotonic() < deadline:
            decode._log.flush()
            decode._log.seek(0)
            log = decode._log.read()
            if "(device-direct)" in log or "(device-stream)" in log:
                break
            await asyncio.sleep(0.5)
        assert "onboarded" in log, f"no remote prefill:\n{log[-3000:]}"
        assert "(device-direct)" in log or "(device-stream)" in log, (
            f"KV did not move device-direct:\n{log[-3000:]}")

        await watcher.stop()
        await svc.stop()
        await runtime.shutdown()
        await cp.close()
        await cp_server.stop()

    try:
        asyncio.run(asyncio.wait_for(main(), timeout=300))
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
            p._log.flush()
            p._log.seek(0)
            out = p._log.read()
            if out and ("Traceback" in out or "ERROR" in out):
                print(f"--- {p._log.name} (rc={p.poll()}) ---")
                print(out[-2500:])
