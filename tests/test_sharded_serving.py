"""Sharded serving fast paths (VERDICT r3 next-2): decode windows,
speculative decoding, embeddings and the Pallas kernel all work under a
mesh, and a `--tp` worker serves over the distributed runtime.

Greedy output parity against the unsharded engine is the oracle: the
serving path must not depend on how the model is partitioned.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.parallel import MeshConfig, make_mesh

from logit_parity import assert_logit_parity, greedy

SCHED = dict(max_seqs=4, block_size=8, max_pages_per_seq=8,
             max_prefill_chunk=16, decode_buckets=(2, 4),
             prefill_buckets=(8, 16))


PROMPTS = [[5, 6, 7, 8, 9, 10, 5, 6, 7, 8], list(range(20, 34))]


def _build_engine(mesh=None, decode_window=1, spec=0, dp_attention=False,
                  use_pallas=None, kv_quant="none", dp_local=None):
    return EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, dp_attention=dp_attention,
        dp_attention_local=dp_local,
        decode_window=decode_window, window_pipeline_depth=2,
        speculative_tokens=spec,
        use_pallas_decode=use_pallas,
        kv_quant=kv_quant,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(**SCHED)))


def _run_engine(**kwargs):
    core = _build_engine(**kwargs)
    for rid, prompt in zip("ab", PROMPTS):
        core.add_request(rid, prompt, SamplingParams(max_tokens=12))
    outputs = {}
    for _ in range(300):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert not core._requests, "engine did not finish"
    return outputs


@pytest.fixture(scope="module")
def oracle():
    """Unsharded single-step greedy output (the parity reference)."""
    return _run_engine()


@pytest.fixture(scope="module")
def int8_oracle():
    """The int8 tests' reference: the unsharded engine over the same
    int8 cache, as (core, `greedy` result); a sharded int8 engine is
    held to its logits (tests/logit_parity.py says why)."""
    core = _build_engine(kv_quant="int8")
    return core, greedy(core, PROMPTS)


def _assert_int8_parity(name, int8_oracle, **kwargs):
    ref_core, ref = int8_oracle
    core = _build_engine(kv_quant="int8", **kwargs)
    assert_logit_parity(name, ref_core, ref, greedy(core, PROMPTS), PROMPTS)
    return core


def test_sharded_window_matches_unsharded(oracle):
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    got = _run_engine(mesh=mesh, decode_window=4)
    assert got == oracle


def test_sharded_single_step_matches_unsharded(oracle):
    mesh = make_mesh(MeshConfig(tp=4), jax.devices()[:4])
    got = _run_engine(mesh=mesh)
    assert got == oracle


@pytest.mark.parametrize("mesh_cfg", [MeshConfig(tp=4), MeshConfig(pp=2)],
                         ids=["tp4", "pp2"])
def test_params_and_cache_are_born_sharded(monkeypatch, mesh_cfg):
    """Under a mesh nothing is ever whole on device 0: the initialisers
    only run under the jit that carries `out_shardings` (their results
    are tracers, never committed arrays — llama-3-8b's 16 GB of weights
    do not fit one 16 GB chip), and straight after construction every
    device holds its own shard."""
    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.parallel import pipeline

    eager = []

    def traced_only(module, name):
        real = getattr(module, name)

        def wrapper(*args, **kw):
            out = real(*args, **kw)
            eager.extend(name for leaf in jax.tree.leaves(out)
                         if not isinstance(leaf, jax.core.Tracer))
            return out

        monkeypatch.setattr(module, name, wrapper)

    traced_only(engine_mod, "init_params")
    traced_only(kvc, "init_cache")
    traced_only(pipeline, "init_pp_cache")
    n = mesh_cfg.size
    mesh = make_mesh(mesh_cfg, jax.devices()[:n])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64, mesh=mesh,
        enable_prefix_cache=False, scheduler=SchedulerConfig(**SCHED)))
    assert not eager, f"initialised outside the sharded jit: {set(eager)}"

    layers = core.params["layers"]
    wq = (layers["attn"]["wq"] if mesh_cfg.pp > 1
          else layers[0]["attn"]["wq"])
    k0 = core.cache["k"] if mesh_cfg.pp > 1 else core.cache["k"][0]
    for leaf in (wq, k0):
        assert len(leaf.sharding.device_set) == n
        shards = leaf.addressable_shards
        assert len(shards) == n
        assert all(s.data.size * n == leaf.size for s in shards), (
            leaf.shape, [s.data.shape for s in shards])


def test_sharded_spec_decode_matches_unsharded(oracle):
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    got = _run_engine(mesh=mesh, spec=3)
    assert got == oracle


def test_dp_attention_window_matches_unsharded(oracle):
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    got = _run_engine(mesh=mesh, decode_window=4, dp_attention=True)
    assert got == oracle


def test_sharded_pallas_window_matches_unsharded(oracle):
    """The Pallas kernel under shard_map (interpret mode on CPU)."""
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    got = _run_engine(mesh=mesh, decode_window=4, use_pallas=True)
    assert got == oracle


def test_sp_ring_prefill_through_engine(oracle):
    """A SERVED request's prefill demonstrably runs the ring path
    (VERDICT r3 next-4: make_sp_prefill_step was test-only)."""
    mesh = make_mesh(MeshConfig(sp=2, tp=2), jax.devices()[:4])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, sp_prefill_threshold=8,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(**SCHED)))
    core.add_request("a", [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
                     SamplingParams(max_tokens=12))
    core.add_request("b", list(range(20, 34)),
                     SamplingParams(max_tokens=12))
    outputs = {}
    for _ in range(300):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert core.sp_prefill_count == 2, "prefill did not run the ring path"
    assert outputs == oracle


def test_pp_engine_serving(oracle):
    """A pp-mesh engine SERVES via the pipeline step (VERDICT r3 next-4:
    make_pp_step was test-only)."""
    mesh = make_mesh(MeshConfig(pp=2), jax.devices()[:2])
    got = _run_engine(mesh=mesh)
    assert got == oracle


@pytest.mark.parametrize("decode_window", [4, 1],
                         ids=["window", "single_step"])
def test_sharded_int8_matches_unsharded(int8_oracle, decode_window):
    """ISSUE 9 leg 1: the quantized KV plane composes with head-sharded
    tp — scales shard with their kv heads — and the logits stay those of
    the meshless int8 engine on BOTH sharded decode paths (fused window
    and the fused greedy single step, which also covers leg 3's
    make_sharded_greedy_step with an int8 cache)."""
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    core = _assert_int8_parity(f"tp2 int8 window of {decode_window}",
                               int8_oracle, mesh=mesh,
                               decode_window=decode_window)
    if decode_window == 1:
        assert core._greedy_fused is not None, \
            "sharded int8 single-step decode did not take the fused path"
    else:
        assert core.counters.window_dispatches > 0


def test_dp_attention_plain_int8_matches_unsharded(int8_oracle):
    """int8 × PLAIN dp_attention (no locality): the GSPMD slot-sharded
    gather path with P('tp', None) scale buffers — the README matrix
    advertises this combination, so it needs its own parity pin
    (enable_prefix_cache=False would auto-resolve locality; force it
    off to keep the test on the non-local path)."""
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    _assert_int8_parity("dp_attention int8", int8_oracle, mesh=mesh,
                        decode_window=4, dp_attention=True, dp_local=False)


def test_dp_local_pallas_int8_matches_unsharded(int8_oracle):
    """ISSUE 9 leg 2: the Pallas kernel runs SHARD-LOCALLY under
    dp_attention locality (block tables rebase to the shard's local page
    range inside the shard_map body) — with the int8 cache threading its
    scale shards into the kernel's k_scale/v_scale variant."""
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    _assert_int8_parity("dp_local pallas int8", int8_oracle, mesh=mesh,
                        decode_window=4, dp_attention=True, use_pallas=True)


def test_sharded_fused_step_counters():
    """The sharded fused greedy step's loop discipline (ISSUE 9 leg 3):
    in steady single-step decode each engine iteration is ONE fused
    dispatch with ONE host sync and zero new compiled shapes — the same
    pin the meshless path carries in test_decode_window."""
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, decode_window=1, enable_prefix_cache=False,
        scheduler=SchedulerConfig(**SCHED)))
    core.add_request("a", [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
                     SamplingParams(max_tokens=30))
    core.add_request("b", list(range(20, 34)),
                     SamplingParams(max_tokens=30))
    for _ in range(6):   # prefill + warm the fused program
        core.step()
    assert core._greedy_fused is not None
    base = core.counters.snapshot()
    n = 8
    for _ in range(n):
        core.step()
    d = core.counters.delta(base)
    assert d["single_step_dispatches"] == n
    assert d["host_syncs"] == n, "fused sharded step must cost 1 sync"
    assert d["xla_cache_misses"] == 0, "steady shape recompiled"


def test_sharded_per_chip_modeled_bytes():
    """Modeled-bytes honesty under meshes (ISSUE 9 satellite): a tp2
    engine sweeps HALF the KV bytes per chip, so
    `effective_bytes_per_token` (and the per-chip mbu derived from it)
    must halve vs meshless; `dynamo_kv_bytes_per_block` reports per-chip
    block bytes on sharded pools."""
    from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

    def run(mesh):
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=64,
            mesh=mesh, enable_prefix_cache=False,
            scheduler=SchedulerConfig(**SCHED)))
        core.add_request("a", [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
                         SamplingParams(max_tokens=12))
        for _ in range(300):
            core.step()
            if not core._requests:
                break
        return core

    meshless = run(None)
    tp2 = run(make_mesh(MeshConfig(tp=2), jax.devices()[:2]))
    assert meshless.kv_shard_count == 1
    assert tp2.kv_shard_count == 2
    b0 = meshless.counters.effective_bytes_per_token
    b2 = tp2.counters.effective_bytes_per_token
    assert b2 > 0
    assert abs(b2 / b0 - 0.5) < 1e-6
    reg = MetricsRegistry()
    kvm = KvCacheMetrics(reg)
    kvm.observe_engine(tp2)
    got = kvm.kv_bytes_per_block.value(labels={"kv_quant": "none"})
    assert got == tp2.cache_cfg.bytes_per_block / 2


def test_sharded_int8_wire_block_mismatch_refused():
    """Disagg / prefix-share between sharded int8 peers keeps refusing
    mixed-mode blocks loudly: the packed wire format is
    sharding-independent, so a bf16 peer's block into a tp2 int8 cache
    must be rejected BEFORE any bytes touch the cache."""
    import numpy as np

    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, kv_quant="int8", enable_prefix_cache=False,
        scheduler=SchedulerConfig(**SCHED)))
    cfg = core.cache_cfg
    bf16_shape = (2, cfg.num_layers, cfg.block_size, cfg.feature_dim)
    with pytest.raises(ValueError, match="kv_quant"):
        core._validate_block(np.zeros(bf16_shape, np.float32))
    # The exact packed block passes the format check.
    core._validate_block(np.zeros(cfg.block_wire_shape, np.int8))


def test_sharded_embeddings():
    mesh = make_mesh(MeshConfig(tp=2, dp=2), jax.devices()[:4])
    cfg = mcfg.get_config("tiny-test")

    def embed(mesh_):
        core = EngineCore(EngineConfig(
            model=cfg, num_blocks=64, mesh=mesh_,
            enable_prefix_cache=False,
            scheduler=SchedulerConfig(**SCHED)))
        return core.embed_tokens([[5, 6, 7, 8], list(range(20, 31))])

    want = embed(None)
    got = embed(mesh)
    assert got.shape == (2, cfg.hidden_size)
    np.testing.assert_allclose(want, got, rtol=2e-2, atol=2e-2)


@pytest.mark.e2e
def test_tp_worker_serves_http():
    """A real-engine worker launched with --tp 2 --dp 2 serves a chat
    completion end-to-end over the distributed runtime (the 'one flag'
    contract, reference `sglang/launch/disagg.sh:25`)."""
    import asyncio
    import os
    import subprocess
    import sys
    import time

    from aiohttp import ClientSession

    from dynamo_tpu.llm.discovery import ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.service import ModelManager
    from dynamo_tpu.runtime.control_plane_tcp import (
        ControlPlaneClient, ControlPlaneServer)
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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

        log = open(f"/tmp/dynamo_tpu_tp_worker_{os.getpid()}.log", "w+")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
        proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.worker",
             "--control-plane", f"127.0.0.1:{cp_port}",
             "--model", "tiny-test", "--model-name", "tiny-tp",
             "--block-size", "8", "--tp", "2", "--dp", "2",
             "--decode-window", "4"],
            env=env, cwd=repo, stdout=log, stderr=subprocess.STDOUT,
            text=True)
        try:
            await watcher.wait_for_model("tiny-tp", timeout=120)
            base = f"http://127.0.0.1:{http_port}"
            async with ClientSession() as s:
                async with s.post(f"{base}/v1/chat/completions", json={
                        "model": "tiny-tp",
                        "messages": [{"role": "user", "content": "hello"}],
                        "max_tokens": 8}) as r:
                    body = await r.json()
                    assert r.status == 200, body
                    assert body["choices"][0]["message"]["content"]
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
            log.flush(); log.seek(0)
            print(log.read()[-2000:])
            log.close()
            await svc.stop()
            await watcher.stop()
            await runtime.shutdown()
            await cp.close()
            await cp_server.stop()

    asyncio.run(main())


def test_pp_prefix_cache_hits(oracle):
    """PP v2 (VERDICT r4 next-10): the tiered prefix cache runs under the
    stacked pp layout — a repeated prompt prefix must HIT (prefill
    skipped) and greedy output must stay identical to the unsharded
    oracle."""
    mesh = make_mesh(MeshConfig(pp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, enable_prefix_cache=True,
        scheduler=SchedulerConfig(**SCHED)))
    assert core._managed_cache, "pp engine must run the tiered source"

    def run(rid):
        core.add_request(rid, [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
                         SamplingParams(max_tokens=12))
        out = []
        for _ in range(300):
            for d in core.step():
                out.extend(d.token_ids)
            if not core._requests:
                break
        assert not core._requests
        return out

    first = run("p1")
    assert first == oracle["a"], "pp+prefix first run diverged"
    # Second identical prompt: the sealed prefix blocks must match.
    second = run("p2")
    assert second == first, "prefix hit changed greedy output"
    # The hit is observable as skipped prefill work: the second request
    # admitted with prefilled > 0 (allocator.match returned cached
    # tokens).  Verify via the manager's match bookkeeping.
    mgr = core.allocator
    cached, pages = mgr.match([5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
                              mgr.prompt_hashes([5, 6, 7, 8, 9, 10,
                                                 5, 6, 7, 8]))
    assert cached > 0, "sealed prefix blocks not matchable under pp"
    if pages:
        mgr.release(pages)


def test_pp_block_extract_inject_roundtrip():
    """The stacked-layout block ops must move the exact bytes the
    flat-layout ops define (the canonical [2, L, bs, F] block)."""
    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.parallel.pipeline import (
        init_pp_cache, make_pp_block_ops, pp_cache_pspecs)
    from dynamo_tpu.parallel.sharding import shard_pytree

    cfg = mcfg.get_config("tiny-test")
    mesh = make_mesh(MeshConfig(pp=2), jax.devices()[:2])
    cache_cfg = kvc.KvCacheConfig.for_model(cfg, num_blocks=8,
                                            block_size=8,
                                            dtype=np.float32)
    cache = shard_pytree(init_pp_cache(cache_cfg), pp_cache_pspecs(), mesh)
    ex, inj = make_pp_block_ops(8, mesh)
    rng = np.random.default_rng(0)
    blk = rng.standard_normal(
        (2, cfg.num_layers, 8, cache_cfg.feature_dim)).astype(np.float32)
    cache = inj(cache, np.int32(3), blk)
    out = np.asarray(ex(cache, np.int32(3)))
    np.testing.assert_array_equal(out, blk)
    # Other pages stay zero.
    other = np.asarray(ex(cache, np.int32(2)))
    assert (other == 0).all()
