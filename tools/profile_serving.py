"""Instrument the EngineCore serving loop: where does wall-clock go
relative to the raw window device time?"""

import time

import jax
import numpy as np

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params
from dynamo_tpu.runtime.compile_cache import enable_compile_cache

BATCH, CTX, BLOCK, MAX_PAGES = 64, 512, 64, 128


def main():
    enable_compile_cache()
    cfg = mcfg.get_config("llama-3-1b")
    params = init_params(cfg, jax.random.key(0))
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=1 + BATCH * (MAX_PAGES // 8),
        enable_prefix_cache=False, decode_window=8,
        scheduler=SchedulerConfig(
            max_seqs=BATCH, block_size=BLOCK, max_pages_per_seq=MAX_PAGES,
            max_prefill_chunk=512, max_batched_tokens=8192,
            decode_buckets=(16, 64), prefill_buckets=(512,))), params=params)
    rng = np.random.default_rng(0)
    for i in range(BATCH):
        core.add_request(f"r{i}", rng.integers(1, cfg.vocab_size,
                                               size=CTX).tolist(),
                         SamplingParams(max_tokens=256))
    t0 = time.perf_counter()
    while any(r.state.value in ("waiting", "prefill")
              for r in core._requests.values()):
        core.step()
    print(f"prefill wall {time.perf_counter()-t0:.2f}s")

    # instrument the window internals
    orig_dispatch = core._dispatch_window
    orig_sync = core._sync_one_window
    orig_fn = core._window_fn
    stats = {"dispatch": [], "sync": [], "fncall": []}

    def timed_fn(greedy):
        inner = orig_fn(greedy)

        def wrapped(*a):
            t = time.perf_counter()
            r = inner(*a)
            stats["fncall"].append(time.perf_counter() - t)
            return r
        return wrapped

    def timed_dispatch(work):
        t = time.perf_counter()
        r = orig_dispatch(work)
        stats["dispatch"].append(time.perf_counter() - t)
        return r

    def timed_sync():
        t = time.perf_counter()
        r = orig_sync()
        stats["sync"].append(time.perf_counter() - t)
        return r

    core._window_fn = timed_fn

    core._dispatch_window = timed_dispatch
    core._sync_one_window = timed_sync

    produced = 0
    t0 = time.perf_counter()
    first = None
    while core.has_work:
        d = core.step()
        produced += sum(len(x.token_ids) for x in d)
        if first is None and produced:
            first = time.perf_counter() - t0
    wall = time.perf_counter() - t0
    print(f"decode wall {wall:.2f}s produced {produced} "
          f"tok/s {produced/wall:.0f}")
    print(f"first sync at {first:.2f}s (includes window compile)")
    for k in ("dispatch", "sync", "fncall"):
        v = stats[k]
        ms = [f"{x*1e3:.0f}" for x in v]
        print(f"{k:9s} n={len(v)} ms each: {' '.join(ms)}")


if __name__ == "__main__":
    main()
