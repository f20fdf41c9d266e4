"""Does a per-window D2H token fetch stall the pipelined window stream?

Dispatches 16 windows back-to-back and compares wall-clock with
(a) no intermediate fetches, (b) np.asarray of each window's [K, B]
tokens from a fetch thread (the engine's pattern), (c) fetch every 4th
window (grouped).
"""

import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params, make_decode_window
from dynamo_tpu.runtime.compile_cache import enable_compile_cache

BATCH, CTX, BLOCK, WIDTH, K = 64, 512, 64, 16, 8
N_WIN = 16


def main():
    enable_compile_cache()
    cfg = mcfg.get_config("llama-3-1b")
    params = init_params(cfg, jax.random.key(0))
    num_blocks = 1 + BATCH * WIDTH
    win = jax.jit(
        make_decode_window(cfg, BLOCK, K, use_pallas_decode=True,
                           greedy_only=True),
        donate_argnums=(1,))
    bt = np.zeros((BATCH, WIDTH), np.int32)
    for i in range(BATCH):
        bt[i] = np.arange(1 + i * WIDTH, 1 + (i + 1) * WIDTH)
    bt = jnp.asarray(bt)
    z = jnp.zeros((BATCH,), jnp.float32)
    zi = jnp.zeros((BATCH,), jnp.int32)
    ones = jnp.ones((BATCH,), jnp.float32)
    keys = jax.random.split(jax.random.key(0), BATCH)
    pool = ThreadPoolExecutor(max_workers=1)

    def run(mode):
        cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
            cfg, num_blocks=num_blocks, block_size=BLOCK))
        last = jnp.ones((BATCH,), jnp.int32)
        pos = jnp.full((BATCH,), CTX, jnp.int32)
        seq = jnp.full((BATCH,), CTX + 1, jnp.int32)
        off = zi
        futs = []
        pend = []
        t0 = time.perf_counter()
        for w in range(N_WIN):
            cache, out, pos, seq, off = win(params, cache, last, pos, seq,
                                            bt, z, zi, ones, keys, off)
            last = out[K - 1]
            if mode == "each":
                futs.append(pool.submit(np.asarray, out))
            elif mode == "async_each":
                out.copy_to_host_async()
                futs.append(pool.submit(np.asarray, out))
            elif mode == "group4":
                pend.append(out)
                if len(pend) == 4:
                    grp = jnp.concatenate(pend)
                    pend = []
                    futs.append(pool.submit(np.asarray, grp))
        for f in futs:
            f.result()
        jax.device_get(last)
        return time.perf_counter() - t0

    for mode in ("none", "each", "async_each", "group4", "group4",
                 "async_each", "none"):
        t = run(mode)
        print(f"{mode:7s} {t/N_WIN*1e3:7.1f} ms/window "
              f"({t/N_WIN/K*1e3:.2f} ms/tok)")


if __name__ == "__main__":
    main()
