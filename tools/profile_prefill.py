"""Where does the prefill step's time go on the real chip?

Times the full forward step at serving prefill geometry, then ablations:
matmuls only (attention stubbed), attention only, and the paged-context
gather alone.  Slope-timed (N1 vs N2 runs) to cancel the fixed per-run
cost, matching bench.py methodology.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params, make_forward_step
from dynamo_tpu.runtime.compile_cache import enable_compile_cache

ROWS = 16          # prefill batch rows (8192-token budget / 512 chunk)
CHUNK = 512
BLOCK = 64


def slope(fn, n1=2, n2=6):
    def run(n):
        t0 = time.perf_counter()
        x = None
        for _ in range(n):
            x = fn()
        jax.device_get(jax.tree.leaves(x)[0].ravel()[0])
        return time.perf_counter() - t0

    run(1)  # compile
    t1, t2 = run(n1), run(n2)
    return (t2 - t1) / (n2 - n1)


def main():
    enable_compile_cache()
    cfg = mcfg.get_config("llama-3-1b")
    params = init_params(cfg, jax.random.key(0))
    pages = CHUNK // BLOCK
    num_blocks = 1 + ROWS * pages
    cache_cfg = kvc.KvCacheConfig.for_model(cfg, num_blocks=num_blocks,
                                            block_size=BLOCK)
    cache = kvc.init_cache(cache_cfg)
    step = jax.jit(make_forward_step(cfg, BLOCK), donate_argnums=(1,))

    tokens = jnp.ones((ROWS, CHUNK), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(CHUNK, dtype=jnp.int32),
                                 (ROWS, CHUNK))
    seq_lens = jnp.full((ROWS,), CHUNK, jnp.int32)
    bt = np.zeros((ROWS, pages), np.int32)
    for i in range(ROWS):
        bt[i] = np.arange(1 + i * pages, 1 + (i + 1) * pages)
    bt = jnp.asarray(bt)
    sample_pos = jnp.full((ROWS,), CHUNK - 1, jnp.int32)

    state = {"cache": cache}

    def full():
        logits, state["cache"] = step(params, state["cache"], tokens,
                                      positions, seq_lens, bt, sample_pos)
        return logits

    s_full = slope(full)
    toks = ROWS * CHUNK
    flops_tok = 2 * sum(int(np.prod(p.shape))
                        for p in jax.tree.leaves(params))
    print(f"full step: {s_full*1e3:.1f} ms, {toks/s_full:.0f} tok/s, "
          f"MFU~{toks/s_full*flops_tok/197e12:.3f}")

    # Ablation: params-matmul-only proxy — dense transformer without
    # attention context (q@k of the chunk only, no cache gather).
    h = jnp.ones((ROWS, CHUNK, cfg.hidden_size), jnp.bfloat16)

    def mm_only():
        x = h
        for _ in range(cfg.num_layers):
            q = x @ params["layers"][0]["wq"].astype(jnp.bfloat16) \
                if isinstance(params["layers"][0], dict) else x
            x = x + 0.0 * q[..., :cfg.hidden_size]
        return x

    # Attention-only: the paged_attention op at this geometry.
    from dynamo_tpu.ops.attention import paged_attention

    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.ones((ROWS, CHUNK, Hq, D), jnp.bfloat16)
    kctx = jnp.ones((ROWS, CHUNK, Hkv, D), jnp.bfloat16)
    kv_pos = jnp.broadcast_to(jnp.arange(CHUNK, dtype=jnp.int32),
                              (ROWS, CHUNK))
    attn = jax.jit(lambda q, k, v: paged_attention(
        q, k, v, kv_pos, kv_pos, seq_lens))

    def attn_only():
        return attn(q, kctx, kctx)

    s_attn = slope(attn_only)
    print(f"attention only (1 layer): {s_attn*1e3:.2f} ms; "
          f"x{cfg.num_layers} = {s_attn*cfg.num_layers*1e3:.1f} ms")

    # Gather-only: context materialisation from the paged cache.
    slots = kvc.slots_for_positions(bt, kv_pos, BLOCK) \
        if hasattr(kvc, "slots_for_positions") else None
    if slots is not None:
        layer_k = state["cache"]["k"][0]

        gather = jax.jit(lambda lk, s: jnp.take(lk, s.reshape(-1), axis=0))

        def gather_only():
            return gather(layer_k, slots)

        s_g = slope(gather_only)
        print(f"context gather (1 layer, k only): {s_g*1e3:.2f} ms; "
              f"x{cfg.num_layers}x2 = {s_g*cfg.num_layers*2*1e3:.1f} ms")


if __name__ == "__main__":
    main()
