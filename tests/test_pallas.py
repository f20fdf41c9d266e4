"""Pallas paged-decode kernel == XLA gathered-attention path.

Runs in interpreter mode on the CPU test mesh (pallas_call(interpret=True));
the same kernel compiles for a described v5e in tests/test_tpu_compile.py
and runs on the chip in chipbench's cells.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas import paged_decode_attention


def test_kernel_matches_xla_gather_path():
    B, Hq, Hkv, D, bs, P = 3, 8, 4, 64, 8, 4
    S = 32 * bs
    q = jax.random.normal(jax.random.key(1), (B, Hq, D), jnp.float32)
    kc = jax.random.normal(jax.random.key(2), (S, Hkv * D), jnp.bfloat16)
    vc = jax.random.normal(jax.random.key(3), (S, Hkv * D), jnp.bfloat16)
    # Non-contiguous, per-sequence page assignments.
    bt = jnp.asarray([[3, 9, 17, 2], [11, 4, 0, 0], [21, 0, 0, 0]],
                     jnp.int32)
    seq_lens = jnp.asarray([29, 9, 1], jnp.int32)

    out = paged_decode_attention(q, kc, vc, bt, seq_lens, block_size=bs,
                                 interpret=True)

    ctx_pos = jnp.broadcast_to(jnp.arange(P * bs, dtype=jnp.int32),
                               (B, P * bs))
    slots = kvc.slots_for_positions(bt, ctx_pos, bs)
    k_ctx, v_ctx = kvc.gather_kv(kc, vc, slots, Hkv)
    ref = paged_attention(q[:, None], k_ctx, v_ctx,
                          (seq_lens - 1)[:, None], ctx_pos, seq_lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_engine_output_identical_with_pallas_decode():
    """Greedy engine output must not depend on the attention backend."""
    def run(use_pallas):
        core = EngineCore(EngineConfig(
            model=mcfg.get_config("tiny-test"), num_blocks=64,
            use_pallas_decode=use_pallas,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))
        core.add_request("a", [5, 6, 7, 8, 9, 10], SamplingParams(max_tokens=5))
        core.add_request("b", list(range(20, 39)), SamplingParams(max_tokens=5))
        outputs = {}
        for _ in range(200):
            for d in core.step():
                outputs.setdefault(d.request_id, []).extend(d.token_ids)
            if not core._requests:
                break
        return outputs

    assert run(True) == run(False)
