"""The lowered text (StableHLO, CPU, kernels in interpret mode, no source
locations) of one greedy decode window and one packed prefill step of each
tiny preset, as sha256 digests: what `tests/test_window_lowering.py` holds to
the digests recorded from the tree before window layers existed.

    JAX_PLATFORMS=cpu python tests/lowered_text.py [repo root] > digests.json

lowers the tree at `repo root` (default: this file's), so that a parent
checkout and a change are lowered by ONE script from one place."""

from __future__ import annotations

import hashlib
import json
import os
import sys

PRESETS = ("tiny-test", "tiny-moe", "tiny-h1", "tiny-pattern")
BS = 8


def digests() -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    out = {}
    for name in PRESETS:
        cfg = get_config(name)
        moe = "grouped" if cfg.is_moe else "dense"
        params = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(lambda: kvc.init_cache(
            kvc.KvCacheConfig.for_model(cfg, 16, BS, state_slots=2)))
        i32 = jax.ShapeDtypeStruct((4,), np.int32)
        f32 = jax.ShapeDtypeStruct((4,), np.float32)
        bts = jax.ShapeDtypeStruct((4, 4), np.int32)
        keys = jax.ShapeDtypeStruct((4, 2), np.uint32)
        state = (i32,) if cfg.has_ssm else ()
        window = jax.jit(llama.make_decode_window(
            cfg, BS, 4, use_pallas_decode=True, greedy_only=True,
            moe_mode=moe, with_expert_load=cfg.is_moe))
        text = window.lower(params, cache, i32, i32, i32, bts, f32, i32,
                            f32, keys, i32, *state).as_text()
        out[f"{name}.decode_window"] = hashlib.sha256(
            text.encode()).hexdigest()
        t = jax.ShapeDtypeStruct((32,), np.int32)
        packed = jax.jit(llama.make_packed_prefill_step(
            cfg, BS, moe_mode=moe))
        text = packed.lower(params, cache, t, t, t, bts, i32, i32, i32, i32,
                            *state).as_text()
        out[f"{name}.packed_prefill"] = hashlib.sha256(
            text.encode()).hexdigest()
    return out


if __name__ == "__main__":
    root = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    print(json.dumps(digests(), indent=1, sort_keys=True))
