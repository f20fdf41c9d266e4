"""The plain reference of the dense block (`references/dense_gqa_swiglu.py`,
found by name as a run finds it) against the program's own forward step, at
tiny widths on the CPU, with and without grouped-query sharing.  jax is
imported inside the tests: collecting this file touches no accelerator
library."""

import pytest

from chipbench import pieces


def _reference():
    return pieces.load("references", "dense_gqa_swiglu", needs=("forward",))


def _tiny(kv_heads: int = 4) -> dict:
    return {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 8,
            "num_key_value_heads": kv_heads, "rope_theta": 1e6,
            "rms_norm_eps": 1e-5, "max_position_embeddings": 512}


@pytest.mark.parametrize("kv_heads", [4, 8], ids=["gqa-8-4", "mha-8-8"])
def test_reference_agrees_with_the_program(kv_heads):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf

    reference = _reference()
    hf = _tiny(kv_heads)
    cfg = config_from_hf(hf, "t").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(1))
    n, t = 37, 40
    toks = np.random.default_rng(0).integers(1, 512, size=n)
    ref = np.asarray(reference.forward(hf, params, toks))
    assert ref.shape == (n, 512) and np.isfinite(ref).all()

    step = llama.make_forward_step(cfg, 8)
    cache = kvc.init_cache(kvc.KvCacheConfig(
        num_blocks=16, block_size=8, num_layers=2, num_kv_heads=kv_heads,
        head_dim=8, dtype=jnp.float32))
    tokens = np.zeros((1, t), np.int32)
    tokens[0, :n] = toks
    pos = np.full((1, t), 16 * 8 * 100, np.int32)
    pos[0, :n] = np.arange(n)
    out = step(params, cache, jnp.asarray(tokens), jnp.asarray(pos),
               jnp.asarray([n], jnp.int32),
               jnp.arange(1, 9, dtype=jnp.int32)[None],
               jnp.asarray([n - 1], jnp.int32))
    got = np.asarray(out[0])[0]
    # float32 on both sides: only the order of accumulation differs.
    assert np.max(np.abs(got - ref[n - 1])) < 1e-4


def test_padding_after_the_sequence_changes_nothing():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf

    reference = _reference()
    hf = _tiny()
    cfg = config_from_hf(hf, "t").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(2))
    toks = list(range(1, 20))
    a = np.asarray(reference.forward(hf, params, toks))
    b = np.asarray(reference.forward(hf, params, toks + [0] * 13))[:19]
    assert np.max(np.abs(a - b)) < 1e-5
