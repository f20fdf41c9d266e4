"""`references/sdar_moe_block_diffusion.py` against the program at tiny
widths on the CPU: the whole block-causal forward, one expert layer by both
of the program's expert paths, and the shares of a layer's experts adding up
to the whole layer's result.  Also that the mask really is by block."""

import numpy as np
import pytest

from chipbench import pieces

HF = {"model_type": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
      "moe_intermediate_size": 32, "num_attention_heads": 8,
      "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 384,
      "num_hidden_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "diffusion_block_length": 4, "denoising_steps": 4,
      "mask_token_id": 383}


@pytest.fixture(scope="module")
def tiny_sdar():
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama, loader

    cfg = loader.config_from_hf(HF, "t").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(5))
    # Norm weights are 1 at init: make them count.
    k = iter(jax.random.split(jax.random.key(6), 64))

    def jitter(w):
        return w + 0.2 * jax.random.normal(next(k), w.shape, w.dtype)

    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm"):
            layer[name] = jitter(layer[name])
        for name in ("q_norm", "k_norm"):
            layer["attn"][name] = jitter(layer["attn"][name])
    ref = pieces.load("references", "sdar_moe_block_diffusion")
    return cfg, params, ref


@pytest.mark.parametrize("n", [8, 13, 24])
def test_whole_forward_matches_the_programs_block_causal_step(tiny_sdar, n):
    """One chunk through the program's unified step on an empty paged cache
    (gather path, block mask) against the reference, every position.  13 is
    no multiple of the block: the tail is an open block, which sees itself."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    cfg, params, ref = tiny_sdar
    tokens = np.random.default_rng(n).integers(1, 380, size=n)
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, num_blocks=8, block_size=16))
    step = llama.make_forward_step(cfg, 16, with_expert_load=True)
    logits, _cache, _load = step(
        params, cache, jnp.asarray(tokens[None], jnp.int32),
        jnp.arange(n, dtype=jnp.int32)[None], jnp.asarray([n], jnp.int32),
        jnp.asarray([[1, 2]], jnp.int32))
    want = np.asarray(ref.forward(HF, params, tokens.tolist()))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=2e-5)
    # And the mask is by block: a causal forward of the same tokens differs.
    causal = np.asarray(ref.forward(dict(HF, diffusion_block_length=1),
                                    params, tokens.tolist()))
    assert np.abs(causal[:-1] - want[:-1]).max() > 1e-2


@pytest.mark.parametrize("renorm", [True, False])
def test_one_expert_layer_dense_grouped_and_reference(tiny_sdar, renorm):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import moe as moe_ops

    cfg, params, ref = tiny_sdar
    if not renorm:
        # No configuration states gates that are not renormalised: the
        # program refuses them (the reference has both, for when one does).
        with pytest.raises(ValueError, match="norm_topk_prob"):
            cfg.replace(norm_topk_prob=False).validate()
        return
    hf = dict(HF, norm_topk_prob=renorm)
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(9), (1, 24, 64), jnp.float32)
    want = np.asarray(ref.moe_layer(hf, layer, x[0]))
    h = llama.rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    dense, load_d = moe_ops.moe_dense(cfg, layer["moe"], h)
    grouped, load_g = moe_ops.moe_grouped(cfg, layer["moe"], h,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(dense[0]), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(grouped[0]), want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(load_d), np.asarray(load_g))
    assert int(load_g[:-1].sum()) == 24 * 2


@pytest.mark.parametrize("shares", [(4, 4), (2, 3, 3), (1, 7)])
def test_the_shares_of_a_layers_experts_add_up_to_the_layer(tiny_sdar, shares):
    """A chip that holds experts [first, first + n) routes over all 8 and
    computes its own experts' part: the parts add up to the whole layer's
    result, in the program (both paths) and in the reference.  The program
    has no sharded caller of its expert layer yet, so a share is made
    here: the whole layer with the down projections of the experts held
    elsewhere at zero, which leaves the routing over all 8 as it is and
    makes every assignment to such an expert add exactly nothing."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import moe as moe_ops

    cfg, params, ref = tiny_sdar
    layer = params["layers"][0]
    moe = layer["moe"]
    x = jax.random.normal(jax.random.key(4), (2, 10, 64), jnp.float32)
    h = llama.rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    whole, load = moe_ops.moe_dense(cfg, moe, h)
    whole_ref = ref.moe_layer(HF, layer, x.reshape(20, 64))
    parts = {"dense": 0.0, "grouped": 0.0, "ref": 0.0}
    first = 0
    for n in shares:
        held = {k: (v if k == "router" else v[first: first + n])
                for k, v in moe.items()}
        here = (jnp.arange(cfg.num_experts) >= first) \
            & (jnp.arange(cfg.num_experts) < first + n)
        share = dict(moe, w_down=jnp.where(here[:, None, None],
                                           moe["w_down"], 0.0))
        out_d, load_d = moe_ops.moe_dense(cfg, share, h)
        out_g, _ = moe_ops.moe_grouped(cfg, share, h, interpret=True)
        parts["dense"] = parts["dense"] + out_d
        parts["grouped"] = parts["grouped"] + out_g
        parts["ref"] = parts["ref"] + ref.moe_layer(
            HF, dict(layer, moe=held), x.reshape(20, 64),
            held=(first, n))
        # Routing is over all experts whatever is held here.
        np.testing.assert_array_equal(np.asarray(load_d), np.asarray(load))
        first += n
    np.testing.assert_allclose(np.asarray(parts["dense"]),
                               np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(parts["grouped"]),
                               np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(parts["ref"]),
                               np.asarray(whole_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole).reshape(20, 64),
                               np.asarray(whole_ref), atol=2e-5)


def test_given_choices_are_used_and_minus_one_chooses_here(tiny_sdar):
    cfg, params, ref = tiny_sdar
    tokens = list(range(1, 13))
    own = np.asarray(ref.forward(HF, params, tokens))
    free = np.full((2, 12, 2), -1, np.int32)
    np.testing.assert_allclose(
        np.asarray(ref.forward(HF, params, tokens, choices=free)), own,
        atol=1e-6)
    forced = free.copy()
    forced[0, 5] = (0, 1)          # token 5 is made to use experts 0 and 1
    got = np.asarray(ref.forward(HF, params, tokens, choices=forced))
    assert np.abs(got[4:8] - own[4:8]).max() > 1e-3   # its whole block moves
    np.testing.assert_allclose(got[:4], own[:4], atol=1e-6)  # none before
    only = np.asarray(ref.forward(HF, params, tokens, positions=[3, 9]))
    np.testing.assert_allclose(only, own[[3, 9]], atol=1e-6)
