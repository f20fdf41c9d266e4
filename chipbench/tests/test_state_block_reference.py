"""The plain reference of the parallel hybrid block (a Mamba-2 state-space
mixer beside grouped-query attention) held from both sides at tiny widths on
the CPU: against the family's published modeling code (`transformers`'
FalconH1ForCausalLM, `torch_forward`, float32, seeded; the checkpoint it saves
is read back by the program's loader, so the tensor names are the published
ones), and against the program (prefill in chunks through the slot, segments
packed in one chunk, decode windows and single steps), with the comparison's
two controls refused."""

import json
import os

import numpy as np
import pytest

from chipbench import check, pieces, run

HF = {"model_type": "falcon_h1", "hidden_size": 64, "intermediate_size": 128,
      "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
      "vocab_size": 256, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
      "rope_theta": 10000.0, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "mamba_d_ssm": 64, "mamba_n_heads": 4,
      "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 2,
      "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_expand": 2,
      "mamba_conv_bias": True, "mamba_proj_bias": False,
      "mamba_rms_norm": True, "mamba_norm_before_gate": False,
      "mamba_use_mlp": True, "attention_bias": False, "mlp_bias": False,
      "projectors_bias": False, "hidden_act": "silu",
      "embedding_multiplier": 1.7, "lm_head_multiplier": 0.3,
      "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.6,
      "key_multiplier": 0.5, "ssm_in_multiplier": 0.8,
      "ssm_out_multiplier": 0.7, "mlp_multipliers": [0.9, 0.8],
      "ssm_multipliers": [0.9, 0.7, 0.8, 1.1, 0.6]}


@pytest.fixture(scope="module")
def state_reference():
    return pieces.load("references", "parallel_mamba2_gqa_swiglu")


@pytest.fixture(scope="module")
def state_tiny():
    """(cfg, params): float32, seeded, norm weights moved off 1."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama, loader

    cfg = loader.config_from_hf(HF, "tiny-h1").replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(5))
    k = iter(jax.random.split(jax.random.key(6), 64))

    def jitter(w):
        return w + 0.2 * jax.random.normal(next(k), w.shape, w.dtype)

    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm"):
            layer[name] = jitter(layer[name])
        layer["ssm"]["norm"] = jitter(layer["ssm"]["norm"])
        layer["ssm"]["D"] = jitter(layer["ssm"]["D"])
    return cfg, params


def test_reference_equals_the_published_modeling_code(state_reference,
                                                      tmp_path):
    """FalconH1ForCausalLM's own forward (`torch_forward`: the chunked form)
    against the reference's recurrence, on the checkpoint the published code
    saves and the program's loader reads by the published names."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import jax.numpy as jnp

    from dynamo_tpu.models import loader

    config = transformers.FalconH1Config(**HF)
    config._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.FalconH1ForCausalLM(config).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("A_log"):
                p.copy_(torch.log(torch.rand_like(p) * 15 + 1))
            elif name.endswith(("dt_bias", ".D")) or "norm" in name:
                p.copy_(1 + 0.3 * torch.randn_like(p))
            elif "conv1d" in name:
                p.copy_(0.5 * torch.randn_like(p))
            else:
                p.copy_(torch.randn_like(p) * p.shape[-1] ** -0.5)
    tokens = torch.randint(1, 256, (1, 37))
    with torch.no_grad():
        want = model(tokens).logits[0].numpy()
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    names = set(model.state_dict())
    for part in ("mamba.in_proj", "mamba.conv1d", "mamba.A_log", "mamba.D",
                 "mamba.dt_bias", "mamba.norm", "mamba.out_proj",
                 "self_attn.q_proj", "feed_forward.gate_proj",
                 "input_layernorm", "pre_ff_layernorm"):
        assert any(f"layers.0.{part}" in n for n in names), part
    assert "model.final_layernorm.weight" in names
    cfg, params = loader.load_params(str(tmp_path), dtype=jnp.float32)
    assert cfg.has_ssm and set(params["layers"][0]["ssm"]) == {
        "w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm", "w_out"}
    with open(os.path.join(str(tmp_path), "config.json")) as f:
        saved = json.load(f)
    got = np.asarray(state_reference.forward(saved, params,
                                             tokens[0].tolist()))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Only the positions asked for, and the first layer's state beside them.
    some, state = state_reference.forward(saved, params, tokens[0].tolist(),
                                    positions=[3, 36], state_at=37)
    np.testing.assert_allclose(np.asarray(some), want[[3, 36]], atol=2e-5)
    assert state.shape == (4, 16, 8)


@pytest.mark.parametrize("n", [7, 21, 40])
def test_whole_forward_matches_the_program(state_tiny, state_reference, n):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    cfg, params = state_tiny
    toks = np.random.default_rng(n).integers(1, 256, size=n)
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, 16, 8, state_slots=2))
    step = jax.jit(llama.make_forward_step(cfg, 8))
    t = np.zeros((1, 40), np.int32)
    p = np.full((1, 40), 10_000, np.int32)
    t[0, :n], p[0, :n] = toks, np.arange(n)
    logits, cache = step(params, cache, jnp.asarray(t), jnp.asarray(p),
                         jnp.asarray([n]), jnp.asarray([[1, 2, 3, 4, 5]]),
                         None, state_slots=jnp.asarray([1]))
    want, state = state_reference.forward(HF, params, toks.tolist(),
                                          state_at=n)
    np.testing.assert_allclose(np.asarray(logits[0, :n]), np.asarray(want),
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(cache["ssm"][0][1]),
                               np.asarray(state), atol=1e-6)


@pytest.fixture(scope="module")
def state_served(state_tiny):
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    cfg, params = state_tiny
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=96, decode_window=8,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=16,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32))), params=params)
    hf = dict(HF, reference="parallel_mamba2_gqa_swiglu",
              comparison="causal_logits_state_carry",
              warmups=["first_token_packs"])
    return hf, core


LENGTHS = (5, 15, 16, 17, 31, 33, 70, 129)      # across chunks of 8 and 32


def test_the_engine_through_chunks_packs_windows_and_steps(state_served):
    """The comparison as the worker runs it: prompts that cross the scan's
    chunk (8) and the prefill chunk (32), several packed to a chunk, whole
    windows and single steps, and the state the lone sequence leaves in its
    slot; float32 on both sides reads far inside every limit."""
    hf, core = state_served
    out = check.run_check(core, hf, 11, LENGTHS)
    assert out["ok"] is True, out["problems"]
    assert out["compared"] == len(LENGTHS)
    assert out["windows"] > 0 and out["single_steps"] > 0
    assert [i["name"] for i in out["limits"]] == [
        "max_rel_logit_diff", "max_rel_body_logit_diff",
        "max_rel_decode_margin", "max_rel_state_diff"]
    assert all(i["value"] < 1e-4 for i in out["limits"]), out["limits"]


def test_both_controls_fail_a_limit_and_the_engine_is_whole_after(
        state_served):
    """The state stored in bfloat16 is refused by the state's limit (and by
    no logit limit: that is why the state is read), the state-space branch
    zeroed by the logit limits; the engine serves as before afterwards."""
    hf, core = state_served
    comparison = pieces.load("comparisons", "causal_logits_state_carry")
    reference = pieces.load("references", "parallel_mamba2_gqa_swiglu")
    # 15 windows and 2 steps: the roundings add up as on the chip's widths.
    out = comparison.controls(core, hf, 11, reference, LENGTHS,
                              state_tokens=123)
    assert out["None"]["ok"] is True, out["None"]["problems"]

    def over(name):
        return {i["name"] for i in out[name]["limits"]
                if i["value"] > i["limit"]}

    assert out["bf16_state"]["ok"] is False
    assert "max_rel_state_diff" in over("bf16_state")
    assert out["zero_ssm"]["ok"] is False
    assert {"max_rel_logit_diff", "max_rel_body_logit_diff"} \
        <= over("zero_ssm")
    assert str(core.cache["ssm"][0].dtype) == "float32"
    assert float(abs(np.asarray(
        core.params["layers"][0]["ssm"]["w_out"])).max()) > 0
    assert check.run_check(core, hf, 12, LENGTHS[:3])["ok"] is True


def test_the_limits_are_relative_to_the_logits_spread(state_served):
    """`lm_head_multiplier` makes the logits small: a limit in absolute
    logits would pass a model with the state-space branch missing.  The
    comparison divides by the reference's own spread, so the same engine
    under a head multiplier a hundred times smaller reads the same."""
    comparison = pieces.load("comparisons", "causal_logits_state_carry")
    ref = np.asarray([[0.0, 0.002, -0.002, 0.004], [0.0, 0.001, 0.003, 0.0]])
    got = ref[0] + np.asarray([0.0, 0.0005, 0.0, 0.0])
    small = comparison._row(ref, got, [3])
    big = comparison._row(ref * 100, got * 100, [3])
    assert small["logit_rel_max"] == pytest.approx(big["logit_rel_max"])
    assert small["logit_rel_max"] == pytest.approx(0.0005 / ref[0].std())
    assert small["decode"] == pytest.approx(big["decode"])
    assert comparison.REL_MARGIN == 2 * comparison.REL_LOGITS
    assert set(comparison.LENGTHS) >= {5, 127, 128, 129, 511, 513, 700, 1500}
    assert comparison.DECODE_TOKENS >= 25 and comparison.STATE_TOKENS >= 25


def test_a_cell_of_the_benchmark_names_these_pieces():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[
        "falcon-h1-34b-instruct-d6"]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert pieces.named(cfg) == {
        "reference": "parallel_mamba2_gqa_swiglu",
        "comparison": "causal_logits_state_carry",
        "warmups": ["decode_windows_state_slots",
                    "greedy_single_steps_state_slots",
                    "packed_prefill_state_slots", "first_token_packs"]}
    assert entry["reduced"] == ["num_hidden_layers"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = [json.loads(line) for line in f
                   if '"Falcon-H1-34B-Instruct"' in line][0]
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key != "num_hidden_layers":
                assert cfg[key] == value, key
        assert cfg["published"] == row["config"]
