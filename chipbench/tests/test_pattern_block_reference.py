"""The plain reference of the pattern block (a Mamba-2 mixer, attention
without a position term, or latent experts, each alone in its layer, with the
chip's share of the routed experts) held at tiny widths on the CPU: against
the program (a pattern that holds all three kinds; prefill in chunks through
the slot, segments packed in one chunk, decode windows and single steps),
with the comparison's four controls refused; the shares of one expert layer
adding up to the uncut layer; the family's tensor names by a save-and-load
round trip (no checkpoint and no published modeling code are on this
machine)."""

import json
import os

import numpy as np
import pytest

from chipbench import check, pieces, run

REFERENCE = "pattern_mamba2_gqa_latent_moe"
COMPARISON = "causal_logits_state_carry_routed"
CONFIG = "nemotron-3-super-120b-a12b-d11-ep4"
CELL = "nemotron-3-super.reasoning"
HF = {"model_type": "nemotron_h", "hidden_size": 64, "intermediate_size": 48,
      "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "num_hidden_layers": 5,
      "hybrid_override_pattern": "ME*ME", "layer_norm_epsilon": 1e-5,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 8,
      "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
      "use_conv_bias": True, "mamba_proj_bias": False, "use_bias": False,
      "mlp_bias": False, "attention_bias": False, "mlp_hidden_act": "relu2",
      "mamba_hidden_act": "silu", "n_routed_experts": 4,
      "routed_experts_held": {"first": 4, "count": 4, "of": 16},
      "num_experts_per_tok": 6, "moe_intermediate_size": 48,
      "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
      "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
      "norm_topk_prob": True, "routed_scaling_factor": 2.5,
      "num_nextn_predict_layers": 0, "rope_theta": 10000}


@pytest.fixture(scope="module")
def pattern_reference():
    return pieces.load("references", REFERENCE)


def _jittered(cfg, seed):
    """Seeded float32 params with every norm weight and D moved off 1."""
    import jax

    from dynamo_tpu.models import llama

    params = llama.init_params(cfg, jax.random.key(seed))
    k = iter(jax.random.split(jax.random.key(seed + 1), 64))

    def jitter(w):
        return w + 0.2 * jax.random.normal(next(k), w.shape, w.dtype)

    params["final_norm"] = jitter(params["final_norm"])
    for layer in params["layers"]:
        layer["norm"] = jitter(layer["norm"])
        if "ssm" in layer:
            layer["ssm"]["norm"] = jitter(layer["ssm"]["norm"])
            layer["ssm"]["D"] = jitter(layer["ssm"]["D"])
    return params


@pytest.fixture(scope="module")
def pattern_tiny():
    import jax.numpy as jnp

    from dynamo_tpu.models import loader

    cfg = loader.config_from_hf(HF, "tiny-pattern").replace(
        dtype=jnp.float32)
    return cfg, _jittered(cfg, 5)


@pytest.mark.parametrize("n,mode", [(7, "dense"), (21, "grouped"),
                                    (40, "dense")])
def test_whole_forward_matches_the_program(pattern_tiny, pattern_reference,
                                           n, mode):
    """One padded chunk through the program's step against the reference's
    whole forward, the reference choosing its own experts (float32 on both
    sides: the same choices), and the first state layer's slot."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import kv_cache as kvc
    from dynamo_tpu.models import llama

    cfg, params = pattern_tiny
    assert cfg.layer_pattern == "ME*ME" and cfg.experts_held == (4, 4)
    assert cfg.num_experts == 16 and not cfg.use_rope
    toks = np.random.default_rng(n).integers(1, 256, size=n)
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, 16, 8, state_slots=2))
    step = jax.jit(llama.make_forward_step(cfg, 8, moe_mode=mode))
    t = np.zeros((1, 40), np.int32)
    p = np.full((1, 40), 10_000, np.int32)
    t[0, :n], p[0, :n] = toks, np.arange(n)
    logits, cache = step(params, cache, jnp.asarray(t), jnp.asarray(p),
                         jnp.asarray([n]), jnp.asarray([[1, 2, 3, 4, 5]]),
                         None, state_slots=jnp.asarray([1]))
    want, state = pattern_reference.forward(HF, params, toks.tolist(),
                                            state_at=n)
    np.testing.assert_allclose(np.asarray(logits[0, :n]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache["ssm"][0][1]),
                               np.asarray(state), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(pattern_reference):
    """The routed parts of four shares of one expert layer, summed in the
    latent space, with the latent maps and the shared expert counted once,
    equal the uncut reference for the whole layer: the program's layer told
    (first, 4) of 16 computes its own experts' part and no more."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama, loader

    whole_hf = dict(HF, n_routed_experts=16)
    whole_hf.pop("routed_experts_held")
    whole = loader.config_from_hf(whole_hf, "whole").replace(
        dtype=jnp.float32)
    assert whole.experts_held is None and whole.num_experts == 16
    moe = llama.init_params(whole, jax.random.key(3))["layers"][1]["moe"]
    assert moe["w_up"].shape == (16, 32, 48)
    h = jax.random.normal(jax.random.key(4), (1, 37, 64), jnp.float32)
    want, _ = pattern_reference.expert_layer(whole_hf, moe, h[0])
    shared = np.asarray(llama._dense_mlp(moe["shared"], h, "relu2"))[0]
    routed = {k: v for k, v in moe.items() if k != "shared"}
    for mode in ("dense", "grouped"):
        parts = []
        for first in (0, 4, 8, 12):
            cfg = whole.replace(experts_held=(first, 4))
            mine = dict(routed, w_up=moe["w_up"][first:first + 4],
                        w_down=moe["w_down"][first:first + 4])
            out, stats = llama._moe_block(cfg, mine, h, mode, None)
            # The program's own share against the reference given the same.
            ref, _ = pattern_reference.expert_layer(
                dict(whole_hf, n_routed_experts=4, routed_experts_held={
                    "first": first, "count": 4, "of": 16}),
                dict(mine, shared=moe["shared"]), h[0])
            np.testing.assert_allclose(np.asarray(out[0]) + shared,
                                       np.asarray(ref), atol=2e-5)
            assert int(stats[:-1].sum()) == 37 * 6     # routed over all 16
            parts.append(np.asarray(out[0]))
        # `out` is already mapped up: the map is linear, so the sum of the
        # mapped parts is the map of the sum in the latent space.
        np.testing.assert_allclose(sum(parts) + shared, np.asarray(want),
                                   atol=5e-5)
        assert max(np.abs(p).max() for p in parts) > 0.05


def test_tensor_names_round_trip(pattern_tiny, tmp_path):
    """A tiny model saved by the family's tensor names (HF layout: a linear
    map as [out, in], the depthwise convolution as [channels, 1, taps], the
    experts by their index in the MODEL) is read back by the program's
    loader into the parameters it was saved from: of the experts, the held
    range only, and of the vocabulary, the slice."""
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from dynamo_tpu.models import loader

    cfg, params = pattern_tiny
    first, count = cfg.experts_held
    out = {"backbone.embeddings.weight": np.asarray(params["embed"]),
           "backbone.norm_f.weight": np.asarray(params["final_norm"]),
           "lm_head.weight": np.asarray(params["lm_head"]).T}
    for i, layer in enumerate(params["layers"]):
        p = f"backbone.layers.{i}."
        out[p + "norm.weight"] = np.asarray(layer["norm"])
        m = p + "mixer."
        if "ssm" in layer:
            s = layer["ssm"]
            out[m + "in_proj.weight"] = np.asarray(s["w_in"]).T
            out[m + "out_proj.weight"] = np.asarray(s["w_out"]).T
            out[m + "conv1d.weight"] = np.asarray(s["conv_w"]).T[:, None, :]
            out[m + "conv1d.bias"] = np.asarray(s["conv_b"])
            out[m + "norm.weight"] = np.asarray(s["norm"])
            for name in ("A_log", "D", "dt_bias"):
                out[m + name] = np.asarray(s[name])
        elif "attn" in layer:
            for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                                 ("wo", "o")):
                out[f"{m}{theirs}_proj.weight"] = np.asarray(
                    layer["attn"][ours]).T
        else:
            e = layer["moe"]
            out[m + "gate.weight"] = np.asarray(e["router"]).T
            out[m + "gate.e_score_correction_bias"] = np.asarray(
                e["router_bias"])
            out[m + "fc1_latent_proj.weight"] = np.asarray(e["latent_in"]).T
            out[m + "fc2_latent_proj.weight"] = np.asarray(e["latent_out"]).T
            for j in range(count):
                out[f"{m}experts.{first + j}.up_proj.weight"] = np.asarray(
                    e["w_up"][j]).T
                out[f"{m}experts.{first + j}.down_proj.weight"] = np.asarray(
                    e["w_down"][j]).T
            out[m + "shared_experts.up_proj.weight"] = np.asarray(
                e["shared"]["w_up"]).T
            out[m + "shared_experts.down_proj.weight"] = np.asarray(
                e["shared"]["w_down"]).T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as f:
        json.dump(HF, f)
    got_cfg, got = loader.load_params(str(tmp_path), dtype=jnp.float32)
    assert got_cfg.layer_pattern == "ME*ME" and got_cfg.experts_held == (4, 4)
    import jax

    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert len(flat_want) == len(flat_got)
    for path, want in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[path]),
                                      np.asarray(want), err_msg=str(path))


@pytest.fixture(scope="module")
def pattern_served(pattern_tiny):
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    cfg, params = pattern_tiny
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=96, decode_window=8,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=16, max_pages_per_seq=16,
            max_prefill_chunk=32, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 32))), params=params)
    hf = dict(HF, reference=REFERENCE, comparison=COMPARISON,
              warmups=["first_token_packs"])
    return hf, core


LENGTHS = (5, 15, 16, 17, 31, 33, 70, 129)      # across chunks of 8 and 32
LIMITS = ["max_rel_logit_diff", "max_rel_body_logit_diff",
          "max_rel_decode_margin", "max_rel_state_diff",
          "max_choice_shortfall"]


def test_the_engine_through_chunks_packs_windows_and_steps(pattern_served):
    """The comparison as the worker runs it: prompts that cross the scan's
    chunk (8) and the prefill chunk (32), several packed to a chunk, whole
    windows and single steps, the engine's expert choices handed to the
    reference, and the state the lone sequence leaves in its slot; float32
    on both sides reads far inside every limit."""
    hf, core = pattern_served
    assert sorted(core.cache) == ["conv", "k", "ssm", "v"]
    assert [len(core.cache[k]) for k in ("k", "ssm")] == [1, 2]
    out = check.run_check(core, hf, 11, LENGTHS)
    assert out["ok"] is True, out["problems"]
    assert out["compared"] == len(LENGTHS)
    assert out["windows"] > 0 and out["single_steps"] > 0
    assert [i["name"] for i in out["limits"]] == LIMITS
    assert all(i["value"] < 1e-4 for i in out["limits"]), out["limits"]


def test_every_control_fails_a_limit_and_the_engine_is_whole_after(
        pattern_served):
    """bfloat16 state: the state's limit.  The mixers' outputs zeroed, the
    held experts' part zeroed: the logit limits (the routed quarter is
    visible beside the shared expert).  The router cut to the held experts:
    the shortfall's limit, and with the reference taking the engine's
    choices no other.  The engine serves as before afterwards."""
    hf, core = pattern_served
    comparison = pieces.load("comparisons", COMPARISON)
    reference = pieces.load("references", REFERENCE)
    out = comparison.controls(core, hf, 11, reference, LENGTHS,
                              state_tokens=123)
    assert out["None"]["ok"] is True, out["None"]["problems"]
    assert set(out) == {"None", *comparison.CONTROLS}

    def over(name):
        return {i["name"] for i in out[name]["limits"]
                if i["value"] > i["limit"]}

    assert "max_rel_state_diff" in over("bf16_state")
    assert {"max_rel_logit_diff", "max_rel_body_logit_diff"} \
        <= over("zero_ssm")
    assert {"max_rel_logit_diff", "max_rel_body_logit_diff"} \
        <= over("zero_routed")
    assert over("route_over_held") == {"max_choice_shortfall"}
    for name in comparison.CONTROLS:
        assert out[name]["ok"] is False, name
    assert str(core.cache["ssm"][0].dtype) == "float32"
    moe = core.params["layers"][1]["moe"]
    assert float(abs(np.asarray(moe["latent_out"])).max()) > 0
    assert float(np.asarray(moe["router_bias"]).min()) > -10
    assert check.run_check(core, hf, 12, LENGTHS[:3])["ok"] is True


def test_the_cell_of_the_benchmark_names_these_pieces():
    """Entries by name, lists by membership: nothing here depends on where
    in its list an entry stands or on what else a list holds."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    named = pieces.named(cfg)
    assert named["reference"] == REFERENCE
    assert named["comparison"] == COMPARISON
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert set(cfg["reduced"]) == set(entry["reduced"])
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reasoning", 1)
    assert cfg["routed_experts_held"] == {"first": 0, "count": 128,
                                          "of": 512}
    assert cfg["hybrid_override_pattern"] == cfg["published_pattern"][:11]
    assert len(cfg["published_pattern"]) == \
        cfg["published"]["num_hidden_layers"] == 88
    for key in ("attention_position", "gated_norm", "latent_maps",
                "shared_expert", "router", "state_dtype", "A_log", "dt_bias",
                "D", "e_score_correction_bias", "weights", "tensor_names",
                "tokenizer"):
        assert key in cfg["assumed"], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = [json.loads(line) for line in f
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
        assert entry["source"] == row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            if key == "hybrid_override_pattern":
                assert cfg["published_pattern"] == value
            elif key not in entry["reduced"]:
                assert cfg[key] == value, key
            assert key == "hybrid_override_pattern" \
                or cfg["published"][key] == value


def test_the_mix_is_the_issues_letter_for_letter():
    from chipbench import traffic

    mix = traffic.load_mix(os.path.join(run.HERE, "traffic"), "reasoning")
    assert mix["input_tokens"] == {"median": 128, "sigma": 0.8, "min": 16,
                                   "max": 1024}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5, "min": 128,
                                    "max": 1024}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_ms"]) == (
        10, 30, 3000)
    reqs = traffic.schedule(mix, 4.0, 7, 10.0, 40.0)
    judged = [r for r in reqs if r.due_s >= 0]
    assert 170 < sum(r.n_in for r in judged) / len(judged) < 185
    assert 545 < sum(r.n_out for r in judged) / len(judged) < 575
