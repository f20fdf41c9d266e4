"""Checkpoint loading: HF-format safetensors → engine param pytree.

Role of the reference's `lib/llm/src/local_model.rs:39-236` + `hub.rs`
(resolve a model path, build the deployment card, hand real weights to the
engine) — minus the hub download (no egress in this environment; a local
directory in HF layout is the contract, which is also what a mounted model
cache looks like in deployment).

Name mapping (HF Llama/Mixtral → dynamo_tpu.models.llama pytree):

    model.embed_tokens.weight            embed                [V, H]
    model.norm.weight                    final_norm           [H]
    lm_head.weight                       lm_head (transposed) [H, V]
    model.layers.N.input_layernorm       layers[N].attn_norm
    model.layers.N.post_attention_ln     layers[N].mlp_norm
    ...self_attn.{q,k,v}_proj.weight     attn.w{q,k,v} (transposed)
    ...self_attn.o_proj.weight           attn.wo       (transposed)
    ...mlp.{gate,up,down}_proj.weight    mlp.w_{gate,up,down} (transposed)
    ...block_sparse_moe.gate.weight      moe.router    (transposed)
    ...block_sparse_moe.experts.E.w{1,3,2}  moe.w_{gate,up,down}[E]
    ...mlp.gate.weight                   moe.router    (Qwen3-MoE / SDAR)
    ...mlp.experts.E.{gate,up,down}_proj moe.w_{gate,up,down}[E]
    ...self_attn.{q,k}_norm.weight       attn.{q,k}_norm  [head_dim]

Latent attention and DeepSeek-V3-style experts (`glm4_moe_lite`; no
checkpoint of it was at hand, so these names are the DeepSeek-V3 family's
and are held by a save-and-load test, tests/test_latent_model.py):

    ...self_attn.q_a_proj.weight         attn.wq_a     (transposed)
    ...self_attn.q_a_layernorm.weight    attn.q_a_norm
    ...self_attn.q_b_proj.weight         attn.wq_b     (transposed)
    ...self_attn.kv_a_proj_with_mqa      attn.wkv_a    (transposed)
    ...self_attn.kv_a_layernorm.weight   attn.kv_a_norm
    ...self_attn.kv_b_proj.weight        attn.wkv_b    (transposed)
    ...mlp.gate.e_score_correction_bias  moe.router_bias  [E] float32
    ...mlp.shared_experts.{gate,up,down}_proj  moe.shared.w_{gate,up,down}
    (`model.layers.N` past `num_hidden_layers`, the multi-token-prediction
    block, is not read.)

The `falcon_h1` block (a Mamba-2 mixer beside the attention; the names are
the published modeling code's, held by a save-and-load test,
tests/test_state_slots.py):

    model.layers.N.mamba.in_proj.weight  ssm.w_in      (transposed)
    ...mamba.conv1d.{weight,bias}        ssm.conv_w [taps, channels], conv_b
    ...mamba.{A_log,D,dt_bias}           ssm.{A_log,D,dt_bias}  [heads] f32
    ...mamba.norm.weight                 ssm.norm
    ...mamba.out_proj.weight             ssm.w_out     (transposed)
    ...feed_forward.{gate,up,down}_proj  mlp.w_{gate,up,down} (transposed)
    ...pre_ff_layernorm.weight           layers[N].mlp_norm
    model.final_layernorm.weight         final_norm

The `nemotron_h` block (one mixer or one feed-forward part a layer, by
`hybrid_override_pattern`; `modeling_nemotron_h.py` is not on this machine,
so the names are the family's as `transformers`' mamba2 / bamba /
deepseek_v3 siblings write them, held by a save-and-load test,
chipbench/tests/test_pattern_block_reference.py):

    backbone.embeddings.weight           embed
    backbone.layers.N.norm.weight        layers[N].norm
    backbone.layers.N.mixer.in_proj ...  ssm.* as above          ("M" layers)
    ...mixer.{q,k,v,o}_proj.weight       attn.w{q,k,v,o}         ("*" layers)
    ...mixer.gate.weight                 moe.router              ("E" layers)
    ...mixer.gate.e_score_correction_bias  moe.router_bias  [E] float32
    ...mixer.fc1_latent_proj.weight      moe.latent_in   [H, latent]
    ...mixer.fc2_latent_proj.weight      moe.latent_out  [latent, H]
    ...mixer.experts.E.{up,down}_proj    moe.w_{up,down}[E - first] (the
                                         experts held here only)
    ...mixer.shared_experts.{up,down}_proj  moe.shared.w_{up,down}
    backbone.norm_f.weight               final_norm
    lm_head.weight                       lm_head (its first vocab_size rows)

HF stores `nn.Linear` weights as [out, in]; our pytree multiplies x @ W so
every projection transposes on load.  GQA head order: HF q head h shares
kv head h // G (blocked) — ops/attention.py uses the same convention, and
our RoPE is the half-split (NeoX/Llama) rotation HF uses, so logits match
a `transformers` forward to float tolerance (locked by
tests/test_loader.py).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig

Params = Dict


def config_from_hf(hf: dict, name: str = "") -> ModelConfig:
    """Map an HF config.json dict to our ModelConfig (Llama/Mistral/Qwen
    family, Mixtral MoE, Gemma-2, glm4_moe_lite, falcon_h1)."""
    arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
    gemma2 = "Gemma2" in arch or hf.get("model_type") == "gemma2"
    num_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads
    max_context = hf.get("max_position_embeddings", 8192)
    if gemma2 and hf.get("sliding_window"):
        # Gemma-2 alternates sliding-window and global layers; this
        # engine runs every layer global, which is EXACT while context
        # stays within the window — clamp rather than silently diverge.
        max_context = min(max_context, int(hf["sliding_window"]))
    query_scale = None
    if gemma2 and hf.get("query_pre_attn_scalar"):
        query_scale = float(hf["query_pre_attn_scalar"]) ** -0.5
    model_type = hf.get("model_type", "")
    # The Qwen3 block (and SDAR, which derives from it): RMSNorm on each
    # head of q and k; its MoE form names the expert count `num_experts`
    # and gives the experts a width of their own.
    qwen3 = model_type in ("qwen3", "qwen3_moe", "sdar", "sdar_moe")
    # SDAR generates by diffusion over blocks.  Its config.json gives
    # neither the block length nor the schedule (they are arguments of its
    # generate()); a config may state them, else the model card's defaults.
    sdar = model_type in ("sdar", "sdar_moe")
    diffusion = {}
    if sdar or hf.get("diffusion_block_length", 1) > 1:
        diffusion = dict(
            diffusion_block_length=int(hf.get("diffusion_block_length", 4)),
            denoising_steps=int(hf.get("denoising_steps", 4)),
            remasking=hf.get("remasking", "low_confidence_static"),
            confidence_threshold=float(hf.get("confidence_threshold", 0.9)),
            mask_token_id=hf.get("mask_token_id", 151669 if sdar else None))
    latent = {}
    if hf.get("kv_lora_rank"):
        # Latent attention: the head's score width is nope + rope, and the
        # one latent row serves every head.
        head_dim = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
        if hf.get("rope_scaling") or float(
                hf.get("partial_rotary_factor", 1)) != 1.0:
            raise ValueError("latent attention: rope_scaling and a partial "
                             "rotary factor other than 1 are not implemented")
        if not hf.get("q_lora_rank"):
            raise ValueError("latent attention without q_lora_rank (a "
                             "full-rank q projection) is not implemented")
        latent = dict(
            q_lora_rank=int(hf["q_lora_rank"]),
            kv_lora_rank=int(hf["kv_lora_rank"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
            qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]))
    if model_type == "nemotron_h":
        return _nemotron_h_config(hf, name)
    if model_type == "cohere2_moe":
        return _cohere2_moe_config(hf, name)
    routed = {}
    if hf.get("n_routed_experts"):
        # The DeepSeek-V3 expert layer: sigmoid scores, choice by score +
        # learned bias, a shared expert, leading dense layers.
        if hf.get("topk_method", "noaux_tc") != "noaux_tc":
            raise ValueError(f"topk_method {hf.get('topk_method')!r} is not "
                             "implemented (noaux_tc is)")
        if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing (n_group, topk_group > "
                             "1) is not implemented")
        routed = dict(
            router_scoring="sigmoid",
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            first_k_dense=int(hf.get("first_k_dense_replace", 0)))
    state = {}
    if model_type == "falcon_h1":
        if hf.get("attn_layer_indices") or hf.get("rope_scaling") \
                or hf.get("attention_bias") or hf.get("mlp_bias") \
                or hf.get("projectors_bias"):
            raise ValueError("falcon_h1: attn_layer_indices, rope_scaling "
                             "and attention/mlp/projector biases are not "
                             "implemented")
        d_ssm = hf.get("mamba_d_ssm") or int(
            hf["mamba_expand"] * hf["hidden_size"])
        state = dict(
            mamba_d_ssm=int(d_ssm),
            mamba_n_heads=int(hf["mamba_n_heads"]),
            mamba_d_head=int(hf["mamba_d_head"]),
            mamba_d_state=int(hf["mamba_d_state"]),
            mamba_n_groups=int(hf.get("mamba_n_groups", 1)),
            mamba_d_conv=int(hf.get("mamba_d_conv", 4)),
            mamba_chunk_size=int(hf.get("mamba_chunk_size", 128)),
            mamba_conv_bias=bool(hf.get("mamba_conv_bias", True)),
            mamba_proj_bias=bool(hf.get("mamba_proj_bias", False)),
            mamba_rms_norm=bool(hf.get("mamba_rms_norm", True)),
            mamba_norm_before_gate=bool(
                hf.get("mamba_norm_before_gate", False)),
            embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
            lm_head_multiplier=float(hf.get("lm_head_multiplier", 1.0)),
            attention_in_multiplier=float(
                hf.get("attention_in_multiplier", 1.0)),
            attention_out_multiplier=float(
                hf.get("attention_out_multiplier", 1.0)),
            key_multiplier=float(hf.get("key_multiplier", 1.0)),
            ssm_in_multiplier=float(hf.get("ssm_in_multiplier", 1.0)),
            ssm_out_multiplier=float(hf.get("ssm_out_multiplier", 1.0)),
            mlp_multipliers=tuple(
                float(m) for m in hf.get("mlp_multipliers", (1.0, 1.0))),
            ssm_multipliers=tuple(
                float(m) for m in hf.get("ssm_multipliers", (1.0,) * 5)))
    else:
        # A state-space model under a type this function does not map
        # would be served as a plain dense decoder without a word.
        stated = sorted(k for k in hf if k.startswith("mamba_"))
        if stated:
            raise ValueError(
                f"model_type {model_type!r} states state-space keys "
                f"({', '.join(stated)}) and is not mapped: only falcon_h1's "
                "Mamba-2 mixer beside attention is implemented")
    return ModelConfig(
        **latent, **routed, **state,
        name=name or hf.get("model_type", "hf-model"),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=(num_heads if latent
                      else hf.get("num_key_value_heads", num_heads)),
        head_dim=head_dim,
        intermediate_size=hf["intermediate_size"],
        max_context=max_context,
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        num_experts=(hf.get("num_local_experts") or hf.get("num_experts")
                     or hf.get("n_routed_experts") or 0),
        num_experts_per_token=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf.get("moe_intermediate_size"),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        qk_norm=bool(hf.get("qk_norm", qwen3)),
        **diffusion,
        # HF omits defaulted keys from config.json; Gemma-2's default is
        # TIED embeddings (Llama's is untied).
        tie_embeddings=bool(hf.get("tie_word_embeddings", gemma2)),
        activation="gelu_tanh" if gemma2 else "silu",
        attn_soft_cap=hf.get("attn_logit_softcapping") if gemma2 else None,
        final_soft_cap=(hf.get("final_logit_softcapping")
                        if gemma2 else None),
        post_norms=gemma2,
        rms_offset=gemma2,
        embed_scale=gemma2,
        query_scale=query_scale,
    )


def _nemotron_h_config(hf: dict, name: str) -> ModelConfig:
    """The `nemotron_h` keys: a layer kind a character of
    `hybrid_override_pattern`, the Mamba-2 mixer under its own key names,
    attention without a position term, experts in a latent space behind the
    DeepSeek-V3 router, an ungated `relu2` MLP.  `routed_experts_held`
    ({"first", "count", "of"}; a deployment's key, not the model's) says
    which of the model's `of` experts this chip holds: `n_routed_experts`
    is then their count."""
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern names {len(pattern)} "
            f"layers, num_hidden_layers is {hf['num_hidden_layers']}")
    if hf.get("num_nextn_predict_layers"):
        raise ValueError("nemotron_h: the multi-token-prediction head "
                         "(num_nextn_predict_layers > 0) is not implemented")
    for key in ("use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias"):
        if hf.get(key):
            raise ValueError(f"nemotron_h: {key} is not implemented")
    if hf.get("mlp_hidden_act", "relu2") != "relu2" \
            or hf.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("nemotron_h: mlp_hidden_act relu2 and "
                         "mamba_hidden_act silu are implemented, not "
                         f"{hf.get('mlp_hidden_act')!r} / "
                         f"{hf.get('mamba_hidden_act')!r}")
    if hf.get("topk_method", "noaux_tc") != "noaux_tc" \
            or int(hf.get("n_group", 1)) != 1 \
            or int(hf.get("topk_group", 1)) != 1:
        raise ValueError("nemotron_h: group-limited routing (n_group, "
                         "topk_group > 1) is not implemented")
    if hf.get("sliding_window") or hf.get("moe_shared_expert_overlap"):
        raise ValueError("nemotron_h: sliding_window and "
                         "moe_shared_expert_overlap are not implemented")
    n_heads, d_head = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    held = hf.get("routed_experts_held")
    experts = int(hf.get("n_routed_experts") or 0)
    experts_held = None
    if held:
        if int(held["count"]) != experts:
            raise ValueError(
                "nemotron_h: routed_experts_held.count is "
                f"{held['count']}, n_routed_experts (the experts held "
                f"here) is {experts}")
        experts_held = (int(held["first"]), int(held["count"]))
        experts = int(held["of"])
    eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
    return ModelConfig(
        name=name or "nemotron_h",
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"], layer_pattern=pattern,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads",
                            hf["num_attention_heads"]),
        head_dim=hf.get("head_dim") or hf["hidden_size"]
        // hf["num_attention_heads"],
        use_rope=False,
        intermediate_size=hf["intermediate_size"],
        max_context=hf.get("max_position_embeddings", 8192),
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rms_norm_eps=eps, activation="relu2",
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        num_experts=experts, experts_held=experts_held,
        num_experts_per_token=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf.get("moe_intermediate_size"),
        moe_latent_size=int(hf.get("moe_latent_size") or 0),
        shared_expert_size=int(
            hf.get("moe_shared_expert_intermediate_size") or 0),
        n_shared_experts=int(hf.get("n_shared_experts") or 0),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="sigmoid" if experts else "softmax",
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        mamba_d_ssm=n_heads * d_head, mamba_n_heads=n_heads,
        mamba_d_head=d_head, mamba_d_state=int(hf["ssm_state_size"]),
        mamba_n_groups=int(hf.get("n_groups", 1)),
        mamba_d_conv=int(hf.get("conv_kernel", 4)),
        mamba_chunk_size=int(hf.get("chunk_size", 128)),
        mamba_conv_bias=bool(hf.get("use_conv_bias", True)))


def _cohere2_moe_config(hf: dict, name: str) -> ModelConfig:
    """The `cohere2_moe` keys: a window or a full layer by `layer_types`
    (`sliding_attention` layers see `sliding_window` positions and apply the
    interleaved-pair rotary embedding, `full_attention` layers are causal
    with no position term), attention and experts in parallel on one
    mean-subtracting norm, a sigmoid router without a correction bias, the
    shared experts averaged, tied embeddings under `logit_scale`.
    `routed_experts_held` ({"first", "count", "of"}; a deployment's key, not
    the model's) says which of the model's `of` experts this chip holds:
    `num_experts` is then their count."""
    layers = int(hf["num_hidden_layers"])
    kinds = list(hf.get("layer_types") or ())
    if len(kinds) != layers:
        raise ValueError(
            f"cohere2_moe: layer_types names {len(kinds)} layers, "
            f"num_hidden_layers is {layers}")
    unknown = sorted(set(kinds) - {"sliding_attention", "full_attention"})
    if unknown:
        raise ValueError(f"cohere2_moe: unknown layer type(s) {unknown}; "
                         "sliding_attention and full_attention are "
                         "implemented")
    window = int(hf.get("sliding_window") or 0)
    if "sliding_attention" in kinds and window <= 0:
        raise ValueError("cohere2_moe: sliding_attention layers need "
                         "sliding_window")
    refused = {
        "attention_bias": bool(hf.get("attention_bias")),
        "use_qk_norm": bool(hf.get("use_qk_norm")),
        "first_k_dense_replace (prefix dense layers)":
            bool(hf.get("first_k_dense_replace")),
        "use_parallel_block false": not hf.get("use_parallel_block", True),
        "use_gated_activation false":
            not hf.get("use_gated_activation", True),
        "rotary_pct other than 1": float(hf.get("rotary_pct", 1)) != 1.0,
        "logit_scale other than 1": float(hf.get("logit_scale", 1)) != 1.0,
        "rope_scaling": bool(hf.get("rope_scaling")) or (
            (hf.get("rope_parameters") or {}).get(
                "rope_type", "default") != "default"),
        "hidden_act other than silu": hf.get("hidden_act", "silu") != "silu",
        "expert_selection_fn other than sigmoid":
            hf.get("expert_selection_fn", "sigmoid") != "sigmoid",
        "shared_expert_combination_strategy other than average":
            hf.get("shared_expert_combination_strategy",
                   "average") != "average",
        "position_embedding_type other than rope_gptj":
            hf.get("position_embedding_type", "rope_gptj") != "rope_gptj",
        "per-head attention sink terms": bool(hf.get("attention_sinks")),
    }
    for what, stated in refused.items():
        if stated:
            raise ValueError(f"cohere2_moe: {what} is not implemented")
    held = hf.get("routed_experts_held")
    experts = int(hf["num_experts"])
    experts_held = None
    if held:
        if int(held["count"]) != experts:
            raise ValueError(
                "cohere2_moe: routed_experts_held.count is "
                f"{held['count']}, num_experts (the experts held here) is "
                f"{experts}")
        experts_held = (int(held["first"]), int(held["count"]))
        experts = int(held["of"])
    sliding = tuple(k == "sliding_attention" for k in kinds)
    heads = int(hf["num_attention_heads"])
    return ModelConfig(
        name=name or "cohere2_moe",
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=layers, num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        intermediate_size=hf["intermediate_size"],
        max_context=hf.get("max_position_embeddings", 8192),
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rms_norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
        norm_kind="layer", parallel_block=True,
        layer_windows=tuple(window if s else 0 for s in sliding),
        layer_rope=sliding, rope_interleaved=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        num_experts=experts, experts_held=experts_held,
        num_experts_per_token=int(hf.get("num_experts_per_tok", 2)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="sigmoid", router_score_bias=False,
        n_shared_experts=int(hf.get("num_shared_experts") or 0),
        shared_experts_mean=bool(hf.get("num_shared_experts")))


class _TensorSource:
    """All safetensors shards of a checkpoint, keyed by tensor name."""

    def __init__(self, model_dir: str) -> None:
        from safetensors import safe_open

        self._handles = []
        self._where: Dict[str, int] = {}
        shards = sorted(f for f in os.listdir(model_dir)
                        if f.endswith(".safetensors"))
        if not shards:
            raise FileNotFoundError(f"no .safetensors files in {model_dir}")
        for i, fname in enumerate(shards):
            h = safe_open(os.path.join(model_dir, fname), framework="np")
            self._handles.append(h)
            for key in h.keys():
                self._where[key] = i

    def get(self, name: str) -> np.ndarray:
        idx = self._where.get(name)
        if idx is None:
            raise KeyError(f"tensor {name!r} not in checkpoint "
                           f"(have e.g. {sorted(self._where)[:5]})")
        return self._handles[idx].get_tensor(name)

    def __contains__(self, name: str) -> bool:
        return name in self._where


def load_params(model_dir: str,
                cfg: Optional[ModelConfig] = None,
                dtype=None) -> Tuple[ModelConfig, Params]:
    """Load an HF-layout checkpoint directory into (config, params).

    `dtype=None` keeps the config's dtype (bf16 for real models).  Arrays
    land as jnp arrays on the default device; for sharded serving the
    engine re-places them with shard_pytree (device_put moves, no copy
    through host when layouts agree).
    """
    if cfg is None:
        with open(os.path.join(model_dir, "config.json")) as f:
            cfg = config_from_hf(json.load(f),
                                 name=os.path.basename(model_dir.rstrip("/")))
    cfg.validate()
    dtype = dtype or cfg.dtype
    src = _TensorSource(model_dir)

    def lin(name: str) -> jnp.ndarray:
        # HF nn.Linear [out, in] -> ours [in, out].
        return jnp.asarray(src.get(name)).T.astype(dtype)

    def vec(name: str) -> jnp.ndarray:
        return jnp.asarray(src.get(name)).astype(dtype)

    if cfg.has_pattern:
        return cfg, _load_pattern_params(cfg, src, lin, vec, dtype)

    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        if cfg.is_latent:
            attn = {
                "wq_a": lin(p + "self_attn.q_a_proj.weight"),
                "q_a_norm": vec(p + "self_attn.q_a_layernorm.weight"),
                "wq_b": lin(p + "self_attn.q_b_proj.weight"),
                "wkv_a": lin(p + "self_attn.kv_a_proj_with_mqa.weight"),
                "kv_a_norm": vec(p + "self_attn.kv_a_layernorm.weight"),
                "wkv_b": lin(p + "self_attn.kv_b_proj.weight"),
                "wo": lin(p + "self_attn.o_proj.weight"),
            }
        else:
            attn = {
                "wq": lin(p + "self_attn.q_proj.weight"),
                "wk": lin(p + "self_attn.k_proj.weight"),
                "wv": lin(p + "self_attn.v_proj.weight"),
                "wo": lin(p + "self_attn.o_proj.weight"),
            }
        layer = {"attn": attn,
                 "attn_norm": vec(p + "input_layernorm.weight")}
        if cfg.has_ssm:
            layer["ssm"] = _load_ssm(cfg, src, p + "mamba.", lin, vec, dtype)
        if cfg.qk_norm:
            layer["attn"]["q_norm"] = vec(p + "self_attn.q_norm.weight")
            layer["attn"]["k_norm"] = vec(p + "self_attn.k_norm.weight")
        if cfg.post_norms:
            # Gemma-2 naming: post_attention_layernorm is a TRUE
            # post-norm; the pre-MLP norm is pre_feedforward_layernorm
            # (in Llama, post_attention_layernorm is the pre-MLP norm).
            layer["post_attn_norm"] = vec(
                p + "post_attention_layernorm.weight")
            layer["mlp_norm"] = vec(p + "pre_feedforward_layernorm.weight")
            layer["post_mlp_norm"] = vec(
                p + "post_feedforward_layernorm.weight")
        elif cfg.has_ssm:
            layer["mlp_norm"] = vec(p + "pre_ff_layernorm.weight")
        else:
            layer["mlp_norm"] = vec(p + "post_attention_layernorm.weight")
        if cfg.layer_is_moe(i):
            experts_gate = []
            experts_up = []
            experts_down = []
            # Mixtral names the block `block_sparse_moe` with w1/w3/w2;
            # the Qwen3-MoE family `mlp` with gate/up/down_proj.
            mixtral = (p + "block_sparse_moe.gate.weight") in src
            block = p + ("block_sparse_moe." if mixtral else "mlp.")
            names = (("w1", "w3", "w2") if mixtral
                     else ("gate_proj", "up_proj", "down_proj"))
            for e in range(cfg.num_experts):
                ep = block + f"experts.{e}."
                experts_gate.append(lin(ep + names[0] + ".weight"))
                experts_up.append(lin(ep + names[1] + ".weight"))
                experts_down.append(lin(ep + names[2] + ".weight"))
            layer["moe"] = {
                "router": lin(block + "gate.weight"),
                "w_gate": jnp.stack(experts_gate),
                "w_up": jnp.stack(experts_up),
                "w_down": jnp.stack(experts_down),
            }
            if cfg.router_scoring == "sigmoid":
                layer["moe"]["router_bias"] = jnp.asarray(src.get(
                    block + "gate.e_score_correction_bias")).astype(
                        jnp.float32)
            if cfg.n_shared_experts:
                sp = block + "shared_experts."
                layer["moe"]["shared"] = {
                    "w_gate": lin(sp + "gate_proj.weight"),
                    "w_up": lin(sp + "up_proj.weight"),
                    "w_down": lin(sp + "down_proj.weight")}
        else:
            mlp = p + ("feed_forward." if cfg.has_ssm else "mlp.")
            layer["mlp"] = {
                "w_gate": lin(mlp + "gate_proj.weight"),
                "w_up": lin(mlp + "up_proj.weight"),
                "w_down": lin(mlp + "down_proj.weight"),
            }
        layers.append(layer)

    params: Params = {
        "embed": jnp.asarray(src.get("model.embed_tokens.weight")).astype(dtype),
        "final_norm": vec("model.final_layernorm.weight" if cfg.has_ssm
                          else "model.norm.weight"),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        if "lm_head.weight" in src:
            params["lm_head"] = lin("lm_head.weight")
        else:
            cfg = cfg.replace(tie_embeddings=True)
    return cfg, params


def _load_ssm(cfg: ModelConfig, src, m: str, lin, vec, dtype) -> Params:
    """A Mamba-2 mixer's tensors under the prefix `m`."""
    def f32(name: str) -> jnp.ndarray:
        return jnp.asarray(src.get(name)).astype(jnp.float32)

    out = {
        "w_in": lin(m + "in_proj.weight"),
        # nn.Conv1d, depthwise: [channels, 1, taps] -> [taps, channels]
        "conv_w": jnp.asarray(
            src.get(m + "conv1d.weight"))[:, 0, :].T.astype(dtype),
        "A_log": f32(m + "A_log"), "D": f32(m + "D"),
        "dt_bias": f32(m + "dt_bias"),
        "w_out": lin(m + "out_proj.weight")}
    if cfg.mamba_conv_bias:
        out["conv_b"] = vec(m + "conv1d.bias")
    if cfg.mamba_rms_norm:
        out["norm"] = vec(m + "norm.weight")
    return out


def _load_pattern_params(cfg: ModelConfig, src, lin, vec, dtype) -> Params:
    """The `nemotron_h` names (module docstring): one `mixer` a layer, of
    the kind the pattern gives; of an expert layer the experts held here."""
    first, count = cfg.experts_local
    layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        layer = {"norm": vec(p + "norm.weight")}
        if kind == "M":
            layer["ssm"] = _load_ssm(cfg, src, m, lin, vec, dtype)
        elif kind == "*":
            layer["attn"] = {w: lin(f"{m}{n}_proj.weight") for w, n in (
                ("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))}
        else:
            moe = {
                "router": lin(m + "gate.weight"),
                "router_bias": jnp.asarray(src.get(
                    m + "gate.e_score_correction_bias")).astype(jnp.float32),
                "w_up": jnp.stack([
                    lin(f"{m}experts.{e}.up_proj.weight")
                    for e in range(first, first + count)]),
                "w_down": jnp.stack([
                    lin(f"{m}experts.{e}.down_proj.weight")
                    for e in range(first, first + count)])}
            if cfg.moe_latent_size:
                moe["latent_in"] = lin(m + "fc1_latent_proj.weight")
                moe["latent_out"] = lin(m + "fc2_latent_proj.weight")
            if cfg.shared_size:
                moe["shared"] = {
                    "w_up": lin(m + "shared_experts.up_proj.weight"),
                    "w_down": lin(m + "shared_experts.down_proj.weight")}
            layer["moe"] = moe
        layers.append(layer)
    return {
        "embed": jnp.asarray(src.get("backbone.embeddings.weight"))[
            :cfg.vocab_size].astype(dtype),
        "final_norm": vec("backbone.norm_f.weight"),
        "layers": layers,
        "lm_head": lin("lm_head.weight")[:, :cfg.vocab_size]}


def resolve_model(path_or_preset: str):
    """Resolve a --model argument: an HF-layout directory (real weights) or
    a preset name (random weights; bench/test mode).

    Returns (cfg, params_or_None, tokenizer_spec, chat_template_or_None).
    """
    from dynamo_tpu.models import config as mcfg

    if path_or_preset.endswith(".gguf") and os.path.isfile(path_or_preset):
        from dynamo_tpu.models.gguf import load_gguf

        cfg, params, tok = load_gguf(path_or_preset)
        # Serving tokenizer: GGUF embeds a sentencepiece-style vocab; the
        # byte tokenizer keeps the surface functional while the vocab
        # (extracted — the gguf_metadata.rs parity point) rides the card
        # for clients that want it.
        spec = {"kind": "byte"}
        if tok:
            spec["gguf_tokenizer"] = {k: tok[k] for k in
                                      ("model", "bos_token_id",
                                       "eos_token_id") if k in tok}
        return cfg, params, spec, None
    if (not os.path.exists(path_or_preset)
            and path_or_preset.count("/") == 1
            and not path_or_preset.startswith(".")):
        # `org/repo` → local HF hub cache (models/hub.py; the reference's
        # hub.rs resolution, cache-only in a no-egress environment).
        # Preset names never contain '/', so this cannot shadow them.
        from dynamo_tpu.models.hub import resolve_cached_repo

        path_or_preset = resolve_cached_repo(path_or_preset)
    if os.path.isdir(path_or_preset):
        cfg, params = load_params(path_or_preset)
        spec = {"kind": "byte"}
        tok_path = os.path.join(path_or_preset, "tokenizer.json")
        if os.path.exists(tok_path):
            with open(tok_path) as f:
                spec = {"kind": "hf_inline", "json": f.read()}
        template = None
        cfg_path = os.path.join(path_or_preset, "tokenizer_config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                tok_cfg = json.load(f)
            template = tok_cfg.get("chat_template")
            eos = tok_cfg.get("eos_token")
            if isinstance(eos, dict):
                eos = eos.get("content")
            if eos and spec.get("kind") == "hf_inline":
                spec["eos_token"] = eos
        return cfg, params, spec, template
    return mcfg.get_config(path_or_preset), None, {"kind": "byte"}, None
