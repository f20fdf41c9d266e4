"""Model architecture configs.

Plays the role of the reference's `ModelDeploymentCard` model-info slice
(`lib/llm/src/model_card.rs:90-120` — context length, vocab, etc.) plus the
engine-side architecture hyperparameters the reference leaves to vLLM.

Presets: Llama-3 1B/8B/70B, Mixtral-8x7B (routed experts), Gemma-2 9B, and
tiny configs for CPU tests (dense, routed, block-diffusion, Gemma-style,
latent attention).  What is supported beyond them is what `ModelConfig` can
state and `models/loader.config_from_hf` maps: the Llama/Mistral/Qwen3 dense
block, Mixtral and Qwen3-MoE routed experts, SDAR block diffusion, and the
`glm4_moe_lite` block (latent attention, a shared expert beside
sigmoid-routed experts, leading dense layers), and the `falcon_h1` block (a
Mamba-2 state-space mixer beside grouped-query attention in every layer, with
its muP multipliers), and the `nemotron_h` block (layers of three kinds by
a pattern: a Mamba-2 mixer, attention without a position term, or experts
that work in a latent space, each alone in its layer, with the chip's share
of the routed experts), and the `cohere2_moe` block (window layers beside
full ones by `layer_types`, attention and experts in parallel on one
mean-subtracting norm, the interleaved-pair rotary embedding on the window
layers alone, averaged shared experts).  No DeepSeek-R1-class preset
exists: multi-token-prediction heads and group-limited routing are not
implemented.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp


# What every builder says when a model with recurrent state is asked for what
# it has no form for (engine/engine.py refuses the planes by these names).
STATE_NO_DIFFUSION = (
    "a state-space mixer does not serve a block-diffusion model: a block's "
    "denoising forwards would each advance the recurrent state")
STATE_MESHLESS = (
    "a model with state-space layers serves meshless: its per-sequence "
    "state slots have no head-sharded (tp), slot-sharded (dp), pipeline or "
    "ring/sequence-parallel form")
# The same for a model with window layers: its second page group (pool and
# table) has one form, the meshless causal engine with bf16 pages.
WINDOW_MESHLESS = (
    "a model with window layers serves meshless: its window page group (a "
    "pool and a table of its own) has no sharded, pipeline or "
    "ring/sequence-parallel form")
WINDOW_NO_INT8 = (
    "a model with window layers has no int8 KV form: kv_quant='int8' is "
    "refused beside a window page group")
WINDOW_NO_SPECULATION = (
    "a model with window layers serves without speculative decoding: a "
    "rejected draft would have released pages behind a window that moves "
    "back")
WINDOW_NO_TRANSFER = (
    "a model with window layers has no tier offload, drain migration or "
    "disagg block transfer: host_blocks, disk_blocks, remote_fetch_fn, "
    "block export and block import are refused (an exported block says "
    "nothing of the window group's pages)")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture of a Llama-family (optionally MoE) decoder-only LM.

    All shapes are chosen TPU-first: `head_dim` a multiple of 128 where the
    real models allow it, activations in bfloat16, and sizes that tile onto
    the MXU without padding.
    """

    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    max_context: int = 8192
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    # MoE (Mixtral-style). num_experts == 0 means dense MLP.
    num_experts: int = 0
    num_experts_per_token: int = 2
    # Per-expert dispatch capacity (tokens per expert per source shard)
    # for moe_mode="dispatch".  None = exact (nothing can overflow —
    # serving default).  A bounded capacity trades exactness for a
    # smaller all-to-all buffer; overflow assignments are DROPPED and
    # counted in the stats vector's tail slot
    # (dynamo_moe_dropped_tokens_total), never silent.
    moe_capacity: Optional[int] = None
    # Tie input embedding and LM head (small models).
    tie_embeddings: bool = False
    # Gemma-family knobs (all default to the Llama conventions):
    activation: str = "silu"              # "silu" | "gelu_tanh"
    attn_soft_cap: Optional[float] = None  # attention-logit soft cap
    final_soft_cap: Optional[float] = None  # lm-head-logit soft cap
    post_norms: bool = False              # post-attn/post-mlp RMSNorms
    rms_offset: bool = False              # norm scales by (1 + w)
    embed_scale: bool = False             # embeddings x sqrt(hidden)
    query_scale: Optional[float] = None   # replaces head_dim**-0.5
    # Width of one routed expert where it differs from `intermediate_size`
    # (Qwen3-MoE family: `moe_intermediate_size`); None = intermediate_size.
    moe_intermediate_size: Optional[int] = None
    # Gates: softmax over all experts, top-k, renormalised over the chosen
    # (equal to the softmax over the chosen logits, the Mixtral
    # convention).  False (the full softmax's values, not renormalised) is
    # refused until a configuration states it: ops/moe.router_topk.
    norm_topk_prob: bool = True
    # RMSNorm over head_dim on each head of q and k before the rotary
    # embedding, one weight vector shared by all heads (Qwen3 family).
    qk_norm: bool = False
    # Generation by diffusion over blocks (SDAR family).  Block length 1
    # is the causal decoder: one token a sequence a step.  With B > 1 a
    # block of B positions is denoised in forward passes that see the
    # cache and the whole block, `B / denoising_steps` positions unmasked
    # a pass by confidence, then committed to the cache; position i sees
    # position j iff j // B <= i // B, and logits predict their own
    # position (no shift).
    diffusion_block_length: int = 1
    denoising_steps: int = 1
    remasking: str = "low_confidence_static"   # | "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: Optional[int] = None
    # Latent attention (MLA; the DeepSeek-V2 / glm4_moe_lite block).
    # `kv_lora_rank` > 0 selects it: q through a low-rank pair with a norm
    # between (`q_lora_rank`), one compressed row `[c_kv | k_rope]` a token
    # shared by all heads, heads of `qk_nope_head_dim + qk_rope_head_dim`
    # for scores and `v_head_dim` for values.  The cache holds the row, not
    # keys and values (engine/kv_cache.py), and every read is the
    # weight-absorbed form (models/llama._latent_read).
    # `head_dim` is then the score width and `num_kv_heads` == `num_heads`.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Routed-expert layers of the DeepSeek-V3 kind.  "softmax": gates are
    # the softmax over the chosen logits.  "sigmoid": scores s =
    # sigmoid(logits) in float32, the experts chosen by s + a learned
    # bias (`moe.router_bias`), weighted by s renormalised over the chosen
    # and scaled by `routed_scaling_factor`.
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Always-on experts beside the routed ones (one SwiGLU of width
    # n_shared_experts * expert_size, added to every expert layer's out).
    n_shared_experts: int = 0
    # The first k layers are dense MLPs of `intermediate_size` although
    # the model has experts.
    first_k_dense: int = 0
    # A Mamba-2 state-space mixer beside the attention in every layer, both
    # on one normed input (the `falcon_h1` block).  `mamba_d_ssm` > 0
    # selects it and is the mixer's width (`mamba_expand` is not used);
    # heads of `mamba_d_head`, a state of `mamba_d_state` a head dimension,
    # B and C shared by the heads of a group, a causal depthwise
    # convolution of `mamba_d_conv` taps over [x | B | C].  The recurrent
    # state lives in per-sequence slots beside the paged cache
    # (engine/kv_cache.py: `ssm` and `conv` leaves), is scanned in chunks
    # of `mamba_chunk_size` by a prefill chunk and stepped by a decode
    # step (ops/ssm.py).
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # The gated norm of the mixer's output: RMSNorm over each group of
    # (y * silu(z)) (`norm_before_gate` False).  Without `mamba_rms_norm`
    # the output is y * silu(z) alone.
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    # The muP multipliers of the falcon_h1 block (all 1 elsewhere), applied
    # where the published code applies them: the embedding's output, the
    # head's logits, the attention's input, key and output, the mixer's
    # input and output, the MLP's gate and down projections
    # (`mlp_multipliers`), and the five parts [z | x | B | C | dt] of the
    # mixer's input projection (`ssm_multipliers`).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    # Layers of different kinds by a pattern (the `nemotron_h` block), one
    # character a layer: "M" a Mamba-2 mixer, "*" attention, "E" routed
    # experts; each layer is `x + f(RMSNorm(x))` with that one `f`.  Empty:
    # every layer is alike (all of the above).  The cache then holds pages
    # for the "*" layers only and state slots for the "M" layers only
    # (engine/kv_cache.py), and the step builders walk the layers by kind.
    layer_pattern: str = ""
    # Attention without a position term (`nemotron_h`: the state-space
    # layers carry position): no rotary embedding on q and k.
    use_rope: bool = True
    # Experts that work in a latent space (`nemotron_h`): one map a layer
    # from the hidden size down to `moe_latent_size` in front of the routed
    # experts and one back up behind their sum; the router and the shared
    # expert see the layer's full-width input.  0: experts at full width.
    moe_latent_size: int = 0
    # Width of the shared expert where the model states it on its own
    # (`moe_shared_expert_intermediate_size`); 0: n_shared_experts *
    # expert_size.
    shared_expert_size: int = 0
    # The chip's share of an expert layer: (first, count) of the model's
    # `num_experts` whose weights are held here.  The router keeps its width
    # and its experts a token; the layer computes the part of the result its
    # own experts give (ops/moe.moe_grouped).  None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # Window layers beside full ones (the `cohere2_moe` block), a length a
    # layer: in a layer with a window W > 0 query i sees key j iff 0 <= i -
    # j < W; 0 is a full (causal) layer.  Empty: every layer is full.  The
    # window layers' pages are a group of their own (engine/kv_cache.py: a
    # pool and a table a sequence beside the full layers'), which lets go of
    # a block once it lies wholly behind the window.
    layer_windows: Tuple[int, ...] = ()
    # Which layers apply the rotary embedding (`cohere2_moe`: the window
    # layers do, the full layers have no position term).  Empty: all of
    # them, or none (`use_rope`).
    layer_rope: Tuple[bool, ...] = ()
    # The rotary embedding over interleaved pairs (x0, x1), (x2, x3) ...
    # (`rope_gptj`); False: over the two halves (NeoX/Llama).
    rope_interleaved: bool = False
    # "rms", or "layer": Cohere's norm, mean subtracted and variance over
    # the hidden size in float32, a weight and no bias.
    norm_kind: str = "rms"
    # Attention and experts side by side on the layer's one normed input:
    # x + Attn(h) + FFN(h), no second norm (`use_parallel_block`).
    parallel_block: bool = False
    # The shared experts' outputs are averaged (`n_shared_experts` SwiGLUs
    # of `expert_size`: the one wide SwiGLU's output over their number) and
    # not summed (`shared_expert_combination_strategy: average`).
    shared_experts_mean: bool = False
    # The sigmoid router chooses by s + a learned bias; False: by s alone
    # (a model whose config.json has no such bias).
    router_score_bias: bool = True

    def window_of(self, i: int) -> Optional[int]:
        """Layer i's window, None for a full layer."""
        return (self.layer_windows[i] or None) if self.layer_windows else None

    def rope_of(self, i: int) -> bool:
        return self.layer_rope[i] if self.layer_rope else self.use_rope

    @property
    def has_window(self) -> bool:
        return any(self.layer_windows)

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """Layers whose pages are the window group's."""
        return tuple(i for i in self.attention_layers if self.window_of(i))

    @property
    def max_window(self) -> int:
        return max(self.layer_windows, default=0)

    @property
    def has_ssm(self) -> bool:
        """Does the model carry state-space mixers (and so a slot of
        recurrent state a sequence): in every layer beside its attention,
        or in the pattern's "M" layers."""
        return self.mamba_d_ssm > 0

    @property
    def has_pattern(self) -> bool:
        return bool(self.layer_pattern)

    def layer_kind(self, i: int) -> str:
        """"M", "*" or "E" under a pattern; "" where every layer is alike."""
        return self.layer_pattern[i] if self.layer_pattern else ""

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        """Layers that write pages of K and V (all without a pattern)."""
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) in ("", "*"))

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """Layers that keep a slot of recurrent state a sequence."""
        if not self.has_ssm:
            return ()
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) in ("", "M"))

    @property
    def experts_local(self) -> Tuple[int, int]:
        """(first, count) of the experts whose weights this chip holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def shared_size(self) -> int:
        """Width of the always-on expert (0: none)."""
        return self.shared_expert_size \
            or self.n_shared_experts * self.expert_size

    @property
    def gated_mlp(self) -> bool:
        """SwiGLU-style (gate, up, down); False: up, activation, down."""
        return self.activation != "relu2"

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the mixer's convolution runs over: [x | B | C]."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mamba_proj_size(self) -> int:
        """Width of the mixer's input projection: [z | x | B | C | dt]."""
        return self.mamba_d_ssm + self.mamba_conv_dim + self.mamba_n_heads

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values of one token's latent row: c_kv then k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Width the row is stored at: `latent_dim` rounded up to the 128
        lanes the kernels' DMA tiles by; the padding is zeros."""
        return -(-self.latent_dim // 128) * 128

    @property
    def kv_feature_dim(self) -> int:
        """Width of one layer's cache row (K, or the latent row)."""
        return self.latent_row if self.is_latent else self.kv_size

    @property
    def attn_out_size(self) -> int:
        """Width of the concatenated heads that `wo` takes."""
        return self.num_heads * (self.v_head_dim if self.is_latent
                                 else self.head_dim)

    @property
    def uses_multipliers(self) -> bool:
        return any(m != 1.0 for m in (
            self.embedding_multiplier, self.lm_head_multiplier,
            self.attention_in_multiplier, self.attention_out_multiplier,
            self.key_multiplier, self.ssm_in_multiplier,
            self.ssm_out_multiplier, *self.mlp_multipliers,
            *self.ssm_multipliers))

    def layer_is_moe(self, i: int) -> bool:
        if self.has_pattern:
            return self.layer_pattern[i] == "E"
        return self.is_moe and i >= self.first_k_dense

    @property
    def num_moe_layers(self) -> int:
        if self.has_pattern:
            return self.layer_pattern.count("E")
        return max(0, self.num_layers - self.first_k_dense) \
            if self.is_moe else 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_diffusion(self) -> bool:
        return self.diffusion_block_length > 1

    @property
    def expert_size(self) -> int:
        """Intermediate width of one routed expert."""
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def unmask_per_step(self) -> int:
        """Positions a denoising forward decides under the static rule."""
        return max(1, self.diffusion_block_length
                   // max(1, self.denoising_steps))

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def validate(self) -> None:
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads (GQA)")
        if self.is_moe and self.num_experts_per_token > self.num_experts:
            raise ValueError("num_experts_per_token > num_experts")
        if self.moe_capacity is not None and self.moe_capacity <= 0:
            raise ValueError("moe_capacity must be positive (None = exact)")
        if self.activation not in ("silu", "gelu_tanh", "relu2"):
            raise ValueError(f"unknown activation {self.activation!r}")
        self._validate_pattern()
        self._validate_window()
        if self.is_moe and not self.norm_topk_prob:
            raise ValueError("norm_topk_prob=False (gates not renormalised "
                             "over the chosen experts) is not implemented")
        if self.diffusion_block_length < 1 or self.denoising_steps < 1:
            raise ValueError("diffusion_block_length and denoising_steps "
                             "must be positive")
        if self.is_diffusion:
            if self.remasking not in ("low_confidence_static",
                                      "low_confidence_dynamic"):
                raise ValueError(f"unknown remasking {self.remasking!r}")
            if self.mask_token_id is None or not (
                    0 <= self.mask_token_id < self.vocab_size):
                raise ValueError("a block-diffusion model needs a "
                                 "mask_token_id inside the vocabulary")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_scoring {self.router_scoring!r}")
        if self.first_k_dense and not (
                self.is_moe and self.first_k_dense < self.num_layers):
            raise ValueError("first_k_dense needs a model with experts and "
                             "at least one expert layer behind the dense ones")
        if (self.n_shared_experts or self.router_scoring != "softmax"
                or self.routed_scaling_factor != 1.0) and not self.is_moe:
            raise ValueError("shared experts, sigmoid routing and a routed "
                             "scaling factor need a model with experts")
        if self.is_latent:
            if min(self.q_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) <= 0:
                raise ValueError(
                    "latent attention needs q_lora_rank, qk_nope_head_dim, "
                    "qk_rope_head_dim and v_head_dim (a full-rank q "
                    "projection is not implemented)")
            if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError("latent attention: head_dim must be "
                                 "qk_nope_head_dim + qk_rope_head_dim")
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even")
            if self.num_kv_heads != self.num_heads:
                raise ValueError("latent attention has one latent row for "
                                 "all heads: num_kv_heads == num_heads")
            # What the latent cache cannot do: refused here, not by an
            # engine failing later.
            if self.is_diffusion:
                raise ValueError("latent attention (MLA) does not serve a "
                                 "block-diffusion model: the latent kernels "
                                 "have no block mask")
            if self.qk_norm or self.attn_soft_cap is not None \
                    or self.post_norms:
                raise ValueError("latent attention (MLA) composes with "
                                 "neither q/k head norms, an attention "
                                 "soft cap nor post-norms")
        if self.has_ssm:
            if min(self.mamba_n_heads, self.mamba_d_head,
                   self.mamba_d_state, self.mamba_n_groups) <= 0:
                raise ValueError("a state-space mixer needs mamba_n_heads, "
                                 "mamba_d_head, mamba_d_state and "
                                 "mamba_n_groups")
            if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
                raise ValueError("state-space mixer: mamba_n_heads * "
                                 "mamba_d_head must be mamba_d_ssm")
            if self.mamba_n_heads % self.mamba_n_groups \
                    or self.mamba_d_ssm % self.mamba_n_groups:
                raise ValueError("state-space mixer: mamba_n_groups must "
                                 "divide mamba_n_heads and mamba_d_ssm")
            if self.mamba_d_conv < 2 or self.mamba_chunk_size < 1:
                raise ValueError("state-space mixer: mamba_d_conv >= 2 and "
                                 "mamba_chunk_size >= 1")
            if self.mamba_proj_bias:
                raise ValueError("state-space mixer: mamba_proj_bias is "
                                 "not implemented")
            if self.mamba_norm_before_gate:
                raise ValueError("state-space mixer: mamba_norm_before_gate "
                                 "(the norm ahead of the gate) is not "
                                 "implemented")
            if len(self.ssm_multipliers) != 5 \
                    or len(self.mlp_multipliers) != 2:
                raise ValueError("ssm_multipliers has five values ([z | x | "
                                 "B | C | dt]) and mlp_multipliers two "
                                 "(gate, down)")
            # What recurrent state cannot do: refused here by name.
            if self.is_diffusion:
                raise ValueError(STATE_NO_DIFFUSION)
            if self.is_latent or self.post_norms or self.qk_norm \
                    or (self.is_moe and not self.has_pattern):
                raise ValueError(
                    "a state-space mixer composes with neither latent "
                    "attention, post-norms nor q/k head norms, and with "
                    "routed experts only by a layer pattern: no mapped "
                    "model has them together")
        elif self.uses_multipliers:
            raise ValueError("the muP multipliers are the falcon_h1 "
                             "block's: a model without a state-space mixer "
                             "states none")

    def _validate_pattern(self) -> None:
        """What a layer pattern, latent experts, a share of the experts and
        the ungated activation need, and what they have no form for."""
        if self.has_pattern:
            if len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} names "
                    f"{len(self.layer_pattern)} layers, num_layers is "
                    f"{self.num_layers}")
            if "-" in self.layer_pattern:
                raise ValueError(
                    "layer_pattern: '-' (a layer that is a plain MLP alone) "
                    "is not implemented: 'M' (Mamba-2 mixer), '*' "
                    "(attention) and 'E' (routed experts) are")
            unknown = sorted(set(self.layer_pattern) - set("M*E"))
            if unknown:
                raise ValueError(
                    f"layer_pattern: unknown layer kind(s) {unknown}; 'M' "
                    "(Mamba-2 mixer), '*' (attention) and 'E' (routed "
                    "experts) are implemented")
            if "*" not in self.layer_pattern:
                raise ValueError("layer_pattern: a model without an "
                                 "attention layer has no paged cache to "
                                 "serve from")
            if ("M" in self.layer_pattern) != self.has_ssm:
                raise ValueError("layer_pattern: 'M' layers need the "
                                 "mixer's sizes (mamba_d_ssm ...), and the "
                                 "sizes need an 'M' layer")
            if ("E" in self.layer_pattern) != self.is_moe:
                raise ValueError("layer_pattern: 'E' layers need "
                                 "num_experts, and experts need an 'E' "
                                 "layer")
            if self.is_latent or self.is_diffusion or self.first_k_dense \
                    or self.tie_embeddings or self.embed_scale \
                    or self.rms_offset or self.uses_multipliers:
                raise ValueError(
                    "a layer pattern composes with neither latent "
                    "attention, block diffusion, leading dense layers, tied "
                    "embeddings, the Gemma conventions nor the muP "
                    "multipliers")
        if not self.gated_mlp and not self.has_pattern:
            raise ValueError("activation 'relu2' (an ungated MLP) is the "
                             "pattern block's: a model whose layers are all "
                             "alike has a gated MLP")
        if self.moe_latent_size < 0 or (
                self.moe_latent_size and not self.is_moe):
            raise ValueError("moe_latent_size needs a model with experts")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not self.is_moe or count < 1 or first < 0 \
                    or first + count > self.num_experts:
                raise ValueError(
                    f"experts_held {self.experts_held!r} is no range of the "
                    f"model's {self.num_experts} experts")

    def _validate_window(self) -> None:
        """What window layers, the parallel block, the mean-subtracting
        norm and the averaged shared experts need, and what they have no
        form for: refused here by name."""
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"unknown norm_kind {self.norm_kind!r}")
        for name in ("layer_windows", "layer_rope"):
            per = getattr(self, name)
            if per and len(per) != self.num_layers:
                raise ValueError(
                    f"{name} names {len(per)} layers, num_layers is "
                    f"{self.num_layers}")
        if any(w < 0 for w in self.layer_windows):
            raise ValueError("layer_windows: a window is a positive length, "
                             "0 a full layer")
        if self.has_window:
            if self.is_latent:
                raise ValueError("latent attention (MLA) under a window is "
                                 "not implemented: the latent kernels have "
                                 "no window")
            if self.is_diffusion:
                raise ValueError("block diffusion under a window is not "
                                 "implemented: a block's mask and a window "
                                 "have no common kernel")
            if self.has_ssm or self.has_pattern:
                raise ValueError("window layers beside state-space layers "
                                 "or under a layer pattern are not "
                                 "implemented: no mapped model has them "
                                 "together")
            if len(set(w for w in self.layer_windows if w)) != 1:
                raise ValueError("window layers of different lengths are "
                                 "not implemented: one window group has one "
                                 "length")
            if len(self.window_layers) == self.num_layers:
                raise ValueError("a model whose layers all have a window "
                                 "is not implemented: the full layers' "
                                 "pages carry admission")
        if self.parallel_block and not (
                self.is_moe and not self.first_k_dense
                and not self.has_pattern and not self.has_ssm
                and not self.post_norms):
            raise ValueError("the parallel block (attention and experts on "
                             "one norm) is implemented for a model whose "
                             "every layer has experts, without a pattern, "
                             "state-space layers or post-norms")
        if self.shared_experts_mean and not self.n_shared_experts:
            raise ValueError("shared_experts_mean needs n_shared_experts")
        if (self.layer_rope or self.rope_interleaved) and self.is_latent:
            raise ValueError("per-layer and interleaved rotary embeddings "
                             "are not implemented for latent attention")

    def param_count(self) -> int:
        """Approximate parameter count (for memory planning / bench labels).
        Under a pattern, of what is held here."""
        h, v = self.hidden_size, self.vocab_size
        if self.has_pattern:
            return self._pattern_param_count()
        if self.is_latent:
            attn = (h * self.q_lora_rank + self.q_lora_rank * self.q_size
                    + h * self.latent_dim + self.kv_lora_rank * self.num_heads
                    * (self.qk_nope_head_dim + self.v_head_dim)
                    + self.attn_out_size * h
                    + self.q_lora_rank + self.kv_lora_rank)
        else:
            attn = h * self.q_size + 2 * h * self.kv_size + self.q_size * h
        dense = 3 * h * self.intermediate_size
        moe = (self.experts_local[1] * 3 * h * self.expert_size
               + h * self.num_experts
               + 3 * h * self.n_shared_experts * self.expert_size
               + (self.num_experts if self.router_scoring == "sigmoid"
                  and self.router_score_bias else 0))
        n_moe = self.num_moe_layers
        per_layer = attn + (h if self.parallel_block else 2 * h)
        if self.has_ssm:
            per_layer += self._mixer_param_count()
        if self.qk_norm:
            per_layer += 2 * self.head_dim
        emb = v * h * (1 if self.tie_embeddings else 2)
        return (self.num_layers * per_layer + n_moe * moe
                + (self.num_layers - n_moe) * dense + emb + h)


    def _mixer_param_count(self) -> int:
        h = self.hidden_size
        return (h * self.mamba_proj_size + self.mamba_d_ssm * h
                + self.mamba_conv_dim * self.mamba_d_conv
                + (self.mamba_conv_dim if self.mamba_conv_bias else 0)
                + 3 * self.mamba_n_heads
                + (self.mamba_d_ssm if self.mamba_rms_norm else 0))

    def _pattern_param_count(self) -> int:
        h = self.hidden_size
        mixer = self._mixer_param_count()
        attn = h * self.q_size + 2 * h * self.kv_size + self.q_size * h
        lat = self.moe_latent_size or h
        mats = 3 if self.gated_mlp else 2
        moe = (h * self.num_experts + self.num_experts
               + (2 * h * lat if self.moe_latent_size else 0)
               + mats * h * self.shared_size
               + self.experts_local[1] * mats * lat * self.expert_size)
        per = {"M": mixer, "*": attn, "E": moe}
        return (sum(per[k] + h for k in self.layer_pattern)
                + 2 * self.vocab_size * h + h)


# Tiny configs for CPU tests: small enough to run a full correctness check
# on the 8-device virtual mesh in milliseconds, but with GQA + enough heads
# to exercise every sharding axis.
TINY = ModelConfig(
    name="tiny-test",
    vocab_size=256,
    hidden_size=64,
    num_layers=2,
    num_heads=8,
    num_kv_heads=4,
    head_dim=16,
    intermediate_size=128,
    max_context=512,
    rope_theta=10_000.0,
    dtype=jnp.float32,
    tie_embeddings=True,
)

TINY_MOE = TINY.replace(name="tiny-moe", num_experts=8, num_experts_per_token=2)

LLAMA3_1B = ModelConfig(
    name="llama-3-1b",
    vocab_size=128_256,
    hidden_size=2048,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    intermediate_size=8192,
    max_context=8192,
    tie_embeddings=True,
)

LLAMA3_8B = ModelConfig(
    name="llama-3-8b",
    vocab_size=128_256,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14_336,
    max_context=8192,
)

LLAMA3_70B = ModelConfig(
    name="llama-3-70b",
    vocab_size=128_256,
    hidden_size=8192,
    num_layers=80,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=28_672,
    max_context=8192,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32_000,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14_336,
    max_context=32_768,
    rope_theta=1_000_000.0,
    num_experts=8,
    num_experts_per_token=2,
)

# Block-diffusion decoder over routed experts at test size: q/k head norms,
# experts narrower than `intermediate_size`, blocks of 4 denoised in 4 passes.
TINY_SDAR = TINY.replace(
    name="tiny-sdar", tie_embeddings=False, num_experts=8,
    num_experts_per_token=2, moe_intermediate_size=32, qk_norm=True,
    diffusion_block_length=4, denoising_steps=4, mask_token_id=255)

# Latent attention, a shared expert beside sigmoid-routed experts behind one
# dense layer (the glm4_moe_lite block) at test size.
TINY_MLA = TINY.replace(
    name="tiny-mla", tie_embeddings=False, num_layers=3, num_kv_heads=8,
    head_dim=24, q_lora_rank=32, kv_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
    num_experts_per_token=2, moe_intermediate_size=32,
    router_scoring="sigmoid", routed_scaling_factor=1.8, n_shared_experts=1,
    first_k_dense=1)

# A Mamba-2 mixer beside grouped-query attention in every layer (the
# falcon_h1 block) at test size, every multiplier off 1.
TINY_H1 = TINY.replace(
    name="tiny-h1", tie_embeddings=False, num_layers=2,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    embedding_multiplier=1.7, lm_head_multiplier=0.3,
    attention_in_multiplier=0.9, attention_out_multiplier=0.6,
    key_multiplier=0.5, ssm_in_multiplier=0.8, ssm_out_multiplier=0.7,
    mlp_multipliers=(0.9, 0.8), ssm_multipliers=(0.9, 0.7, 0.8, 1.1, 0.6))

# Layers of three kinds by a pattern (the nemotron_h block) at test size: a
# Mamba-2 mixer, attention without a position term, experts in a latent
# space behind a sigmoid router with an ungated shared expert; this "chip"
# holds the second quarter of 16 experts.
TINY_PATTERN = TINY.replace(
    name="tiny-pattern", tie_embeddings=False, num_layers=5,
    layer_pattern="ME*ME", use_rope=False, activation="relu2",
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    num_experts=16, num_experts_per_token=6, moe_intermediate_size=48,
    moe_latent_size=32, shared_expert_size=96, n_shared_experts=1,
    router_scoring="sigmoid", routed_scaling_factor=2.5,
    experts_held=(4, 4))

# Window layers beside a full one (the cohere2_moe block) at test size:
# attention and experts in parallel on one mean-subtracting norm, the
# interleaved-pair rotary embedding on the window layers alone, a sigmoid
# router without a bias, two averaged shared experts; this "chip" holds the
# second quarter of 16 experts.
TINY_WINDOW = TINY.replace(
    name="tiny-window", num_layers=4, layer_windows=(24, 24, 24, 0),
    layer_rope=(True, True, True, False), rope_interleaved=True,
    norm_kind="layer", parallel_block=True, num_experts=16,
    num_experts_per_token=4, intermediate_size=32,
    router_scoring="sigmoid", router_score_bias=False, n_shared_experts=2,
    shared_experts_mean=True, experts_held=(4, 4))

TINY_GEMMA = TINY.replace(
    name="tiny-gemma",
    activation="gelu_tanh",
    attn_soft_cap=50.0,
    final_soft_cap=30.0,
    post_norms=True,
    rms_offset=True,
    embed_scale=True,
    query_scale=16.0 ** -0.5,
)

GEMMA2_9B = ModelConfig(
    name="gemma-2-9b",
    vocab_size=256_000,
    hidden_size=3584,
    num_layers=42,
    num_heads=16,
    num_kv_heads=8,
    head_dim=256,
    intermediate_size=14_336,
    # Gemma-2 alternates sliding-window (4096) and global layers; this
    # preset runs every layer global, which is EXACT while context stays
    # within the window — max_context is clamped accordingly (window
    # layers themselves are `layer_windows`, mapped for `cohere2_moe`).
    max_context=4096,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_embeddings=True,
    activation="gelu_tanh",
    attn_soft_cap=50.0,
    final_soft_cap=30.0,
    post_norms=True,
    rms_offset=True,
    embed_scale=True,
    query_scale=224.0 ** -0.5,
)

PRESETS = {
    c.name: c
    for c in (TINY, TINY_MOE, TINY_SDAR, TINY_MLA, TINY_H1, TINY_PATTERN,
              TINY_WINDOW, TINY_GEMMA, LLAMA3_1B,
              LLAMA3_8B, LLAMA3_70B, MIXTRAL_8X7B, GEMMA2_9B)
}


def get_config(name: str) -> ModelConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}") from None
