"""Bytes and operations of the latent-attention block with a shared expert
beside routed experts behind leading dense layers, as functions of the
configuration's published keys, and the two scalings the readers of its layer
metrics share.  Kept with the benchmark: a share of a roofline is only as
good as the count it divides by, and no PR that claims a gain may move it.

The block (references/ holds its forward): per layer the attention's five
matrices, `q_a` H x q_lora_rank, `q_b` q_lora_rank x heads x (nope + rope),
`kv_a` H x (kv_lora_rank + rope), `kv_b` kv_lora_rank x heads x (nope + v),
`o` heads x v x H; the first `first_k_dense_replace` layers a SwiGLU MLP of
`intermediate_size`, the others a router H x E, a shared expert and E routed
experts, each a SwiGLU of `moe_intermediate_size`.  The cache holds one row
a token a layer: kv_lora_rank + rope values, stored at the next multiple of
128 lanes.  Attention reads it in the weight-absorbed form: a (query,
context) pair costs, a head a layer, a dot product over the row's
kv_lora_rank + rope values and a weighted sum over its kv_lora_rank."""

from __future__ import annotations

from chipbench import readers

BYTES_PER_PARAM = 2     # bf16 weights, activations and cache rows
LANES = 128


def attn_params(hf: dict) -> int:
    """Parameters of one layer's attention (its two inner norms included)."""
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    qr, r = hf["q_lora_rank"], hf["kv_lora_rank"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    return (h * qr + qr * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h + qr + r)


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert (the shared one is
    `n_shared_experts` of them wide)."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def moe_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * BYTES_PER_PARAM


def dense_bytes_per_step(hf: dict) -> int:
    """Weights a decode step streams whatever the routing: every layer's
    attention and norms, the leading dense MLPs, every expert layer's router
    (its float32 bias too) and shared expert, the final norm, the head."""
    h, layers, n_moe = hf["hidden_size"], hf["num_hidden_layers"], moe_layers(hf)
    params = (layers * (attn_params(hf) + 2 * h)
              + (layers - n_moe) * 3 * h * hf["intermediate_size"]
              + n_moe * (h * hf["n_routed_experts"]
                         + hf.get("n_shared_experts", 0) * expert_params(hf))
              + h + h * hf["vocab_size"])
    return params * BYTES_PER_PARAM + n_moe * hf["n_routed_experts"] * 4


def row_values(hf: dict) -> int:
    """Values of one token's cache row as stored: kv_lora_rank + rope,
    rounded up to the lanes."""
    n = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    return -(-n // LANES) * LANES


def row_bytes(hf: dict) -> int:
    """Bytes of one token's row in one layer, padding included."""
    return row_values(hf) * BYTES_PER_PARAM


def pair_operations(hf: dict) -> int:
    """Operations of one (query, context) pair in one layer, all heads: the
    score over the row's kv_lora_rank + rope values and the weighted sum over
    its kv_lora_rank (padding computes zeros and is not counted)."""
    r, dr = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    return 2 * hf["num_attention_heads"] * ((r + dr) + r)


def token_matmul_operations(hf: dict) -> int:
    """Operations of one token through every layer's matrices: attention
    (kv_b in its absorbed halves costs what it would materialised, a token),
    the leading dense MLPs, the router, the shared expert and the
    `num_experts_per_tok` routed experts of every expert layer.  The head is
    not here: prefill runs it on one row a segment."""
    h, layers, n_moe = hf["hidden_size"], hf["num_hidden_layers"], moe_layers(hf)
    macs = (layers * (attn_params(hf) - hf["q_lora_rank"]
                      - hf["kv_lora_rank"])
            + (layers - n_moe) * 3 * h * hf["intermediate_size"]
            + n_moe * (h * hf["n_routed_experts"]
                       + (hf.get("n_shared_experts", 0)
                          + hf["num_experts_per_tok"]) * expert_params(hf)))
    return 2 * macs


def decode_in_trace(ctx):
    """(the trace's decode role, trace decode steps over the decode steps
    the counters saw between the capture's scrapes) or None: the counters'
    edges and the capture's are not the same instants, so what is read off
    counters is scaled to the steps the trace really holds."""
    role = readers._role(ctx, "decode")
    counted = readers._decode_steps(ctx, "capture")
    if role is None or not counted:
        return None
    return role, role["steps"] / counted


def prefill_in_trace(ctx):
    """(the trace's prefill role, trace prefill calls over the prefill calls
    the counters saw between the capture's scrapes) or None."""
    role = readers._role(ctx, "prefill")
    counted = readers._engine(ctx, "prefill_dispatches", "capture")
    if role is None or not counted:
        return None
    return role, role["calls"] / counted
