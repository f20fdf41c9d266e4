"""Bytes and operations a configuration's programs need, computed from its
published sizes (the keys of a Hugging Face `config.json`).  Kept with the
benchmark so that no PR that claims a gain can change the yardstick."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "add it with its source, do not guess")
    return table[device_kind]


# Keys of a block whose bytes the dense count below would get wrong: routed
# experts (a step streams the experts its tokens chose, not one MLP) and a
# latent cache (the attention projections are other matrices).  Such a
# configuration brings its own bytes and operations in the file of the layer
# metric that reads them.
NOT_COUNTED = ("num_experts", "num_local_experts", "n_routed_experts",
               "kv_lora_rank")


def _dims(hf: dict):
    for key in NOT_COUNTED:
        if hf.get(key):
            raise ValueError(
                f"the configuration states {key} = {hf[key]!r}: model_bytes "
                "counts the dense GQA + SwiGLU block only; count this block "
                "in the layer metric's own file, do not guess")
    h = hf["hidden_size"]
    heads = hf["num_attention_heads"]
    d = hf.get("head_dim") or h // heads
    kv = hf.get("num_key_value_heads", heads)
    return h, heads, d, kv, hf["intermediate_size"], hf["num_hidden_layers"]


def weight_params(hf: dict) -> int:
    """Parameters one decode step reads: every layer and the output head
    (the input embedding is a gather of a few rows, not a sweep)."""
    h, heads, d, kv, f, layers = _dims(hf)
    attn = h * heads * d * 2 + h * kv * d * 2
    return layers * (attn + 3 * h * f + 2 * h) + h + h * hf["vocab_size"]


def weight_bytes_per_step(hf: dict, bytes_per_param: int = 2) -> int:
    """HBM bytes of weights one decode step of a dense model streams."""
    return weight_params(hf) * bytes_per_param
