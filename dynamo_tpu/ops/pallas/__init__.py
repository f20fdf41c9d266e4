"""Pallas TPU kernels for the serving hot path.

The reference's one mandated CUDA kernel is the KV block scatter/gather
(`lib/llm/src/kernels/block_copy.cu:41`); on TPU the block copies compile
to XLA dynamic slices (engine/kv_cache.py:make_block_ops) and the kernel
budget goes where it pays: paged-attention decode, which would otherwise
materialise a full gathered context per step.
"""

from dynamo_tpu.ops.pallas.latent_attention import (
    latent_decode_attention,
    latent_geometry_ok,
    latent_prefill_attention,
)
from dynamo_tpu.ops.pallas.moe_grouped import (
    dequantize_moe_params,
    grouped_expert_ffn,
    moe_grouped_geometry_ok,
    moe_params_quantized,
    quantize_moe_params,
)
from dynamo_tpu.ops.pallas.paged_attention import (
    mosaic_geometry_ok,
    paged_block_attention,
    paged_decode_attention,
    paged_window_decode_attention,
)
from dynamo_tpu.ops.pallas.paged_prefill import (
    PACK_ALIGN,
    paged_prefill_attention,
    paged_window_prefill_attention,
)
from dynamo_tpu.ops.pallas.ring_attention import (
    ring_flash_attention,
    ring_geometry_ok,
    ring_kernel_supported,
)

__all__ = ["paged_decode_attention", "paged_block_attention",
           "paged_prefill_attention", "paged_window_decode_attention",
           "paged_window_prefill_attention", "latent_decode_attention",
           "latent_prefill_attention", "latent_geometry_ok",
           "mosaic_geometry_ok", "PACK_ALIGN",
           "grouped_expert_ffn", "moe_grouped_geometry_ok",
           "quantize_moe_params", "dequantize_moe_params",
           "moe_params_quantized", "ring_flash_attention",
           "ring_geometry_ok", "ring_kernel_supported"]
