"""Bytes the decode steps of the capture had to read over what the chip's HBM could deliver in the device time they took, in percent of the published peak: the whole decode step of the latent-attention block over routed experts.

A step reads the weights every token uses once (every layer's attention, the leading dense MLP, every expert layer's router and shared expert, the head: latent_block.dense_bytes_per_step), the three matrices of every distinct routed expert its rows touched (the worker's decode-only tally, padding rows included: the kernel streams them), and the latent rows the engine's own model says attention swept (kv_read_bytes_modeled, rows at their stored width).  Tallies are over the capture's scrapes, scaled to the decode steps the trace really holds."""

from chipbench import block_readers, latent_block, readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    held = latent_block.decode_in_trace(ctx)
    touched = block_readers.tally(ctx, 'moe_decode_experts_touched', 'capture')
    kv = readers._engine(ctx, 'kv_read_bytes_modeled', 'capture')
    if held is None or touched is None or kv is None or not ctx.peaks:
        return None
    role, scale = held
    hf = ctx.config
    need = role['steps'] * latent_block.dense_bytes_per_step(hf) \
        + scale * (touched * latent_block.expert_bytes(hf) + kv)
    return 100.0 * need / (role['seconds'] * ctx.peaks['hbm_bytes_per_s'])
