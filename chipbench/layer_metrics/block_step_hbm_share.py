"""Bytes the block programs' forwards of the capture had to read over what the chip's HBM could deliver in the device time they took, in percent of the published peak bandwidth.

A forward of this block reads, in every layer, the attention projections and the router once (2 x H x heads x D + 2 x H x kv_heads x D + H x E parameters: 38 MB at the published widths), the three matrices of every expert touched, and the KV the engine's own model says attention swept; then the output head once (H x vocab: the denoising forwards score all B positions, the commit one row, both stream the whole head).  Experts touched by block forwards and the modeled KV bytes are the worker's tallies over the capture's scrapes, scaled to the block program calls the trace really holds."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

BYTES_PER_PARAM = 2


def dense_bytes_per_forward(hf: dict) -> int:
    """Weights every forward streams whatever the routing: attention and
    router of each layer, norms, the output head."""
    h = hf['hidden_size']
    d = hf['head_dim']
    attn = 2 * h * hf['num_attention_heads'] * d \
        + 2 * h * hf['num_key_value_heads'] * d
    layer = attn + h * hf['num_experts'] + 2 * h + 2 * d
    return (hf['num_hidden_layers'] * layer + h + h * hf['vocab_size']) \
        * BYTES_PER_PARAM


def expert_bytes(hf: dict) -> int:
    return 3 * hf['hidden_size'] * hf['moe_intermediate_size'] \
        * BYTES_PER_PARAM


def read(ctx):
    held = block_readers.trace_forwards(ctx)
    fw = block_readers.forwards(ctx, 'capture')
    touched = block_readers.tally(ctx, 'diffusion_experts_touched', 'capture')
    kv = ctx.delta('worker', 'dynamo_worker_engine_kv_read_bytes_modeled',
                   'capture')
    if held is None or fw is None or touched is None or kv is None \
            or not ctx.peaks:
        return None
    role = ctx.trace['roles']['decode']
    if role['seconds'] <= 0:
        return None
    forwards, calls, _prefills = held
    need = forwards * dense_bytes_per_forward(ctx.config) \
        + (touched * expert_bytes(ctx.config) + kv) * calls / fw[1]
    return 100.0 * need / (role['seconds'] * ctx.peaks['hbm_bytes_per_s'])
