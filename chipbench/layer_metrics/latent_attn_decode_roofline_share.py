"""The latent decode attention kernel's share of its roofline: the least time the chip could take for the context the decode steps of the capture swept -- the larger of its bytes over the HBM peak and its operations over the bf16 peak -- over the kernel's device time.

Bytes: every context token's row once a layer at its stored width (the engine's kv_read_bytes_modeled; 1,280 B a token a layer at 640 values).  Operations: latent_block.pair_operations a context token a layer (one query a row: 2 x 20 x 1088).  Bytes bind (1.56 ns against 0.22 ns a token-layer on a v5e).  The tally is over the capture's scrapes, scaled to the decode steps the trace really holds."""

from chipbench import latent_block, readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    held = latent_block.decode_in_trace(ctx)
    seconds = (ctx.trace or {}).get('kernels_s', {}).get('attn_decode')
    kv = readers._engine(ctx, 'kv_read_bytes_modeled', 'capture')
    if held is None or not seconds or kv is None or not ctx.peaks:
        return None
    hf = ctx.config
    need_bytes = held[1] * kv
    need_ops = need_bytes / latent_block.row_bytes(hf) \
        * latent_block.pair_operations(hf)
    least = max(need_bytes / ctx.peaks['hbm_bytes_per_s'],
                need_ops / ctx.peaks['bf16_flops_per_s'])
    return 100.0 * least / seconds
