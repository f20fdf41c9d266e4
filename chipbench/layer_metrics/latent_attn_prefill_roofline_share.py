"""The latent prefill attention kernel's share of its roofline: the least time the chip could take for the causal (query, context) pairs of the capture's prefill chunks -- the larger of their operations over the bf16 peak and their bytes over the HBM peak -- over the kernel's device time.

Operations: latent_block.pair_operations a pair a layer (2 x 20 x 1088).  Bytes, a lower bound: a chunk holds at most `max_prefill_chunk` (512) queries, so a context row is read at least once for every 512 pairs it is in, and every query row comes in and its output goes out once.  Operations bind (113 ns against 1.6 ns a context token a chunk a layer on a v5e).  Pairs and tokens are the worker's tallies over the capture's scrapes, scaled to the prefill calls the trace really holds."""

from chipbench import block_readers, latent_block, readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

CHUNK = 512     # the worker's default max_prefill_chunk, which the cell runs


def read(ctx):
    held = latent_block.prefill_in_trace(ctx)
    seconds = (ctx.trace or {}).get('kernels_s', {}).get('attn_prefill')
    tokens = readers._engine(ctx, 'prefill_tokens_dispatched', 'capture')
    pairs = block_readers.tally(ctx, 'prefill_attn_pairs', 'capture')
    if held is None or not seconds or tokens is None or pairs is None \
            or not ctx.peaks:
        return None
    hf = ctx.config
    layers, scale = hf['num_hidden_layers'], held[1]
    need_ops = scale * pairs * latent_block.pair_operations(hf) * layers
    query_io = hf['num_attention_heads'] * latent_block.BYTES_PER_PARAM * (
        latent_block.row_values(hf) + hf['kv_lora_rank'])
    need_bytes = scale * layers * (
        pairs / CHUNK * latent_block.row_bytes(hf) + tokens * query_io)
    least = max(need_ops / ctx.peaks['bf16_flops_per_s'],
                need_bytes / ctx.peaks['hbm_bytes_per_s'])
    return 100.0 * least / seconds
