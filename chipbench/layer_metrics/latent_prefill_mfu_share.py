"""Operations the prefill calls of the capture had to do over what the chip could do in bf16 in the device time they took, in percent of the published peak: the whole prefill chunk of the latent-attention block over routed experts.

A chunk's tokens each pass every layer's matrices (latent_block.token_matmul_operations: 1.136 GFLOP a token at the published widths, 4 routed experts and the shared one a token), and its causal (query, context) token pairs each cost a score and a weighted sum a head a layer in the absorbed form (latent_block.pair_operations x layers).  Tokens and pairs are the worker's tallies (prefill_tokens_dispatched, prefill_attn_pairs, reckoned on the host from the chunks' lengths) over the capture's scrapes, scaled to the prefill calls the trace really holds.  Padding rows and the one head row a segment are not counted."""

from chipbench import block_readers, latent_block, readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    held = latent_block.prefill_in_trace(ctx)
    tokens = readers._engine(ctx, 'prefill_tokens_dispatched', 'capture')
    pairs = block_readers.tally(ctx, 'prefill_attn_pairs', 'capture')
    if held is None or tokens is None or pairs is None or not ctx.peaks:
        return None
    role, scale = held
    hf = ctx.config
    need = scale * (tokens * latent_block.token_matmul_operations(hf)
                    + pairs * latent_block.pair_operations(hf)
                    * hf['num_hidden_layers'])
    return 100.0 * need / (role['seconds'] * ctx.peaks['bf16_flops_per_s'])
