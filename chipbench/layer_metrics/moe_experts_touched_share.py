"""Percent of a layer's experts that get at least one row in a forward: distinct experts touched (summed over layers and forwards, prefill chunks included) over expert layers run x the experts a layer has."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return block_readers.ratio(ctx, 'moe_experts_touched', 'moe_layer_forwards',
                               100.0 / ctx.config['num_experts'])
