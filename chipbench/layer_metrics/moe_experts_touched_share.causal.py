"""Percent of a layer's routed experts that get at least one row in a forward of a causal engine: distinct experts touched (summed over expert layers and forwards: prefill chunks, decode window steps and single steps) over expert layers run x the routed experts a layer has (`n_routed_experts`, as this family's config.json spells it)."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return block_readers.ratio(ctx, 'moe_experts_touched', 'moe_layer_forwards',
                               100.0 / ctx.config['n_routed_experts'])
