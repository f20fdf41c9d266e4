"""Held experts that got at least one row, of the experts held here, an expert-layer forward of the window (decode steps and prefill chunks alike): dynamo_worker_moe_experts_touched_total over dynamo_worker_moe_layer_forwards_total x routed_experts_held.count. Read only from a program that holds a share (it has the local-assignments series)."""

from chipbench import pattern_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = pattern_block.local_experts_touched_share
