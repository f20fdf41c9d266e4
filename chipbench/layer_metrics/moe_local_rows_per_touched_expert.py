"""Rows a touched held expert computes, an expert-layer forward of the window: dynamo_worker_moe_local_assignments_total over dynamo_worker_moe_experts_touched_total."""

from chipbench import pattern_block

LAYER = 'step programs'
UNIT = 'rows/expert'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = pattern_block.local_rows_per_touched_expert
