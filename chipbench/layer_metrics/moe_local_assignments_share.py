"""Of the (token, expert) pairs the router chose over all the model's experts (dynamo_worker_moe_routed_assignments_total), those whose expert is held here (dynamo_worker_moe_local_assignments_total): about count / of, 25 % at 128 of 512. It guards routing over all the model's experts: a router cut to the held ones reads 100."""

from chipbench import pattern_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = pattern_block.local_assignments_share
