"""State slots that live sequences hold, as a share of all (dynamo_ssm_state_slots used / capacity), averaged over the window's scrapes."""

from chipbench import state_block

LAYER = 'scheduler'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = state_block.slots_used_share
