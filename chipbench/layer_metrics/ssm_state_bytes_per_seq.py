"""Bytes of recurrent state one sequence holds over all layers, from the worker's gauge dynamo_ssm_state_bytes_per_slot: 25,165,824 of float32 scan state + 184,320 of convolution tail at the published widths and 6 layers. It guards the state staying float32 and fixed in size."""

from chipbench import state_block

LAYER = 'paged KV cache'
UNIT = 'bytes/seq'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = state_block.state_bytes_per_seq_gauge
