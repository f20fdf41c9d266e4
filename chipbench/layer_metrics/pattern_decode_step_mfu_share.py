"""The whole decode step's share of the peak that binds it, HBM bytes, for the pattern block (a mixer, attention or routed experts alone in a layer): the weights every row uses once a step (pattern_block.weight_bytes_every_row), the held experts the step's rows touched x their bytes (the touched experts a layer forward of the decode calls dispatched inside the capture, dynamo_worker_moe_capture_decode_experts_touched_total / ..._layer_forwards_total, x the expert layers), each live row's recurrent state in and out over the state layers (dynamo_worker_ssm_capture_decode_row_steps_total / ..._decode_steps_total x 2 x pattern_block.state_bytes_per_seq) and the pages the engine's own model says attention swept, over what the HBM could deliver in the device time the capture's decode steps took. Named `mfu` as latent_decode_step_mfu_share is: the driver's word for a whole-step share."""

from chipbench import pattern_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = pattern_block.decode_step_mfu_share
