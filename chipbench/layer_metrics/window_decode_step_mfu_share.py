"""The whole decode step of the parallel window block against the peak that binds it, HBM bytes: the weights every row uses once a step, the held experts the step's rows touched, the K and V rows attention read (a window layer's inside its window), over the bytes the HBM could deliver in the decode programs' device time of the capture."""

from chipbench import window_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = window_block.decode_step_mfu_share
