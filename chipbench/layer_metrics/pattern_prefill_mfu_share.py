"""The whole prefill chunk's share of the peak that binds it for the pattern block: its bytes (the weights every row uses and the held experts the chunk touched, a call) over the HBM peak, or its operations (every prompt token through the matrices and the chunked scan of the state layers, its held assignments through their experts, every causal pair through the attention layers) over the bf16 peak, whichever is larger, over the device time the capture's prefill calls took. Tokens, touched experts and held assignments are those of the calls dispatched inside the capture."""

from chipbench import pattern_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = pattern_block.prefill_mfu_share
