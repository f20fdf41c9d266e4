"""The whole prefill chunk's operations over the bf16 peak in the device time the chunks of the capture took: every prompt token (the trace's prefill calls x the tokens a call of the calls dispatched inside the capture, Δdynamo_worker_ssm_capture_prefill_tokens_total ÷ Δ..._capture_prefill_calls_total) through every layer's matrices (2 x state_block.layer_matmul_params) and the chunked scan (state_block.scan_operations_per_token), every causal pair through attention (Δprefill_attn_pairs x state_block.pair_operations)."""

from chipbench import state_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = state_block.prefill_mfu_share
