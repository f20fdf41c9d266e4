"""The prefill chunk's chunked scan's share of its roofline: the trace's prefill calls x the prompt tokens a call of the calls dispatched inside the capture (Δdynamo_worker_ssm_capture_prefill_tokens_total ÷ Δ..._capture_prefill_calls_total) x layers x state_block.scan_operations_per_token over the bf16 peak (operations bind), over the device time of the kernel the configuration labels `ssm_scan`."""

from chipbench import state_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = state_block.chunk_scan_roofline_share
