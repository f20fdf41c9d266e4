"""The decode step's state update's share of its roofline: each live row's float32 scan state once in and once out a layer (the trace's decode steps x the live rows a step of the calls dispatched inside the capture, Δdynamo_worker_ssm_capture_decode_row_steps_total ÷ Δ..._capture_decode_steps_total, x layers x 2 x 4.19 MB) over the HBM peak, or its operations over the bf16 peak, whichever is larger (bytes bind), over the device time of the kernel the configuration labels `ssm_update`. Counted from the configuration's keys and the counters: the same work whatever implements it. The rows are those of the capture's own seconds: read over the capture's scrapes, 27 s apart around a 3 s trace, a run whose rows rose in the drain read 107 %."""

from chipbench import state_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = state_block.state_update_roofline_share
