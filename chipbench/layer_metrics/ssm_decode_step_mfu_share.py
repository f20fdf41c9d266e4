"""The whole decode step's share of the peak that binds it, which at these row counts is HBM bytes, for the hybrid block with a state-space mixer beside attention: the weights once a step (state_block.weight_bytes_per_step), each live row's recurrent state read and written (the trace's decode steps x the live rows a step of the calls dispatched inside the capture, Δdynamo_worker_ssm_capture_decode_row_steps_total ÷ Δ..._capture_decode_steps_total, x 2 x state_block.state_bytes_per_seq), and the pages the engine's own model says attention swept (kv_read_bytes_modeled), over what the HBM could deliver in the device time the decode steps of the capture took. Named `mfu` as latent_decode_step_mfu_share is: the word by which the driver knows a whole-step share. The pages alone are read over the capture's scrapes and scaled to the decode steps the trace holds: those scrapes lie the profile's collection apart, so the rows are taken from the tallies that move only inside a capture."""

from chipbench import state_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = state_block.decode_step_mfu_share
