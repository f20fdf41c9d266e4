"""The grouped expert kernel's share of its roofline: the least time the chip could take for the expert layers the capture holds -- the larger of their bytes over the HBM peak and their operations over the bf16 peak -- over the kernel's device time.

Bytes: every distinct expert touched streams its three matrices once a layer-forward (3 x H x F x 2 B: 9.44 MB at H 2048, F 768), and every (token, expert) row moves in and out once (2 x H x 2 B).  Operations: 6 x H x F a row (three matmuls of 2 x H x F).  Experts touched and rows are the worker's tallies over the capture's scrapes (block forwards and prefill chunks, padding rows included: the kernel computes them), scaled from the counters' edges to the forwards the trace really holds, as decode_hbm_share scales its KV bytes."""

from chipbench import block_readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

BYTES_PER_PARAM = 2     # bf16 weights and activations


def expert_bytes(hf: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return 3 * hf['hidden_size'] * hf['moe_intermediate_size'] \
        * BYTES_PER_PARAM


def row_bytes(hf: dict) -> int:
    """Bytes one (token, expert) row moves: its input and its output."""
    return 2 * hf['hidden_size'] * BYTES_PER_PARAM


def row_operations(hf: dict) -> int:
    """Operations of one row through one expert: gate, up and down."""
    return 6 * hf['hidden_size'] * hf['moe_intermediate_size']


def read(ctx):
    held = block_readers.trace_forwards(ctx)
    seconds = (ctx.trace or {}).get('kernels_s', {}).get('moe_expert')
    touched = block_readers.tally(ctx, 'moe_experts_touched', 'capture')
    rows = block_readers.tally(ctx, 'moe_assignments', 'capture')
    layers = block_readers.tally(ctx, 'moe_layer_forwards', 'capture')
    if held is None or not seconds or not layers or touched is None \
            or rows is None or not ctx.peaks:
        return None
    hf = ctx.config
    # Expert layers the trace holds over those the counters saw.
    scale = (held[0] + held[2]) * hf['num_hidden_layers'] / layers
    need_bytes = scale * (touched * expert_bytes(hf) + rows * row_bytes(hf))
    need_ops = scale * rows * row_operations(hf)
    least = max(need_bytes / ctx.peaks['hbm_bytes_per_s'],
                need_ops / ctx.peaks['bf16_flops_per_s'])
    return 100.0 * least / seconds
