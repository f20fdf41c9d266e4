"""The grouped expert kernel's share of its roofline in a causal engine: the least time the chip could take for the expert layers the capture holds -- the larger of their bytes over the HBM peak and their operations over the bf16 peak -- over the kernel's device time.

Bytes: every distinct expert touched streams its three matrices once a layer-forward (3 x H x F x 2 B: 18.87 MB at H 2048, F 1536), and every (token, expert) row moves in and out once (2 x H x 2 B).  Operations: 6 x H x F a row.  Experts touched, rows and expert layers run are the worker's tallies over the capture's scrapes (prefill chunks, decode window steps and single steps, padding rows included: the kernel computes them).  Scaled from the counters' edges to what the trace holds by expert layers: the trace's decode steps and prefill calls x the expert layers a forward has (num_hidden_layers - first_k_dense_replace), over the expert layers the counters saw."""

from chipbench import block_readers, latent_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    seconds = (ctx.trace or {}).get('kernels_s', {}).get('moe_expert')
    touched = block_readers.tally(ctx, 'moe_experts_touched', 'capture')
    rows = block_readers.tally(ctx, 'moe_assignments', 'capture')
    layers = block_readers.tally(ctx, 'moe_layer_forwards', 'capture')
    if not seconds or not layers or touched is None or rows is None \
            or not ctx.peaks:
        return None
    hf = ctx.config
    roles = ctx.trace['roles']
    forwards = (roles.get('decode') or {}).get('steps', 0) \
        + (roles.get('prefill') or {}).get('calls', 0)
    scale = forwards * latent_block.moe_layers(hf) / layers
    row_io = 2 * hf['hidden_size'] * latent_block.BYTES_PER_PARAM
    need_bytes = scale * (touched * latent_block.expert_bytes(hf)
                          + rows * row_io)
    need_ops = scale * rows * 2 * latent_block.expert_params(hf)
    least = max(need_bytes / ctx.peaks['hbm_bytes_per_s'],
                need_ops / ctx.peaks['bf16_flops_per_s'])
    return 100.0 * least / seconds
