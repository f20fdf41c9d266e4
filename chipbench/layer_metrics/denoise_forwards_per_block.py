"""Denoising forwards a block program call runs before its commit: the change of diffusion_forwards_total{kind=denoise} over that of {kind=commit} in the window (4 for a fresh block of 4 under the static rule, fewer where a prompt's tail opened the block)."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = 'forwards/block'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    fw = block_readers.forwards(ctx)
    return None if fw is None else fw[0] / fw[1]
