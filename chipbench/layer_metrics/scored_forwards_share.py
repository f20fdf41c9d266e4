"""Percent of the block programs' forwards that ran past their last K/V write, to the head and the unmasking (diffusion_scored_forwards_total over diffusion_forwards_total of both kinds): 100 where every forward runs whole, 80 where the commit of a block of four denoising steps stops once the final tokens' K and V are in the cache. None on a program that does not count them."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    scored = block_readers.tally(ctx, 'diffusion_scored_forwards')
    fw = block_readers.forwards(ctx)
    if scored is None or fw is None:
        return None
    return 100.0 * scored / (fw[0] + fw[1])
