"""Bytes of cache one token takes over all layers, from the pool's own gauge: bytes a block / tokens a block.  10,240 at 640 stored values a row over 8 layers (9,216 unpadded); 163,840 if 20 heads' keys and values were stored.  It guards the cache staying latent."""

LAYER = 'paged KV cache'
UNIT = 'bytes/token'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    page = (ctx.scrapes.get('window_end') or {}).get('worker') or {}
    per_block = [v for k, v in page.items()
                 if k.startswith('dynamo_kv_bytes_per_block')]
    if not per_block:
        return None
    return per_block[0] / ctx.config['assumed']['block_size']
