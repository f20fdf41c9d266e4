"""Share of the window during which the head of the queue stood unadmitted (no slot, pages under the watermark, or held). Explains TTFT."""

from chipbench import request_readers

LAYER = 'scheduler'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return request_readers.admit_blocked_share(ctx)
