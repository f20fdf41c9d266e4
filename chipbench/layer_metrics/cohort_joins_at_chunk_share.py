"""Percent of the rows a decode-window cohort took in that joined it at the first decode dispatch after the chunk that completed their prompt (`dynamo_worker_cohort_joins_total{at="chunk"}`): no window of the old cohort stood in front of them on the device queue. The rest joined at a later settle (`at="settle"`: rows batched in the ready pool of a large cohort, a join after single steps). Between the window's scrapes. A program without the series has nothing to read, and a window in which no row joined has no share. What it explains is the request's wait for its cohort (`req_cohort_wait_ms.mean`, `itl_cohort_wait_share`). A serving loop that hands a first token to its client as it is read puts that wait between the client's first and second token, which is how the share moves `itl_ms.mean`; one that hands tokens over at the end of a draining iteration, as the parent of the PR that added the series did, shows the same wait as time to the first token."""

LAYER = 'EngineCore'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

_JOINS = 'dynamo_worker_cohort_joins_total{at="%s"}'


def read(ctx):
    chunk = ctx.delta('worker', _JOINS % 'chunk')
    settle = ctx.delta('worker', _JOINS % 'settle')
    if chunk is None or settle is None or chunk + settle <= 0:
        return None
    return 100.0 * chunk / (chunk + settle)
