"""99th percentile of QueryResult.queued_s (enqueue to dispatch) over the
window's answered queries: the micro-batcher's linger and queueing."""
from bench import readers


def read(ctx):
    waits = [r.result.queued_s * 1e3 for r in ctx.requests
             if r.result is not None]
    return readers.p(waits, 99)
