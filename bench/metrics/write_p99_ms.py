"""99th percentile, over the window's write batches, of the time from a
batch being due to the return of the flush that published it."""
from bench import readers


def read(ctx):
    lat = [(w.done - (ctx.t0 + w.due)) * 1e3 for w in ctx.writes
           if w.error is None]
    return readers.p(lat, 99)
