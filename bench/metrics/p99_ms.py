"""99th percentile of query latency from when each query was due, over
every query due in the window (open loop)."""
from bench import readers


def read(ctx):
    if ctx.traffic["loop"] != "open":
        return None
    return readers.p(readers.latencies_ms(ctx), 99)
