"""Median query latency, timed from when each query was due (open loop)."""
from bench import readers


def read(ctx):
    if ctx.traffic["loop"] != "open":
        return None
    return readers.p(readers.latencies_ms(ctx), 50)
