"""Milliseconds per second of the window in which a collection of
generation 1 or 2 held the process (runtime.gc_pause_s over the window,
from the registry's sum).  None where the program has no collector hook."""
from bench import readers


def read(ctx):
    if "runtime.gc_pause_s" not in ctx.registry[-1]:
        return None
    _, pauses = readers.delta(ctx, "runtime.gc_pause_s")
    return pauses / (ctx.t_close - ctx.t0) * 1e3
