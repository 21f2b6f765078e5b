"""Host milliseconds per batch outside the launch: the dispatch prologue
and resolve, i.e. serve.dispatch_s minus serve.kernel_s, per batch, from
the registry's count and sum over the window."""
from bench import readers


def read(ctx):
    n, dispatch = readers.delta(ctx, "serve.dispatch_s")
    _, kernel = readers.delta(ctx, "serve.kernel_s")
    return (dispatch - kernel) / n * 1e3 if n else None
