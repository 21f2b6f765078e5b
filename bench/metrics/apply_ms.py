"""Host milliseconds per store flush (store.apply_s over the window)."""
from bench import readers


def read(ctx):
    n, total = readers.delta(ctx, "store.apply_s")
    return total / n * 1e3 if n else None
