"""Milliseconds per batch the serving thread waited for the store lock
while it took the batch's snapshot (store.lock_wait_s over the window)."""
from bench import readers


def read(ctx):
    n, waited = readers.delta(ctx, "store.lock_wait_s")
    return waited / n * 1e3 if n else None
