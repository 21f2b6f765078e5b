"""Host milliseconds per batch in which the serving thread neither ran
nor waited on the device (waiting for the GIL or a core, or blocked on
the host): chunk taken to resolve done (serve.prologue_s +
serve.dispatch_s) less the readback's wait (serve.wait_s) and the
thread's CPU time (serve.cpu_s), per batch, from the registry's count
and sum over the window."""
from bench import readers


def read(ctx):
    n, prologue = readers.delta(ctx, "serve.prologue_s")
    if not n:
        return None
    _, dispatch = readers.delta(ctx, "serve.dispatch_s")
    _, wait = readers.delta(ctx, "serve.wait_s")
    _, cpu = readers.delta(ctx, "serve.cpu_s")
    return (prologue + dispatch - wait - cpu) / n * 1e3
