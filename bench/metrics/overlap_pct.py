"""Share of the window's dispatches launched while another batch of the
server was still in flight, in percent: serve.overlap (1.0 for such a
launch, else 0.0, one observation per dispatch) as sum over count, from
the registry over the window.  None where the program has no such
histogram."""
from bench import readers


def read(ctx):
    if "serve.overlap" not in ctx.registry[-1]:
        return None
    n, overlapped = readers.delta(ctx, "serve.overlap")
    return 100.0 * overlapped / n if n else None
