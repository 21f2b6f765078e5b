"""Real rows over bucket rows of the batches dispatched in the window
(ServerStats deltas), in percent."""


def read(ctx):
    before, after = ctx.stats
    real = after["queries"] - before["queries"]
    pad = after["padded_rows"] - before["padded_rows"]
    return 100.0 * real / (real + pad) if real + pad else None
