"""Share of the roofline a query launch reaches: the least time for its
work (an exact scan of the chip's live points for the window's mean real
rows per batch, bench/roofline.py) over the launch's device time."""
from bench import readers


def read(ctx):
    return readers.query_roofline(ctx)
