"""Device milliseconds per launch of the query program's XLA module
(readers.QUERY_MODULE) in the profiler trace."""
from bench import readers


def read(ctx):
    t = readers.query_launch_s(ctx)
    return None if t is None else t * 1e3
