"""What the metric readers in ``bench/metrics/`` share.

A reader gets ``ctx``: the cell's ``config`` and ``traffic``, the
window's ``t0``/``t_close``/``seconds``, its ``requests`` and ``writes``
(``bench/traffic.py`` records), ``stats`` and ``registry`` (the server's
``ServerStats`` and metrics registry, read when the window opened and
when it closed), ``trace`` (the reduced profiler trace of a traced run,
else None), ``device_kind``, ``dim``, ``points_per_chip`` and
``setup_s``.
"""

from __future__ import annotations

import math

import numpy as np

# The service's query program as the profiler names its XLA module:
# runtime/knn_server.py build_query_program jits the shard_map of ``fn``.
QUERY_MODULE = "jit_fn"


def latencies_ms(ctx) -> np.ndarray:
    """Open loop: each answered query's time from when it was due."""
    return np.array([(r.done - (ctx.t0 + r.due)) * 1e3
                     for r in ctx.requests if r.result is not None])


def p(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None


def delta(ctx, name: str) -> tuple:
    """(count, sum) a registry histogram gained over the window."""
    before, after = (r.get(name, {"count": 0, "sum": 0.0})
                     for r in ctx.registry)
    return after["count"] - before["count"], after["sum"] - before["sum"]


def window_rows(ctx) -> float:
    """Mean real rows per dispatched batch over the window."""
    before, after = ctx.stats
    batches = after["batches"] - before["batches"]
    return (after["queries"] - before["queries"]) / batches if batches \
        else math.nan


def query_launch_s(ctx):
    """Mean device seconds per launch of the query program, or None."""
    if ctx.trace is None:
        return None
    mod = ctx.trace["modules"].get(QUERY_MODULE)
    if not mod or not mod["launches"]:
        return None
    return mod["seconds"] / mod["launches"]


def query_roofline(ctx):
    """Percent of the least time, for the launch's work, that a launch of
    the query program takes (bench/roofline.py)."""
    from bench import roofline
    t = query_launch_s(ctx)
    rows = window_rows(ctx)
    if t is None or math.isnan(rows):
        return None
    flops, nbytes = roofline.query_work(rows, ctx.points_per_chip, ctx.dim)
    least = roofline.least_time(flops, nbytes,
                                roofline.peaks(ctx.device_kind))
    return 100.0 * least / t
