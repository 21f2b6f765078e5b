"""The micro-batcher's overlap share on a hand-made window: the share of
dispatches launched while another batch was in flight, from the
program's serve.overlap histogram."""

import types

import pytest

from bench import spec


def ctx_with(before: dict, after: dict):
    return types.SimpleNamespace(registry=[before, after])


def hist(count, total):
    return {"count": count, "sum": total}


@pytest.mark.parametrize("name", ["overlap_pct.lat", "overlap_pct.tput"])
def test_overlap_pct_share_of_dispatches(name):
    read = spec.metric_reader(name)
    # 40 dispatches in the window, 30 of them launched over another
    ctx = ctx_with({"serve.overlap": hist(10, 4.0)},
                   {"serve.overlap": hist(50, 34.0)})
    assert read(ctx) == pytest.approx(75.0)
    idle = {"serve.overlap": hist(10, 4.0)}
    assert read(ctx_with(idle, idle)) is None     # nothing dispatched
    # a program without the histogram (one that never overlaps)
    assert read(ctx_with({}, {"serve.dispatch_s": hist(5, 0.1)})) is None
