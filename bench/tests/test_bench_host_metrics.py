"""The host-side readers (collector pauses, time off the CPU, store lock
waits) on a hand-made window, and device idle time put down to the
program's spans on a written trace and on the recorded v5e trace."""

import gzip
import types

import numpy as np
import pytest

from bench import idle_spans, spec, tracing


def ctx_with(before: dict, after: dict, seconds: float = 2.0):
    return types.SimpleNamespace(registry=[before, after], t0=10.0,
                                 t_close=10.0 + seconds)


def hist(count, total):
    return {"count": count, "sum": total}


def test_gc_pause_ms_per_second_of_window():
    read = spec.metric_reader("gc_pause_ms.lat")
    ctx = ctx_with({"runtime.gc_pause_s": hist(3, 0.5)},
                   {"runtime.gc_pause_s": hist(7, 0.62)})
    assert read(ctx) == pytest.approx(0.12 / 2.0 * 1e3)
    still = ctx_with({"runtime.gc_pause_s": hist(3, 0.5)},
                     {"runtime.gc_pause_s": hist(3, 0.5)})
    assert read(still) == 0.0
    assert read(ctx_with({}, {})) is None        # a program with no hook


def test_host_offcpu_ms_per_batch():
    read = spec.metric_reader("host_offcpu_ms.tput")
    names = ("serve.prologue_s", "serve.dispatch_s", "serve.wait_s",
             "serve.cpu_s")
    before = {n: hist(5, 1.0) for n in names}
    # 4 batches: 0.004 s prologue + 0.060 s dispatch, of which 0.040 s
    # the readback's wait and 0.016 s on the CPU: 0.008 s off it
    after = {n: hist(9, 1.0 + d) for n, d in
             zip(names, (0.004, 0.060, 0.040, 0.016))}
    assert read(ctx_with(before, after)) == pytest.approx(0.008 / 4 * 1e3)
    assert read(ctx_with(before, before)) is None
    assert read(ctx_with({}, {})) is None


def test_store_lock_wait_ms_per_batch():
    read = spec.metric_reader("store_lock_wait_ms")
    ctx = ctx_with({"store.lock_wait_s": hist(10, 0.2)},
                   {"store.lock_wait_s": hist(30, 0.23)})
    assert read(ctx) == pytest.approx(0.03 / 20 * 1e3)
    assert read(ctx_with({}, {})) is None


# Device 0 runs ops over 0-10, 20-30, 50-60 and 80-90 us, so it idles over
# 10-20, 30-50 and 60-80 us.  Host line 1 is the serving thread: it waits
# for work 0-5, dispatches 5-45 (reading back 8-15, resolving 15-28),
# then waits 45-62.  Line 2 is the writer, applying 32-40 (not the
# serving line, so it names nothing) and collecting 70-75 (a collection
# names its time on any line).  Line 3 holds a runtime event, no span.
SPANS = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 80000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 7000000 }
    events { metadata_id: 4 offset_ps: 15000000 duration_ps: 13000000 }
    events { metadata_id: 1 offset_ps: 45000000 duration_ps: 17000000 }
  }
  lines { id: 2 name: "python3" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 32000000 duration_ps: 8000000 }
    events { metadata_id: 6 offset_ps: 70000000 duration_ps: 5000000 }
  }
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 10000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "knn.batcher.wait" } }
  event_metadata { key: 2 value { id: 2 name: "knn.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "knn.kernel.readback" } }
  event_metadata { key: 4 value { id: 4 name: "knn.resolve" } }
  event_metadata { key: 5 value { id: 5 name: "knn.store.apply" } }
  event_metadata { key: 6 value { id: 6 name: "knn.gc" } }
  event_metadata { key: 7 value { id: 7 name: "np.asarray(jax.Array)" } }
}
"""


def test_idle_by_span_hand_worked():
    from jax.profiler import ProfileData
    got = idle_spans.idle_by_span(
        ProfileData.text_proto_to_serialized_xspace(SPANS))
    # 10-15 readback, 15-20 resolve; 30-45 dispatch, 45-50 wait;
    # 60-62 wait, 62-70 and 75-80 nothing, 70-75 gc
    want = {"dispatch": 15e-6, "unnamed": 13e-6, "batcher.wait": 7e-6,
            "kernel.readback": 5e-6, "resolve": 5e-6, "gc": 5e-6}
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    assert list(got)[:2] == ["dispatch", "unnamed"]


def test_longest_gaps_name_every_line():
    from jax.profiler import ProfileData
    got = idle_spans.longest_gaps(
        ProfileData.text_proto_to_serialized_xspace(SPANS), top=3)
    # serving line first, then the writer's; at each gap's midpoint
    assert got == [[pytest.approx(20e-6), ["dispatch", None]],
                   [pytest.approx(20e-6), [None, "gc"]],
                   [pytest.approx(10e-6), ["resolve", None]]]


def test_idle_by_span_without_program_spans_is_all_unnamed():
    """The recorded v5e trace predates the program's spans: all of its
    idle time between the first and the last op is unnamed, and equals
    that stretch less the busy time the trace reducer counts."""
    xspace = gzip.open(spec.BENCH / "testdata"
                       / "stream_v5e.xplane.pb.gz").read()
    got = idle_spans.idle_by_span(xspace)
    assert list(got) == ["unnamed"]
    from jax.profiler import ProfileData
    ops = [ln for pl in ProfileData.from_serialized_xspace(xspace).planes
           if pl.name == "/device:TPU:0" for ln in pl.lines
           if ln.name == "XLA Ops"][0]
    merged = tracing.union(idle_spans._intervals(tracing._events(ops)))
    stretch = (merged[-1, 1] - merged[0, 0]) * 1e-9
    busy = tracing.reduce(xspace, n_devices=1)["busy_s"]
    assert got["unnamed"] == pytest.approx(stretch - busy)
    assert np.isfinite(got["unnamed"]) and got["unnamed"] > 0
