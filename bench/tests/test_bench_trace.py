"""The trace reducer: hand-worked numbers on a written trace, and the
numbers it gives on a trace recorded on a TPU v5e."""

import gzip
import types

import numpy as np
import pytest

from bench import readers, spec, tracing

# Two chips.  Device 0: one module launch of 10 us holding two ops that
# overlap (0-5 us and 3-7 us), then an all-gather 9-10 us; a second
# launch 20-24 us with one op; device 1: one op 0-2 us.  Host: one event
# covering the 10-20 us gap.
WRITTEN = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 11 offset_ps: 20000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "tpu_custom_call.2" } }
  event_metadata { key: 3 value { id: 3 name: "all-gather.3" } }
  event_metadata { key: 10 value { id: 10 name: "jit_fn(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_fn(7)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "resolve" } }
}
"""


def written():
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(WRITTEN)


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]], float)
    np.testing.assert_array_equal(tracing.union(iv),
                                  [[0, 3], [5, 8], [10, 11]])
    assert tracing.union(np.zeros((0, 2))).shape == (0, 2)


def test_reduce_hand_worked():
    r = tracing.reduce(written(), n_devices=2)
    # device 0 busy 0-7, 9-10, 20-24 us = 12 us; device 1 2 us
    assert r["busy_s"] == pytest.approx((12e-6 + 2e-6) / 2)
    assert r["modules"] == {"jit_fn": {"launches": 2,
                                       "seconds": pytest.approx(14e-6)}}
    assert r["collective_s"] == pytest.approx(1e-6)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(9e-6)]
    assert [g[0] for g in r["idle_gaps"]] == ["host: resolve",
                                              "host: no event"]
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(10e-6),
                                              pytest.approx(2e-6)]


def test_reduce_wants_every_device():
    with pytest.raises(RuntimeError, match="expected 4"):
        tracing.reduce(written(), n_devices=4)


RECORDED = spec.BENCH / "testdata" / "stream_v5e.xplane.pb.gz"


def recorded():
    """150 ms of a ``msturing100-store.stream`` trace on one TPU v5e:
    device 0's "XLA Modules" and "XLA Ops" lines and the host events
    longer than 0.2 ms, as the profiler recorded them."""
    return gzip.open(RECORDED).read()


def test_reduce_recorded_v5e_trace():
    r = tracing.reduce(recorded(), n_devices=1)
    assert r["busy_s"] == pytest.approx(0.109353877)
    mods = r["modules"]
    assert mods[readers.QUERY_MODULE] == {
        "launches": 7, "seconds": pytest.approx(0.080961602)}
    assert mods["jit__scatter_apply"] == {
        "launches": 8, "seconds": pytest.approx(0.026770393)}
    assert r["collective_s"] == 0.0
    label, seconds = r["top_ops"][0]
    assert label == "_l2_padded.1 = f32[128,1179648]{1,0:T(8,128)} " \
                    "custom-call"
    assert seconds == pytest.approx(0.041479521)
    assert len(r["top_ops"]) == tracing.TOP == len(r["idle_gaps"])
    assert r["idle_gaps"][0] == ["host: np.asarray(jax.Array)",
                                 pytest.approx(0.007636737)]


def test_roofline_share_on_recorded_trace():
    """The query launch's share of its roofline, from the recorded trace
    with 16 rows a batch over 2^20 live points of width 100."""
    trace = tracing.reduce(recorded(), n_devices=1)
    ctx = types.SimpleNamespace(
        trace=trace, dim=100, points_per_chip=1 << 20,
        device_kind="TPU v5 lite",
        stats=[{"batches": 0, "queries": 0},
               {"batches": 10, "queries": 160}])
    launch = 0.080961602 / 7
    least = (1 << 20) * 404 / 819e9          # memory bound
    assert readers.query_launch_s(ctx) == pytest.approx(launch)
    share = readers.query_roofline(ctx)
    assert share == pytest.approx(100 * least / launch)
    assert 0 < share <= 100
