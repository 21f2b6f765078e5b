"""Each fault a cell can have makes ``correct`` come out false: a whole
run at a small size on the CPU, with the timed path broken underneath."""

import numpy as np
import pytest

from bench.tests.tiny import run_tiny


def _state_unchanged(monkeypatch):
    """A flush returns a new generation but leaves the device data as it
    was."""
    from repro.store import mutable

    def same(self, slots):
        s = self._snap
        return s.points, s.ids, s.valid, s.labels
    monkeypatch.setattr(mutable.MutableStore, "_scatter_locked", same)


def _half_batch(monkeypatch):
    """Half of each batch's real rows are left out of the launch."""
    from repro.runtime import knn_server
    build = knn_server.KnnServer._build_executable

    def halved(self):
        fn = build(self)

        def call(*args):
            l_arr = np.array(args[-2])
            real = np.flatnonzero(l_arr)
            l_arr[real[len(real) // 2:]] = 0
            return fn(*args[:-2], l_arr, args[-1])
        return call
    monkeypatch.setattr(knn_server.KnnServer, "_build_executable", halved)


def _stale_snapshot(monkeypatch):
    """Flushes publish new generations, but every dispatch serves the
    snapshot it first saw, from load, and reports that one's generation:
    exact answers, for a state left behind."""
    from repro.runtime import knn_server
    backing = knn_server.KnnServer._backing_arrays
    frozen = {}

    def first_seen(self):
        if self not in frozen:
            frozen[self] = backing(self)
        return frozen[self]
    monkeypatch.setattr(knn_server.KnnServer, "_backing_arrays", first_seen)


def _answer_altered(monkeypatch):
    """Each answer's nearest id is changed where the answer is made."""
    from repro.runtime import knn_server
    resolve = knn_server._resolve

    def altered(future, result=None, error=None):
        if result is not None:
            ids = np.array(result.ids)
            ids[0] += 1
            result = result._replace(ids=ids)
        return resolve(future, result=result, error=error)
    monkeypatch.setattr(knn_server, "_resolve", altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "stale_snapshot": _stale_snapshot, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("name,fault", [
    ("msturing100-store.stream", "state_unchanged"),
    ("msturing100-store.stream", "stale_snapshot"),
    ("msturing100-store.stream", "half_batch"),
    ("msturing100-store.batch", "half_batch"),
    ("msturing100-store.stream", "answer_altered"),
    ("msturing100-store.batch", "answer_altered"),
])
def test_a_fault_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny(name)
    assert res["correct"] is False, res["check"]


def test_a_stale_snapshot_is_caught_by_freshness_alone(monkeypatch):
    """Answers exact for the generation they report pass every other
    number; only the freshness count sees that they were left behind."""
    _stale_snapshot(monkeypatch)
    check = run_tiny("msturing100-store.stream")["check"]
    assert check["stale"]["value"] > 0
    assert check["id_miss"]["value"] == 0 and check["missing"]["value"] == 0
