"""The traffic generator: schedules fixed by the seed, the closed loop's
outstanding count, and the writer's live set."""

import threading
import time
import types
from concurrent.futures import Future

import numpy as np
import pytest

from bench import data, traffic


def test_poisson_schedule_is_fixed_by_the_seed():
    a = traffic.arrivals(data.rng(2**31 + 7, "arrivals"), 1500.0, 20.0)
    b = traffic.arrivals(data.rng(2**31 + 7, "arrivals"), 1500.0, 20.0)
    c = traffic.arrivals(data.rng(2**31 + 8, "arrivals"), 1500.0, 20.0)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every seed offers the same count over the window, in order
    assert len(a) == len(c) == 30000
    assert np.all(np.diff(a) >= 0) and 0.0 <= a[0] and a[-1] < 20.0
    # exponential-looking gaps: mean 1/rate, coefficient of variation ~1
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 1500, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


@pytest.mark.parametrize("mix,n", [([[10, 1.0]], 1001),
                                   ([[10, 0.5], [100, 0.5]], 1001)])
def test_l_mix_is_met_exactly(mix, n):
    ls = traffic.l_values(data.rng(3, "l"), mix, n)
    assert len(ls) == n
    for l, share in mix:
        assert abs((ls == l).sum() - share * n) <= 1
    np.testing.assert_array_equal(
        ls, traffic.l_values(data.rng(3, "l"), mix, n))


def test_points_depend_on_seed_and_tag_only():
    ctrs = data.centers(9, 16, 100, 8.0)
    a = data.cluster_points(9, "points", 70000, ctrs, threads=1)
    b = data.cluster_points(9, "points", 70000, ctrs, threads=4)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.float32 and a.shape == (70000, 100)
    assert not np.array_equal(
        a[:100], data.cluster_points(9, "queries", 100, ctrs))


class _FakeServer:
    """Futures resolved by the test, in submission order; notes the
    thread each query was submitted from."""

    def __init__(self):
        self.pending = []
        self.threads = []

    def submit(self, query, l):
        fut = Future()
        self.threads.append(threading.current_thread().name)
        self.pending.append(fut)
        return fut

    def resolve(self, n):
        done, self.pending = self.pending[:n], self.pending[n:]
        for f in done:
            f.set_result(types.SimpleNamespace())


def _eventually(cond, timeout=10.0):
    end = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < end, "timed out"
        time.sleep(0.001)


def test_closed_loop_keeps_exactly_64_outstanding():
    srv = _FakeServer()
    loop = traffic.ClosedLoop(srv, np.zeros((8, 4), np.float32),
                              np.full(8, 10), clients=64)
    loop.start(time.perf_counter(), seconds=60.0)
    assert loop.outstanding() == 64
    for n in (1, 32, 64, 7):
        srv.resolve(n)
        _eventually(lambda: len(srv.pending) == 64)
        assert loop.outstanding() == 64
    assert len(loop.requests) == 64 + 1 + 32 + 64 + 7
    # once the window has closed, answers submit nothing new
    loop._t_end = time.perf_counter()
    srv.resolve(64)
    _eventually(lambda: loop.outstanding() == 0)
    loop.join()
    assert len(loop.requests) == 64 + 1 + 32 + 64 + 7
    # the callers' next queries go out from their own thread, never from
    # the thread that resolved the answer (the server's, in a run)
    assert set(srv.threads[64:]) == {"bench-closed-loop"}
    traffic.collect(loop.requests)
    assert all(r.result is not None for r in loop.requests)


class _FakeStore:
    """Checks what a store would refuse: reused ids, dead deletes."""

    def __init__(self, n):
        self.live = set(range(n))
        self.used = set(range(n))
        self.gen = 0

    def insert(self, pts, ids):
        ids = set(int(i) for i in ids)
        assert not ids & self.used, "an id was used twice"
        self.used |= ids
        self.live |= ids

    def delete(self, ids):
        ids = set(int(i) for i in ids)
        assert ids <= self.live, "a dead id was deleted"
        self.live -= ids

    def flush_store(self):
        self.gen += 1
        return self.gen


def _writer(n, clusters, batches, per_cluster, size=40, seed=5):
    base_labels = data.rng(seed, "labels").integers(0, clusters, n)
    order = data.rng(seed, "write_order").permutation(clusters)
    labels = traffic.Writer.insert_labels(order, batches, size, per_cluster)
    pool = np.zeros((len(labels), 4), np.float32)
    store = _FakeStore(n)
    w = traffic.Writer(store, pool, labels, base_labels, order,
                       every_ms=20, inserts=size, deletes=size,
                       per_cluster=per_cluster)
    for k in range(batches):
        w.apply(w.make_batch(k * 0.02))
    assert all(b.error is None for b in w.batches)
    return w, store, base_labels, order


def test_writer_keeps_the_live_set_and_deletes_only_live_ids():
    n = 1 << 20
    w, store, _, _ = _writer(n, 16, 300, 250)
    assert len(store.live) == n == w.n_live == len(w.live_ids())
    assert set(w.live_ids().tolist()) == store.live
    assert [b.generation for b in w.batches] == list(range(1, 301))


def test_writer_goes_cluster_by_cluster_oldest_first():
    w, _, base_labels, order = _writer(1 << 20, 16, 300, 250)
    deleting = np.roll(order, -8)
    for k, b in enumerate(w.batches):
        c = deleting[k // 250]
        assert (base_labels[b.del_ids] == c).all()
        assert w.pool_labels[40 * k] == order[k // 250] != c
    # within a centre the oldest ids go first
    first = np.concatenate([b.del_ids for b in w.batches[:250]])
    np.testing.assert_array_equal(first, np.sort(first))


def test_writer_moves_on_when_a_centre_runs_out():
    """A small live set: centres empty, the writer deletes around the
    next ones, and ids it inserted die after the loaded ones."""
    w, store, _, _ = _writer(256, 4, 40, 5, size=8)
    assert len(store.live) == 256 == len(w.live_ids())
    gone = np.concatenate([b.del_ids for b in w.batches])
    assert len(np.unique(gone)) == len(gone) == 320
    assert (gone >= 256).any()
