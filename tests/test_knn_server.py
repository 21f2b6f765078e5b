"""Micro-batched kNN query service: bucketing/padding round-trip,
per-request l masking vs the gather baseline, the O(log l) round smoke
test under the service path, the stop() drain contract, and ServerStats
thread-safety under concurrent observe/snapshot."""

import math
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.core as core
from repro.configs.knn_service import CONFIG
from repro.parallel.compat import shard_map
from repro.runtime import KnnServer
from repro.runtime.knn_server import ServerStats

K = 8
DIM = 8
N = K * 256


@pytest.fixture(scope="module")
def pts():
    return np.random.default_rng(3).normal(size=(N, DIM)).astype(np.float32)


def _server(pts, mesh, **overrides):
    kw = dict(dim=DIM, l=8, l_max=32, bucket_sizes=(1, 2, 4, 8))
    kw.update(overrides)
    return KnnServer(pts, cfg=CONFIG.replace(**kw), mesh=mesh,
                     axis_name="x")


def _brute(points, queries, l):
    d = ((queries[:, None, :] - points[None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=1, kind="stable")[:, :l]
    return np.take_along_axis(d, idx, 1), idx


def test_batched_multi_l_matches_simple(mesh8, rng, pts):
    """knn_query_batched with per-row l == knn_simple row by row."""
    l_max = 32
    ls = np.array([1, 5, 32, 17], np.int32)
    q = rng.normal(size=(4, DIM)).astype(np.float32)
    pids = np.arange(N, dtype=np.int32)

    def fn(p, i, qq, la, k):
        res = core.knn_query_batched(p, i, qq, l_max, la, k, axis_name="x")
        sd, si = core.knn_simple(p, i, qq, l_max, axis_name="x")
        return res.dists, res.ids, sd, si

    f = jax.jit(shard_map(
        fn, mesh=mesh8,
        in_specs=(P("x"), P("x"), P(None), P(None), P(None)),
        out_specs=(P(None),) * 4))
    d, i, sd, si = f(pts, pids, q, ls, jax.random.PRNGKey(0))
    d, i, sd, si = map(np.asarray, (d, i, sd, si))
    for b, l in enumerate(ls):
        # row b's first l slots hold exactly the l nearest...
        np.testing.assert_allclose(np.sort(d[b, :l]), sd[b, :l], rtol=1e-5)
        assert set(i[b, :l].tolist()) == set(si[b, :l].tolist())
        # ...and everything past l is sentinel padding
        assert np.all(np.isinf(d[b, l:]))
        assert np.all(i[b, l:] == 2**31 - 1)


def test_batched_zero_l_rows_select_nothing(mesh8, rng, pts):
    """l=0 rows (the micro-batcher's padding) come back all-sentinel."""
    q = rng.normal(size=(3, DIM)).astype(np.float32)
    pids = np.arange(N, dtype=np.int32)
    ls = np.array([4, 0, 9], np.int32)

    def fn(p, i, qq, la, k):
        res = core.knn_query_batched(p, i, qq, 16, la, k, axis_name="x")
        return res.dists, res.ids

    f = jax.jit(shard_map(
        fn, mesh=mesh8,
        in_specs=(P("x"), P("x"), P(None), P(None), P(None)),
        out_specs=(P(None), P(None))))
    d, i = map(np.asarray, f(pts, pids, q, ls, jax.random.PRNGKey(1)))
    assert np.all(np.isinf(d[1]))
    bd, _ = _brute(pts, q, 16)
    np.testing.assert_allclose(np.sort(d[0, :4]), bd[0, :4], rtol=1e-4)
    np.testing.assert_allclose(np.sort(d[2, :9]), bd[2, :9], rtol=1e-4)


def test_server_bucketing_and_padding_round_trip(mesh8, rng, pts):
    """Odd request counts pad to the next bucket and answers still match
    brute force per request, at each request's own l."""
    srv = _server(pts, mesh8)
    qs = rng.normal(size=(5, DIM)).astype(np.float32)
    ls = [1, 3, 32, 17, 8]
    res = srv.query_batch(qs, ls)

    assert srv.stats.queries == 5
    assert srv.stats.batches == 1
    assert srv.stats.bucket_counts == {8: 1}     # 5 -> bucket 8
    assert srv.stats.padded_rows == 3

    for r, q, l in zip(res, qs, ls):
        assert r.l == l and len(r.dists) == l and len(r.ids) == l
        bd, bi = _brute(pts, q[None], l)
        # documented contract: dists arrive ascending, no client-side sort
        np.testing.assert_allclose(r.dists, bd[0], rtol=1e-4)
        assert set(r.ids.tolist()) == set(bi[0].tolist())


def test_server_bucket_for_is_smallest_fit(mesh8, pts):
    srv = _server(pts, mesh8)
    assert srv._bucket_for(1) == 1
    assert srv._bucket_for(2) == 2
    assert srv._bucket_for(3) == 4
    assert srv._bucket_for(8) == 8
    # more pending than the largest bucket: drained in max-bucket chunks
    assert srv._bucket_for(9) == 8


def test_server_padding_no_leak(mesh8, rng, pts):
    """A query answered alone equals the same query inside a padded batch
    (padding rows and neighbors' rows must not interact)."""
    srv = _server(pts, mesh8)
    q = rng.normal(size=(DIM,)).astype(np.float32)
    alone = srv.query_batch(q[None], [16])[0]
    crowd_qs = np.stack([q] + [rng.normal(size=(DIM,)).astype(np.float32)
                               for _ in range(2)])
    crowd = srv.query_batch(crowd_qs, [16, 3, 32])[0]
    np.testing.assert_allclose(np.sort(alone.dists), np.sort(crowd.dists),
                               rtol=1e-6)
    assert set(alone.ids.tolist()) == set(crowd.ids.tolist())


def test_server_gather_baseline_agrees(mesh8, rng, pts):
    """sampler='selection' and sampler='gather' answer identically."""
    sel = _server(pts, mesh8)
    gat = _server(pts, mesh8, sampler="gather")
    qs = rng.normal(size=(4, DIM)).astype(np.float32)
    ls = [2, 32, 9, 1]
    for a, b in zip(sel.query_batch(qs, ls), gat.query_batch(qs, ls)):
        np.testing.assert_allclose(np.sort(a.dists), np.sort(b.dists),
                                   rtol=1e-5)
        assert set(a.ids.tolist()) == set(b.ids.tolist())
    # A/B accounting: gather pays its l_max-word payload in messages
    assert gat.query_batch(qs[:1], [4])[0].rounds == 1
    assert sel.query_batch(qs[:1], [4])[0].rounds > 1


def test_server_values_lookup(mesh8, rng, pts):
    vals = rng.integers(0, 50, N).astype(np.int32)
    srv = KnnServer(pts, vals,
                    cfg=CONFIG.replace(dim=DIM, l=8, l_max=16,
                                       bucket_sizes=(4,)),
                    mesh=mesh8, axis_name="x")
    q = rng.normal(size=(DIM,)).astype(np.float32)
    r = srv.query_batch(q[None], [8])[0]
    _, bi = _brute(pts, q[None], 8)
    assert sorted(r.values.tolist()) == sorted(vals[bi[0]].tolist())


def test_server_values_sentinel_slots(mesh8, rng):
    """Requests for more neighbors than finite points get -1 values in the
    sentinel slots (not an out-of-bounds lookup)."""
    n_small = K * 2
    small = rng.normal(size=(n_small, DIM)).astype(np.float32)
    vals = np.arange(n_small, dtype=np.int32)
    srv = KnnServer(small, vals,
                    cfg=CONFIG.replace(dim=DIM, l=8, l_max=32,
                                       bucket_sizes=(1,)),
                    mesh=mesh8, axis_name="x")
    r = srv.query_batch(rng.normal(size=(1, DIM)).astype(np.float32),
                        [32])[0]
    assert np.all(np.isinf(r.dists[n_small:]))
    assert np.all(r.values[n_small:] == -1)
    assert sorted(r.values[:n_small].tolist()) == vals.tolist()


def test_server_multi_axis_mesh_k_is_axis_size(mesh42, rng, pts):
    """On a multi-axis mesh only the service axis counts as k machines."""
    srv = KnnServer(pts, cfg=CONFIG.replace(dim=DIM, l=8, l_max=16,
                                            bucket_sizes=(2,)),
                    mesh=mesh42, axis_name="model")
    assert srv.k == 2
    assert srv.m_local == N // 2
    q = rng.normal(size=(DIM,)).astype(np.float32)
    r = srv.query_batch(q[None], [8])[0]
    bd, _ = _brute(pts, q[None], 8)
    np.testing.assert_allclose(np.sort(r.dists), bd[0], rtol=1e-4)


def test_server_iterations_log_l_smoke(mesh8, rng, pts):
    """Theorem 2.4 via the service path: with the Lemma 2.3 prune the
    selection runs on <= 11*l survivors, so iterations stay O(log l)
    regardless of n — checked with the repo's standard generous constant."""
    l_max = 32
    srv = _server(pts, mesh8, l_max=l_max, bucket_sizes=(8,))
    qs = rng.normal(size=(8, DIM)).astype(np.float32)
    res = srv.query_batch(qs, [l_max] * 8)
    bound = 8 * math.ceil(math.log2(11 * l_max)) + 16
    assert all(r.iterations <= bound for r in res)
    assert all(r.survivors <= 11 * l_max for r in res)


def test_server_background_batcher(mesh8, rng, pts):
    """Futures submitted while the micro-batcher thread runs resolve to
    the same answers as the synchronous path."""
    srv = _server(pts, mesh8)
    srv.warmup()
    qs = rng.normal(size=(6, DIM)).astype(np.float32)
    with srv.serving():
        futs = [srv.submit(q, 8) for q in qs]
        res = [f.result(timeout=60) for f in futs]
    for r, q in zip(res, qs):
        bd, _ = _brute(pts, q[None], 8)
        np.testing.assert_allclose(np.sort(r.dists), bd[0], rtol=1e-4)


def test_server_determinism_across_fresh_instances(mesh8, rng, pts):
    """Identical PRNG seed + identical store generation => bit-identical
    QueryResult from two fresh KnnServer instances (the dispatch-time
    snapshot-capture contract: nothing about a server's private lifetime
    — construction order, warmup, thread timing — may leak into answers)."""
    from repro.store import MutableStore
    qs = rng.normal(size=(5, DIM)).astype(np.float32)
    ls = [1, 3, 32, 17, 8]

    # static backing, one server warmed up and one not
    a, b = _server(pts, mesh8), _server(pts, mesh8)
    b.warmup()
    for ra, rb in zip(a.query_batch(qs, ls), b.query_batch(qs, ls)):
        assert ra.dists.tobytes() == rb.dists.tobytes()
        assert np.array_equal(ra.ids, rb.ids)
        assert ra.generation == rb.generation == 0

    # mutable backing: both servers share one store generation
    store = MutableStore(DIM, capacity_per_shard=64, axis_name="x")
    ids = store.insert(rng.normal(size=(200, DIM)).astype(np.float32))
    store.flush()
    store.delete(ids[::5])
    store.flush()
    kw = dict(dim=DIM, l=8, l_max=32, bucket_sizes=(1, 2, 4, 8))
    a, b = (KnnServer(store=store, cfg=CONFIG.replace(**kw), seed=0)
            for _ in range(2))
    for ra, rb in zip(a.query_batch(qs, ls), b.query_batch(qs, ls)):
        assert ra.dists.tobytes() == rb.dists.tobytes()
        assert np.array_equal(ra.ids, rb.ids)
        assert ra.generation == rb.generation == store.generation


def test_server_rejects_bad_requests(mesh8, pts):
    srv = _server(pts, mesh8)
    with pytest.raises(ValueError):
        srv.submit(np.zeros(DIM, np.float32), 0)
    with pytest.raises(ValueError):
        srv.submit(np.zeros(DIM, np.float32), srv.cfg.l_max + 1)
    with pytest.raises(ValueError):
        srv.submit(np.zeros(DIM + 1, np.float32), 4)
    with pytest.raises(ValueError, match="route_compute"):
        _server(pts, mesh8, route_compute="gpu")
    srv.flush()


# ---- stop() drain contract -----------------------------------------------

def test_server_stop_drains(mesh8, rng, pts):
    """The documented stop() contract: every request pending at stop()
    entry resolves before stop() returns, each dispatched exactly once
    (stats.queries is the double-dispatch detector — a request served
    twice would count twice), correct against brute force, and stop() is
    idempotent with submit/flush still serving synchronously after."""
    srv = _server(pts, mesh8, max_wait_ms=50.0)
    srv.warmup()
    qs = rng.normal(size=(16, DIM)).astype(np.float32)
    srv.start()
    futs = [srv.submit(q, 8) for q in qs]
    srv.stop()              # requests still lingering in the batcher
    assert all(f.done() for f in futs)
    for f, q in zip(futs, qs):
        r = f.result(timeout=0)
        bd, _ = _brute(pts, q[None], 8)
        np.testing.assert_allclose(np.sort(r.dists), bd[0], rtol=1e-4)
    assert srv.stats.queries == len(qs)
    assert srv._thread is None

    srv.stop()              # idempotent
    f = srv.submit(qs[0], 8)
    srv.flush()
    assert f.done()
    assert srv.stats.queries == len(qs) + 1


def test_server_stop_races_with_itself(mesh8, rng, pts):
    """Concurrent stop() callers: exactly one joins the thread (the
    handle is captured-and-cleared under the lock), every pending
    request resolves, and nothing dispatches twice."""
    srv = _server(pts, mesh8, max_wait_ms=20.0)
    srv.warmup()
    srv.start()
    qs = rng.normal(size=(12, DIM)).astype(np.float32)
    futs = [srv.submit(q, 4) for q in qs]
    stoppers = [threading.Thread(target=srv.stop) for _ in range(3)]
    for t in stoppers:
        t.start()
    for t in stoppers:
        t.join()
    assert srv._thread is None
    assert all(f.done() for f in futs)
    assert srv.stats.queries == len(qs)


# ---- ServerStats thread-safety -------------------------------------------

def test_server_stats_concurrent_observe_and_snapshot():
    """Regression for the unlocked observe()/placement_stats() race:
    writer threads hammer observe() while a reader takes snapshot()s,
    and every snapshot must be internally consistent — the cross-field
    invariants hold inside any single snapshot, and the final totals
    are exact (no lost updates)."""
    stats = ServerStats()
    buckets = (1, 2, 4, 8)
    per_thread = 400
    n_writers = 4
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            s = stats.snapshot()
            if s["batches"] != sum(s["bucket_counts"].values()):
                bad.append(("batches", s))
            if s["queries"] + s["padded_rows"] != sum(
                    b * c for b, c in s["bucket_counts"].items()):
                bad.append(("rows", s))
            if s["routed_batches"] * 8 < s["touched_shards"]:
                bad.append(("touched", s))

    def writer(seed):
        wrng = np.random.default_rng(seed)
        for _ in range(per_thread):
            b = int(wrng.choice(buckets))
            n_real = int(wrng.integers(1, b + 1))
            touched = int(wrng.integers(1, 9)) if b % 2 else None
            stats.observe(b, n_real, touched=touched)

    rt = threading.Thread(target=reader)
    wts = [threading.Thread(target=writer, args=(s,))
           for s in range(n_writers)]
    rt.start()
    for t in wts:
        t.start()
    for t in wts:
        t.join()
    stop.set()
    rt.join()

    assert not bad, bad[0]
    final = stats.snapshot()
    assert final["batches"] == n_writers * per_thread
    assert final["batches"] == sum(final["bucket_counts"].values())
    assert final["queries"] + final["padded_rows"] == sum(
        b * c for b, c in final["bucket_counts"].items())
    # deterministic totals: replay each writer's seeded sequence
    want_q = want_pad = want_t = want_rb = 0
    for s in range(n_writers):
        wrng = np.random.default_rng(s)
        for _ in range(per_thread):
            b = int(wrng.choice(buckets))
            n_real = int(wrng.integers(1, b + 1))
            t = int(wrng.integers(1, 9)) if b % 2 else None
            want_q += n_real
            want_pad += b - n_real
            if t is not None:
                want_t += t
                want_rb += 1
    assert final["queries"] == want_q
    assert final["padded_rows"] == want_pad
    assert final["touched_shards"] == want_t
    assert final["routed_batches"] == want_rb


def test_server_stats_rejects_touched_sentinel():
    """Satellite of the -1 sentinel fix: QueryResult.shards_touched
    defaults to -1 ("never routed"), and a leaked sentinel must never
    enter the prune-rate inputs — it would silently *raise* the
    reported rate.  observe() counts it as invalid instead."""
    stats = ServerStats()
    stats.observe(4, 4, touched=3)
    stats.observe(4, 4, touched=-1)        # the sentinel, leaked
    stats.observe(4, 4, touched=-7)        # any negative, same treatment
    stats.observe(4, 4, touched=0)         # zero is a real observation
    s = stats.snapshot()
    assert s["touched_shards"] == 3
    assert s["routed_batches"] == 2        # touched=3 and touched=0
    assert s["invalid_touched"] == 2
    assert s["batches"] == 4               # batch counting is unaffected


@pytest.mark.parametrize("route", ["exact", "pruned"])
def test_touched_sentinel_never_served_or_observed(mesh8, pts, route):
    """Both routes end to end: every served result carries a
    non-negative shards_touched (the -1 default never escapes
    the dispatch), the prune math saw no invalid observations, and the
    serve.touched_shards histogram observed only real counts — exactly
    one per dispatched batch, k under route="exact"."""
    srv = _server(pts, mesh8, route=route)
    rng = np.random.default_rng(9)
    res = srv.query_batch(rng.normal(size=(6, DIM)).astype(np.float32),
                          [8] * 6)
    assert all(r.shards_touched >= 0 for r in res)
    if route == "exact":
        assert all(r.shards_touched == K for r in res)
    s = srv.stats.snapshot()
    assert s["invalid_touched"] == 0
    hist = srv.obs.metrics.get("serve.touched_shards").snapshot()
    assert hist["count"] == s["batches"]
    assert hist["min"] >= 0


READBACK_FORMS = {
    "selection": dict(),
    "gather": dict(sampler="gather"),
    "pruned_host": dict(route="pruned", route_compute="host"),
    "device_route": dict(route="pruned", route_compute="device"),
    "approx": dict(search="approx", index_buckets=4),
    "exact_predict": dict(predict="vote", num_classes=4),
    "ensemble": dict(predict="vote", num_classes=4,
                     predict_mode="ensemble"),
}


@pytest.mark.parametrize("form", list(READBACK_FORMS))
def test_readback_matches_plain_reads(mesh8, pts, monkeypatch, form):
    """The overlapped readback (every output's copy started as the launch
    returns, all read after one wait) hands the dispatch the same host
    arrays as a plain ``np.asarray`` per output: every QueryResult field
    it feeds is bit-identical, for each executable form the server
    builds.  The same batch is served twice by one server at the same
    key (the batch counter reset) and generation, once per readback."""
    from repro.runtime import knn_server as ks
    knobs = READBACK_FORMS[form]
    labels = (np.random.default_rng(5).integers(0, 4, N).astype(np.float32)
              if "predict" in knobs else None)
    srv = KnnServer(pts, labels=labels, mesh=mesh8, axis_name="x",
                    cfg=CONFIG.replace(dim=DIM, l_max=16, bucket_sizes=(4,),
                                       **knobs))
    qs = np.random.default_rng(6).normal(size=(3, DIM)).astype(np.float32)
    ls = [1, 5, 16]
    overlapped = srv.query_batch(qs, ls)
    copies = srv.obs.metrics.histogram("serve.copy_s").count

    def plain(out):
        def collect():
            host = (tuple(np.asarray(x) for x in out)
                    if isinstance(out, tuple) else np.asarray(out))
            return host, 0.0, 0.0
        return collect

    monkeypatch.setattr(ks, "_readback", plain)
    monkeypatch.setattr(ks._OneBuffer, "__call__",
                        lambda self, *args: self.inner(*args))
    srv._batch_counter = 0
    reads = srv.query_batch(qs, ls)
    srv.close()
    assert copies == 1
    for a, b in zip(overlapped, reads):
        assert a.dists.tobytes() == b.dists.tobytes()
        assert a.ids.tobytes() == b.ids.tobytes()
        assert (a.survivors, a.iterations) == (b.survivors, b.iterations)
        assert (a.label, a.confidence) == (b.label, b.confidence)
        assert a.generation == b.generation
    if "predict" in knobs:
        assert all(r.label is not None for r in overlapped)


# ---- one launch in flight -----------------------------------------------

def _pending_buckets(srv, qs, ls):
    """Submit every query before the micro-batcher starts, so the loop
    takes its chunks in the order flush() would: full buckets first."""
    return [srv.submit(q, l) for q, l in zip(qs, ls)]


def test_second_full_bucket_launches_before_first_is_read_back(mesh8, pts):
    """Two full buckets pending and a slow device (the kernels run in
    interpret mode): the loop launches the second while the first still
    runs, reads the first back only after, and ``serve.overlap`` counts
    one overlapped launch of two.  The ring stays well formed: each
    batch's launch and finish are sibling ``dispatch`` trees."""
    from repro.obs.trace import build_trees
    srv = _server(pts, mesh8, bucket_sizes=(4,), l_max=16, obs_trace=True)
    srv.warmup()
    qs = np.random.default_rng(11).normal(size=(8, DIM)).astype(np.float32)
    futs = _pending_buckets(srv, qs, [8] * 8)
    with srv.serving():
        res = [f.result(timeout=120) for f in futs]
    for r, q in zip(res, qs):
        bd, _ = _brute(pts, q[None], 8)
        np.testing.assert_allclose(r.dists, bd[0], rtol=1e-4)
    overlap = srv.obs_snapshot()["metrics"]["serve.overlap"]
    assert (overlap["count"], overlap["sum"]) == (2, 1.0)
    recs = srv.obs.tracer.spans()
    build_trees(recs)
    assert srv.obs.tracer.active_count() == 0
    by_id = {r["span"]: r for r in recs}

    def batch(r):
        while r["name"] != "dispatch":
            r = by_id[r["parent"]]
        return r["attrs"]["batch"]

    launch = {batch(r): r for r in recs if r["name"] == "kernel.launch"}
    readback = {batch(r): r for r in recs if r["name"] == "kernel.readback"}
    assert launch[1]["t0"] < readback[0]["t0"]
    assert readback[0]["t1"] <= readback[1]["t0"]
    dispatches = [r for r in recs if r["name"] == "dispatch"]
    assert sorted(d["attrs"]["batch"] for d in dispatches) == [0, 0, 1, 1]
    srv.close()


def _matrix_server(form, pts, mesh8):
    knobs = dict(dim=DIM, l_max=16, bucket_sizes=(4,), obs_trace=True)
    if form.startswith("store"):
        from repro.store import MutableStore
        cfg = CONFIG.replace(**knobs, store_capacity_per_shard=64,
                             sampler="gather" if form == "store_gather"
                             else "selection")
        store = MutableStore(DIM, mesh=mesh8, axis_name="x",
                             **cfg.store_kwargs())
        ids = store.insert(pts[:400])
        store.flush()
        store.delete(ids[::7])
        store.flush()
        return KnnServer(store=store, cfg=cfg), pts[:400]
    extra = {"static": {}, "gather": dict(sampler="gather"),
             "pruned_host": dict(route="pruned", route_compute="host"),
             "device_route": dict(route="pruned", route_compute="device"),
             "exact_predict": dict(predict="vote", num_classes=4)}[form]
    labels = (np.random.default_rng(5).integers(0, 4, N).astype(np.float32)
              if "predict" in extra else None)
    return KnnServer(pts, labels=labels, mesh=mesh8, axis_name="x",
                     cfg=CONFIG.replace(**knobs, **extra)), pts


@pytest.mark.parametrize("form", ["static", "gather", "store",
                                  "store_gather", "pruned_host",
                                  "device_route", "exact_predict"])
def test_overlapped_answers_match_a_loop_that_never_overlaps(mesh8, pts,
                                                              form):
    """The serving loop with a launch in flight answers bit for bit as
    flush(), which never overlaps: the same chunks, batch keys and
    generation, for each backing, sampler, route and exact predict."""
    from repro.obs.trace import build_trees
    srv, _ = _matrix_server(form, pts, mesh8)
    qs = np.random.default_rng(12).normal(size=(11, DIM)).astype(np.float32)
    ls = [1, 5, 16, 8, 3, 16, 2, 9, 16, 4, 7]
    futs = _pending_buckets(srv, qs, ls)
    with srv.serving():
        served = [f.result(timeout=120) for f in futs]
    assert srv.obs.metrics.histogram("serve.overlap").total >= 1.0
    build_trees(srv.obs.tracer.spans())
    srv._batch_counter = 0
    flushed = srv.query_batch(qs, ls)
    srv.close()
    assert [r.bucket for r in served] == [r.bucket for r in flushed]
    for a, b in zip(served, flushed):
        assert a.dists.tobytes() == b.dists.tobytes()
        assert a.ids.tobytes() == b.ids.tobytes()
        assert (a.survivors, a.iterations) == (b.survivors, b.iterations)
        assert (a.label, a.confidence) == (b.label, b.confidence)
        assert (a.generation, a.shards_touched) == (b.generation,
                                                    b.shards_touched)
    if form == "exact_predict":
        assert all(r.label is not None for r in served)


def test_stop_and_concurrent_flush_resolve_fifo_exactly_once(mesh8, pts,
                                                          monkeypatch):
    """stop() and a caller's flush() racing the loop while it has a batch
    in flight (its readback held back 50 ms): every future resolves
    exactly once (``stats.queries`` is the double-dispatch detector) and
    futures resolve in the order they were submitted, whichever thread
    launched their batch."""
    from repro.runtime import knn_server as ks
    real = ks._readback

    def slow_in_the_loop(out):
        collect = real(out)

        def later():
            if threading.current_thread().name == "knn-microbatcher":
                time.sleep(0.05)
            return collect()
        return later

    srv = _server(pts, mesh8, bucket_sizes=(4,), l_max=16, max_wait_ms=1.0)
    srv.warmup()
    monkeypatch.setattr(ks, "_readback", slow_in_the_loop)
    rng = np.random.default_rng(13)
    futs, order = [], []

    def submit(n):
        for q in rng.normal(size=(n, DIM)).astype(np.float32):
            f = srv.submit(q, 4)
            f.add_done_callback(lambda _, k=len(futs): order.append(k))
            futs.append(f)

    submit(8)
    srv.start()
    while srv._taken < 1:          # the loop has its first batch
        time.sleep(1e-4)
    submit(18)
    flusher = threading.Thread(target=srv.flush)
    flusher.start()
    submit(5)
    srv.stop()
    flusher.join()
    assert all(f.done() and f.exception() is None for f in futs)
    assert srv.stats.queries == len(futs)
    assert order == list(range(len(futs)))
    assert srv._taken == srv._finished
    srv.close()


def test_failed_next_launch_fails_only_its_own_futures(mesh8, pts):
    """The launch of batch n+1 raises while n is in flight: n+1's
    futures get the error, n still resolves, first, and the loop keeps
    serving."""
    srv = _server(pts, mesh8, bucket_sizes=(4,), l_max=16, obs_trace=True)
    srv.warmup()
    launches = []
    real = srv._fn

    def second_fails(*args):
        launches.append(len(launches))
        if len(launches) == 2:
            raise RuntimeError("launch failed")
        return real(*args)

    srv._fn = second_fails
    qs = np.random.default_rng(14).normal(size=(8, DIM)).astype(np.float32)
    futs = _pending_buckets(srv, qs, [8] * 8)
    order = []
    for k, f in enumerate(futs):
        f.add_done_callback(lambda _, k=k: order.append(k))
    with srv.serving():
        for f in futs[:4]:
            r = f.result(timeout=120)
            assert r.ids.shape == (8,)
        for f in futs[4:]:
            with pytest.raises(RuntimeError, match="launch failed"):
                f.result(timeout=120)
        again = srv.submit(qs[0], 8).result(timeout=120)
    assert order == list(range(8))
    assert again.ids.tobytes() == futs[0].result().ids.tobytes()
    assert srv.obs.metrics.value("serve.dispatch_errors") == 1
    # the failed launch was the overlapped one: only dispatches that
    # answer are observed
    overlap = srv.obs.metrics.histogram("serve.overlap")
    assert (overlap.count, overlap.total) == (2, 0.0)
    assert srv.obs.tracer.active_count() == 0
    srv.close()


def test_failed_finish_fails_its_own_futures_and_the_loop_serves_on(mesh8,
                                                                    pts):
    """A failure after the readback (here the shadow audit's replay, a
    launch of its own) hands that batch's futures the error; the batch
    launched over it still answers, its turn still comes, and stop()
    returns."""
    srv = _server(pts, mesh8, bucket_sizes=(4,), l_max=16, route="pruned",
                  obs_audit_every=1, obs_trace=True)
    srv.warmup()
    replay = srv._exact_replay
    calls = []

    def first_fails(*args):
        calls.append(None)
        if len(calls) == 1:
            raise RuntimeError("replay failed")
        return replay(*args)

    srv._exact_replay = first_fails
    qs = np.random.default_rng(15).normal(size=(8, DIM)).astype(np.float32)
    futs = _pending_buckets(srv, qs, [8] * 8)
    with srv.serving():
        for f in futs[:4]:
            with pytest.raises(RuntimeError, match="replay failed"):
                f.result(timeout=120)
        for f, q in zip(futs[4:], qs[4:]):
            bd, _ = _brute(pts, q[None], 8)
            np.testing.assert_allclose(f.result(timeout=120).dists, bd[0],
                                       rtol=1e-4)
    assert srv.obs.metrics.value("serve.dispatch_errors") == 1
    assert srv._taken == srv._finished == 2
    assert srv.obs.tracer.active_count() == 0
    srv.close()
