"""Exactness matrix: ``KnnServer`` answers against a float64 brute force.

One case per combination of the server's serving options and a query
scenario, each checked against an independent reference written here:
the float64 squared distances from every query to every live point.

Axes:

* backing — ``static`` (``points=``) or ``store`` (a ``MutableStore``);
* algorithm — ``selection`` with and without the Lemma 2.3 sample-prune
  (``use_sampling``), or the ``gather`` baseline;
* route — ``exact``, or ``pruned`` with the decision on the host or on
  the device (``route_compute``);
* scenario — ``single`` (one query, l = 1); ``mixed_l`` (three queries in
  a 4-row bucket at l = 1, 7, l_max, so a padding row rides along);
  ``clustered_far`` (a full 8-row bucket near cluster centres far from
  the origin at l = l_max, where pruning fires on the static layout);
  ``ties`` (queries among exact duplicate points, with l cutting through
  a tie); and, for store servers only, ``after_writes`` (64 inserts and
  64 deletes that include the queries' current nearest neighbours, then
  a flush).

The constructor accepts every combination of these axes, so none is left
out.

Every case checks, per answered row: l distinct live ids; each id's
reported distance and the sorted distances within float32 rounding of
the reference; every point strictly nearer than the l-th neighbour (by
more than that rounding) returned, so ids agree up to ties; and
``1 <= shards_touched <= k``.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.configs.knn_service import CONFIG
from repro.data import sharded_clusters
from repro.runtime import KnnServer
from repro.store import MutableStore

K = 8
M = 256                    # points a shard
DIM = 8
L_MAX = 32
BUCKETS = (1, 4, 8)
SHIFT = 16.0               # cluster centres ~50 from the origin, sd 1
CAP = M + 64               # store slots a shard: room for the inserts
F32_EPS = float(np.finfo(np.float32).eps)

ALGORITHMS = {
    "selection_sampled": dict(sampler="selection", use_sampling=True),
    "selection_unsampled": dict(sampler="selection", use_sampling=False),
    "gather": dict(sampler="gather"),
}
ROUTES = {
    "exact": dict(route="exact"),
    "pruned_host": dict(route="pruned", route_compute="host"),
    "pruned_device": dict(route="pruned", route_compute="device"),
}
SCENARIOS = ("single", "mixed_l", "clustered_far", "ties")
SEEDS = {"single": 0, "mixed_l": 1, "clustered_far": 2, "ties": 3,
         "after_writes": 4}

CASES = [(backing, algo, route, scenario)
         for backing in ("static", "store")
         for algo in ALGORITHMS
         for route in ROUTES
         for scenario in SCENARIOS + (("after_writes",)
                                      if backing == "store" else ())]


def _data():
    """Clusters far from the origin, one a shard in the static layout,
    with three points of each cluster tripled in place (exact ties)."""
    rng = np.random.default_rng(1808)
    pts, centers = sharded_clusters(K, M, DIM, shift=SHIFT, rng=rng)
    anchors = np.arange(K) * M            # first point of each cluster
    for a in anchors:
        pts[a + 1] = pts[a + 2] = pts[a]  # the same shard holds the copies
    return pts, centers, anchors


PTS, CENTERS, ANCHORS = _data()


def _queries(scenario: str, rng):
    """(queries (B, DIM) f32, ls) for a scenario."""
    if scenario == "single":
        q = PTS[rng.integers(0, len(PTS))] + rng.normal(size=DIM)
        return q[None].astype(np.float32), [1]
    if scenario == "mixed_l":
        near = PTS[rng.integers(0, len(PTS), 2)] + rng.normal(size=(2, DIM))
        # its l_max neighbours lie in two clusters, so in two shards
        between = (CENTERS[0] + CENTERS[1]) / 2
        return (np.vstack([near, between]).astype(np.float32),
                [1, 7, L_MAX])
    if scenario in ("clustered_far", "after_writes"):
        q = CENTERS[np.arange(8) % 2] + 0.5 * rng.normal(size=(8, DIM))
        return q.astype(np.float32), [L_MAX] * 8
    assert scenario == "ties"
    # at a tripled point (three ids at distance 0) and just off one
    # (three ids at one distance), with l = 1 and 2 inside the tie
    a = PTS[ANCHORS[:4]].astype(np.float64)
    q = a + np.array([0.0, 0.0, 0.25, 0.25])[:, None] * rng.normal(
        size=(4, DIM))
    return q.astype(np.float32), [1, 2, 2, L_MAX]


class _Served:
    """A server and the test's own record of the points it holds."""

    def __init__(self, server, ids, pts, store=None):
        self.server, self.store = server, store
        self.ids = np.asarray(ids, np.int64)
        self.pts = np.asarray(pts, np.float64)

    def close(self):
        self.server.close()
        if self.store is not None:
            self.store.close()


def _build(mesh, backing, algo, route) -> _Served:
    cfg = CONFIG.replace(dim=DIM, l=1, l_max=L_MAX, bucket_sizes=BUCKETS,
                         store_capacity_per_shard=CAP,
                         **ALGORITHMS[algo], **ROUTES[route])
    if backing == "static":
        srv = KnnServer(PTS, cfg=cfg, mesh=mesh, axis_name="x")
        s = _Served(srv, np.arange(len(PTS)), PTS)
    else:
        store = MutableStore(DIM, mesh=mesh, axis_name="x",
                             **cfg.store_kwargs())
        ids = store.insert(PTS)
        store.flush()
        s = _Served(KnnServer(store=store, cfg=cfg), ids, PTS, store)
    s.server.warmup()
    return s


@pytest.fixture(scope="module")
def served(mesh8):
    """Every server of the matrix, built and warmed once.  Three threads
    overlap one server's XLA compiles, which run outside the GIL, with
    another's tracing: about 40 s in place of 70 s on a CPU host."""
    keys = list(dict.fromkeys(c[:3] for c in CASES))
    with ThreadPoolExecutor(3) as pool:
        built = dict(zip(keys, pool.map(lambda k: _build(mesh8, *k),
                                        keys)))
    yield built
    for s in built.values():
        s.close()


def _check(s: _Served, queries, ls, results, min_generation):
    """Each answer against the float64 brute force over ``s``'s points."""
    q64 = queries.astype(np.float64)
    d_all = ((q64[:, None, :] - s.pts[None]) ** 2).sum(-1)
    pmax = np.linalg.norm(s.pts, axis=1).max()
    pos = {int(i): j for j, i in enumerate(s.ids)}
    for b, (r, l) in enumerate(zip(results, ls)):
        # f32 rounding of |q|^2 - 2 q.p + |p|^2 over DIM terms, summed
        tol = (DIM + 2) * F32_EPS * (np.linalg.norm(q64[b]) + pmax) ** 2
        assert r.l == l and len(r.ids) == l and len(r.dists) == l
        assert 1 <= r.shards_touched <= s.server.k
        assert r.generation >= min_generation
        ids = [int(i) for i in r.ids]
        assert len(set(ids)) == l, ids
        assert all(i in pos for i in ids), "id not live"
        d = d_all[b]
        mine = d[[pos[i] for i in ids]]
        np.testing.assert_allclose(r.dists, mine, rtol=0, atol=tol)
        ref = np.sort(d)[:l]
        np.testing.assert_allclose(r.dists, ref, rtol=0, atol=tol)
        # ids agree up to ties: everything nearer than the l-th
        # neighbour by more than the rounding must be answered
        must = s.ids[d < ref[-1] - tol]
        assert set(must.tolist()) <= set(ids), (b, l)


def _after_writes(s: _Served, queries, rng):
    """Insert 64 points near the queries, delete 64 live ids that include
    each query's current nearest neighbours, flush; return the flush's
    generation."""
    d_all = ((queries.astype(np.float64)[:, None, :] - s.pts[None]) ** 2
             ).sum(-1)
    nearest = np.unique(np.argsort(d_all, axis=1)[:, :4])
    rest = np.setdiff1d(np.arange(len(s.ids)), nearest)
    gone = np.concatenate(
        [nearest, rng.choice(rest, 64 - len(nearest), replace=False)])
    new = (queries[rng.integers(0, len(queries), 64)]
           + rng.normal(size=(64, DIM))).astype(np.float32)
    new_ids = s.server.insert(new)
    s.server.delete(s.ids[gone])
    gen = s.server.flush_store()
    keep = np.ones(len(s.ids), bool)
    keep[gone] = False
    s.ids = np.concatenate([s.ids[keep], np.asarray(new_ids, np.int64)])
    s.pts = np.vstack([s.pts[keep], new.astype(np.float64)])
    return gen


@pytest.mark.parametrize("backing,algo,route,scenario", CASES,
                         ids=["-".join(c) for c in CASES])
def test_server_matches_brute_force(served, backing, algo, route, scenario):
    s = served[backing, algo, route]
    rng = np.random.default_rng(SEEDS[scenario])
    queries, ls = _queries(scenario, rng)
    min_generation = 0 if s.store is None else s.store.generation
    if scenario == "after_writes":
        min_generation = _after_writes(s, queries, rng)
    results = s.server.query_batch(queries, ls)
    assert results[0].bucket == min(b for b in BUCKETS if b >= len(ls))
    _check(s, queries, ls, results, min_generation)
    if (backing, scenario) == ("static", "clustered_far") and \
            route != "exact":
        # the pruned route really prunes on this family
        assert all(r.shards_touched < K for r in results)
