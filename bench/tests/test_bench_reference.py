"""The float64 reference against a plain oracle: each query's float64
distance to every point live at its generation, and one full sort.

The reference screens blocks in float32 on a thread pool and computes
only the points that can be nearest in float64; its answers must be the
oracle's bit for bit, with one thread or several and at any block size.
"""

import numpy as np
import pytest

from bench import data, reference

SEED = 2**31 + 4242
DIM = 24
SCANS = [(1, 256), (4, 1000)]          # (threads, base points per block)


def oracle(live, queries, gens, ls, dist=None):
    """Every live point's distance, sorted by (distance, id)."""
    ids = np.arange(live.n_ids)
    pts = live.points_of(ids)
    p64 = pts.astype(np.float64)
    out = []
    for q, g, l in zip(queries, gens, ls):
        if dist is None:
            q64 = np.asarray(q, np.float64)[None, :]
            d = (q64 * q64).sum(1) - 2.0 * (p64 * q64).sum(1) \
                + (p64 * p64).sum(1)
        else:
            d = dist(np.asarray(q, np.float32)[None, :], pts)[0]
        ok = live.alive(ids, g)
        order = np.lexsort((ids[ok], d[ok]))[:l]
        out.append((ids[ok][order], d[ok][order]))
    return out


def clusters(n, tag="points"):
    ctrs = data.centers(SEED, 4, DIM, 8.0)
    return data.cluster_points(SEED, tag, n, ctrs), ctrs


def base_only():
    pts, ctrs = clusters(3000)
    live = reference.LiveSet(pts, 0)
    qs = data.cluster_points(SEED, "queries", 40, ctrs)
    ls = data.rng(SEED, "l").choice([1, 10, 50], len(qs))
    return live, qs, [0] * len(qs), ls


def writes():
    """Inserts and deletes over two generations; queries at all three."""
    pts, ctrs = clusters(3000)
    live = reference.LiveSet(pts, 0)
    rng = data.rng(SEED, "writes")
    ins1 = data.cluster_points(SEED, "ins1", 200, ctrs)
    ins2 = data.cluster_points(SEED, "ins2", 100, ctrs)
    live.log([(1, np.arange(3000, 3200), ins1,
               rng.choice(3000, 300, replace=False))])
    live.log([(2, np.arange(3200, 3300), ins2,
               np.concatenate([rng.choice(3200, 150, replace=False)]))])
    qs = data.cluster_points(SEED, "queries", 45, ctrs)
    gens = [i % 3 for i in range(len(qs))]
    ls = [10 if i % 2 else 40 for i in range(len(qs))]
    return live, qs, gens, ls


def l_near_keep():
    """l close to the candidates kept per query, and to the base size."""
    pts, ctrs = clusters(400)
    live = reference.LiveSet(pts, 0)
    qs = data.cluster_points(SEED, "queries", 30, ctrs)
    ls = [300 if i % 3 else 330 for i in range(len(qs))]
    live.log([(1, np.arange(400, 400), np.empty((0, DIM), np.float32),
               np.arange(0, 400, 7))])
    return live, qs, [i % 2 for i in range(len(qs))], ls


def ties():
    """Integer points, each twice over: many exactly equal distances."""
    g = data.rng(SEED, "ties")
    pts = g.integers(-3, 4, (1500, DIM)).astype(np.float32)
    pts = np.concatenate([pts, pts[::-1]])
    live = reference.LiveSet(pts, 0)
    qs = np.concatenate([pts[:20], g.integers(-3, 4, (20, DIM))]).astype(
        np.float32)
    return live, qs, [0] * len(qs), [5 if i % 2 else 60
                                     for i in range(len(qs))]


def mostly_deleted():
    """Most of each query's nearest points deleted: its candidates cannot
    hold l live ones, so the reference scans every base point for it."""
    pts, ctrs = clusters(3000)
    labels = np.argmin(((pts[:, None, :] - ctrs[None]) ** 2).sum(2), 1)
    live = reference.LiveSet(pts, 0)
    gone = np.flatnonzero(labels == 0)[10:]
    live.log([(1, np.arange(3000, 3000), np.empty((0, DIM), np.float32),
               gone)])
    qs = data.cluster_points(SEED, "queries", 12, ctrs,
                             labels=np.zeros(12, np.int64))
    return live, qs, [1] * len(qs), [30] * len(qs)


def far_from_origin():
    """Tight clusters far from the origin: the float32 expansion's
    rounding error exceeds the gaps between the nearest distances, so the
    screen's order is not float64's and only its margin keeps them."""
    ctrs = data.centers(SEED, 4, DIM, 400.0)
    pts = data.cluster_points(SEED, "points", 3000, ctrs)
    live = reference.LiveSet(pts, 0)
    live.log([(1, np.arange(3000, 3100),
               data.cluster_points(SEED, "ins", 100, ctrs),
               np.arange(0, 3000, 5))])
    qs = data.cluster_points(SEED, "queries", 30, ctrs)
    return live, qs, [i % 2 for i in range(len(qs))], [10] * len(qs)


CASES = {"base_only": base_only, "far_from_origin": far_from_origin,
         "writes": writes, "l_near_keep": l_near_keep, "ties": ties,
         "mostly_deleted": mostly_deleted}


def assert_same(got, want):
    assert len(got) == len(want)
    for (ids, d), (w_ids, w_d) in zip(got, want):
        np.testing.assert_array_equal(ids, w_ids)
        assert d.dtype == np.float64
        assert d.tobytes() == w_d.tobytes()


@pytest.fixture
def scan(request, monkeypatch):
    workers, chunk = request.param
    monkeypatch.setattr(reference, "WORKERS", workers)
    monkeypatch.setattr(reference, "CHUNK", chunk)
    monkeypatch.setattr(reference, "SAMPLE", 512)


@pytest.mark.parametrize("scan", SCANS, indirect=True)
@pytest.mark.parametrize("case", CASES)
def test_search_is_the_full_sort(case, scan):
    live, qs, gens, ls = CASES[case]()
    assert_same(reference.search(live, qs, gens, ls),
                oracle(live, qs, gens, ls))


def _rowwise(q, p):
    """A plug-in distance: float64 per query, as the control is one."""
    return np.stack([reference.f64_distance(x, p) for x in q])


@pytest.mark.parametrize("scan", SCANS, indirect=True)
@pytest.mark.parametrize("case", ["writes", "ties", "mostly_deleted"])
def test_a_plugged_in_distance_takes_the_same_path(case, scan):
    live, qs, gens, ls = CASES[case]()
    assert_same(reference.search(live, qs, gens, ls, _rowwise),
                oracle(live, qs, gens, ls, _rowwise))


def test_the_deleted_case_scans_every_point_for_its_queries(monkeypatch):
    """``mostly_deleted`` reaches the per-query scan; the others do not."""
    monkeypatch.setattr(reference, "SAMPLE", 512)
    scanned = []
    whole = reference._scan

    def counted(live, metric, rows, bound):
        scanned.append(len(rows))
        return whole(live, metric, rows, bound)
    monkeypatch.setattr(reference, "_scan", counted)
    for name, case in CASES.items():
        scanned.clear()
        live, qs, gens, ls = case()
        reference.search(live, qs, gens, ls)
        per_query = scanned[1:]
        assert (len(per_query) > 0) == (name == "mostly_deleted"), name


def test_blocks_in_flight_stay_within_the_pool(monkeypatch):
    """512 queries at 8,192 points a block: at most POOL_BYTES held."""
    monkeypatch.setattr(reference, "WORKERS", 1000)
    seen = []
    real = reference.ThreadPoolExecutor

    def pool(workers):
        seen.append(workers)
        return real(workers)
    monkeypatch.setattr(reference, "ThreadPoolExecutor", pool)
    reference._map(lambda s: s, range(0, 1 << 20, reference.CHUNK), 512)
    assert seen and seen[0] * 512 * reference.CHUNK \
        * reference.BLOCK_BYTES <= reference.POOL_BYTES
