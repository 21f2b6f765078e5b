"""The plain reference and the comparison that decides ``correct``.

The reference is an exact l-nearest-neighbour search by brute force in
float64 over the live set that the benchmark itself loaded and wrote, at
the generation each answer reports.  It imports nothing of the program
and takes nothing the program made: the points are the benchmark's own,
ids are the ones the benchmark assigned, and which ids are live at which
generation comes from the benchmark's log of its write batches.

Its distance is ``f64_distance``: |q|^2 - 2 q.p + |p|^2 in float64, each
term a plain numpy row sum, so that a distance depends on its two points
alone (a BLAS product's last bit depends on where its block and thread
split put the column).  The brute force screens every base point in
float32 blocks on a thread pool, keeping each point whose screened
distance lies within twice its float32 rounding bound of the query's
threshold, and computes only the few that can be nearest in float64: the
answer is the one a full float64 sort over every live point gives, bit
for bit, whatever the block size or the number of threads.

The comparison follows ``chip_smoke.py`` (float64 brute force, float32
rounding-bound tie rule), copied here so the yardstick cannot move with
the program.  For each checked answer:

* every returned id must be live at the answer's generation, and the l
  ids distinct; otherwise the answer counts l (or its dead ids) towards
  ``id_miss``;
* rank by rank, an id that differs from the reference's counts towards
  ``id_miss`` unless the two points' float64 distances lie within the
  sum of their float32 rounding bounds (a tie);
* ``dist_err`` is the largest |returned distance - float64 distance|
  over the bound ``GAMMA * (|q| + |p|)^2`` of the distance expansion
  ``|q|^2 - 2 q.p + |p|^2`` in float32 over ``dim`` terms.

The control is the same search with the distance computed at the
precision below the one the program states: the program's distance
matmul runs at float32 HIGHEST, so the control's runs at ``high``, three
bfloat16 passes (the product of the low halves dropped), written out
explicitly so it means the same on every backend.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from threadpoolctl import threadpool_limits

U32 = 2.0 ** -24
CHUNK = 1 << 13          # base points per scan block
SAMPLE = 1 << 17         # leading base points that set each threshold
MARGIN = 64              # candidates kept beyond l per query
NEVER = np.iinfo(np.int64).max
WORKERS = os.cpu_count() or 1
POOL_BYTES = 4 << 30     # blocks in flight, all workers together
BLOCK_BYTES = 16         # per query and point of a block in flight


def gamma(dim: int) -> float:
    """float32 rounding-error factor gamma_{dim+2} of the expansion."""
    n = dim + 2
    return n * U32 / (1 - n * U32)


def f64_distance(q: np.ndarray, p: np.ndarray) -> np.ndarray:
    """(m,) squared L2 distances of one query ``q`` (dim,) to ``p``
    (m, dim) in float64, ``|q|^2 - 2 q.p + |p|^2`` with each term a row
    sum of float64 products."""
    q = np.asarray(q, np.float64)[None, :]
    p = np.asarray(p, np.float64)
    return (q * q).sum(1) - 2.0 * (p * q).sum(1) + (p * p).sum(1)


_HIGH3 = None


def high3_distances(q: np.ndarray, p: np.ndarray) -> np.ndarray:
    """The control's distances: the dot product in three bfloat16 passes
    (hi*hi + hi*lo + lo*hi, float32 accumulation), the norms in float32;
    computed with JAX on the default device.

    The halves are rounded with ``reduce_precision``, not by a round trip
    through bfloat16, which XLA may drop as excess precision; products of
    bfloat16 values are exact in float32, so HIGHEST adds nothing to them.
    """
    global _HIGH3
    if _HIGH3 is None:
        import jax
        import jax.numpy as jnp
        from jax import lax

        def bf16(x):
            return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

        def split(x):
            hi = bf16(x)
            return hi, bf16(x - hi)

        def dot(a, b):
            return jnp.dot(a, b.T, precision=lax.Precision.HIGHEST)

        def fn(q, p):
            qh, ql = split(q)
            ph, pl = split(p)
            qp = dot(qh, ph) + dot(qh, pl) + dot(ql, ph)
            return ((q * q).sum(1)[:, None] - 2.0 * qp
                    + (p * p).sum(1)[None, :])

        _HIGH3 = jax.jit(fn)
    out = _HIGH3(np.asarray(q, np.float32), np.asarray(p, np.float32))
    return np.asarray(out, np.float64)


class LiveSet:
    """The benchmark's own record of the store's contents.

    Ids ``0 .. n-1`` are the loaded points, live from generation ``gen0``;
    each logged write batch inserts the next ids and deletes some live
    ones, both taking effect at the generation its flush returned.
    """

    def __init__(self, points: np.ndarray, gen0: int):
        self.base = points
        self.n_base = len(points)
        self.gen0 = gen0
        self.generations = {gen0}
        self.inserted = np.empty((0, points.shape[1]), np.float32)
        self._gen_in = np.empty(0, np.int64)            # per inserted id
        self._gen_out = np.full(self.n_base, NEVER)     # per id

    @property
    def n_ids(self) -> int:
        return self.n_base + len(self.inserted)

    def log(self, batches) -> None:
        """Apply write batches ``(gen, ins_ids, ins_pts, del_ids)``."""
        batches = list(batches)
        if not batches:
            return
        ins_ids = np.concatenate(
            [np.asarray(b[1], np.int64) for b in batches])
        expect = np.arange(self.n_ids, self.n_ids + len(ins_ids))
        if not np.array_equal(ins_ids, expect):
            raise ValueError("inserted ids must continue the id sequence")
        self.inserted = np.concatenate(
            [self.inserted] + [np.asarray(b[2], np.float32)
                               for b in batches])
        self._gen_in = np.concatenate(
            [self._gen_in] + [np.full(len(b[1]), b[0], np.int64)
                              for b in batches])
        self._gen_out = np.concatenate(
            [self._gen_out, np.full(len(ins_ids), NEVER)])
        for gen, _, _, del_ids in batches:
            self._gen_out[np.asarray(del_ids, np.int64)] = gen
            self.generations.add(gen)

    def points_of(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        out = np.empty((len(ids), self.base.shape[1]), np.float32)
        b = ids < self.n_base
        out[b] = self.base[ids[b]]
        out[~b] = self.inserted[ids[~b] - self.n_base]
        return out

    def alive(self, ids: np.ndarray, gen: int) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        ok = (ids >= 0) & (ids < self.n_ids)
        safe = np.where(ok, ids, 0)
        gen_in = np.full(len(ids), self.gen0, np.int64)
        new = safe >= self.n_base
        gen_in[new] = self._gen_in[safe[new] - self.n_base]
        return ok & (gen_in <= gen) & (gen < self._gen_out[safe])


class _Float32Screen:
    """The float64 reference's scan.  A block is screened in float32 as
    ``|p|^2 - 2 q.p`` (``|q|^2``, the same along a row, is added after),
    with a margin of twice the float32 rounding bound of the expansion,
    ``gamma * (|q| + |p|)^2``, at the block's largest ``|p|``; the points
    that can be nearest are computed again by ``f64_distance``."""

    def __init__(self, queries: np.ndarray):
        self.q = queries
        q64 = queries.astype(np.float64)
        self.qn = (q64 * q64).sum(1)
        self.qabs = np.sqrt(self.qn)
        self.two_gamma = 2.0 * gamma(queries.shape[1])

    def block(self, rows: np.ndarray, p: np.ndarray) -> tuple:
        """Screened distances of queries ``rows`` to ``p`` less their
        ``offset`` (len(rows), m); each row's margin and offset."""
        pn = (p * p).sum(1)
        s = (-2.0 * self.q[rows]) @ p.T
        s += pn
        pmax = np.sqrt(float(pn.max()) * (1.0 + self.two_gamma))
        return (s, self.two_gamma * (self.qabs[rows] + pmax) ** 2,
                self.qn[rows])

    def exact(self, j: int, p: np.ndarray) -> np.ndarray:
        return f64_distance(self.q[j], p)


class _Plain:
    """Distances by ``dist`` as they are: no margin, none computed again."""

    exact = None

    def __init__(self, queries: np.ndarray, dist):
        self.q = queries
        self.dist = dist

    def block(self, rows: np.ndarray, p: np.ndarray) -> tuple:
        zero = np.zeros(len(rows))
        return np.asarray(self.dist(self.q[rows], p), np.float64), zero, zero


def _map(fn, starts: range, nq: int) -> list:
    """``fn`` over the blocks at ``starts``, results in block order, on as
    many threads as keep the blocks in flight within ``POOL_BYTES``."""
    workers = min(WORKERS, len(starts),
                  POOL_BYTES // (BLOCK_BYTES * max(nq, 1) * CHUNK))
    if workers <= 1:
        return [fn(s) for s in starts]
    # one BLAS thread per block: the threads here are the parallelism
    with threadpool_limits(1, user_api="blas"), \
            ThreadPoolExecutor(workers) as pool:
        return list(pool.map(fn, starts))


def _at_least(x: np.ndarray, dtype) -> np.ndarray:
    """The least values of ``dtype`` not below ``x``."""
    y = x.astype(dtype)
    return np.where(y < x, np.nextafter(y, np.array(np.inf, dtype)), y)


def _bound(live: LiveSet, metric, keep: int) -> np.ndarray:
    """Per query, an upper bound on the keep-th smallest distance over
    the base: the keep-th smallest screened one over the leading
    ``SAMPLE`` points, plus the margin."""
    nq = len(metric.q)
    if live.n_base <= keep:
        return np.full(nq, np.inf)
    n = min(live.n_base, max(SAMPLE, keep))
    every = np.arange(nq)

    def one(start):
        s, e, off = metric.block(every, live.base[start:min(start + CHUNK,
                                                                n)])
        k = min(keep, s.shape[1])
        return np.partition(s, k - 1, axis=1)[:, :k] + off[:, None], e

    parts = _map(one, range(0, n, CHUNK), nq)
    low = np.concatenate([p[0] for p in parts], axis=1)
    return (np.partition(low, keep - 1, axis=1)[:, keep - 1]
            + np.max([p[1] for p in parts], axis=0))


def _scan(live: LiveSet, metric, rows: np.ndarray, bound: np.ndarray):
    """Every base point that can lie at or below ``bound`` from a query of
    ``rows``: (first, ids, screened, margin), grouped by query in the
    order of ``rows``, ascending by id within; query i's run starts at
    ``first[i]``."""

    def one(start):
        s, e, off = metric.block(rows, live.base[start:start + CHUNK])
        r, c = np.nonzero(s <= _at_least(bound + e - off, s.dtype)[:, None])
        return r, start + c, s[r, c] + off[r], e[r]

    parts = _map(one, range(0, live.n_base, CHUNK), len(rows))
    r, ids, s, e = (np.concatenate(x) for x in zip(*parts))
    order = np.argsort(r, kind="stable")
    first = np.searchsorted(r[order], np.arange(len(rows) + 1))
    return first, ids[order], s[order], e[order]


def search(live: LiveSet, queries, gens, ls, dist=None):
    """Exact l-NN of each query over the live set at its generation, by
    ``f64_distance``, or by ``dist`` ((nq, dim), (m, dim) -> (nq, m), the
    control's); returns one ``(ids, dists)`` pair per query, ascending by
    (distance, id)."""
    queries = np.asarray(queries, np.float32).reshape(
        -1, live.base.shape[1])
    if not len(queries):
        return []
    metric = _Float32Screen(queries) if dist is None else _Plain(queries,
                                                                 dist)
    keep = int(max(ls)) + MARGIN
    every = np.arange(len(queries))
    bound = _bound(live, metric, keep)
    first, cand_ids, cand_s, cand_e = _scan(live, metric, every, bound)
    n_ins = len(live.inserted)
    ins_ids = np.arange(live.n_base, live.n_base + n_ins)
    if n_ins:
        ins_s, ins_e, off = metric.block(every, live.inserted)
        ins_s = ins_s + off[:, None]
    out = []
    for j, (g, l) in enumerate(zip(gens, ls)):
        run = slice(first[j], first[j + 1])
        ids, s, e = cand_ids[run], cand_s[run], cand_e[run]
        ok = live.alive(ids, g)
        if (np.isfinite(bound[j])
                and np.count_nonzero(ok & (s + e <= bound[j])) < l):
            # more deletions among the candidates than the margin covers:
            # every base point for this query
            _, ids, s, e = _scan(live, metric, every[j:j + 1],
                                 np.array([np.inf]))
            ok = live.alive(ids, g)
        ids, s, e = ids[ok], s[ok], e[ok]
        if n_ins:
            i_ok = live.alive(ins_ids, g)
            ids = np.concatenate([ids, ins_ids[i_ok]])
            s = np.concatenate([s, ins_s[j][i_ok]])
            e = np.concatenate([e, np.full(np.count_nonzero(i_ok),
                                           ins_e[j])])
        if len(ids) > l:
            # the l nearest lie within their margins of the l-th bound
            near = s - e <= np.partition(s + e, l - 1)[l - 1]
            ids, s = ids[near], s[near]
        d = s if metric.exact is None else metric.exact(
            j, live.points_of(ids))
        order = np.lexsort((ids, d))[:l]
        out.append((ids[order], d[order]))
    return out


def compare(live: LiveSet, answers: list, truth: list) -> dict:
    """Numbers compared for ``correct`` over ``answers``.

    ``answers``: ``(query, l, generation, ids, dists)`` per checked
    answer; ``truth``: the float64 ``search`` result for each."""
    g = gamma(live.base.shape[1])
    id_miss = 0
    dist_err = 0.0
    for (q, l, gen, ids, dists), (want_ids, want_d) in zip(answers, truth):
        ids = np.asarray(ids, np.int64)
        if gen not in live.generations:
            id_miss += l
            continue
        ok = live.alive(ids, gen)
        if not ok.all() or len(set(ids.tolist())) != l:
            id_miss += int(max((~ok).sum(), 1))
            continue
        q64 = np.asarray(q, np.float64)
        p = live.points_of(ids).astype(np.float64)
        d64 = ((p - q64) ** 2).sum(1)
        qn = np.linalg.norm(q64)
        bound = g * (qn + np.linalg.norm(p, axis=1)) ** 2
        err = np.abs(np.asarray(dists, np.float64) - d64) / bound
        dist_err = max(dist_err, float(err.max()))
        differ = ids != want_ids
        if differ.any():
            wp = live.points_of(want_ids[differ]).astype(np.float64)
            w_bound = g * (qn + np.linalg.norm(wp, axis=1)) ** 2
            tie = (np.abs(d64[differ] - want_d[differ])
                   <= bound[differ] + w_bound)
            id_miss += int((~tie).sum())
    return {"id_miss": id_miss, "dist_err": dist_err}
