"""The plain reference and the comparison that decides ``correct``.

The reference is an exact l-nearest-neighbour search by brute force in
float64 over the live set that the benchmark itself loaded and wrote, at
the generation each answer reports.  It imports nothing of the program
and takes nothing the program made: the points are the benchmark's own,
ids are the ones the benchmark assigned, and which ids are live at which
generation comes from the benchmark's log of its write batches.

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

import numpy as np

U32 = 2.0 ** -24
CHUNK = 1 << 17          # base points per scan block
MARGIN = 64              # candidates kept beyond l per query
NEVER = np.iinfo(np.int64).max


def gamma(dim: int) -> float:
    """float32 rounding-error factor gamma_{dim+2} of the expansion."""
    n = dim + 2
    return n * U32 / (1 - n * U32)


def f64_distances(q: np.ndarray, p: np.ndarray) -> np.ndarray:
    """(nq, m) squared L2 distances in float64."""
    q = q.astype(np.float64)
    p = p.astype(np.float64)
    return ((q * q).sum(1)[:, None] - 2.0 * (q @ p.T)
            + (p * p).sum(1)[None, :])


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


def _base_candidates(live: LiveSet, queries: np.ndarray, keep: int, dist):
    """(nq, keep) ids and distances of the nearest base points, by
    (distance, id).

    The keep-th smallest distance within the first block bounds each
    query's keep-th smallest over all points from above, so only the
    points at or below it are kept from each block."""
    nq = len(queries)
    keep = min(keep, live.n_base)
    d0 = dist(queries, live.base[:max(CHUNK, keep)])
    tau = np.partition(d0, keep - 1, axis=1)[:, keep - 1]
    rows, ids, ds = [], [], []
    for s in range(0, live.n_base, CHUNK):
        d = dist(queries, live.base[s:s + CHUNK])
        r, c = np.nonzero(d <= tau[:, None])
        rows.append(r)
        ids.append(s + c)
        ds.append(d[r, c])
    rows, ids, ds = (np.concatenate(x) for x in (rows, ids, ds))
    order = np.lexsort((ids, ds, rows))
    rows, ids, ds = rows[order], ids[order], ds[order]
    first = np.searchsorted(rows, np.arange(nq))
    take = first[:, None] + np.arange(keep)[None, :]
    return ids[take], ds[take]


def search(live: LiveSet, queries: np.ndarray, gens, ls, dist=f64_distances):
    """Exact l-NN of each query over the live set at its generation, by
    ``dist``; returns one ``(ids, dists)`` pair per query, ascending by
    (distance, id)."""
    queries = np.asarray(queries, np.float32)
    keep = int(max(ls)) + MARGIN
    cand_ids, cand_d = _base_candidates(live, queries, keep, dist)
    n_ins = len(live.inserted)
    ins_ids = np.arange(live.n_base, live.n_base + n_ins)
    ins_d = (dist(queries, live.inserted) if n_ins
             else np.empty((len(queries), 0)))
    out = []
    for j, (g, l) in enumerate(zip(gens, ls)):
        b_ok = live.alive(cand_ids[j], g)
        if b_ok.sum() < l and keep < live.n_base:
            # more deletions among the candidates than the margin covers:
            # scan every base point for this query
            d_all = dist(queries[j:j + 1], live.base)[0]
            all_ids = np.arange(live.n_base)
            ok = live.alive(all_ids, g)
            b_ids, b_d = all_ids[ok], d_all[ok]
        else:
            b_ids, b_d = cand_ids[j][b_ok], cand_d[j][b_ok]
        i_ok = live.alive(ins_ids, g) if n_ins else np.zeros(0, bool)
        ids = np.concatenate([b_ids, ins_ids[i_ok]])
        d = np.concatenate([b_d, ins_d[j][i_ok]])
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
