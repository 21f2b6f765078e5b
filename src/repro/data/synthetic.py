"""Deterministic synthetic point clouds for the kNN service.

The uniform generator mirrors the paper's experiment (Section 3: uniform
points in [0, 2^32)), generalized to d dimensions; the clustered and
labelled generators feed the routing, placement and prediction paths.
"""

from __future__ import annotations

import numpy as np


def uniform_points(n: int, dim: int, seed: int = 0,
                   high: float = 2**32 - 1) -> np.ndarray:
    """The paper's dataset: n points uniform in [0, high)^dim (f32)."""
    rng = np.random.default_rng(seed)
    return (rng.random((n, dim)) * high).astype(np.float32)


def gaussian_clusters(n: int, dim: int, num_classes: int, seed: int = 0):
    """Labeled clusters for the kNN classification example."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=8.0, size=(num_classes, dim))
    labels = rng.integers(0, num_classes, n)
    pts = centers[labels] + rng.normal(size=(n, dim))
    return pts.astype(np.float32), labels.astype(np.int32)


def labeled_mixture(n: int, dim: int, num_classes: int, *,
                    separation: float = 6.0, seed: int = 0):
    """Equal-prior isotropic Gaussian mixture with known Bayes-optimal
    labels — the prediction plane's benchmark workload.

    ``num_classes`` unit-variance isotropic components at mutual
    distance ~``separation``, equal priors: for that family the Bayes
    rule is exactly "nearest component center" (equal covariances and
    priors cancel in the likelihood ratio), so :func:`bayes_labels`
    gives the true optimum any predictor is scored against, and
    ``separation`` dials the Bayes error from coin-flip (0) to
    negligible (>= 8).  Returns ``(points (n, dim) f32, labels (n,)
    int32, centers (num_classes, dim) f64)`` — labels are the
    *component* assignments (identical to the Bayes label for all but
    the overlap-region points).  Seeded and deterministic: every
    (n, dim, num_classes, separation, seed) tuple replays the same
    instance, so the bench, the CI gate, and the property harness all
    score against the same ground truth.
    """
    rng = np.random.default_rng(seed)
    # Centers: random directions pushed to ~separation from the
    # centroid, so pairwise gaps scale with `separation`, not dim.
    raw = rng.normal(size=(num_classes, dim))
    raw = raw - raw.mean(axis=0)
    centers = raw / np.maximum(
        np.linalg.norm(raw, axis=1, keepdims=True), 1e-30) * separation
    labels = rng.integers(0, num_classes, n)
    pts = centers[labels] + rng.normal(size=(n, dim))
    return pts.astype(np.float32), labels.astype(np.int32), centers


def bayes_labels(points, centers) -> np.ndarray:
    """The Bayes-optimal label of each point under the
    :func:`labeled_mixture` family: the nearest component center
    (equal priors + equal isotropic covariances ⇒ the likelihood-ratio
    rule reduces to nearest-center; f64 host math, ties broken toward
    the lowest class like every vote in this repo)."""
    pts = np.asarray(points, np.float64)
    d = ((pts[:, None, :] - np.asarray(centers)[None]) ** 2).sum(-1)
    return d.argmin(axis=1).astype(np.int32)


def drifting_clusters(k: int, per_step: int, dim: int, *, steps: int,
                      drift: float = 4.0, scale: float = 12.0,
                      seed: int = 0):
    """Drifting-cluster stream: k gaussian clusters whose centers take a
    length-``drift`` random-walk step between emissions — the workload
    where reactive affinity placement goes stale and incremental summary
    radii inflate along the walked path (the adaptive-maintenance A/B,
    benchmarks/bench_serve.py; also driven by tests/test_adaptive.py).

    Yields ``steps`` pairs of (points (k·per_step, dim) f32 cluster-major
    — rows [c·per_step, (c+1)·per_step) near that step's centers[c] —
    and centers (k, dim) f64 *as used for that batch*).  Seeded and
    deterministic: the same (k, per_step, dim, steps, drift, scale, seed)
    always replays the same stream, so benchmark variants and tests can
    ingest the identical points under different store policies.
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=scale, size=(k, dim))
    for _ in range(steps):
        pts = np.concatenate(
            [centers[c] + rng.normal(size=(per_step, dim))
             for c in range(k)])
        yield pts.astype(np.float32), centers.copy()
        step = rng.normal(size=(k, dim))
        centers = centers + drift * step / np.maximum(
            np.linalg.norm(step, axis=1, keepdims=True), 1e-30)


def sharded_clusters(k: int, per_shard: int, dim: int, *, scale: float = 8.0,
                     shift: float = 0.0, seed: int = 0, rng=None):
    """One gaussian cluster per shard, laid out contiguously — the
    routing-friendly workload (shard j owns rows [j·m, (j+1)·m), all near
    centers[j]).  Used by the exactness harness (tests/test_routing.py)
    and the routing benches, which must measure the same instance family.

    ``shift`` pushes every center away from the origin (the f32
    catastrophic-cancellation stress).  Returns (points (k·m, dim) f32,
    centers (k, dim) f64).
    """
    if rng is None:
        rng = np.random.default_rng(seed)
    centers = rng.normal(scale=scale, size=(k, dim)) + shift
    pts = np.concatenate(
        [centers[j] + rng.normal(size=(per_shard, dim)) for j in range(k)])
    return pts.astype(np.float32), centers
