"""Points and queries made from the run's seed, in float32 chunks.

The distribution is the repo's ``data/synthetic.gaussian_clusters``:
``clusters`` centres drawn N(0, center_scale^2) per coordinate, each point
a uniformly chosen centre plus N(0, 1) noise.  That function builds the
whole set in float64 in one piece (16 GiB at 2^24 x 128); this copy fills
a float32 array chunk by chunk, each chunk from its own child of the
seed, on a few threads (numpy's generators release the GIL).  The result
depends on the seed and the stream's tag only, never on the thread count.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 16


def seed_sequence(seed: int, tag: str) -> np.random.SeedSequence:
    """One independent stream per (seed, tag); seeds of any size."""
    return np.random.SeedSequence([seed % 2**64, *tag.encode()])


def rng(seed: int, tag: str) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, tag))


def centers(seed: int, clusters: int, dim: int,
            scale: float) -> np.ndarray:
    return rng(seed, "centers").normal(
        scale=scale, size=(clusters, dim)).astype(np.float32)


def cluster_points(seed: int, tag: str, n: int, ctrs: np.ndarray,
                   threads: int = 8, labels: np.ndarray = None,
                   with_labels: bool = False):
    """(n, dim) float32 points of the mixture around ``ctrs``.

    ``labels`` fixes each point's centre instead of drawing it;
    ``with_labels`` also returns the (n,) centre of each point."""
    dim = ctrs.shape[1]
    out = np.empty((n, dim), np.float32)
    lab_out = np.empty(n, np.int64)
    starts = range(0, n, CHUNK)
    children = seed_sequence(seed, tag).spawn(len(starts))

    def fill(i: int) -> None:
        s = starts[i]
        e = min(s + CHUNK, n)
        g = np.random.default_rng(children[i])
        lab = g.integers(0, len(ctrs), e - s)
        if labels is not None:
            lab = labels[s:e]
        g.standard_normal((e - s, dim), dtype=np.float32, out=out[s:e])
        out[s:e] += ctrs[lab]
        lab_out[s:e] = lab

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(len(starts))))
    return (out, lab_out) if with_labels else out
