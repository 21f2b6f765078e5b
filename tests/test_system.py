"""End-to-end behaviour test: the paper's l-NN classification path.

The standalone service path: Algorithm 2 over a sharded point set, with
the winners' labels voted into a class prediction.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.core as core
from repro.parallel.compat import shard_map


def test_knn_service_path(mesh8, rng):
    """The quickstart service: classification over clusters."""
    from repro.data import gaussian_clusters
    n, dim, C, l = 8 * 512, 8, 4, 16
    pts, labels = gaussian_clusters(n, dim, C, seed=2)
    pids = np.arange(n, dtype=np.int32)
    centers_q = np.stack([pts[labels == c][:3].mean(0) for c in range(C)])

    def fn(p, i, lab, q, key):
        res = core.knn_query(p, i, q, l, key, axis_name="x",
                             gather_results=False)
        m = p.shape[0]
        start = jax.lax.axis_index("x") * m
        rows = jnp.clip(res.local_ids - start, 0, m - 1)
        pred, _ = core.knn_classify(res.mask, lab[rows], C, axis_name="x")
        return pred

    f = jax.jit(shard_map(
        fn, mesh=mesh8,
        in_specs=(P("x"), P("x"), P("x"), P(None), P(None)),
        out_specs=P(None)))
    pred = f(pts, pids, labels, centers_q.astype(np.float32),
             jax.random.PRNGKey(0))
    # cluster centers must classify to their own cluster
    assert np.asarray(pred).tolist() == list(range(C))
