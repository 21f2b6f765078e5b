"""Per-kernel shape/dtype sweeps against the ref.py oracles (interpret mode).

Contract (repo deliverable c): for each Pallas kernel, sweep shapes and
dtypes and assert_allclose against the pure-jnp oracle.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.distance_topk import distance_topk as dtk_kernel
from repro.kernels.l2_distance import l2_distance as l2_kernel
from repro.kernels.local_topk import local_topk as ltk_kernel

SHAPES = [  # (B, d, m)
    (8, 128, 256),
    (16, 256, 512),
    (1, 512, 1024),
    (13, 300, 777),     # padding path
    (4, 64, 96),        # padding path
]
DTYPES = [np.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=1.0) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_l2_distance_sweep(rng, shape, dtype):
    B, d, m = shape
    q = rng.normal(size=(B, d)).astype(np.float32).astype(dtype)
    p = rng.normal(size=(m, d)).astype(np.float32).astype(dtype)
    out = ops.l2_distance(q, p)
    want = ref.l2_distance_ref(q, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("l", [1, 16, 100])
def test_distance_topk_sweep(rng, shape, dtype, l):
    B, d, m = shape
    l = min(l, m)
    q = rng.normal(size=(B, d)).astype(np.float32).astype(dtype)
    p = rng.normal(size=(m, d)).astype(np.float32).astype(dtype)
    v, i = ops.distance_topk(q, p, l)
    rv, ri = ref.distance_topk_ref(q, p, l)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), **_tol(dtype))
    if dtype == np.float32:  # id sets only well-defined without bf16 ties
        for b in range(B):
            assert set(np.asarray(i)[b].tolist()) == set(
                np.asarray(ri)[b].tolist()), b


@pytest.mark.parametrize("shape", [(8, 512), (5, 1000), (16, 4096)])
@pytest.mark.parametrize("l", [1, 7, 128])
def test_local_topk_sweep(rng, shape, l):
    B, m = shape
    l = min(l, m)
    x = rng.normal(size=(B, m)).astype(np.float32)
    v, i = ops.local_topk(x, l)
    rv, ri = ref.local_topk_ref(x, l)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-5)
    assert (np.asarray(i) == np.asarray(ri)).all()


def test_duplicate_values_stable(rng):
    """Tie-break parity with lax.top_k (smaller index wins)."""
    x = np.round(rng.normal(size=(4, 512)), 1).astype(np.float32)
    v, i = ops.local_topk(x, 32)
    rv, ri = ref.local_topk_ref(x, 32)
    assert (np.asarray(i) == np.asarray(ri)).all()


def test_direct_kernel_blocks(rng):
    """Exercise non-default BlockSpec tilings on the raw kernels."""
    q = rng.normal(size=(16, 256)).astype(np.float32)
    p = rng.normal(size=(512, 256)).astype(np.float32)
    for bb, bm, bk in [(8, 128, 128), (16, 256, 256), (8, 512, 128)]:
        out = l2_kernel(q, p, block_b=bb, block_m=bm, block_k=bk,
                        interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.l2_distance_ref(q, p)),
                                   rtol=1e-4, atol=1e-3)
        v, i = dtk_kernel(q, p, 16, block_b=bb, block_m=bm, block_k=bk,
                          interpret=True)
        rv, _ = ref.distance_topk_ref(q, p, 16)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv),
                                   rtol=1e-4, atol=1e-3)
    x = rng.normal(size=(8, 1024)).astype(np.float32)
    for bb, bm in [(8, 256), (4, 512)]:
        v, i = ltk_kernel(x, 16, block_b=bb, block_m=bm, interpret=True)
        rv, ri = ref.local_topk_ref(x, 16)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-5)


def test_oracle_fallback_large_l(rng):
    """l > MAX_L must route to the oracle transparently."""
    q = rng.normal(size=(4, 64)).astype(np.float32)
    p = rng.normal(size=(2048, 64)).astype(np.float32)
    v, i = ops.distance_topk(q, p, 512)
    rv, ri = ref.distance_topk_ref(q, p, 512)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("l", [255, 256, 257])
def test_specialization_envelope_boundary(rng, l):
    """The l <= MAX_L (256) fused kernel and the l2_distance + lax.top_k
    fallback must agree across the routing seam: l = 255 and 256 run the
    kernel, 257 silently falls back — all three must match the oracle."""
    from repro.kernels.distance_topk import MAX_L
    assert MAX_L == 256            # the seam this test pins
    B, d, m = 4, 32, 512
    q = rng.normal(size=(B, d)).astype(np.float32)
    p = rng.normal(size=(m, d)).astype(np.float32)
    # routing truth, straight from the dispatcher's own gate
    _, reason = ops._fused_gate(l, d, 8, 256, 512)
    assert (reason is None) == (l <= MAX_L)
    v, i = ops.distance_topk(q, p, l)
    rv, ri = ref.distance_topk_ref(q, p, l)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-3)
    for b in range(B):
        assert set(np.asarray(i)[b].tolist()) == set(
            np.asarray(ri)[b].tolist()), b


@pytest.mark.parametrize("shape", [(8, 128, 256), (13, 300, 777)])
@pytest.mark.parametrize("l", [1, 16])
def test_masked_distance_topk_sweep(rng, shape, l):
    """The fused kernel's masked path (mutable-store tombstones) against
    the masked oracle: masked rows never appear, sentinel ids in +inf
    slots."""
    B, d, m = shape
    q = rng.normal(size=(B, d)).astype(np.float32)
    p = rng.normal(size=(m, d)).astype(np.float32)
    valid = rng.random(m) > 0.4
    v, i = ops.distance_topk(q, p, l, valid=valid)
    rv, ri = ref.masked_distance_topk_ref(q, p, valid, l)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-3)
    dead = set(np.flatnonzero(~valid).tolist())
    for b in range(B):
        got = set(np.asarray(i)[b].tolist())
        assert got == set(np.asarray(ri)[b].tolist()), b
        assert not (got & dead), "tombstoned id surfaced"


def test_masked_distance_topk_all_invalid(rng):
    """Fully-masked store shard: all +inf distances, all sentinel ids."""
    q = rng.normal(size=(4, 64)).astype(np.float32)
    p = rng.normal(size=(256, 64)).astype(np.float32)
    v, i = ops.distance_topk(q, p, 8, valid=np.zeros(256, bool))
    assert np.all(np.isinf(np.asarray(v)))
    assert np.all(np.asarray(i) == 2**31 - 1)


def test_masked_l2_distance(rng):
    q = rng.normal(size=(8, 128)).astype(np.float32)
    p = rng.normal(size=(256, 128)).astype(np.float32)
    valid = rng.random(256) > 0.5
    out = np.asarray(ops.l2_distance(q, p, valid=valid))
    want = np.asarray(ref.masked_l2_distance_ref(q, p, valid))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-3)
    assert np.all(np.isinf(out[:, ~valid]))


@pytest.mark.parametrize("path", ["l2_kernel", "dtk_kernel", "ref",
                                  "jnp"])
def test_distance_matmul_runs_at_full_f32_precision(path):
    """Every distance matmul of exact search asks for HIGHEST: on a TPU
    the default precision is one bf16 pass, whose error is ~100x the f32
    rounding bound the answers are checked against (chip_smoke.py)."""
    import jax
    from repro.core.knn import squared_l2_distances
    q, p = jnp.zeros((8, 128)), jnp.zeros((256, 128))
    fn = {
        "l2_kernel": lambda: l2_kernel(jnp.zeros((128, 128)), p,
                                       block_k=128, interpret=True),
        "dtk_kernel": lambda: dtk_kernel(q, p, 8, block_k=128,
                                         interpret=True),
        "ref": lambda: ref.l2_distance_ref(q, p),
        "jnp": lambda: squared_l2_distances(q, p),
    }[path]
    jaxpr = str(jax.make_jaxpr(fn)())
    assert "dot_general" in jaxpr
    assert jaxpr.count("precision=(Precision.HIGHEST") == jaxpr.count(
        "dot_general")
