"""Blocked squared-L2 distance matrix — Pallas TPU kernel.

The compute hot-spot of the paper's l-NN pipeline (Algorithm 2, Step 8:
``d_ij = dis(p_ij, q)`` for every local point) is a matmul in disguise:

    ||q - p||^2 = ||q||^2 - 2 q.p + ||p||^2

so the kernel is a (B, d) x (d, m) MXU contraction with a rank-1 epilogue.

Two orientations of the point operand, so the kernel reads the point
buffer where and how it lies in device memory (no per-call copy of it):

* **rows** — ``points`` is (m, d), tiled (bm, bk).  The device keeps an
  f32 (m, d) array row-major when d is a multiple of 128 (the 128-d
  servers, ``chip_smoke.py``).
* **cols** — ``points`` is the (d, m) view ``p.T`` of an array the device
  keeps column-major, tiled (bk, bm): the contraction is a plain
  (bb, d) x (d, bm) matmul and ||p||^2 sums over sublanes.  The device
  picks that layout when it pads less, e.g. at d = 100 (104 sublanes
  against 128 lanes), so ``p.T`` is a free bitcast there.

``ops.l2_distance`` picks the orientation from the shape alone
(``ops.points_transposed``) and sizes the blocks: the whole width in one
block (bk = d, no lane pad) whenever the tiles fit the VMEM budget, the
query block the batch rounded up to the dtype's sublane multiple, and a
``pl.cdiv`` grid over m whose last block may be ragged (out-of-range
columns are computed from whatever the block holds and never written;
each output column depends on its own point only).  Only a width too
wide for VMEM keeps a k grid axis: the f32 accumulator tile (bb, bm)
then lives in VMEM scratch across the k-steps, with the squared-norm
partial sums in two skinny scratch columns, so HBM still sees each
operand once.

The dispatcher tallies the form of each compiled specialization in the
process registry, ``kernel.l2_distance.form.rows`` / ``.cols`` (at trace
time, so once per specialization, not per launch); it shows under
``KnnServer.obs_snapshot()["kernel"]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_K = 256


def _partials(q_ref, p_ref, cols: bool):
    """q.p, ||q||^2 and ||p||^2 of one (q tile, point tile) pair."""
    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    # MXU contraction at full f32 precision (the default runs as one bf16
    # pass on TPU, far outside the f32 rounding bound exact search is
    # held to): (bb, bk) x (bk, bm), the point tile (bk, bm) in the cols
    # form and (bm, bk) in the rows form.
    qp = jax.lax.dot_general(
        q, p, (((1,), (0,) if cols else (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # Norm partials on the VPU, same operands, no extra HBM traffic.
    q2 = jnp.sum(q * q, axis=1, keepdims=True)
    p2 = (jnp.sum(p * p, axis=0, keepdims=True) if cols
          else jnp.sum(p * p, axis=1)[None, :])
    return qp, q2, p2


def _epilogue(out_ref, q2, qp, p2):
    out_ref[...] = jnp.maximum(q2 - 2.0 * qp + p2, 0.0).astype(out_ref.dtype)


def _kernel(q_ref, p_ref, out_ref, *, cols: bool):
    """One (i, j) grid step over the whole width."""
    qp, q2, p2 = _partials(q_ref, p_ref, cols)
    _epilogue(out_ref, q2, qp, p2)


def _kernel_k(q_ref, p_ref, out_ref, acc_ref, q2_ref, p2_ref, *, nk: int,
              cols: bool):
    """One (i, j, k) grid step of a width split into nk blocks.

    acc_ref: (bb, bm) f32 accumulator; q2_ref: (bb, 1) running ||q||^2;
    p2_ref: (1, bm) running ||p||^2.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q2_ref[...] = jnp.zeros_like(q2_ref)
        p2_ref[...] = jnp.zeros_like(p2_ref)

    qp, q2, p2 = _partials(q_ref, p_ref, cols)
    acc_ref[...] += qp
    q2_ref[...] += q2
    p2_ref[...] += p2

    @pl.when(k == nk - 1)
    def _done():
        _epilogue(out_ref, q2_ref[...], acc_ref[...], p2_ref[...])


def l2_distance(
    queries: jax.Array,
    points: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    points_transposed: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """(B, d) x (m, d) -> (B, m) squared distances; with
    ``points_transposed`` the points come as (d, m) (the cols form).

    B and d must divide ``block_b`` and ``block_k``; m need not divide
    ``block_m`` (ragged last block).  ``ops.l2_distance`` is the
    general-shape entry point that picks the form and the blocks.
    """
    B, d = queries.shape
    d2, m = points.shape if points_transposed else points.shape[::-1]
    assert d == d2, (d, d2)
    assert B % block_b == 0 and d % block_k == 0, (
        "B and d must divide their blocks; call ops.l2_distance")
    nb, nm, nk = B // block_b, pl.cdiv(m, block_m), d // block_k
    cols = points_transposed
    if cols:
        p_spec = pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j))
    else:
        p_spec = pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k))
    if nk == 1:
        kernel = functools.partial(_kernel, cols=cols)
        scratch = []
    else:
        kernel = functools.partial(_kernel_k, nk=nk, cols=cols)
        scratch = [pltpu.VMEM((block_b, block_m), jnp.float32),
                   pltpu.VMEM((block_b, 1), jnp.float32),
                   pltpu.VMEM((1, block_m), jnp.float32)]

    return pl.pallas_call(
        kernel,
        grid=(nb, nm, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            p_spec,
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(queries, points)
