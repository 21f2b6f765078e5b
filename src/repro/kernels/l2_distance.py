"""Blocked squared-L2 distance matrix — Pallas TPU kernel.

The compute hot-spot of the paper's l-NN pipeline (Algorithm 2, Step 8:
``d_ij = dis(p_ij, q)`` for every local point) is a matmul in disguise:

    ||q - p||^2 = ||q||^2 - 2 q.p + ||p||^2

so the kernel is a (B, d) x (d, m) MXU contraction with a rank-1 epilogue.
Tiling (DESIGN.md hardware-adaptation): the grid is (B/bb, m/bm, d/bk); the
f32 accumulator tile (bb, bm) lives in VMEM scratch across the k-steps, and
the squared-norm partial sums ride along in two skinny scratch columns —
norms are accumulated *inside* the same k-loop so HBM sees each operand
exactly once (arithmetic intensity = the matmul's, the epilogue is free).

Block shapes default to MXU-aligned (128 multiples); `ops.py` pads inputs to
alignment and slices the result (padding points produce garbage distances in
padded columns which the caller slices away; padded d-lanes are zero-filled
and contribute nothing).
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


def _kernel(q_ref, p_ref, out_ref, acc_ref, q2_ref, p2_ref, *, nk: int):
    """One (i, j, k) grid step.

    q_ref:  (bb, bk) query tile        p_ref: (bm, bk) point tile
    out_ref:(bb, bm) output tile       acc_ref: f32 VMEM accumulator
    q2_ref: (bb, 1) running ||q||^2    p2_ref: (1, bm) running ||p||^2
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q2_ref[...] = jnp.zeros_like(q2_ref)
        p2_ref[...] = jnp.zeros_like(p2_ref)

    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)

    # MXU contraction: (bb, bk) x (bk, bm), at full f32 precision (the
    # default runs as one bf16 pass on TPU, far outside the f32 rounding
    # bound exact search is held to).
    acc_ref[...] += jax.lax.dot_general(
        q, p, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # Norm partials on the VPU, same operands, no extra HBM traffic.
    q2_ref[...] += jnp.sum(q * q, axis=1, keepdims=True)
    p2_ref[...] += jnp.sum(p * p, axis=1)[None, :]

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = q2_ref[...] - 2.0 * acc_ref[...] + p2_ref[...]
        out_ref[...] = jnp.maximum(dist, 0.0).astype(out_ref.dtype)


def l2_distance(
    queries: jax.Array,
    points: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """(B, d) x (m, d) -> (B, m) squared distances.  Dims must divide blocks
    (use `ops.l2_distance` for the padded general-shape entry point)."""
    B, d = queries.shape
    m, d2 = points.shape
    assert d == d2, (d, d2)
    assert B % block_b == 0 and m % block_m == 0 and d % block_k == 0, (
        "unpadded shapes must divide block sizes; call ops.l2_distance")
    nb, nm, nk = B // block_b, m // block_m, d // block_k

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(nb, nm, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_b, block_m), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.float32),
            pltpu.VMEM((1, block_m), jnp.float32),
        ],
        interpret=interpret,
    )(queries, points)
