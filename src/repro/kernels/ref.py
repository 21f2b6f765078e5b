"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` is the semantic ground truth: the kernels must match these
within dtype tolerance for every shape/dtype in the sweep
(`tests/test_kernels.py`).  These are also the CPU fallbacks used by
`ops.py` when a shape violates a kernel's specialization envelope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def l2_distance_ref(queries: jax.Array, points: jax.Array) -> jax.Array:
    """(B, d), (m, d) -> (B, m) squared L2 distances, f32 accumulation.

    Matches the kernel's contraction order: d = |q|^2 - 2 q.p + |p|^2,
    clamped at zero (the expansion can go epsilon-negative in finite
    precision; distances are non-negative by definition).  The dot runs
    at full f32 precision, like the kernels (TPU's default is one bf16
    pass).
    """
    q = queries.astype(jnp.float32)
    p = points.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    p2 = jnp.sum(p * p, axis=-1)
    qp = jnp.matmul(q, p.T, precision=lax.Precision.HIGHEST)
    return jnp.maximum(q2 - 2.0 * qp + p2[None, :], 0.0)


def local_topk_ref(values: jax.Array, l: int):
    """(B, m) -> ((B, l) ascending values, (B, l) indices): l smallest.

    Ties broken toward the smaller index (lax.top_k's stable order on the
    negated input).
    """
    neg_top, idx = lax.top_k(-values.astype(jnp.float32), l)
    return -neg_top, idx.astype(jnp.int32)


def distance_topk_ref(queries: jax.Array, points: jax.Array, l: int):
    """Fused oracle: l smallest squared distances + point indices."""
    d = l2_distance_ref(queries, points)
    return local_topk_ref(d, l)


def masked_l2_distance_ref(queries: jax.Array, points: jax.Array,
                           valid: jax.Array) -> jax.Array:
    """Masked distance oracle: invalid point rows come back as +inf.

    ``valid``: (m,) bool — the mutable store's live-slot mask.  Masking
    happens *before* any top-l reduction a caller runs downstream, so a
    tombstoned slot can never win a neighbor slot (it competes as +inf,
    the same sentinel the paper uses for fake padding points).
    """
    d = l2_distance_ref(queries, points)
    return jnp.where(valid[None, :].astype(jnp.bool_), d, jnp.inf)


def masked_distance_topk_ref(queries: jax.Array, points: jax.Array,
                             valid: jax.Array, l: int):
    """Masked fused oracle: top-l over live slots only.

    Slots whose distance is +inf (masked or padding) report the
    INT32_MAX sentinel id — a deleted point's id must never surface,
    even attached to an infinite distance.
    """
    d = masked_l2_distance_ref(queries, points, valid)
    v, i = local_topk_ref(d, l)
    return v, jnp.where(jnp.isfinite(v), i, jnp.int32(2**31 - 1))
