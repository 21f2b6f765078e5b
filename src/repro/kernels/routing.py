"""Device-side shard routing — the ``route_shards`` decision as a Pallas
kernel riding the service launch.

Host routing (repro.store.summaries.route_shards) costs an
O(B·k·(m·dim + r)) numpy pass per dispatch *before* the persistent
executable can launch — a serial host bottleneck the paper's O(log K)
serving bound never charged for.  This module folds the identical
decision into the executable's prologue: the per-shard summary operands
(centroid/radius ball, pivot-ball union, projection sketch, live counts)
ship as small replicated arrays, and the kernel emits the (B, k) active
mask that gates the fused distance+top-l collective — the touched-shard
set returns *with* the batch instead of being computed on host ahead of
it.

**Parity contract** (tests/test_routing.py): the kernel's mask is
bit-identical to the host numpy ``route_shards`` on every tested
instance.  The host computes bounds in f64; the kernel computes the same
*structure* in f32 — same direct-difference distances, same
max-of-lower-bounds / min-of-upper-bounds, same slack-and-error-margin
keep rule — so the two can only disagree when a bound lands within f32
rounding (~1e-7 relative) of the decision boundary, while the margin
itself is ``T·slack + err`` with slack 1e-4 and a magnitude-absolute err
term.  Two structural rules keep that argument honest:

* distances are accumulated coordinate-by-coordinate as ``Σ (q_d−c_d)²``
  — NOT the ``|q|² − 2q·c + |c|²`` expansion, whose catastrophic
  cancellation at q ≈ c carries absolute error ~sqrt(eps)·|q| and would
  break parity for clusters far from the origin;
* the cumulative-live threshold is computed sort-free:
  ``T = min{ ub_s : Σ_j live_j · [ub_j <= ub_s] >= l }`` over the k
  candidate uppers, which equals the host's stable-argsort prefix
  formulation *including ties* (every shard with ub <= ub_s is counted
  regardless of tie order, so the count at each candidate threshold is
  order-independent).  O(k²) vectorized compares — no sorting network in
  the kernel.

The math core (:func:`_route_rows`) is plain traced jnp shared verbatim
by the Pallas kernel body and the jnp oracle (:func:`route_mask_ref`),
so interpret mode, compiled mode, and the oracle fallback execute the
same float ops in the same order.  Shape alignment (block padding,
lane-dim padding for the Mosaic path) lives in ops.py like the other
kernels'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 8

_F32_EPS = float(np.finfo(np.float32).eps)       # 2^-23


def pack_summaries(s) -> tuple[np.ndarray, ...]:
    """Flatten a :class:`~repro.store.ShardSummaries` into the kernel's
    f32 operand tuple (host numpy; upload/caching is the caller's —
    the server re-packs once per generation, not per dispatch).

    Layouts put k on the lane (last) dim throughout so every per-shard
    op in the kernel is a clean 2D broadcast: ``centsT`` (dim, k),
    ``radii``/``live`` (1, k), ``loT``/``hiT`` (r, k), ``pivT``
    (m·dim, k) slot-major (slot p owns rows [p·dim, (p+1)·dim)),
    ``pivrT``/``occT``/``pliveT`` (m, k), ``rmax`` (1, 1), ``dirsT``
    (dim, r).  Single-pivot summaries (``pivots is None``) pack one
    all-unoccupied dummy slot — the occupancy mask zeroes its
    contribution exactly the way the host skips the pivot pass, and the
    operand signature stays fixed across generations.  ``pliveT`` holds
    the per-ball live credits (zeros when the summaries carry none),
    feeding the ball-granular threshold stage.  ``rmax`` is the
    generation's ``max live (|centroid| + radius)`` feeding the pipeline
    error bound.
    """
    k, dim = s.centroids.shape
    centsT = np.ascontiguousarray(s.centroids.T, np.float32)
    radii = s.radii[None].astype(np.float32)
    live = s.live[None].astype(np.float32)
    if s.directions.shape[0]:
        loT = np.ascontiguousarray(s.proj_lo.T, np.float32)
        hiT = np.ascontiguousarray(s.proj_hi.T, np.float32)
        dirsT = np.ascontiguousarray(s.directions.T, np.float32)
    else:
        # no sketch: one neutral interval (gap identically 0)
        loT = np.full((1, k), -np.inf, np.float32)
        hiT = np.full((1, k), np.inf, np.float32)
        dirsT = np.zeros((dim, 1), np.float32)
    if s.pivots is None:
        pivT = np.zeros((dim, k), np.float32)
        pivrT = np.zeros((1, k), np.float32)
        occT = np.zeros((1, k), np.float32)
        pliveT = np.zeros((1, k), np.float32)
    else:
        m = s.pivots.shape[1]
        pivT = np.ascontiguousarray(
            np.transpose(s.pivots, (1, 2, 0)).reshape(m * dim, k),
            np.float32)
        pivrT = np.ascontiguousarray(s.pivot_radii.T, np.float32)
        occT = (np.arange(m)[:, None]
                < s.pivot_count[None, :]).astype(np.float32)
        pliveT = (np.ascontiguousarray(s.pivot_live.T, np.float32)
                  if s.pivot_live is not None
                  else np.zeros((m, k), np.float32))
    alive = s.live > 0
    R = (float((np.linalg.norm(s.centroids[alive], axis=1)
                + s.radii[alive]).max()) if alive.any() else 0.0)
    rmax = np.full((1, 1), R, np.float32)
    return (centsT, radii, live, loT, hiT, pivT, pivrT, occT, pliveT,
            rmax, dirsT)


def _sq_dists(q, matT, dim: int, row0: int):
    """(bb, k) f32 squared direct-difference distances from each query
    row to the k columns of ``matT`` rows [row0, row0+dim) — accumulated
    coordinate-by-coordinate (see module docstring on cancellation)."""
    acc = jnp.zeros((q.shape[0], matT.shape[1]), jnp.float32)
    for d in range(dim):
        diff = q[:, d:d + 1] - matT[row0 + d:row0 + d + 1, :]
        acc = acc + diff * diff
    return acc


def _route_rows(q, l_arr, centsT, radii, live, loT, hiT, pivT, pivrT,
                occT, pliveT, rmax, dirsT, *, dim_real: int, slack: float):
    """The routing decision on one query block — f32 mirror of the host
    route_shards, op for op.  ``q`` (bb, dim), ``l_arr`` (bb, 1) int32;
    returns (bb, k) int32 (1 = shard active).  ``dim_real`` is the
    caller's true dim (the error-bound constant — zero-padded trailing
    coordinates cancel in every distance but must not inflate it)."""
    bb, dim = q.shape
    k = centsT.shape[1]
    m = occT.shape[0]
    r = loT.shape[0]
    inf = jnp.float32(jnp.inf)

    # aggregate-ball bracket (distance units)
    dc = jnp.sqrt(_sq_dists(q, centsT, dim, 0))
    lbd = jnp.maximum(dc - radii, 0.0)
    ubd = dc + radii

    # pivot-ball union bracket; unoccupied slots are neutral.  Per-slot
    # distances are kept for the ball-granular threshold stage below.
    plb = jnp.full((bb, k), inf, jnp.float32)
    pub = jnp.full((bb, k), -inf, jnp.float32)
    dp_slots = []
    for p in range(m):
        dp = jnp.sqrt(_sq_dists(q, pivT, dim, p * dim))
        dp_slots.append(dp)
        occ = occT[p:p + 1, :] > 0.0
        plb = jnp.minimum(plb, jnp.where(
            occ, jnp.maximum(dp - pivrT[p:p + 1, :], 0.0), inf))
        pub = jnp.maximum(pub, jnp.where(
            occ, dp + pivrT[p:p + 1, :], -inf))
    has = jnp.max(occT, axis=0, keepdims=True) > 0.0
    lbd = jnp.maximum(lbd, jnp.where(has, plb, 0.0))
    ubd = jnp.minimum(ubd, jnp.where(has, pub, inf))

    # projection-sketch lower bound (1-Lipschitz interval gaps)
    qp = jax.lax.dot_general(q, dirsT, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    for rr in range(r):
        gap = jnp.maximum(jnp.maximum(
            loT[rr:rr + 1, :] - qp[:, rr:rr + 1],
            qp[:, rr:rr + 1] - hiT[rr:rr + 1, :]), 0.0)
        lbd = jnp.maximum(lbd, gap)

    alive = live > 0.0                                   # (1, k)
    lb = jnp.where(alive, lbd * lbd, inf)
    ub = jnp.where(alive, ubd * ubd, inf)

    # sort-free cumulative-live threshold (module docstring)
    lf = l_arr.astype(jnp.float32)                       # (bb, 1)
    T = jnp.full((bb, 1), inf, jnp.float32)
    for s_ in range(k):
        ub_s = ub[:, s_:s_ + 1]
        cnt = jnp.sum(jnp.where(ub <= ub_s, live, 0.0), axis=1,
                      keepdims=True)
        T = jnp.minimum(T, jnp.where(cnt >= lf, ub_s, inf))

    # ball-granular threshold from per-pivot live credits — the host
    # _pivot_threshold mirrored: candidates are (slot, shard) ball upper
    # bounds, counted against the credits of every ball at or below
    # them; min() with the shard-level T can only tighten (credits are
    # safe undercounts).  Slots with zero credit are non-candidates
    # (tub = inf), exactly like the host's occ & live > 0 gate.
    tubs = []
    for p in range(m):
        credit = (occT[p:p + 1, :] > 0.0) & (pliveT[p:p + 1, :] > 0.0)
        bub = dp_slots[p] + pivrT[p:p + 1, :]
        tubs.append(jnp.where(credit, bub * bub, inf))
    for p_c in range(m):
        for s_ in range(k):
            ub_c = tubs[p_c][:, s_:s_ + 1]
            cnt = jnp.zeros((bb, 1), jnp.float32)
            for p in range(m):
                cnt = cnt + jnp.sum(
                    jnp.where(tubs[p] <= ub_c, pliveT[p:p + 1, :], 0.0),
                    axis=1, keepdims=True)
            T = jnp.minimum(T, jnp.where(cnt >= lf, ub_c, inf))

    # f32-pipeline error margin: 16·(dim+1)·eps·(|q| + R)^2
    q2 = jnp.zeros((bb, 1), jnp.float32)
    for d in range(dim_real):
        q2 = q2 + q[:, d:d + 1] * q[:, d:d + 1]
    err = (jnp.float32(16.0 * (dim_real + 1) * _F32_EPS)
           * (jnp.sqrt(q2) + rmax) ** 2)
    t_eff = T * jnp.float32(1.0 + slack) + err           # (bb, 1)

    keep = alive & (lb <= t_eff) & (l_arr > 0)
    return keep.astype(jnp.int32)


def _kernel(q_ref, l_ref, cents_ref, rad_ref, live_ref, lo_ref, hi_ref,
            piv_ref, pivr_ref, occ_ref, plive_ref, rmax_ref, dirs_ref,
            out_ref, *, dim_real: int, slack: float):
    out_ref[...] = _route_rows(
        q_ref[...], l_ref[...], cents_ref[...], rad_ref[...],
        live_ref[...], lo_ref[...], hi_ref[...], piv_ref[...],
        pivr_ref[...], occ_ref[...], plive_ref[...], rmax_ref[...],
        dirs_ref[...], dim_real=dim_real, slack=slack)


def route_mask(queries, ls, centsT, radii, live, loT, hiT, pivT, pivrT,
               occT, pliveT, rmax, dirsT, *, dim_real: int,
               slack: float = 1e-4,
               block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """(B, dim) queries + per-row ls (B, 1) int32 -> (B, k) int32 active
    mask, as a Pallas call gridded over B blocks (summary operands are
    whole-array blocks replicated to every grid step — they are O(k·dim)
    small).  B must divide ``block_b``; ops.route_mask is the padded
    general entry point with the oracle fallback.
    """
    B, dim = queries.shape
    k = centsT.shape[1]
    assert B % block_b == 0, (B, block_b)
    assert ls.shape == (B, 1), ls.shape
    summary_ops = (centsT, radii, live, loT, hiT, pivT, pivrT, occT,
                   pliveT, rmax, dirsT)
    kern = functools.partial(_kernel, dim_real=dim_real, slack=slack)
    return pl.pallas_call(
        kern,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, dim), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ] + [pl.BlockSpec(op.shape, lambda i: (0, 0))
             for op in summary_ops],
        out_specs=pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, k), jnp.int32),
        interpret=interpret,
    )(queries, ls, *summary_ops)


def route_mask_ref(queries, ls, centsT, radii, live, loT, hiT, pivT,
                   pivrT, occT, pliveT, rmax, dirsT, *, dim_real: int,
                   slack: float = 1e-4):
    """Pure-jnp oracle — literally the kernel's shared math core on the
    whole batch at once (same ops, same order: bit-identical to the
    interpret-mode kernel, and still a single fused device computation
    when traced into the service executable)."""
    return _route_rows(queries, ls, centsT, radii, live, loT, hiT, pivT,
                       pivrT, occT, pliveT, rmax, dirsT,
                       dim_real=dim_real, slack=slack)


# ---- in-shard bucket index mask (the store/index.py tier, device-side) ---


def pack_index(index) -> tuple[np.ndarray, ...]:
    """Flatten a :class:`~repro.store.index.ShardIndex` into the index
    kernel's f32 operand tuple: ``bcentsT`` (dim, k·b) with flat column
    ``j·b + t`` for shard j bucket t, ``bradii``/``blive`` (1, k·b).
    Unoccupied or emptied buckets carry live 0, which is the kernel's
    occupancy gate (their lb/ub are forced to inf).  Cached by the
    server per frozen index, like pack_summaries."""
    k, b, dim = index.centers.shape
    occ = ((np.arange(b)[None, :] < index.count[:, None])
           & (index.live > 0))
    bcentsT = np.ascontiguousarray(
        index.centers.reshape(k * b, dim).T, np.float32)
    bradii = np.where(occ, index.radii, 0.0).reshape(1, -1).astype(
        np.float32)
    blive = np.where(occ, index.live, 0).reshape(1, -1).astype(np.float32)
    return bcentsT, bradii, blive


def _index_rows(q, l_arr, rows, bcentsT, bradii, blive, *,
                oversample: float):
    """The bucket keep decision on one query block — the f32 mirror of
    the host ``store.index.bucket_keep`` *structure* (keep rule, gating,
    sort-free threshold).  NOT a bit-parity contract: the tier is
    approximate on either path, so each path's recall is measured
    against its own exact replay rather than against the other path.
    ``rows`` (bb, k) int32 is the routing keep mask (buckets in pruned
    shards are non-candidates); returns (bb, k·b) int32."""
    bb, dim = q.shape
    kb = bcentsT.shape[1]
    k = rows.shape[1]
    b = kb // k
    inf = jnp.float32(jnp.inf)
    d = jnp.sqrt(_sq_dists(q, bcentsT, dim, 0))          # (bb, kb)
    # shard gate expanded to bucket columns via a 0/1 matmul (no lane-dim
    # reshape/repeat — Mosaic-clean, and a single fused dot elsewhere)
    col_shard = jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1) // b
    row_shard = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    expand = (col_shard == row_shard).astype(jnp.float32)      # (k, kb)
    gate = jax.lax.dot_general(
        (rows > 0).astype(jnp.float32), expand,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) > 0.0              # (bb, kb)
    g = gate & (blive > 0.0)
    lbd = jnp.maximum(d - bradii, 0.0)
    lb = jnp.where(g, lbd * lbd, inf)
    ubd = d + bradii
    ub = jnp.where(g, ubd * ubd, inf)
    # sort-free cumulative-live threshold at the oversampled target
    lf = l_arr.astype(jnp.float32)                       # (bb, 1)
    target = jnp.maximum(lf, jnp.ceil(jnp.float32(oversample) * lf))
    T = jnp.full((bb, 1), inf, jnp.float32)
    for c in range(kb):
        ub_c = ub[:, c:c + 1]
        cnt = jnp.sum(jnp.where(ub <= ub_c, blive, 0.0), axis=1,
                      keepdims=True)
        T = jnp.minimum(T, jnp.where(cnt >= target, ub_c, inf))
    keep = g & (lb <= T) & (l_arr > 0)
    return keep.astype(jnp.int32)


def _index_kernel(q_ref, l_ref, rows_ref, cents_ref, rad_ref, live_ref,
                  out_ref, *, oversample: float):
    out_ref[...] = _index_rows(
        q_ref[...], l_ref[...], rows_ref[...], cents_ref[...],
        rad_ref[...], live_ref[...], oversample=oversample)


def index_mask(queries, ls, rows, bcentsT, bradii, blive, *,
               oversample: float = 2.0, block_b: int = DEFAULT_BLOCK_B,
               interpret: bool = False):
    """(B, dim) queries + (B, 1) ls + (B, k) routing keep -> (B, k·b)
    int32 bucket keep, as a Pallas call gridded over B blocks (index
    operands replicate to every grid step — O(k·b·dim) small).
    ops.index_mask is the padded general entry point with the oracle
    fallback."""
    B, dim = queries.shape
    kb = bcentsT.shape[1]
    k = rows.shape[1]
    assert B % block_b == 0, (B, block_b)
    assert ls.shape == (B, 1), ls.shape
    kern = functools.partial(_index_kernel, oversample=oversample)
    index_ops = (bcentsT, bradii, blive)
    return pl.pallas_call(
        kern,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, dim), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ] + [pl.BlockSpec(op.shape, lambda i: (0, 0))
             for op in index_ops],
        out_specs=pl.BlockSpec((block_b, kb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, kb), jnp.int32),
        interpret=interpret,
    )(queries, ls, rows, *index_ops)


def index_mask_ref(queries, ls, rows, bcentsT, bradii, blive, *,
                   oversample: float = 2.0):
    """Pure-jnp oracle — the kernel's shared math core on the whole
    batch (same ops, same order; fuses into the service executable when
    traced)."""
    return _index_rows(queries, ls, rows, bcentsT, bradii, blive,
                       oversample=oversample)
