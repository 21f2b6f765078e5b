"""Algorithm 2 — distributed l-nearest-neighbors, end to end.

Paper Section 2.2.  Pipeline per query batch (each step annotated with its
paper line and its collective cost):

  1. distance computation  d_ij = dis(p_ij, q)        local (Step 8; Pallas
     kernel `kernels.distance_topk` on the hot path, jnp fallback here)
  2. local top-l reduction, +inf sentinel padding      local (Step 2)
  3. sample-and-prune to O(l) survivors                1 all_gather + 1 psum
     (Steps 3-7, `core.sampling`)
  4. Algorithm 1 selection on survivors                O(log l) x (all_gather
     + psum) of O(B) scalars  (`core.selection`)
  5. output: per-shard mask of the l winners           local
     optional result gather into a replicated (B, l) buffer: 1 psum of O(l)

Only *distances and ids* ever cross the network (the paper's privacy note:
points themselves, which may be high-dimensional, stay put).

Also provided: the paper's experimental baseline (`knn_simple`, Section 3):
gather every machine's local top-l to one place and reduce — O(l) rounds /
O(k l) values on the wire; used by `benchmarks/bench_fig2.py` to reproduce
the paper's speedup figure, and by tests as a second oracle.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import sampling
from repro.core.selection import (SelectionResult, select_l_smallest,
                                  selected_mask)


class KnnResult(NamedTuple):
    """Distributed l-NN answer.

    ``mask``/``local_dists``/``local_ids`` are per-shard (the paper's native
    output form: "each machine outputs all the points <= max").  When
    ``gather_results=True``, ``dists``/``ids`` hold the l winners replicated
    on every shard (ascending +inf-padded slots), else they are None.
    """

    mask: jax.Array                 # (B, L) bool, per-shard winners
    local_dists: jax.Array          # (B, L) per-shard candidate distances
    local_ids: jax.Array            # (B, L) per-shard candidate global ids
    selection: SelectionResult      # replicated threshold + iteration stats
    prune: sampling.PruneResult     # Lemma 2.3 stats
    dists: jax.Array | None         # (B, l) replicated, or None
    ids: jax.Array | None           # (B, l) replicated, or None
    local_labels: jax.Array | None = None  # (B, L) labels aligned with mask


def squared_l2_distances(queries: jax.Array, points: jax.Array) -> jax.Array:
    """`(B, d) x (m, d) -> (B, m)` squared euclidean distances (jnp reference).

    The MXU-friendly expansion ||q||^2 - 2 q.p + ||p||^2: one (B, d) @ (d, m)
    matmul dominates.  The Pallas kernel `kernels.l2_distance` implements the
    same contraction with explicit VMEM tiling; `kernels/ref.py` mirrors this
    function as the oracle.
    """
    q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    p2 = jnp.sum(points.astype(jnp.float32) ** 2, axis=-1)
    qp = jnp.matmul(queries.astype(jnp.float32),
                    points.astype(jnp.float32).T,
                    precision=lax.Precision.HIGHEST)   # not a bf16 pass
    return jnp.maximum(q2 - 2.0 * qp + p2[None, :], 0.0)


def local_top_l(d: jax.Array, ids: jax.Array, l: int, extra=None):
    """Per-shard top-l smallest (Algorithm 2, Step 2), +inf sentinel padded.

    ``d``: (B, m) distances, ``ids``: (B, m) or (m,) global ids.  When the
    shard holds fewer than l points the paper pads with "fake" sentinel
    points of infinite value; callers with m < l must pre-pad (XLA shapes are
    static, so the pad is part of the buffer layout, not data-dependent).

    ``extra`` ((m,) or (B, m), optional) is a per-slot payload — the
    prediction plane's label buffer — reordered by the *same* top-l
    permutation, so ``extra[b, i]`` stays the payload of the point behind
    ``d[b, i]``/``ids[b, i]``.  With ``extra`` the return is a 3-tuple
    (payload pad slots carry 0; they sit behind +inf distances, which
    every consumer masks on).
    """
    if ids.ndim == 1:
        ids = jnp.broadcast_to(ids[None], d.shape)
    if extra is not None and extra.ndim == 1:
        extra = jnp.broadcast_to(extra[None], d.shape)
    m = d.shape[-1]
    if m <= l:
        pad = l - m
        d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=2**31 - 1)
        if extra is not None:
            return d, ids, jnp.pad(extra, ((0, 0), (0, pad)))
        return d, ids
    neg_top, top_idx = lax.top_k(-d, l)
    out_d = -neg_top
    out_ids = jnp.take_along_axis(ids, top_idx, axis=-1)
    if extra is not None:
        return out_d, out_ids, jnp.take_along_axis(extra, top_idx, axis=-1)
    return out_d, out_ids


def gather_selected(d, gid, mask, l: int, *, axis_name: str):
    """Pack the globally selected elements into replicated (B, l) buffers.

    Rank-stable pack: shard j's winners land after all winners of shards
    < j, preserving nothing about intra-order (callers sort the l-sized
    result locally if they need ascending order — l is small).  Cost: one
    all_gather of a scalar count + one psum of 2 l floats (this is the
    *output* step; the paper's Algorithm 2 leaves results distributed, so
    this is optional).
    """
    B = d.shape[0]
    my_cnt = jnp.sum(mask.astype(jnp.int32), axis=-1)            # (B,)
    all_cnt = lax.all_gather(my_cnt, axis_name)                  # (k, B)
    me = lax.axis_index(axis_name)
    offset = jnp.sum(jnp.where(
        (jnp.arange(all_cnt.shape[0]) < me)[:, None], all_cnt, 0), axis=0)

    rank = jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1       # (B, L)
    col = jnp.where(mask, offset[:, None] + rank, l)             # l => dropped
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], col.shape)

    dbuf = jnp.zeros((B, l + 1), d.dtype).at[rows, col].add(
        jnp.where(mask, d, 0), mode="drop")
    ibuf = jnp.zeros((B, l + 1), jnp.int32).at[rows, col].add(
        jnp.where(mask, gid, 0), mode="drop")
    dists = lax.psum(dbuf[:, :l], axis_name)
    ids = lax.psum(ibuf[:, :l], axis_name)
    # Unfilled slots (when fewer than l finite points exist) become +inf.
    filled = jnp.arange(l)[None] < lax.psum(my_cnt, axis_name)[:, None]
    dists = jnp.where(filled, dists, jnp.inf)
    ids = jnp.where(filled, ids, 2**31 - 1)
    return dists, ids


def _masked_distances(distances_fn, queries, points, point_valid):
    """Distance matrix with tombstoned rows at +inf.

    ``distances_fn`` implementations that can push the mask down into
    their own top-l machinery (kernels/ops.py) advertise it with a
    ``supports_valid`` attribute and receive ``valid=`` directly;
    otherwise the mask is applied here, before the local top-l — either
    way an invalid point competes as the paper's +inf fake point.
    """
    if point_valid is None:
        return distances_fn(queries, points)
    if getattr(distances_fn, "supports_valid", False):
        return distances_fn(queries, points, valid=point_valid)
    d = distances_fn(queries, points)
    return jnp.where(point_valid[None, :].astype(jnp.bool_), d, jnp.inf)


def _apply_shard_routing(point_valid, shard_active, m):
    """Fold the ``route="pruned"`` whole-shard mask into the point mask.

    ``shard_active`` is this shard's routing flag (a (1,)-slice of the
    per-batch (k,) active vector, or a scalar): False means the
    summaries-layer lower-bound test (store/summaries.py route_shards)
    proved this shard cannot hold a winner, so every one of its points
    enters the pipeline at +inf — upstream of the fused distance+top-l
    kernel, through the same ``valid`` operand tombstones use.  Exactness
    is the *caller's* contract: the flag must come from a sound bound
    against the same snapshot generation being queried.
    """
    if shard_active is None:
        return point_valid
    flag = jnp.reshape(shard_active, ()).astype(jnp.bool_)
    if point_valid is None:
        return jnp.broadcast_to(flag, (m,))
    return point_valid & flag


def _fold_candidates(point_valid, point_candidates):
    """Fold the ``search="approx"`` bucket-candidate mask into the point
    mask (store/index.py): a non-candidate competes as the paper's +inf
    fake point, exactly like a tombstone or a routed-away shard.  Unlike
    those two, candidate pruning is NOT exact — the caller opts in to a
    measured recall contract (DESIGN.md §13)."""
    if point_candidates is None:
        return point_valid
    pc = point_candidates.astype(jnp.bool_)
    return pc if point_valid is None else point_valid & pc


def _knn_pipeline(
    points, point_ids, queries, l_buf, l_run, key, *,
    axis_name, distances_fn, use_sampling, num_pivots, gather_results,
    point_valid=None, shard_active=None, point_candidates=None,
    point_labels=None,
) -> KnnResult:
    """Shared Algorithm 2 body.

    ``l_buf`` is the static per-shard buffer width (the paper's "exactly l
    points per machine"); ``l_run`` is the runtime selection rank — a scalar
    (classic single-l path) or a (B,) int32 array (the service's per-request
    l, bounded by ``l_buf``).  The selection threshold is per-row, so rows
    with smaller l simply stop earlier in composite-key order; their unused
    output slots come back as +inf sentinels from ``gather_selected``.

    ``point_valid`` ((m,) bool, optional) is the mutable store's live-slot
    mask: invalid slots enter the pipeline at +inf, making them
    indistinguishable from the paper's fake sentinel points — they are
    never sampled as survivors, never selected, never gathered.
    ``shard_active`` (optional) is the pruned-routing whole-shard flag
    (:func:`_apply_shard_routing`); ``point_candidates`` ((m,) bool,
    optional) is the approximate in-shard candidate mask
    (:func:`_fold_candidates`).  ``point_labels`` ((m,) f32, optional)
    is the prediction plane's per-slot payload, carried through the
    local top-l permutation into ``KnnResult.local_labels`` so
    :func:`knn_classify`/:func:`knn_regress` can vote over exactly the
    selected winners (tombstoned / routed-away / non-candidate slots
    never reach the mask, so they never vote).
    """
    point_valid = _apply_shard_routing(point_valid, shard_active,
                                       points.shape[0])
    point_valid = _fold_candidates(point_valid, point_candidates)
    d_full = _masked_distances(distances_fn, queries, points, point_valid)
    labels_top = None
    if point_labels is not None:
        d, gid, labels_top = local_top_l(d_full, point_ids, l_buf,
                                         extra=point_labels)
    else:
        d, gid = local_top_l(d_full, point_ids, l_buf)           # (B, l_buf)

    if use_sampling:
        prune = sampling.sample_prune(d, key, l_run, axis_name=axis_name)
    else:
        finite = jnp.isfinite(d)
        cnt = lax.psum(jnp.sum(finite.astype(jnp.int32), -1), axis_name)
        prune = sampling.PruneResult(
            valid=finite, radius=jnp.full(d.shape[:1], jnp.inf),
            survivors=cnt, applied=jnp.zeros(d.shape[:1], bool))

    sel = select_l_smallest(
        d, gid, l_run, jax.random.fold_in(key, 1), axis_name=axis_name,
        valid=prune.valid, num_pivots=num_pivots)
    mask = selected_mask(d, gid, sel, valid=prune.valid)

    dists = ids = None
    if gather_results:
        dists, ids = gather_selected(d, gid, mask, l_buf, axis_name=axis_name)
    return KnnResult(mask=mask, local_dists=d, local_ids=gid, selection=sel,
                     prune=prune, dists=dists, ids=ids,
                     local_labels=labels_top)


def knn_query(
    points: jax.Array,
    point_ids: jax.Array,
    queries: jax.Array,
    l: int,
    key: jax.Array,
    *,
    axis_name: str,
    distances_fn=squared_l2_distances,
    use_sampling: bool = True,
    num_pivots: int = 1,
    gather_results: bool = True,
    point_valid: jax.Array | None = None,
    shard_active: jax.Array | None = None,
    point_candidates: jax.Array | None = None,
    point_labels: jax.Array | None = None,
) -> KnnResult:
    """Full Algorithm 2 inside a shard_map context.

    ``points``: (m, dim) this shard's points; ``point_ids``: (m,) globally
    unique int32 ids; ``queries``: (B, dim) replicated query batch.
    ``num_pivots > 1`` enables the beyond-paper multi-pivot selection.
    ``point_valid`` ((m,) bool, optional): live-slot mask for mutable
    stores — invalid slots are treated as the paper's +inf fake points.
    ``shard_active`` (optional): this shard's ``route="pruned"`` flag —
    False masks the whole shard the same way (store/summaries.py).
    ``point_labels`` ((m,) f32, optional): the prediction plane's label
    payload, returned top-l-aligned in ``KnnResult.local_labels``.
    """
    return _knn_pipeline(
        points, point_ids, queries, l, l, key, axis_name=axis_name,
        distances_fn=distances_fn, use_sampling=use_sampling,
        num_pivots=num_pivots, gather_results=gather_results,
        point_valid=point_valid, shard_active=shard_active,
        point_candidates=point_candidates, point_labels=point_labels)


def knn_query_batched(
    points: jax.Array,
    point_ids: jax.Array,
    queries: jax.Array,
    l_max: int,
    l: jax.Array,
    key: jax.Array,
    *,
    axis_name: str,
    distances_fn=squared_l2_distances,
    use_sampling: bool = True,
    num_pivots: int = 1,
    gather_results: bool = True,
    point_valid: jax.Array | None = None,
    shard_active: jax.Array | None = None,
    point_candidates: jax.Array | None = None,
    point_labels: jax.Array | None = None,
) -> KnnResult:
    """Algorithm 2 with a *per-request* neighbor count — the serving form.

    The micro-batched query service (runtime/knn_server.py) coalesces
    requests with heterogeneous l into one device batch.  XLA needs static
    shapes, so all buffers are sized by the shared upper bound ``l_max``
    while the selection rank ``l`` is data: a (B,) int32 array, one entry
    per request, ``0 <= l[b] <= l_max``.  All B selection problems run in
    lockstep through the same Algorithm 1 while-loop (per-row ``done``
    freezing — a row that found its rank-l threshold stops moving), so a
    mixed-l batch costs the rounds of its *hardest* row, not the sum.

    Rows with ``l[b] == 0`` (the micro-batcher's bucket padding) select
    nothing and return all-+inf slots; their queries never influence other
    rows (every step is row-independent except the shared iteration count).
    Gathered outputs are (B, l_max): row b's first l[b] slots hold its
    ascending-by-pack winners, the rest are +inf / INT32_MAX sentinels.
    """
    l = jnp.minimum(jnp.broadcast_to(jnp.asarray(l, jnp.int32),
                                     queries.shape[:1]), l_max)
    return _knn_pipeline(
        points, point_ids, queries, l_max, l, key, axis_name=axis_name,
        distances_fn=distances_fn, use_sampling=use_sampling,
        num_pivots=num_pivots, gather_results=gather_results,
        point_valid=point_valid, shard_active=shard_active,
        point_candidates=point_candidates, point_labels=point_labels)


def knn_simple(
    points: jax.Array,
    point_ids: jax.Array,
    queries: jax.Array,
    l: int,
    *,
    axis_name: str,
    distances_fn=squared_l2_distances,
    point_valid: jax.Array | None = None,
    shard_active: jax.Array | None = None,
    point_candidates: jax.Array | None = None,
):
    """The paper's baseline "simple method" (Section 3).

    Local top-l, then gather all k*l candidates and reduce.  O(l) rounds in
    the k-machine model (k*l values over the leader's links); one
    all_gather of l values per shard here.  Returns replicated ascending
    (dists, ids) of shape (B, l); +inf slots (fewer than l live points)
    carry the INT32_MAX sentinel id.  ``shard_active`` masks this whole
    shard when pruned routing proved it loser-only (same contract as
    :func:`knn_query`).
    """
    point_valid = _apply_shard_routing(point_valid, shard_active,
                                       points.shape[0])
    point_valid = _fold_candidates(point_valid, point_candidates)
    d_full = _masked_distances(distances_fn, queries, points, point_valid)
    d, gid = local_top_l(d_full, point_ids, l)
    gd = lax.all_gather(d, axis_name)                            # (k, B, l)
    gi = lax.all_gather(gid, axis_name)
    B = d.shape[0]
    k = gd.shape[0]
    flat_d = jnp.moveaxis(gd, 0, 1).reshape(B, k * l)
    flat_i = jnp.moveaxis(gi, 0, 1).reshape(B, k * l)
    neg_top, idx = lax.top_k(-flat_d, l)
    dists = -neg_top
    ids = jnp.take_along_axis(flat_i, idx, axis=-1)
    # +inf slots may still carry a real (masked-out or padded) point's id
    # from the local buffer; a dead point's id must never surface.
    ids = jnp.where(jnp.isfinite(dists), ids, 2**31 - 1)
    from repro.parallel.collectives import replicate
    return (replicate(dists, axis_name), replicate(ids, axis_name))


def knn_classify(mask, labels, num_classes: int, *, axis_name: str):
    """Majority vote over the selected neighbors — fully distributed.

    ``labels``: (B, L) int32 per-shard labels aligned with the knn buffers
    (gather-free: the label histogram, not the points, crosses the network —
    the paper's privacy property extends to inference).
    """
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.int32)
    hist = jnp.sum(jnp.where(mask[..., None], onehot, 0), axis=-2)
    hist = lax.psum(hist, axis_name)                             # (B, C)
    return jnp.argmax(hist, axis=-1), hist


def knn_regress(mask, values, *, axis_name: str):
    """Mean of neighbor target values — fully distributed (1 psum)."""
    num = lax.psum(jnp.sum(jnp.where(mask, values, 0.0), axis=-1), axis_name)
    den = lax.psum(jnp.sum(mask.astype(jnp.float32), axis=-1), axis_name)
    return num / jnp.maximum(den, 1.0)
