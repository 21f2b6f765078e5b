"""Algorithm 1 — distributed randomized selection, SPMD over a mesh axis.

Paper: Fathi, Molla, Pandurangan, "Efficient Distributed Algorithms for the
K-Nearest Neighbors Problem" (2020), Section 2.1.

The paper's k machines map to the shards of a named mesh axis; this module is
written to run *inside* :func:`jax.shard_map` (or any context where
``jax.lax.psum(axis_name)`` is legal).  See DESIGN.md Section 2 for the full
adaptation table.  The two deliberate departures from the paper's pseudocode:

* **Leaderless SPMD.**  The paper elects a leader that owns the control state
  (min, max, remaining rank) and exchanges point-to-point messages with every
  machine each iteration.  On a TPU mesh the all-reduce tree is a hardware
  primitive, so we *replicate* the leader: after each ``psum``/``all_gather``
  every shard holds identical control state and draws identical pseudo-random
  decisions from a shared key.  Lemma 2.1's pivot-uniformity argument is
  preserved exactly — shard i proposes a uniform element of its in-range set,
  and the replicated weighted draw picks shard i with probability n_i / n.

* **Exclusive bounds.**  We maintain the candidate interval as the *open*
  interval (lo, hi) over composite (value, id) keys, so the pivot itself is
  removed from the candidate set every iteration regardless of the branch
  taken.  This turns the paper's w.h.p. termination into deterministic
  termination (at most n iterations; O(log n) w.h.p. as in Theorem 2.2), which
  a fixed-trip-count ``lax.while_loop`` needs.

Round/message accounting (used by the benchmark harness): each iteration costs
one ``all_gather`` of k (pivot-candidate, count) scalar tuples — the paper's
pivot round — plus one ``psum`` of a scalar count — the paper's getSize round.
That is 2 rounds and 2(k-1) messages per iteration, matching Theorem 2.2's
O(log n) rounds / O(k log n) messages.

The ``num_pivots > 1`` mode is a **beyond-paper optimization** (recorded in
EXPERIMENTS.md Section Perf): every shard proposes a pivot and the counts for
*all* k pivots are computed in the same two collectives, tightening the
interval by the best bracketing pair.  Iterations drop from O(log n) to
O(log n / log k) — the collective payload grows from O(1) to O(k) scalars per
shard, which is still far below a single link's per-round bandwidth B.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import counting as ck


class SelectionResult(NamedTuple):
    """Replicated result of a distributed selection.

    ``threshold_*`` is the composite key of the rank-``l`` smallest element;
    an element x is selected iff ``x <= threshold`` in composite order, so
    exactly ``l`` elements are selected globally (Definition 1.1).
    ``iterations`` is the number of while-loop iterations actually executed
    (data-dependent; exposed for the Theorem 2.2 / 2.4 round benchmarks).
    """

    threshold_v: jax.Array   # (B,) float
    threshold_i: jax.Array   # (B,) int32
    iterations: jax.Array    # ()   int32
    converged: jax.Array     # (B,) bool — False only if the cap was hit


class _LoopState(NamedTuple):
    lo_v: jax.Array
    lo_i: jax.Array
    hi_v: jax.Array
    hi_i: jax.Array
    rank: jax.Array       # remaining rank within (lo, hi), int32 (B,)
    done: jax.Array       # (B,) bool
    thr_v: jax.Array
    thr_i: jax.Array
    it: jax.Array         # () int32
    key: jax.Array        # replicated PRNG key


def _propose_local_pivot(v, i, cand_mask, key):
    """Each machine draws one uniform element of its in-range set.

    Algorithm 1, line 5(2): the selected machine picks a point uniformly at
    random among its n_i in-range points.  We have *every* shard propose (the
    replicated weighted draw then discards all but one), folding the shard id
    into the key so proposals are independent across shards.
    """
    n_i = jnp.sum(cand_mask.astype(jnp.int32), axis=-1)            # (B,)
    u = jax.random.randint(key, n_i.shape, 0, jnp.maximum(n_i, 1))
    idx = ck.masked_select_nth(cand_mask, u)                        # (B,)
    pv = jnp.take_along_axis(v, idx[..., None], axis=-1)[..., 0]
    pi = jnp.take_along_axis(i, idx[..., None], axis=-1)[..., 0]
    # Shards with no in-range points propose the +inf sentinel; their count of
    # zero gives them probability zero in the replicated weighted draw.
    empty = n_i == 0
    pv = jnp.where(empty, jnp.inf, pv)
    pi = jnp.where(empty, ck.ID_HI, pi)
    return pv, pi, n_i


def _select_body(state: _LoopState, *, v, i, valid, axis_name, num_pivots):
    key_it = jax.random.fold_in(state.key, state.it)
    # Independent per-shard stream for the local uniform draw; the *shared*
    # stream (key_it) drives every replicated decision.
    key_local = jax.random.fold_in(key_it, lax.axis_index(axis_name))

    cand = ck.in_open_interval(
        v, i,
        state.lo_v[..., None], state.lo_i[..., None],
        state.hi_v[..., None], state.hi_i[..., None],
    )
    if valid is not None:
        # Algorithm 2 pruning: excluded elements are invisible to the search —
        # never pivots, never counted (paper Step 7's "removes any point
        # larger than r and any of added fake data").
        cand = cand & valid

    pv, pi, n_i = _propose_local_pivot(v, i, cand, key_local)

    # ---- paper round 1: pivot selection -----------------------------------
    # all_gather of (candidate value, candidate id, in-range count): k scalar
    # triples per batch row on the wire.
    g_pv = lax.all_gather(pv, axis_name)          # (k, B)
    g_pi = lax.all_gather(pi, axis_name)
    g_n = lax.all_gather(n_i, axis_name)          # (k, B) int32

    if num_pivots <= 1:
        # Faithful single-pivot mode: replicated weighted machine draw
        # (probability n_i / sum n_j — Lemma 2.1), identical on all shards.
        logits = jnp.where(g_n > 0, jnp.log(g_n.astype(jnp.float32)), -jnp.inf)
        choice = jax.random.categorical(key_it, logits, axis=0)      # (B,)
        piv_v = jnp.take_along_axis(g_pv, choice[None], axis=0)[0]
        piv_i = jnp.take_along_axis(g_pi, choice[None], axis=0)[0]
        piv_v = piv_v[None]                                          # (1, B)
        piv_i = piv_i[None]
    else:
        # Beyond-paper multi-pivot mode: evaluate every shard's proposal.
        piv_v, piv_i = g_pv, g_pi                                    # (k, B)

    # ---- paper round 2: getSize(lo, p] ------------------------------------
    # Count, per shard, elements in (lo, p] for each pivot, then one psum.
    le_piv = ck.key_le(v[None], i[None], piv_v[..., None], piv_i[..., None])
    local_cnt = jnp.sum((le_piv & cand[None]).astype(jnp.int32), axis=-1)
    cnt = lax.psum(local_cnt, axis_name)                             # (P, B)

    rank = state.rank[None]                                          # (1, B)
    # Pivots outside (lo, hi) (sentinel proposals) must not bracket.
    valid = ck.in_open_interval(
        piv_v, piv_i, state.lo_v[None], state.lo_i[None],
        state.hi_v[None], state.hi_i[None])

    hit = valid & (cnt == rank)
    below = valid & (cnt < rank)       # pivot can become the new lo
    above = valid & (cnt > rank)       # pivot can become the new hi

    # Tightest bracketing: new lo = max pivot with cnt < rank (and subtract
    # its count); new hi = min pivot with cnt > rank.  With one pivot this
    # degenerates to the paper's if/else on s vs l (lines 9-13).
    NEG = (-jnp.inf, ck.ID_LO)
    POS = (jnp.inf, ck.ID_HI)

    bv = jnp.where(below, piv_v, NEG[0])
    bi = jnp.where(below, piv_i, NEG[1])
    # lexicographic argmax over pivot axis
    best_lo_v, best_lo_i, best_lo_cnt = _key_argmax(bv, bi, cnt)
    av = jnp.where(above, piv_v, POS[0])
    ai = jnp.where(above, piv_i, POS[1])
    best_hi_v, best_hi_i = _key_argmin(av, ai)

    any_hit = jnp.any(hit, axis=0)
    # threshold from the (unique, if any) hitting pivot
    hv = jnp.where(hit, piv_v, jnp.inf)
    hi_ = jnp.where(hit, piv_i, ck.ID_HI)
    hit_v, hit_i = _key_argmin(hv, hi_)

    has_lo = jnp.any(below, axis=0)
    has_hi = jnp.any(above, axis=0)

    new_lo_v = jnp.where(has_lo, best_lo_v, state.lo_v)
    new_lo_i = jnp.where(has_lo, best_lo_i, state.lo_i)
    new_rank = jnp.where(has_lo, state.rank - best_lo_cnt, state.rank)
    new_hi_v = jnp.where(has_hi, best_hi_v, state.hi_v)
    new_hi_i = jnp.where(has_hi, best_hi_i, state.hi_i)

    done_now = any_hit & ~state.done
    thr_v = jnp.where(done_now, hit_v, state.thr_v)
    thr_i = jnp.where(done_now, hit_i, state.thr_i)

    keep = state.done  # frozen rows
    return _LoopState(
        lo_v=jnp.where(keep, state.lo_v, new_lo_v),
        lo_i=jnp.where(keep, state.lo_i, new_lo_i),
        hi_v=jnp.where(keep, state.hi_v, new_hi_v),
        hi_i=jnp.where(keep, state.hi_i, new_hi_i),
        rank=jnp.where(keep, state.rank, new_rank),
        done=state.done | any_hit,
        thr_v=thr_v,
        thr_i=thr_i,
        it=state.it + 1,
        key=state.key,
    )


def _key_argmin(v, i):
    """Lexicographic min over axis 0 of a (P, B) composite-key array.

    Min value first, then min id among value ties — exactly lexicographic
    order, with no custom reduction primitive.
    """
    mv = jnp.min(v, axis=0)
    tie = v == mv[None]
    mi = jnp.min(jnp.where(tie, i, ck.ID_HI), axis=0)
    return mv, mi


def _key_argmax(v, i, payload):
    """Lexicographic max over axis 0, carrying an int payload along."""
    mv = jnp.max(v, axis=0)
    tie_v = v == mv[None]
    mi = jnp.max(jnp.where(tie_v, i, ck.ID_LO), axis=0)
    sel = tie_v & (i == mi[None])
    # (value, id) pairs are globally unique, so `sel` has exactly one hit per
    # column among real keys; max is a safe extraction.
    mp = jnp.max(jnp.where(sel, payload, jnp.int32(-2147483648)), axis=0)
    return mv, mi, mp


def select_l_smallest(
    v: jax.Array,
    i: jax.Array,
    l: jax.Array,
    key: jax.Array,
    *,
    axis_name: str,
    valid: jax.Array | None = None,
    max_iterations: int | None = None,
    num_pivots: int = 1,
) -> SelectionResult:
    """Find the composite-key threshold of the ``l`` smallest elements.

    Must be called inside a :func:`jax.shard_map` (or pmap) context where
    ``axis_name`` is bound.  ``v``/``i`` are the per-shard local elements,
    shape ``(B, m)`` (``B`` independent selection problems — e.g. a decode
    batch — solved in lockstep; collective payloads are ``O(B)`` scalars).
    ``+inf`` entries are sentinels and are never selected unless ``l`` exceeds
    the number of finite elements.

    ``l`` may be a scalar or ``(B,)`` int array (1 <= l).  The returned
    threshold satisfies ``count(x <= threshold) == min(l, n_finite + n_inf)``
    globally.

    ``max_iterations`` defaults to the Theorem 2.2 w.h.p. bound
    ``8 * ceil(log2(n_global)) + 16``; rows that somehow exceed it report
    ``converged=False`` (probability <= 1/n; callers may re-run with a fresh
    key — the result is still a valid *lower* bound threshold, never wrong,
    just possibly rank-deficient).
    """
    if v.ndim == 1:
        v = v[None]
        i = i[None]
        if valid is not None and valid.ndim == 1:
            valid = valid[None]
    B, m = v.shape
    from repro.parallel.collectives import axis_size
    k = axis_size(axis_name)
    n_global = m * k
    if max_iterations is None:
        # Theorem 2.2 w.h.p. bound with generous constant; the deterministic
        # exclusive-bound update guarantees progress, so hitting the cap has
        # probability <= 1/n (reported via `converged`).
        import math
        max_iterations = 8 * max(1, math.ceil(math.log2(max(n_global, 2)))) + 16

    l = jnp.broadcast_to(jnp.asarray(l, jnp.int32), (B,))
    if valid is None:
        local_total = jnp.full((B,), m, jnp.int32)
    else:
        local_total = jnp.sum(valid.astype(jnp.int32), axis=-1)
    total = lax.psum(local_total, axis_name)
    l = jnp.minimum(l, total)

    # l == 0 rows are done immediately with the -inf threshold.
    zero = l <= 0
    # l == total rows are done immediately with the +inf threshold (select all).
    allsel = l >= total

    state = _LoopState(
        lo_v=jnp.full((B,), -jnp.inf, v.dtype),
        lo_i=jnp.full((B,), ck.ID_LO),
        hi_v=jnp.full((B,), jnp.inf, v.dtype),
        hi_i=jnp.full((B,), ck.ID_HI),
        rank=l,
        done=zero | allsel,
        thr_v=jnp.where(allsel, jnp.inf, -jnp.inf).astype(v.dtype),
        thr_i=jnp.where(allsel, ck.ID_HI, ck.ID_LO),
        it=jnp.int32(0),
        key=key,
    )

    # The loop body mixes the (replicated) control state with per-shard data,
    # so under shard_map's varying-manual-axes checking the carry must be
    # marked as varying over the machine axis up front.
    state = jax.tree.map(
        lambda x: lax.pcast(x, (axis_name,), to="varying"), state)

    body = partial(_select_body, v=v, i=i, valid=valid, axis_name=axis_name,
                   num_pivots=num_pivots)

    def cond(s: _LoopState):
        return (~jnp.all(s.done)) & (s.it < max_iterations)

    final = lax.while_loop(cond, body, state)

    # The control state is replicated by construction (every shard ran the
    # same decisions from the same key), but shard_map's varying-manual-axes
    # checker cannot infer that through a while_loop.  One psum of shard 0's
    # copy (O(B) scalars) makes the invariance provable, so callers can use
    # replicated out_specs with full vma checking enabled.
    from repro.parallel.collectives import replicate
    return SelectionResult(
        threshold_v=replicate(final.thr_v, axis_name),
        threshold_i=replicate(final.thr_i, axis_name),
        iterations=replicate(final.it, axis_name),
        converged=replicate(final.done, axis_name),
    )


def selected_mask(v, i, result: SelectionResult, valid=None):
    """Per-shard boolean mask of the globally selected ``l`` elements."""
    m = ck.key_le(
        v, i, result.threshold_v[..., None], result.threshold_i[..., None])
    if valid is not None:
        m = m & valid
    return m
