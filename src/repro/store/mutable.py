"""Mutable, sharded point store with epoch-swapped snapshots.

The paper — and the whole query path built on it — assumes a static point
set wrapped once by ``core.datastore.build_local``.  Production kNN
services (kNN-LM stores, feature retrieval) must absorb inserts, deletes,
and updates *while serving*.  This module adds that layer without giving
up the repo's static-shape discipline:

* **Capacity-padded shard buffers.**  Each of the k shards owns ``cap``
  slots of a device-resident ``(k*cap, dim)`` point buffer (NamedSharding
  over the service axis) plus parallel ``ids``/``valid`` buffers.  Shapes
  never change, so no mutation ever recompiles an executable; a slot that
  holds no live point is masked by ``valid`` and competes in Algorithm 2
  exactly like the paper's +inf fake padding points.

* **Write-ahead staging.**  Mutations are staged host-side
  (:meth:`insert` / :meth:`delete` / :meth:`update` validate and enqueue;
  nothing is device-visible yet), then :meth:`flush` applies the whole
  batch: ops replay onto the host mirrors in submission order, and the
  net effect — one final value per touched slot — lands on device as a
  single padded scatter.  Auto-flush triggers at ``staging_size`` pending
  ops.

* **Generations / epoch swap.**  Every applied batch produces a fresh
  immutable :class:`StoreSnapshot` (device arrays + generation number);
  readers grab the current snapshot at dispatch time and keep computing
  against it even while newer generations land — jax array immutability
  makes the swap free and torn reads impossible.  The serving integration
  (``runtime/knn_server.py``) reports the generation each answer was
  computed against.

* **Placement policies** (``store/placement.py``).  Deletes leave
  tombstones; each applied insert asks the store's placement policy for
  a destination shard — ``balance`` (the emptiest-shard rule) or
  ``affinity`` (nearest live summary centroid under a balance
  guardrail), so a clustered stream can keep locality that pruned
  routing (Section 8) converts into skipped shards.

* **Compaction / rebalance** (``store/compaction.py``).  When tombstone
  density or shard imbalance crosses its threshold (or a shard's tail
  runs out while global space remains), the store repacks live points
  into dense, balanced prefixes — one full re-upload, one generation
  bump, ids stable throughout.  ``redeal="round_robin"`` deals by id;
  ``redeal="proximity"`` re-deals by Lloyd-centroid affinity under the
  same balanced-within-one guarantee (``store/placement.py``).

* **Adaptive summary maintenance** (``store/adaptive.py``).  The routing
  summaries the store keeps per op are covering but loosening; at the
  tail of every apply (when no repack already rebuilt them exactly) the
  store re-tightens at most one due shard (O(live·dim) host work,
  ``retighten_every`` op-count trigger) and lets a shard whose covering
  radius outgrew the inter-centroid gap schedule its own proximity
  re-deal (``split_radius_factor`` trigger, ``split_cooldown`` applies
  between splits) — pruned routing stays effective mid-stream instead of
  decaying until the next compaction.

* **Maintenance planes** (``store/maintenance.py``).  Under the default
  ``maintenance="inline"`` all of the above runs at the tail of
  ``_apply_locked`` under the store lock — exact, simple, and a stall
  every flush pays.  ``maintenance="background"`` hands re-tightening,
  splits, and auto-compaction to a worker thread: every applied op is
  journaled while the worker holds a capture, the worker prepares exact
  rebuilds / repacked buffers / device uploads entirely off-lock, then
  commits by replaying the journal and swapping the epoch under a short
  lock window.  Forced repacks (a full shard mid-flush) and explicit
  :meth:`compact` stay inline — they are correctness, not hygiene — and
  invalidate any in-flight capture.  Answers stay bit-identical to the
  inline plane at every generation (tests/test_async_maintenance.py).

Protocol details and the trigger math: DESIGN.md Sections 7, 9, 10,
and 11.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import NamedTuple, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs.trace import NULL_TRACER
from repro.parallel.compat import make_mesh
from repro.store import adaptive as adaptive_mod
from repro.store import compaction
from repro.store import index as index_mod
from repro.store import maintenance as maintenance_mod
from repro.store import placement as placement_mod
from repro.store import summaries as summaries_mod

ID_SENTINEL = 2**31 - 1


class StoreFullError(RuntimeError):
    """Raised when an insert cannot fit even after compaction."""


class StoreSnapshot(NamedTuple):
    """One immutable generation of the store, as the device sees it.

    ``points``: (k*cap, dim) f32, sharded over the service axis;
    ``ids``: (k*cap,) int32 global point ids (ID_SENTINEL in dead/free
    slots); ``valid``: (k*cap,) bool live mask; ``live``: global live
    count at this generation; ``labels``: (k*cap,) f32 per-point
    label/value payload riding the same slot layout (None unless the
    store was built ``with_labels=True``) — frozen with the generation
    so prediction can never read labels torn from a different epoch
    than the points that carry them.
    """

    generation: int
    points: jax.Array
    ids: jax.Array
    valid: jax.Array
    live: int
    labels: Optional[jax.Array] = None


@dataclasses.dataclass
class IngestStats:
    inserted: int = 0
    deleted: int = 0
    updated: int = 0
    applies: int = 0               # flushes that produced a generation
    compactions: int = 0
    forced_compactions: int = 0    # repacks forced by a full shard mid-flush
    retightens: int = 0            # scheduled per-shard exact re-tightenings
    splits: int = 0                # radius-triggered proximity re-deals
    last_compact_reason: Optional[str] = None


@dataclasses.dataclass
class _Op:
    kind: str                      # "insert" | "delete" | "update"
    id: int
    point: Optional[np.ndarray] = None
    value: Optional[int] = None
    label: Optional[float] = None  # None on update = keep current label


def _staging(method):
    """Run a staging method (``insert``/``delete``/``update``) inside a
    ``store.stage`` span, ``op`` naming the method."""
    @functools.wraps(method)
    def staged(self, *args, **kwargs):
        with self._obs_tracer().span("store.stage", op=method.__name__):
            return method(self, *args, **kwargs)
    return staged


class _ReadLock:
    """The store lock as the serving path's readers take it: how long a
    thread waited for it is added to that thread's running total, so a
    dispatch can tell its lock waits from the rest of its snapshot
    stage.  An uncontended acquire reads no clock."""

    __slots__ = ("_lock", "_waited")

    def __init__(self, lock):
        self._lock = lock
        self._waited = threading.local()

    def __enter__(self):
        if not self._lock.acquire(blocking=False):
            t0 = time.perf_counter()
            self._lock.acquire()
            self._waited.s = self.waited() + time.perf_counter() - t0

    def __exit__(self, *exc):
        self._lock.release()
        return False

    def waited(self) -> float:
        return getattr(self._waited, "s", 0.0)


class MutableStore:
    """Mutable sharded point store; see module docstring.

    Thread-safe: mutations, flushes, and snapshot reads may come from any
    thread (the serving integration reads snapshots from the micro-batcher
    thread while an ingest thread mutates).
    """

    def __init__(self, dim: int, *, capacity_per_shard: int, mesh=None,
                 axis_name: str = "knn", staging_size: int = 64,
                 compact_tombstone_frac: float = 0.35,
                 compact_imbalance_frac: float = 0.5,
                 auto_compact: bool = True, with_values: bool = False,
                 with_labels: bool = False,
                 track_history: bool = False,
                 summary_projections: int = 8, summary_seed: int = 0,
                 placement="balance", placement_guard_slack: int = 32,
                 redeal: str = "round_robin",
                 summary_pivots: int = 1, retighten_every: int = 0,
                 split_radius_factor: float = 0.0,
                 split_cooldown: int = 2, maintenance: str = "inline",
                 maintenance_probe_sample: int = 64,
                 index_buckets: int = 0):
        if capacity_per_shard < 1:
            raise ValueError("capacity_per_shard must be >= 1")
        if redeal not in ("round_robin", "proximity"):
            raise ValueError(f"redeal must be 'round_robin' or 'proximity', "
                             f"got {redeal!r}")
        if maintenance not in ("inline", "background"):
            raise ValueError(f"maintenance must be 'inline' or 'background', "
                             f"got {maintenance!r}")
        self.dim = int(dim)
        self.axis_name = axis_name
        self.mesh = mesh if mesh is not None else make_mesh(
            (jax.device_count(),), (axis_name,))
        self.k = int(dict(self.mesh.shape)[axis_name])
        self.cap = int(capacity_per_shard)
        self.total = self.k * self.cap
        self.staging_size = int(staging_size)
        self.compact_tombstone_frac = float(compact_tombstone_frac)
        self.compact_imbalance_frac = float(compact_imbalance_frac)
        self.auto_compact = bool(auto_compact)
        self.with_values = bool(with_values)
        self.with_labels = bool(with_labels)
        # Placement subsystem (store/placement.py): the policy object that
        # places every applied insert, and the repack mode that re-deals
        # live points at compaction.
        self._placement = placement_mod.make_placement(
            placement, guard_slack=placement_guard_slack)
        self.placement = self._placement.name
        self.placement_guard_slack = int(placement_guard_slack)
        self.redeal = str(redeal)
        self.stats = IngestStats()

        self._lock = threading.RLock()
        self._read_lock = _ReadLock(self._lock)
        self._sharding = NamedSharding(self.mesh, P(axis_name))

        # Host mirrors — authoritative control plane; the device snapshot
        # is always a pure function of these (mirror first, then upload).
        self._pts = np.zeros((self.total, self.dim), np.float32)
        self._ids = np.full(self.total, ID_SENTINEL, np.int32)
        self._valid = np.zeros(self.total, bool)
        self._slot_of: dict[int, int] = {}
        # Ids are single-use, forever: once staged for insertion an id can
        # never be inserted again, even after deletion.  This is what makes
        # the id -> value map monotone (values_for answers correctly for
        # any generation's ids) and an id denote one immutable point
        # identity across all generations.  Grows with total inserts.
        self._used_ids: set[int] = set()
        self._live = np.zeros(self.k, np.int64)   # live points per shard
        self._used = np.zeros(self.k, np.int64)   # high-water mark per shard
        self._values: dict[int, int] = {}
        # Per-slot label payload mirror (prediction plane).  f32 serves
        # both classification (integer class ids are exact below 2^24)
        # and regression; slots ride the exact same scatter / validity /
        # repack machinery as the points they annotate.  The id -> label
        # map is monotone like _values, so oracle lookups against older
        # generations' ids stay well-defined.
        self._labels = (np.zeros(self.total, np.float32)
                        if self.with_labels else None)
        self._label_of: dict[int, float] = {}
        self._next_id = 0

        # Write-ahead staging.
        self._pending: list[_Op] = []
        self._staged_state: dict[int, bool] = {}  # id -> live after flush
        self._projected_live = 0

        # The labeled variant carries one extra buffer through the same
        # scatter; arity is fixed at construction so the jit cache never
        # sees a mixed signature (maintenance.py calls through this too).
        self._apply_fn = jax.jit(
            _scatter_apply_labeled if self.with_labels else _scatter_apply,
            out_shardings=(self._sharding,) * (4 if self.with_labels else 3))

        # Per-shard pivot summaries for pruned routing (store/summaries.py),
        # in the adaptive form (store/adaptive.py): updated incrementally
        # alongside every op below, rebuilt exactly on repack, re-tightened
        # on schedule / split on radius decay at the tail of each apply,
        # and frozen with each generation so the (snapshot, summaries)
        # pair handed to routing_snapshot() can never disagree.
        self._summ = adaptive_mod.AdaptiveMaintainer(
            self.k, self.dim, num_projections=summary_projections,
            seed=summary_seed, num_pivots=summary_pivots,
            retighten_every=retighten_every,
            split_radius_factor=split_radius_factor)
        self.split_cooldown = int(split_cooldown)
        self._applies_at_split = -(1 << 30)   # no split yet: first may fire

        # In-shard approximate index tier (store/index.py): maintained
        # incrementally beside the summaries at every op site below,
        # rebuilt exactly on any repack, frozen per generation so
        # serving_snapshot()'s (snapshot, summaries, index) triple is
        # generation-coupled.  index_buckets=0 (the default) disables it.
        self._index = (index_mod.IndexMaintainer(
            self.k, self.cap, self.dim, index_buckets)
            if index_buckets > 0 else None)

        self._history: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._track_history = bool(track_history)
        self._snap = self._upload_snapshot_locked(generation=0)
        self._summaries = self._summ.freeze(0)
        self._frozen_index = (self._index.freeze(0)
                              if self._index is not None else None)
        self._record_history()

        # Maintenance plane (store/maintenance.py).  The journal exists
        # only while the background worker holds an outstanding capture:
        # _apply_locked appends every applied op to it so the worker's
        # commit can replay what raced its off-lock preparation; an
        # inline repack (forced, or explicit compact()) invalidates the
        # capture instead — the repack already rebuilt everything the
        # staged work was about to.
        self.maintenance = str(maintenance)
        self._journal: Optional[list] = None
        self._journal_invalid = False
        # Observability plane (src/repro/obs/): attached after
        # construction by the serving layer (KnnServer hands the store
        # its own plane so store applies and maintenance cycles land in
        # the same trace/registry as the queries racing them).  Unattached
        # stores trace into the shared no-op and record no metrics.
        self._obs = None
        # Maintenance-commit clock: a monotone count of committed
        # maintenance cycles (retighten/repack, inline or background)
        # plus the last commit's facts.  The serving layer samples it
        # before and after each dispatch so explain reports can say
        # whether a commit raced the request (obs/explain.py) and the
        # SLO staleness objective can reason about churn.
        self._maint_commits = 0
        self._last_maint_commit: Optional[dict] = None
        self._worker: Optional[maintenance_mod.MaintenanceWorker] = None
        if self.maintenance == "background":
            self._worker = maintenance_mod.MaintenanceWorker(
                self, probe_sample=maintenance_probe_sample)

    def attach_obs(self, plane) -> None:
        """Attach an :class:`repro.obs.ObsPlane`; applies and background
        maintenance cycles from here on emit spans into its tracer and
        timings into its registry.  Late attach is safe (the worker
        re-reads the plane each cycle); attaching replaces any previous
        plane."""
        self._obs = plane

    def _obs_tracer(self):
        return self._obs.tracer if self._obs is not None else NULL_TRACER

    def _obs_registry(self):
        return self._obs.metrics if self._obs is not None else None

    def _note_maint_commit(self, info: dict) -> None:
        """Advance the maintenance-commit clock.  Called by the
        maintenance plane *with the store lock already held* (both
        commit sites sit inside their lock block), so this must not —
        and does not — re-acquire it."""
        self._maint_commits += 1
        self._last_maint_commit = dict(info, seq=self._maint_commits)

    def maint_commit_clock(self) -> tuple:
        """(commit count, last commit info dict or None) — one lock
        acquisition, so a before/after pair brackets a dispatch
        consistently."""
        with self._read_lock:
            return self._maint_commits, self._last_maint_commit

    def lock_wait_s(self) -> float:
        """Seconds the calling thread has waited, in all, for the store
        lock in the serving path's readers (``snapshot``,
        ``routing_snapshot``, ``serving_snapshot``, ``live_per_shard``,
        ``maint_commit_clock``); a dispatch differences it."""
        return self._read_lock.waited()

    def close(self) -> None:
        """Stop the background maintenance worker (no-op when inline or
        already closed).  Staged work in flight is either committed or
        discarded before the worker thread exits; the store itself stays
        fully usable — only unscheduled maintenance stops happening."""
        worker, self._worker = self._worker, None
        if worker is not None:
            worker.stop()
            # final counters stay reportable after close (benchmarks and
            # the concurrency harness read them post-quiesce)
            self._worker_final = worker

    # ---- read side -------------------------------------------------------

    def snapshot(self) -> StoreSnapshot:
        """The current generation (immutable; safe to compute against while
        newer generations land)."""
        with self._read_lock:
            return self._snap

    def routing_snapshot(self):
        """(snapshot, summaries) captured under one lock acquisition —
        the generation-coupling invariant: ``summaries.generation ==
        snapshot.generation`` always, so pruned routing can never consult
        metadata from a different epoch than the one that answers."""
        with self._read_lock:
            return self._snap, self._summaries

    def serving_snapshot(self):
        """(snapshot, summaries, index) captured under one lock
        acquisition — the full serving triple for ``search="approx"``:
        ``index.generation == summaries.generation ==
        snapshot.generation`` always (``index`` is None when the store
        was built with ``index_buckets=0``)."""
        with self._read_lock:
            return self._snap, self._summaries, self._frozen_index

    def summaries(self) -> summaries_mod.ShardSummaries:
        """The current generation's per-shard pivot summaries."""
        with self._lock:
            return self._summaries

    @property
    def summary_projections(self) -> int:
        """Sketch width of this store's routing summaries (servers with
        route="pruned" must be configured to match)."""
        return self._summ.num_projections

    @property
    def summary_seed(self) -> int:
        """Direction-matrix seed of this store's routing summaries."""
        return self._summ.seed

    @property
    def summary_pivots(self) -> int:
        """Pivot balls per shard of this store's routing summaries
        (servers with route="pruned" must be configured to match)."""
        return self._summ.num_pivots

    @property
    def index_buckets(self) -> int:
        """Buckets per shard of this store's approximate index tier —
        0 when disabled (servers with search="approx" must be configured
        to match, like the summary knobs)."""
        return self._index.num_buckets if self._index is not None else 0

    def summary_slack(self) -> np.ndarray:
        """(k,) covering-radius slack of the current generation's
        summaries vs the exact live spread (summaries.summary_slack) —
        the bound-decay observable KnnServer.placement_stats() reports.
        O(live·dim) host probe; never on the dispatch path."""
        with self._lock:
            return summaries_mod.summary_slack(
                self._summaries, self._pts, self._valid, self.cap)

    def maintenance_stats(self) -> dict:
        """Adaptive-maintenance counters and knobs, one dict (the
        placement_stats() payload)."""
        with self._lock:
            out = {
                "summary_pivots": self._summ.num_pivots,
                "retighten_every": self._summ.retighten_every,
                "split_radius_factor": self._summ.split_radius_factor,
                "retightens": self.stats.retightens,
                "splits": self.stats.splits,
                "maintenance": self.maintenance,
            }
            worker = self._worker or getattr(self, "_worker_final", None)
            if worker is not None:
                out["worker"] = worker.stats_dict()
            return out

    @property
    def generation(self) -> int:
        return self.snapshot().generation

    @property
    def live_count(self) -> int:
        """Live points in the *applied* state (staged ops excluded)."""
        with self._lock:
            return int(self._live.sum())

    @property
    def pending_ops(self) -> int:
        with self._lock:
            return len(self._pending)

    @property
    def live_per_shard(self) -> np.ndarray:
        """(k,) live points per shard — the balance the compactor defends."""
        with self._read_lock:
            return self._live.copy()

    def live_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(ids, points) of the applied live set, ascending by id — the
        brute-force oracle view used by tests and benchmarks."""
        with self._lock:
            slots = np.flatnonzero(self._valid)
            order = slots[np.argsort(self._ids[slots], kind="stable")]
            return self._ids[order].copy(), self._pts[order].copy()

    def history(self, generation: int) -> tuple[np.ndarray, np.ndarray]:
        """(ids, points) live at ``generation`` (requires track_history)."""
        if not self._track_history:
            raise RuntimeError("store built with track_history=False")
        with self._lock:
            return self._history[generation]

    def values_for(self, ids: np.ndarray) -> np.ndarray:
        """Map global point ids to their payload values, -1 where absent.

        The id→value map is monotone (entries survive deletion) so lookups
        against older generations' answers stay well-defined.
        """
        with self._lock:
            return np.array([self._values.get(int(i), -1) for i in ids],
                            np.int32)

    def labels_for(self, ids: np.ndarray) -> np.ndarray:
        """Map global point ids to their label payloads, NaN where absent.

        Monotone like the id→value map: a label survives its point's
        deletion, so oracle lookups against older generations' answers
        stay well-defined (requires ``with_labels``).
        """
        if not self.with_labels:
            raise RuntimeError("store built with with_labels=False")
        with self._lock:
            return np.array([self._label_of.get(int(i), np.nan) for i in ids],
                            np.float32)

    def live_labels(self) -> tuple[np.ndarray, np.ndarray]:
        """(ids, labels) of the applied live set, ascending by id —
        aligned with :meth:`live_arrays` (requires ``with_labels``)."""
        if not self.with_labels:
            raise RuntimeError("store built with with_labels=False")
        with self._lock:
            slots = np.flatnonzero(self._valid)
            order = slots[np.argsort(self._ids[slots], kind="stable")]
            return self._ids[order].copy(), self._labels[order].copy()

    # ---- write side (staging) -------------------------------------------

    @_staging
    def insert(self, points, ids=None, values=None, labels=None) -> np.ndarray:
        """Stage point insertions; returns the assigned global ids.

        ``points``: (n, dim) or (dim,).  ``ids`` (optional) must be fresh —
        never used before, not even by a since-deleted point (ids are
        single-use so the id->value map stays monotone); omitted ids are
        assigned from a monotone counter.  ``values`` (optional, requires
        ``with_values``): per-point int payloads.  ``labels`` (optional,
        requires ``with_labels``): per-point f32 label/value payloads for
        the prediction plane (class id or regression target; default 0.0
        when omitted).  Atomic: on any validation error (duplicate/reused
        id, capacity) the whole batch is rejected and nothing is staged.
        """
        points = np.atleast_2d(np.asarray(points, np.float32))
        n = points.shape[0]
        if points.shape != (n, self.dim):
            raise ValueError(f"points shape {points.shape} != (n, {self.dim})")
        if values is not None and not self.with_values:
            raise ValueError("store built with with_values=False")
        if values is not None:
            values = np.broadcast_to(np.asarray(values, np.int32), (n,))
        if labels is not None and not self.with_labels:
            raise ValueError("store built with with_labels=False")
        if labels is not None:
            labels = np.broadcast_to(np.asarray(labels, np.float32), (n,))
        with self._lock:
            if ids is None:
                ids = np.arange(self._next_id, self._next_id + n,
                                dtype=np.int64)
            else:
                ids = np.broadcast_to(np.asarray(ids, np.int64), (n,))
            # validate the whole batch before staging any of it
            if self._projected_live + n > self.total:
                raise StoreFullError(
                    f"store full: capacity {self.total}, projected live "
                    f"{self._projected_live}, insert batch {n}")
            batch = set()
            for pid in ids:
                pid = int(pid)
                if not 0 <= pid < ID_SENTINEL:
                    raise ValueError(f"id {pid} outside [0, 2^31-1)")
                if pid in batch or pid in self._used_ids:
                    raise ValueError(
                        f"id {pid} was already used (ids are single-use)")
                batch.add(pid)
            for t in range(n):
                pid = int(ids[t])
                self._pending.append(_Op(
                    "insert", pid, point=points[t].copy(),
                    value=None if values is None else int(values[t]),
                    label=(0.0 if labels is None else float(labels[t]))
                    if self.with_labels else None))
                self._staged_state[pid] = True
                self._used_ids.add(pid)
                self._next_id = max(self._next_id, pid + 1)
            self._projected_live += n
            self._maybe_autoflush_locked()
            return ids.astype(np.int32)

    @_staging
    def delete(self, ids) -> None:
        """Stage deletions by global id (KeyError if not live/staged).
        Atomic: one unknown id rejects the whole batch, staging nothing."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with self._lock:
            gone = set()
            for pid in ids:
                pid = int(pid)
                if pid in gone or not self._would_be_live(pid):
                    raise KeyError(f"id {pid} is not live")
                gone.add(pid)
            for pid in ids:
                pid = int(pid)
                self._pending.append(_Op("delete", pid))
                self._staged_state[pid] = False
            self._projected_live -= len(ids)
            self._maybe_autoflush_locked()

    @_staging
    def update(self, ids, points, labels=None) -> None:
        """Stage in-place point overwrites (same id, same slot).
        ``labels`` (optional, requires ``with_labels``) overwrites the
        label payload alongside; omitted labels stay as they were.
        Atomic: one unknown id rejects the whole batch, staging nothing."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        points = np.atleast_2d(np.asarray(points, np.float32))
        if points.shape != (len(ids), self.dim):
            raise ValueError(
                f"points shape {points.shape} != ({len(ids)}, {self.dim})")
        if labels is not None and not self.with_labels:
            raise ValueError("store built with with_labels=False")
        if labels is not None:
            labels = np.broadcast_to(np.asarray(labels, np.float32),
                                     (len(ids),))
        with self._lock:
            for pid in ids:
                if not self._would_be_live(int(pid)):
                    raise KeyError(f"id {int(pid)} is not live")
            for t, (pid, pt) in enumerate(zip(ids, points)):
                self._pending.append(_Op(
                    "update", int(pid), point=pt.copy(),
                    label=None if labels is None else float(labels[t])))
            self._maybe_autoflush_locked()

    def _would_be_live(self, pid: int) -> bool:
        if pid in self._staged_state:
            return self._staged_state[pid]
        return pid in self._slot_of

    def _maybe_autoflush_locked(self):
        if len(self._pending) >= self.staging_size:
            self.flush()

    # ---- apply (epoch swap) ---------------------------------------------

    def flush(self) -> int:
        """Apply all staged mutations as one epoch swap; returns the new
        generation (or the current one if nothing was staged)."""
        with self._lock:
            if not self._pending:
                return self._snap.generation
            return self._apply_locked(force_compact=False)

    def compact(self) -> int:
        """Flush staged ops (if any) and force a repack/rebalance; always
        produces a new generation."""
        with self._lock:
            return self._apply_locked(force_compact=True)

    def _apply_locked(self, *, force_compact: bool) -> int:
        t_apply = time.perf_counter()
        with self._obs_tracer().span("store.apply") as span:
            gen, n_ops, repacked = self._apply_ops_locked(force_compact)
            span.annotate(generation=gen, ops=n_ops, repacked=repacked)
        t_done = time.perf_counter()
        if self._obs is not None:
            reg = self._obs.metrics
            reg.histogram("store.apply_s").observe(t_done - t_apply)
            reg.counter("store.applies").inc()
            reg.gauge("store.live").set(self._projected_live)
        return gen

    def _apply_ops_locked(self, force_compact: bool) -> tuple:
        """The apply itself: (new generation, ops applied, repacked)."""
        ops, self._pending = self._pending, []
        self._staged_state = {}
        touched: set[int] = set()
        repacked = False

        for op in ops:
            if op.kind == "insert":
                j = self._pick_shard_locked(op.point)
                if j < 0:
                    # Every shard is at its high-water mark but global
                    # capacity remains (staging checked it): reclaim
                    # tombstones now.  At most once per flush — after a
                    # repack the free tail covers all remaining inserts.
                    self._repack_locked()
                    repacked = True
                    self.stats.forced_compactions += 1
                    self.stats.last_compact_reason = "forced: all shards at high-water"
                    j = self._pick_shard_locked(op.point)
                    assert j >= 0, "repack must free tail space"
                slot = j * self.cap + int(self._used[j])
                self._used[j] += 1
                self._live[j] += 1
                self._summ.insert(j, op.point)
                if self._index is not None:
                    self._index.insert(j, slot, op.point)
                self._pts[slot] = op.point
                self._ids[slot] = op.id
                self._valid[slot] = True
                self._slot_of[op.id] = slot
                if op.value is not None:
                    self._values[op.id] = op.value
                if self.with_labels:
                    self._labels[slot] = op.label
                    self._label_of[op.id] = float(op.label)
                touched.add(slot)
                self.stats.inserted += 1
                if self._journal is not None:
                    self._journal.append(("insert", op.id, j, op.point,
                                          None, op.label))
            elif op.kind == "delete":
                slot = self._slot_of.pop(op.id)
                self._live[slot // self.cap] -= 1
                self._summ.delete(slot // self.cap, self._pts[slot])
                if self._index is not None:
                    self._index.delete(slot)
                if self._journal is not None:
                    self._journal.append(("delete", op.id,
                                          slot // self.cap, None,
                                          self._pts[slot].copy(), None))
                self._valid[slot] = False
                self._ids[slot] = ID_SENTINEL
                touched.add(slot)
                self.stats.deleted += 1
            else:  # update
                slot = self._slot_of[op.id]
                self._summ.update(slot // self.cap, self._pts[slot],
                                  op.point)
                if self._index is not None:
                    self._index.update(slot, op.point)
                if self._journal is not None:
                    self._journal.append(("update", op.id,
                                          slot // self.cap, op.point,
                                          self._pts[slot].copy(), op.label))
                self._pts[slot] = op.point
                if self.with_labels and op.label is not None:
                    self._labels[slot] = op.label
                    self._label_of[op.id] = float(op.label)
                touched.add(slot)
                self.stats.updated += 1

        if force_compact and not repacked:
            self._repack_locked()
            repacked = True
            self.stats.last_compact_reason = "forced: explicit compact()"
        elif (self.auto_compact and self.maintenance == "inline"
              and not repacked):
            decision = compaction.evaluate(
                self._live, self._used, self.cap,
                tombstone_frac=self.compact_tombstone_frac,
                imbalance_frac=self.compact_imbalance_frac,
                registry=self._obs_registry())
            if decision.compact:
                self._repack_locked()
                repacked = True
                self.stats.last_compact_reason = decision.reason

        # Adaptive maintenance (store/adaptive.py, DESIGN.md Section 10):
        # runs only when no repack already rebuilt every bound exactly.
        # A radius-triggered split schedules its own proximity re-deal —
        # the quota clamp and the maintainer's growth guard keep it from
        # re-arming the compactor — else at most ONE due shard gets an
        # O(live·dim) exact re-tightening, round-robin, off any stall
        # path.  maintenance="background" moves this whole tail (and the
        # auto-compact evaluation above) to the worker thread
        # (store/maintenance.py) — the flush publishes immediately and
        # the worker is poked after the swap.
        if self.maintenance == "inline":
            if not repacked:
                j = self._split_due_locked()
                if j is not None:
                    self._repack_locked(redeal="proximity")
                    repacked = True
                    self.stats.splits += 1
                    self._applies_at_split = self.stats.applies
                    self.stats.last_compact_reason = (
                        f"split: shard {j} radius outgrew the centroid gap")
            if not repacked:
                j = self._summ.retighten_due()
                if j is not None:
                    self._summ.retighten(j, self._pts, self._valid,
                                         self.cap)
                    self.stats.retightens += 1

        self._projected_live = int(self._live.sum())
        gen = self._snap.generation + 1
        if repacked:
            # A repack moves slots wholesale: one full upload.
            self._snap = self._upload_snapshot_locked(generation=gen)
        else:
            new_pts, new_ids, new_valid, new_labels = self._scatter_locked(
                sorted(touched))
            self._snap = StoreSnapshot(generation=gen, points=new_pts,
                                       ids=new_ids, valid=new_valid,
                                       live=self._projected_live,
                                       labels=new_labels)
        self.stats.applies += 1
        self._summaries = self._summ.freeze(gen)
        if self._index is not None:
            self._frozen_index = self._index.freeze(gen)
        self._record_history()
        if self._worker is not None:
            self._worker.notify()
        return gen, len(ops), repacked

    def _upload_snapshot_locked(self, *, generation: int) -> StoreSnapshot:
        """Full upload of the mirrors as a fresh snapshot.

        device_put is handed *copies*: the host->device transfer may still
        be in flight when this method returns, and the next flush mutates
        the mirrors in place — uploading the live mirror would let a later
        batch's writes leak into (and tear) this supposedly immutable
        generation under concurrent serving.
        """
        return StoreSnapshot(
            generation=generation,
            points=jax.device_put(self._pts.copy(), self._sharding),
            ids=jax.device_put(self._ids.copy(), self._sharding),
            valid=jax.device_put(self._valid.copy(), self._sharding),
            live=int(self._live.sum()),
            labels=(jax.device_put(self._labels.copy(), self._sharding)
                    if self.with_labels else None))

    def _pick_shard_locked(self, point=None) -> int:
        """Policy-dispatched placement (store/placement.py): hand the
        configured policy the live/used counts — plus the summary
        maintainer's centroid view, if the policy pays attention to it —
        and get back a destination shard; -1 if no shard has tail space
        (the caller then repacks and retries)."""
        if self._placement.uses_centroids:
            centroids, radii, occupied = self._summ.placement_view()
        else:
            centroids = radii = occupied = None
        return self._placement.pick(point, placement_mod.PlacementView(
            live=self._live, used=self._used, cap=self.cap,
            centroids=centroids, radii=radii, occupied=occupied))

    def _split_due_locked(self) -> Optional[int]:
        """Shard the adaptive split trigger fires on this apply, or None;
        the cooldown (applies between splits) is the store's guard, the
        radius/growth conditions are the maintainer's."""
        if (self._summ.split_radius_factor <= 0
                or self.stats.applies - self._applies_at_split
                < self.split_cooldown):
            return None
        return self._summ.split_candidate()

    def _repack_locked(self, redeal: Optional[str] = None):
        """Repack under ``redeal`` (default: the store's configured mode;
        adaptive splits pass "proximity" explicitly — a split exists to
        separate clusters, whatever the compaction-time deal is)."""
        # An inline repack rebuilds mirrors AND summaries exactly; any
        # background capture prepared against the pre-repack layout is
        # now both stale and pointless — invalidate it.
        t_repack = time.perf_counter()
        if self._journal is not None:
            self._journal_invalid = True
        if (redeal or self.redeal) == "proximity":
            centroids, _, occupied = self._summ.placement_view()
            # Quota slack shares the placement guardrail knob, clamped
            # (compaction.redeal_slack) so a re-deal can never leave a
            # skew that would immediately re-arm the compactor.
            slack = compaction.redeal_slack(
                self.placement_guard_slack, self.compact_imbalance_frac,
                self.cap, self.k)
            res = placement_mod.repack_proximity(
                self._pts, self._ids, self._valid, self.k, self.cap,
                id_sentinel=ID_SENTINEL, balance_slack=slack,
                seed_centroids=centroids[occupied] if occupied.any()
                else None)
        else:
            res = compaction.repack(self._pts, self._ids, self._valid,
                                    self.k, self.cap,
                                    id_sentinel=ID_SENTINEL)
        if self.with_labels:
            # Labels follow their points through the re-deal: remap the
            # per-slot payload from the old layout to the new one by id
            # (compaction.remap_payload) — alignment is what the
            # labels-survive-compaction regression test asserts.
            self._labels = compaction.remap_payload(
                self._labels, self._ids, self._valid, res.ids, res.valid)
        self._pts, self._ids, self._valid = res.points, res.ids, res.valid
        self._slot_of = res.slot_of
        self._live, self._used = res.live, res.used
        # Exact rebuild: compaction is the point where the incremental
        # (covering-but-loose) summary bounds get re-tightened.
        self._summ.rebuild(self._pts, self._valid, self.cap)
        if self._index is not None:
            self._index.rebuild(self._pts, self._valid)
        self.stats.compactions += 1
        t_done = time.perf_counter()
        self._obs_tracer().record("store.repack", t_repack, t_done,
                                  redeal=redeal or self.redeal,
                                  plane="inline")
        if self._obs is not None:
            self._obs.metrics.histogram("store.repack_s").observe(
                t_done - t_repack)
            self._obs.metrics.counter("store.repacks").inc()

    def _scatter_locked(self, slots: list[int]):
        """Apply the final per-slot values of one staged batch on device.

        Touched slots are deduplicated by construction (a set), so the
        scatter has unique indices; padding rows point at slot ``total``
        and are dropped.  Padded to powers of two so the jit cache stays
        small across flushes of varying size.
        """
        idx, upd_pts, upd_ids, upd_valid = compaction.scatter_operands(
            slots, self._pts, self._ids, self._valid, self.total,
            self.dim, id_sentinel=ID_SENTINEL)
        if self.with_labels:
            upd_labels = compaction.payload_operand(slots, self._labels,
                                                    len(idx))
            return self._apply_fn(self._snap.points, self._snap.ids,
                                  self._snap.valid, self._snap.labels,
                                  idx, upd_pts, upd_ids, upd_valid,
                                  upd_labels)
        out = self._apply_fn(self._snap.points, self._snap.ids,
                             self._snap.valid, idx, upd_pts, upd_ids,
                             upd_valid)
        return out + (None,)

    def _record_history(self):
        if self._track_history:
            ids, pts = self.live_arrays()
            self._history[self._snap.generation] = (ids, pts)


def _scatter_apply(pts, ids, valid, slots, upd_pts, upd_ids, upd_valid):
    """On-device batched mutation: one scatter per buffer, out-of-range
    (padding) rows dropped.  No donation — older generations stay live for
    in-flight readers (the epoch-swap contract)."""
    return (pts.at[slots].set(upd_pts, mode="drop"),
            ids.at[slots].set(upd_ids, mode="drop"),
            valid.at[slots].set(upd_valid, mode="drop"))


def _scatter_apply_labeled(pts, ids, valid, labels, slots, upd_pts,
                           upd_ids, upd_valid, upd_labels):
    """_scatter_apply with the label payload riding the same scatter —
    same indices, same drop semantics, same no-donation contract, so a
    generation's labels can never tear from its points."""
    return (pts.at[slots].set(upd_pts, mode="drop"),
            ids.at[slots].set(upd_ids, mode="drop"),
            valid.at[slots].set(upd_valid, mode="drop"),
            labels.at[slots].set(upd_labels, mode="drop"))
