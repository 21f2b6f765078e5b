"""Micro-batched distributed kNN query service — Algorithm 2 as a server.

The paper answers one replicated query batch per call; serving "heavy
traffic from millions of users" (ROADMAP) means coalescing many independent
requests — each with its own neighbor count l — into full device batches
against the sharded point set, the way PANDA-style distributed kNN systems
amortize every datastore pass over a query block.  Pipeline:

  submit(q, l) -> [request queue] -> micro-batcher (linger max_wait_ms,
      pad-to-bucket) -> persistent shard_map executable for that bucket
      (B, l_max) shape -> per-request QueryResult (dists / ids / values
      + round/message accounting from SelectionResult)

Static shapes for jit: requests are padded to the smallest configured
bucket size (padding rows carry l=0, which Algorithm 2 resolves to "select
nothing" without touching real rows), and every per-request l shares the
static buffer bound l_max with per-row masking inside
``core.knn.knn_query_batched``.  Each bucket shape therefore compiles
exactly once (``warmup()`` pre-pays all of them) and every subsequent
flush is a cached-executable call.

All tuning — bucket shapes, l_max, linger, sampling, num_pivots, and the
selection-vs-gather A/B — comes from ``configs.knn_service.KnnServiceConfig``;
the server adds no knobs of its own.  benchmarks/bench_serve.py measures
sustained queries/sec and p50/p99 latency for both sampler settings.

The server can also be backed by a mutable store (``store=`` — a
``repro.store.MutableStore``): each dispatch captures the store's current
immutable snapshot, so in-flight micro-batches finish against the
generation they started with while later submissions see the newly
swapped epoch (DESIGN.md Section 7).  ``QueryResult.generation`` reports
which epoch answered.  benchmarks/bench_ingest.py measures ingest
throughput and query latency under concurrent ingest.

With ``cfg.route="pruned"`` each dispatch first consults per-shard pivot
summaries (store/summaries.py; captured in the same lock acquisition as
the snapshot, so routing metadata always matches the answering epoch) and
computes the micro-batch's touched-shard set; shards the lower-bound test
rules out are masked wholesale inside the executable and drop out of the
k-machine message bill (``QueryResult.shards_touched``).  Answers are
bit-identical to ``route="exact"`` — the property harness
tests/test_routing.py enforces this, DESIGN.md Section 8 explains why.
benchmarks/bench_serve.py runs the exact-vs-pruned A/B.

How much a store-backed server can actually prune is the store's
placement policy's doing (store/placement.py, DESIGN.md Section 9):
``placement="affinity"`` + ``redeal="proximity"`` keep clusters
shard-coherent so routing skips shards; ``placement_stats()`` surfaces
the per-shard live histogram and the realized prune rate.
benchmarks/bench_serve.py runs the placement A/B on a clustered
streaming-ingest workload.

How long that pruning *stays* effective under churn is the adaptive
maintenance subsystem's doing (store/adaptive.py, DESIGN.md Section 10):
multi-pivot summaries (``summary_pivots``), scheduled per-shard exact
re-tightening (``retighten_every``), and radius-triggered shard
splitting (``split_radius_factor``) keep the covering bounds tight
mid-stream; ``placement_stats()`` reports the per-shard
``summary_slack`` decay probe and the maintenance counters.
benchmarks/bench_serve.py runs the drifting-cluster adaptive A/B.

With ``cfg.route_compute="device"`` the routing decision itself moves
off the host: the summary operands are packed once per frozen summaries
object (kernels/routing.pack_summaries) and the lower-bound /
cumulative-live threshold test runs as a Pallas prologue inside the same
jitted program as the shard_map query — the touched-shard mask returns
with the batch instead of costing a separate O(B·k·(dim+r)) host numpy
pass per dispatch.  Answers stay bit-identical (tests/test_routing.py
proves mask parity against the host router; DESIGN.md Section 11).

With ``cfg.search="approx"`` the dispatch prologue additionally consults
the per-shard covering-ball bucket index (store/index.py, frozen
generation-coupled with the snapshot — ``serving_snapshot()`` hands out
all three from one lock acquisition): buckets whose distance lower bound
cannot beat the batch's cumulative-live threshold are dropped, and their
slots enter the fused kernel as non-candidates (core/knn.py
``point_candidates`` — masked exactly like tombstones).  This trades the
repo's bit-identical invariant for a *measured* recall contract: every
answer is tagged ``recall_mode="approx"``, the realized candidate
fraction feeds the ``serve.candidate_fraction`` histogram, and the
shadow auditor (mode="recall") replays sampled batches through the
exact collective to measure recall@l against ``cfg.recall_floor``
(DESIGN.md Section 13).  Under ``route_compute="device"`` the bucket
decision runs as the second stage of the same Pallas prologue
(kernels/routing.index_mask), so the candidate mask also rides the
batch's own launch.  benchmarks/bench_serve.py runs the exact-vs-approx
A/B and hard-asserts the recall floor at the candidate-reduction target.

With ``cfg.predict`` set (src/repro/predict/, DESIGN.md Section 15) the
server also answers the paper's endgame — a label for the query — in one
of two modes.  ``predict_mode="exact"`` folds the Algorithm 2 winner
mask into a class vote / value mean *inside* the fused executable (one
extra psum: only the histogram crosses the network; +1 round, +(t-1)
messages) and is bit-identical to a single-machine vote over the true
l nearest neighbors.  ``predict_mode="ensemble"`` skips the selection
collectives entirely: each routed shard answers its local-kNN vote in
ONE message (arXiv 1812.05005) and the host aggregates — the message
bill is exactly ``touched_shards``, and the accuracy gap vs exact is a
measured contract (``cfg.accuracy_floor``, ShadowAuditor
mode="accuracy", bench_serve's accuracy-vs-message-bill table).
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.knn_service import CONFIG, KnnServiceConfig
from repro.core import knn as knn_mod
from repro.kernels import ops as kops
from repro.kernels import routing as routing_mod
from repro.obs import (BatchCapture, ContractAuditor, ExplainRecord,
                       ObsPlane, ShadowAuditor, SloEngine)
from repro.obs import gcwatch
from repro.obs import trace as obs_trace
from repro.obs.export import ObsHttpServer
from repro.obs.metrics import default_registry
from repro.parallel.compat import make_mesh, shard_map
from repro import predict as predict_mod
from repro.store import index as index_mod
from repro.store import summaries as summaries_mod

_ID_SENTINEL = 2**31 - 1

# Same-thread spans of every tracer, the disabled one included, are also
# annotations in a recording jax.profiler session (obs/trace.py), on the
# profiler's clock beside the device's ops.
obs_trace.set_profiler_sink(jax.profiler.TraceAnnotation)


class QueryResult(NamedTuple):
    """Answer for one request.

    ``dists``/``ids`` have the request's own length l, sorted ascending by
    distance (+inf / INT32_MAX sentinel slots last, when fewer than l
    finite points exist).  ``values`` maps ids through the server's
    optional value table (kNN-LM token ids), -1 where absent.
    ``generation`` is the store generation the answer was computed
    against: 0 forever for a static-points server, the epoch number of
    the :class:`~repro.store.MutableStore` snapshot captured at dispatch
    for a store-backed one.

    Round/message accounting follows the k-machine model conventions used
    throughout the repo (see selection.py): the selection path costs 2
    rounds per Algorithm 1 iteration (pivot all_gather + count psum) plus a
    constant number of pipeline rounds (sample-prune and result gather),
    with k-1 leader-tree messages of O(1) scalars per round.  The gather
    baseline is one collective round whose payload is l scalars from each
    of k-1 peers — its ``messages`` entry counts those O(1)-word units, so
    the O(k*l) vs O(k*log l) contrast is directly visible.

    ``shards_touched`` is the size of the carrying batch's touched-shard
    set: k under ``route="exact"``; under ``route="pruned"`` the union,
    over the batch's real rows, of shards the summary lower-bound test
    could not rule out (store/summaries.py).  Pruned shards hold no
    candidates, so in the k-machine model they send nothing — the
    ``messages`` bill charges ``shards_touched - 1`` peers per round
    instead of ``k - 1``.

    ``recall_mode`` tags the answer's exactness contract: ``"exact"``
    (the default) means the true top-l, bit-identical to the paper's
    collective regardless of routing; ``"approx"`` means the answer went
    through the per-shard bucket index (``cfg.search``, store/index.py)
    and carries the measured recall contract (``cfg.recall_floor``,
    shadow-audited) instead.

    ``label``/``confidence`` are the prediction plane's answer
    (``cfg.predict``; None when prediction is off): the majority class
    id (as f32; -1 when no live neighbor voted) with its vote share, or
    the regression mean with the answering fraction.  ``predict_mode``
    tags how it was computed: ``"exact"`` (bit-identical to a
    single-machine vote over the true l-NN) or ``"ensemble"``
    (one-message-per-shard local votes, host-aggregated — dists/ids are
    all-sentinel because no point ever leaves its shard).
    """

    dists: np.ndarray
    ids: np.ndarray
    values: Optional[np.ndarray]
    l: int
    iterations: int        # Algorithm 1 iterations of the carrying batch
    rounds: int            # k-machine rounds of the carrying batch
    messages: int          # O(1)-word messages of the carrying batch
    survivors: int         # Lemma 2.3 post-prune candidate count (this row)
    bucket: int            # device batch shape the request rode in
    queued_s: float        # enqueue -> dispatch
    latency_s: float       # enqueue -> result
    generation: int = 0    # store epoch the answer was computed against
    shards_touched: int = -1   # carrying batch's touched-shard count
    recall_mode: str = "exact"   # "exact" | "approx" (bucket index used)
    explain_ref: object = None   # ExplainRecord handle (obs/explain.py)
    label: Optional[float] = None       # predicted class id / mean value
    confidence: Optional[float] = None  # vote share / answering fraction
    predict_mode: str = "none"   # "none" | "exact" | "ensemble"

    def explain(self) -> Optional[dict]:
        """The per-query explain report (obs/explain.py SCHEMA):
        per-shard routing bounds and threshold, per-bucket keep
        decisions, stage timings, and any maintenance commit that raced
        the request — assembled lazily on first call from the dispatch's
        cheap capture, cached after.  None for results constructed
        without a capture (hand-built in tests)."""
        return None if self.explain_ref is None else self.explain_ref.build()


@dataclasses.dataclass
class ServerStats:
    """Serving counters, safe to update and read from any thread.

    ``observe()`` may race between the micro-batcher thread and a
    caller's ``flush()``; it takes the internal lock, and readers who
    need mutually-consistent values (e.g. ``queries`` vs
    ``bucket_counts``) take ``snapshot()`` rather than reading fields
    one by one — field reads are individually atomic in CPython but a
    multi-field read can tear across a concurrent ``observe()``.
    """

    queries: int = 0
    batches: int = 0
    padded_rows: int = 0
    bucket_counts: dict = dataclasses.field(default_factory=dict)
    # Routing effectiveness (route="pruned" dispatches only): summed
    # touched-shard counts and the batches they came from, the inputs to
    # KnnServer.placement_stats()'s prune rate.
    touched_shards: int = 0
    routed_batches: int = 0
    # Defensive tally: QueryResult.shards_touched's -1 "never routed"
    # sentinel must never be summed into the prune-rate inputs above —
    # one leaked sentinel would silently *raise* the reported prune
    # rate.  A negative ``touched`` is a caller bug; it is counted here
    # instead of poisoning the math (tests/test_knn_server.py pins
    # both routes).
    invalid_touched: int = 0
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def observe(self, bucket: int, n_real: int,
                touched: Optional[int] = None):
        with self._lock:
            self.queries += n_real
            self.batches += 1
            self.padded_rows += bucket - n_real
            self.bucket_counts[bucket] = self.bucket_counts.get(bucket, 0) + 1
            if touched is not None:
                if touched < 0:
                    self.invalid_touched += 1
                else:
                    self.touched_shards += touched
                    self.routed_batches += 1

    def snapshot(self) -> dict:
        """One-lock-acquisition copy of every counter — the consistent
        view: invariants like ``batches == sum(bucket_counts.values())``
        hold inside a snapshot even while ``observe()`` races."""
        with self._lock:
            return {"queries": self.queries, "batches": self.batches,
                    "padded_rows": self.padded_rows,
                    "bucket_counts": dict(self.bucket_counts),
                    "touched_shards": self.touched_shards,
                    "routed_batches": self.routed_batches,
                    "invalid_touched": self.invalid_touched}


@dataclasses.dataclass
class _Pending:
    query: np.ndarray
    l: int
    t_enqueue: float
    future: Future
    # The request's root trace span (obs/trace.py), begun in submit() at
    # t_enqueue on the caller's thread and ended when the micro-batcher
    # resolves the future; the shared no-op span when tracing is off.
    span: object = None


class KnnServer:
    """Serve l-NN queries against a mesh-sharded point set.

    Two backing modes:

    * **Static** — ``points``: (n, dim) host array, sharded over
      ``axis_name`` at construction (n must divide the mesh axis size).
      ``values``: optional (n,) int32 per-point payload (e.g. kNN-LM
      next-token ids), looked up host-side for winners — values never
      cross the device interconnect, preserving the paper's
      only-distances-and-ids-on-the-wire property.

    * **Mutable** — ``store=``: a :class:`repro.store.MutableStore`.  The
      server captures ``store.snapshot()`` at each dispatch: in-flight
      micro-batches keep computing against the generation they captured
      while newer generations land (epoch-swapped serving — snapshots are
      immutable device arrays, so a swap can never tear or drop an
      in-flight query), and every answer reports the generation it was
      computed against.  Buffer shapes are fixed by the store's capacity,
      so mutations never trigger recompilation.

    Synchronous use: ``submit(...)`` then ``flush()`` (or ``query_batch``).
    Server use: ``with server.serving(): ...`` runs the micro-batcher
    thread, which lingers ``cfg.max_wait_ms`` after the first pending
    request to fill a bucket before dispatching.
    """

    def __init__(self, points=None, values=None, labels=None, *, store=None,
                 cfg: KnnServiceConfig = CONFIG, mesh=None,
                 axis_name: str = "knn", seed: int = 0):
        self.cfg = cfg
        if not cfg.bucket_sizes or list(cfg.bucket_sizes) != sorted(
                set(cfg.bucket_sizes)):
            raise ValueError(f"bucket_sizes must be ascending and unique, "
                             f"got {cfg.bucket_sizes}")
        if cfg.route not in ("exact", "pruned"):
            raise ValueError(f"route must be 'exact' or 'pruned', "
                             f"got {cfg.route!r}")
        if cfg.route_compute not in ("host", "device"):
            raise ValueError(f"route_compute must be 'host' or 'device', "
                             f"got {cfg.route_compute!r}")
        if cfg.search not in ("exact", "approx"):
            raise ValueError(f"search must be 'exact' or 'approx', "
                             f"got {cfg.search!r}")
        if cfg.search == "approx" and cfg.index_buckets < 1:
            raise ValueError(f"search='approx' needs index_buckets >= 1, "
                             f"got {cfg.index_buckets}")
        if cfg.predict not in ("none", "vote", "regress"):
            raise ValueError(f"predict must be 'none', 'vote' or "
                             f"'regress', got {cfg.predict!r}")
        if cfg.predict_mode not in ("exact", "ensemble"):
            raise ValueError(f"predict_mode must be 'exact' or 'ensemble', "
                             f"got {cfg.predict_mode!r}")
        self._indexed = cfg.search == "approx"
        self._predict = cfg.predict != "none"
        self._ensemble = self._predict and cfg.predict_mode == "ensemble"
        if self._predict and cfg.sampler != "selection":
            raise ValueError(
                f"predict={cfg.predict!r} needs sampler='selection' "
                f"(the gather baseline has no winner mask to vote over), "
                f"got sampler={cfg.sampler!r}")
        if self._ensemble:
            # The ensemble executable is collective-free by construction
            # (the one-message-per-shard bill is the whole point), so the
            # per-row local-k split must be computed host-side from the
            # touched-shard count — which rules out device routing — and
            # the per-shard local top-l must be the true local top-l,
            # which rules out the approximate bucket index.
            if cfg.search != "exact":
                raise ValueError(
                    "predict_mode='ensemble' requires search='exact' "
                    "(per-shard local votes need the true local top-l)")
            if cfg.route == "pruned" and cfg.route_compute == "device":
                raise ValueError(
                    "predict_mode='ensemble' requires route_compute="
                    "'host': the local-k split needs the touched-shard "
                    "count before the launch")
            if cfg.obs_audit_every > 0 and cfg.predict != "vote":
                raise ValueError(
                    "the accuracy shadow audit (obs_audit_every > 0 with "
                    "predict_mode='ensemble') needs predict='vote' — "
                    "label agreement is defined on class ids")
        self._store = store
        self._labels = None          # device label operand (predict only)
        self._labels_host = None     # host mirror for labels_for (static)
        if store is not None:
            if points is not None or values is not None or labels is not None:
                raise ValueError(
                    "pass either points/values/labels or store=, not both")
            if mesh is not None and mesh != store.mesh:
                raise ValueError("store-backed server uses the store's mesh")
            if self._predict and not store.with_labels:
                raise ValueError(
                    f"predict={cfg.predict!r} needs a labeled store: "
                    f"construct it with with_labels=True "
                    f"(cfg.store_kwargs() does when predict != 'none')")
            self.axis_name = store.axis_name
            self.mesh = store.mesh
            self.k = store.k
            self.dim = store.dim
            self.m_local = store.cap
            self._points = self._ids = None
            self._values = None
        else:
            if points is None:
                raise ValueError("points or store= required")
            self.axis_name = axis_name
            self.mesh = mesh if mesh is not None else make_mesh(
                (jax.device_count(),), (axis_name,))
            # k machines = the size of the service axis only; on a
            # multi-axis mesh the other axes replicate the store and the
            # collectives.
            self.k = int(dict(self.mesh.shape)[axis_name])

            points = np.asarray(points, np.float32)
            n, dim = points.shape
            if n % self.k:
                raise ValueError(
                    f"n_points={n} must divide the mesh axis size {self.k}")
            self.dim = dim
            self.m_local = n // self.k
            sharded = NamedSharding(self.mesh, P(axis_name))
            self._points = jax.device_put(points, sharded)
            self._ids = jax.device_put(np.arange(n, dtype=np.int32), sharded)
            self._values = None if values is None else np.asarray(values,
                                                                  np.int32)
            if labels is not None:
                labels = np.asarray(labels, np.float32)
                if labels.shape != (n,):
                    raise ValueError(f"labels shape {labels.shape} != "
                                     f"({n},)")
                self._labels_host = labels
                self._labels = jax.device_put(labels, sharded)
            if self._predict and self._labels is None:
                raise ValueError(
                    f"predict={cfg.predict!r} on a static server needs "
                    f"the labels= constructor argument")

        # Static-point routing summaries, built once at generation 0
        # (store-backed servers instead capture the store's
        # generation-coupled summaries at every dispatch — the sketch
        # there is the *store's*, configured at store construction, so a
        # conflicting service config must fail loudly rather than be
        # silently ignored).
        self._summaries = None
        if cfg.route == "pruned":
            if store is None:
                self._summaries = summaries_mod.build_summaries(
                    points, self.k,
                    num_projections=cfg.route_num_projections,
                    seed=cfg.route_proj_seed,
                    num_pivots=cfg.summary_pivots)
            elif (store.summary_projections != cfg.route_num_projections
                    or store.summary_seed != cfg.route_proj_seed
                    or store.summary_pivots != cfg.summary_pivots):
                raise ValueError(
                    f"route summary sketch mismatch: store was built with "
                    f"summary_projections={store.summary_projections}"
                    f"/summary_seed={store.summary_seed}"
                    f"/summary_pivots={store.summary_pivots} but cfg asks "
                    f"for route_num_projections={cfg.route_num_projections}"
                    f"/route_proj_seed={cfg.route_proj_seed}"
                    f"/summary_pivots={cfg.summary_pivots}; "
                    f"configure the store, or match the config to it")

        # search="approx" bucket index (store/index.py, DESIGN.md §13).
        # Store-backed: the index is the *store's* — generation-coupled,
        # captured per dispatch via serving_snapshot() — so a knob
        # conflict fails loudly, like the routing sketch above.  Static:
        # built once over the construction points, generation 0 forever.
        self._index0 = None
        if self._indexed:
            if store is None:
                idx = index_mod.IndexMaintainer(
                    self.k, self.m_local, self.dim, cfg.index_buckets)
                idx.rebuild(points, np.ones(len(points), bool))
                self._index0 = idx.freeze(0)
            elif store.index_buckets != cfg.index_buckets:
                raise ValueError(
                    f"search index mismatch: store was built with "
                    f"index_buckets={store.index_buckets} (0 = no index "
                    f"maintained) but cfg asks for "
                    f"index_buckets={cfg.index_buckets}; construct the "
                    f"store from cfg.store_kwargs(), or match the config "
                    f"to it")

        # Pre-flight kernel-dispatch report, one row per bucket shape:
        # the routing (Pallas kernel / interpret / jnp oracle) of the
        # l2_distance step these executables run, plus fused
        # distance_topk eligibility for capacity planning
        # (kernels/ops.py service_envelope).
        self.envelopes = [
            kops.service_envelope(b, self.m_local, self.dim, cfg.l_max)
            for b in cfg.bucket_sizes]

        # The exact-fold executable is built even for ensemble servers:
        # it is the oracle the accuracy shadow audit replays through.
        self._fn = self._build_executable()
        self._ensemble_fn = (self._build_ensemble_executable()
                             if self._ensemble else None)
        # route_compute="device": fold the routing decision into the same
        # jitted program as the query (Pallas prologue, kernels/routing.py).
        # The packed summary operands are cached per frozen-summaries
        # object — identity, not generation, because a background
        # re-tighten re-freezes at the *same* generation with tighter
        # bounds (store/maintenance.py) and the cache must follow it.
        self._route_fn = None
        self._packed_cache = None
        self._ipacked_cache = None
        if cfg.route == "pruned" and cfg.route_compute == "device":
            self._route_fn = _OneBuffer(self._build_device_router())
        self._base_key = jax.random.PRNGKey(seed)
        self._batch_counter = 0

        self._cv = threading.Condition()
        self._pending: list[_Pending] = []
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self.stats = ServerStats()

        # ---- observability plane (src/repro/obs/, DESIGN.md §12) ----
        # Tracer per cfg.obs_trace (no-op when off); a private metrics
        # registry (always live — counters/histograms are O(1) observes);
        # the Theorem-1 contract auditor (always on — it is arithmetic on
        # numbers _dispatch computes anyway) and the sampled shadow-exact
        # auditor (cfg.obs_audit_every > 0 and a pruned route).  A
        # store-backed server attaches its plane to the store so applies
        # and maintenance cycles land in the same trace/registry as the
        # queries racing them.
        self.obs = ObsPlane.from_config(cfg)
        if store is not None:
            store.attach_obs(self.obs)
        reg = self.obs.metrics
        # The host's time per dispatch, split so that what the serving
        # thread neither ran nor waited on the device for (the GIL, the
        # scheduler) is their difference: prologue_s + dispatch_s span
        # chunk taken to resolve done, wait_s is the readback's block on
        # the device and copy_s the rest of the readback, cpu_s the
        # thread's CPU time over the same span; lock_wait_s is the
        # snapshot stage's wait for the store lock.
        self._m = {
            "queued": reg.histogram("serve.queued_s"),
            "prologue": reg.histogram("serve.prologue_s"),
            "snapshot": reg.histogram("serve.snapshot_s"),
            "route": reg.histogram("serve.route_s"),
            "kernel": reg.histogram("serve.kernel_s"),
            "wait": reg.histogram("serve.wait_s"),
            "copy": reg.histogram("serve.copy_s"),
            "resolve": reg.histogram("serve.resolve_s"),
            "dispatch": reg.histogram("serve.dispatch_s"),
            "cpu": reg.histogram("serve.cpu_s"),
            "latency": reg.histogram("serve.latency_s"),
            "rounds": reg.histogram("serve.rounds"),
            "messages": reg.histogram("serve.messages"),
            "touched": reg.histogram("serve.touched_shards"),
            "cand_frac": reg.histogram("serve.candidate_fraction"),
            "errors": reg.counter("serve.dispatch_errors"),
        }
        if store is not None:
            self._m["lock_wait"] = reg.histogram("store.lock_wait_s")
        # Collector pauses land in this registry while the server is
        # open (obs/gcwatch.py); close() leaves.
        gcwatch.HOOK.subscribe(self.obs)
        self._contract = ContractAuditor(reg, k=self.k)
        # The shadow replay audits whichever contract this server
        # serves: byte-identity for pruned exact routing, measured
        # recall@l against the floor for the approximate index tier,
        # ensemble-vs-exact label agreement for ensemble prediction.
        if self._ensemble:
            audit_mode, audit_floor = "accuracy", cfg.accuracy_floor
        elif self._indexed:
            audit_mode, audit_floor = "recall", cfg.recall_floor
        else:
            audit_mode, audit_floor = "bytes", cfg.recall_floor
        self._shadow = (ShadowAuditor(
            reg, every=cfg.obs_audit_every,
            mode=audit_mode, floor=audit_floor)
            if cfg.obs_audit_every > 0 else None)
        self._env_by_bucket = dict(zip(cfg.bucket_sizes, self.envelopes))
        # ---- operator layer (obs/explain.py, obs/slo.py, obs/export.py,
        # DESIGN.md §14) ----
        # Explain captures are always on: per dispatch they cost one
        # small object of references to things the dispatch already
        # holds; the report itself is assembled lazily.  The ring keeps
        # the newest records for explain_last().
        self._explains: deque = deque(maxlen=256)
        # The SLO engine exists only when the config declares at least
        # one objective (slo_* knobs); it shares this server's registry
        # (event windows) and tracer (alert spans).
        self._slo = SloEngine.from_config(cfg, reg, self.obs.tracer)
        # Metrics exposition endpoint: >0 = that localhost port, -1 =
        # ephemeral (tests), 0 = off.
        self._http = None
        if cfg.obs_http_port != 0:
            self._http = ObsHttpServer(
                reg, port=max(cfg.obs_http_port, 0),
                snapshot_fn=self.obs_snapshot)

    # ---- compiled dispatch ---------------------------------------------

    def _build_executable(self):
        return _OneBuffer(build_query_program(
            self.cfg, self.mesh, self.axis_name,
            masked=self._store is not None, predicting=self._predict,
            indexed=self._indexed))

    def _build_ensemble_executable(self):
        """The one-message-per-shard prediction program (predict/
        ensemble.py, arXiv 1812.05005).

        Collective-free by construction: each shard computes its masked
        local top-l (tombstones, routed-away shards, and bucket padding
        enter at +inf exactly as in the exact path) and reduces its
        first ``kl`` finite candidates to a class histogram / (sum,
        count) pair.  The output leaves the program *sharded*
        (out_spec P(axis) → host (k, B, C)): in the k-machine model each
        routed shard sends exactly one O(C) message and nothing else —
        the ``messages == touched_shards`` bill ``_accounting`` charges
        and bench_serve hard-asserts.  The per-row local-k operand
        ``kl`` comes from the host (predict/ensemble.local_k_for), which
        is why ensemble mode requires host-computed routing.
        """
        cfg = self.cfg
        axis = self.axis_name
        l_max = cfg.l_max
        distances_fn = _distances_fn(cfg)
        masked = self._store is not None
        routed = cfg.route == "pruned"
        vote = cfg.predict == "vote"
        num_classes = cfg.num_classes

        def fn(*a):
            it = iter(a)
            pts, pids = next(it), next(it)
            pvalid = next(it) if masked else None
            plabels = next(it)
            active = next(it) if routed else None
            q, kl = next(it), next(it)
            valid = knn_mod._apply_shard_routing(pvalid, active,
                                                 pts.shape[0])
            d_full = knn_mod._masked_distances(distances_fn, q, pts,
                                               valid)
            d, _gid, labels_top = knn_mod.local_top_l(
                d_full, pids, l_max, extra=plabels)
            if vote:
                out = predict_mod.local_vote(d, labels_top, kl,
                                             num_classes)
            else:
                out = predict_mod.local_mean(d, labels_top, kl)
            return out[None]          # (1, B, C) -> stacked (k, B, C)

        n_sharded = 3 + int(masked) + int(routed)
        in_specs = (P(axis),) * n_sharded + (P(None), P(None))
        return jax.jit(shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=P(axis),
            check_vma=False))

    def _build_device_router(self):
        """Outer jitted program: route prologue + the shard_map query.

        The prologue runs ``kops.route_mask`` (the Pallas routing kernel,
        kernels/routing.py) over the whole micro-batch, reduces the
        per-row keep mask to the batch's union ``active`` vector, feeds
        it to the routed executable as its (k,) shard-active operand, and
        returns ``active`` as a fifth output — the touched-shard set
        rides the launch home with the answers, replacing the host
        numpy ``summaries.route_shards`` pass per dispatch.  Nested jit
        inlines, so the whole thing is one cached executable per bucket.

        With ``search="approx"`` the prologue grows its second stage:
        the per-row shard keep feeds ``kops.index_mask`` (the bucket-
        granular threshold kernel), the batch-union bucket keep is
        decoded to the (n,) per-slot candidate operand through the
        cached ``colidx``/``has`` maps (``_index_ops_for``), and the
        bucket keep comes home as a sixth output so the dispatcher can
        report the candidate fraction from the index's own live counts
        without a device readback.
        """
        inner = self._fn.inner
        slack = self.cfg.route_slack

        if not self._indexed:
            def routed(operands, packed, q, l_arr, key):
                rows = kops.route_mask(q, l_arr, packed, slack=slack)
                active = jnp.any(rows, axis=0)
                out = inner(*operands, active, q, l_arr, key)
                # d, i, iters, surv [, label, conf] + the touched set
                return tuple(out) + (active,)

            return routed

        oversample = self.cfg.index_oversample

        def routed_indexed(operands, packed, ipacked, colidx, has,
                           q, l_arr, key):
            rows = kops.route_mask(q, l_arr, packed, slack=slack)
            active = jnp.any(rows, axis=0)
            brows = kops.index_mask(q, l_arr, rows, ipacked,
                                    oversample=oversample)
            keep_any = jnp.any(brows, axis=0)          # (k·b,)
            cand = has & keep_any[colidx]              # (n,) slot mask
            out = inner(*operands, cand, active, q, l_arr, key)
            return tuple(out) + (active, keep_any)

        return routed_indexed

    def _packed_for(self, summ):
        """Kernel-layout summary operands for ``summ``, cached by object
        identity (one frozen ShardSummaries == one packed tuple; a
        benign last-writer-wins race between concurrent dispatchers just
        repacks once more)."""
        cached = self._packed_cache
        if cached is None or cached[0] is not summ:
            cached = (summ, routing_mod.pack_summaries(summ))
            self._packed_cache = cached
        return cached[1]

    def _index_ops_for(self, index):
        """Device-router operands for ``index``, cached by object
        identity like ``_packed_for``: the kernel-layout packed tuple
        (kernels/routing.pack_index) plus the flat slot decode that
        turns the kernel's (k·b,) bucket keep into the executable's
        (n,) per-slot candidate operand — ``colidx = shard·b + bucket``
        per slot, ``has = slot is assigned`` (dead/free slots are never
        candidates)."""
        cached = self._ipacked_cache
        if cached is None or cached[0] is not index:
            packed = routing_mod.pack_index(index)
            a = index.assign                        # (k·cap,) int32
            shard = np.arange(a.shape[0], dtype=np.int32) // self.m_local
            colidx = (shard * index.num_buckets
                      + np.maximum(a, 0)).astype(np.int32)
            cached = (index, packed, colidx, a >= 0)
            self._ipacked_cache = cached
        return cached[1], cached[2], cached[3]

    def _backing_arrays(self):
        """(executable operands, generation, summaries, index) for one
        dispatch.

        Store-backed servers capture the current snapshot here — the
        epoch-swap point.  The returned arrays are immutable, so a batch
        dispatched before a flush finishes cleanly against its own
        generation no matter how many swaps land meanwhile.  Snapshot,
        routing summaries, and (for ``search="approx"``) the bucket
        index come from one lock acquisition (``routing_snapshot`` /
        ``serving_snapshot``), so neither can ever describe a different
        generation than the arrays being queried; for static servers the
        construction-time summaries/index are generation 0 forever.
        """
        if self._store is not None:
            if self._indexed:
                snap, summ, idx = self._store.serving_snapshot()
            else:
                (snap, summ), idx = self._store.routing_snapshot(), None
            ops = (snap.points, snap.ids, snap.valid)
            if self._predict:
                ops = ops + (snap.labels,)
            return ops, snap.generation, summ, idx
        ops = (self._points, self._ids)
        if self._predict:
            ops = ops + (self._labels,)
        return ops, 0, self._summaries, self._index0

    def placement_stats(self) -> dict:
        """Locality and bound fidelity of the layout being served, as
        routing sees it.

        ``live_per_shard``: per-shard live histogram (the balance the
        placement guardrail and the compactor defend; uniform
        ``m_local`` for a static server).  ``prune_rate``: fraction of
        shard visits the summary lower-bound test avoided across all
        routed dispatches so far — ``1 − touched/(batches·k)``, 0.0
        until a ``route="pruned"`` batch has run.  ``summary_slack``:
        per-shard covering-radius decay (maintained radius minus exact
        live radius, summaries.summary_slack) — how much certified
        pruning power incremental maintenance has cost since the last
        exact rebuild; identically 0.0 for a static server, whose
        summaries are exact at construction forever.  ``maintenance``:
        the adaptive subsystem's knobs and counters (re-tightenings,
        splits — store/adaptive.py).  Benchmarks read this after an
        ingest phase to report per-policy prune rate and bound decay
        (DESIGN.md Sections 9 and 10).
        """
        snap = self.stats.snapshot()
        touched = snap["touched_shards"]
        routed = snap["routed_batches"]
        if self._store is not None:
            hist = [int(v) for v in self._store.live_per_shard]
            placement = self._store.placement
            redeal = self._store.redeal
            slack = [float(v) for v in self._store.summary_slack()]
            maintenance = self._store.maintenance_stats()
        else:
            hist = [self.m_local] * self.k
            placement = redeal = "static"
            slack = [0.0] * self.k
            maintenance = {"summary_pivots": self.cfg.summary_pivots,
                           "retighten_every": 0,
                           "split_radius_factor": 0.0,
                           "retightens": 0, "splits": 0}
        rate = 1.0 - touched / (routed * self.k) if routed else 0.0
        return {"placement": placement, "redeal": redeal,
                "live_per_shard": hist, "routed_batches": routed,
                "prune_rate": rate,
                "summary_slack": slack,
                "max_summary_slack": max(slack) if slack else 0.0,
                "maintenance": maintenance}

    def obs_snapshot(self) -> dict:
        """The unified observability view (DESIGN.md §12): one dict with
        the legacy serving counters, this server's metric registry
        (per-stage latency histograms, round/message/touched histograms,
        store + maintenance timings when a store is attached), the
        process-wide kernel-fallback counters (kernels/ops.py tallies
        into the default registry — no server handle down there), tracer
        ring stats, both auditors' verdicts, and ``placement_stats()``.
        Benchmarks consume this instead of private tallies
        (benchmarks/common.py ``obs_section``)."""
        shadow = (self._shadow.snapshot() if self._shadow is not None
                  else {"every": 0, "checks": 0, "divergences": 0,
                        "details": []})
        return {
            "server": self.stats.snapshot(),
            "metrics": self.obs.metrics.snapshot(),
            "kernel": default_registry().snapshot(prefix="kernel."),
            "trace": self.obs.tracer.stats(),
            "audit": {"contract": self._contract.snapshot(),
                      "shadow": shadow},
            "slo": (self._slo.snapshot() if self._slo is not None
                    else {"objectives": {}, "firing": [],
                          "alerts_fired": 0, "alerts_cleared": 0}),
            "placement": self.placement_stats(),
        }

    def explain_last(self, n: int = 1) -> list[dict]:
        """Built explain reports of the newest ``n`` resolved requests
        (oldest of the n first) — the operator's "why was that one
        slow/broad?" entry point; ``QueryResult.explain()`` answers the
        same for a result you still hold."""
        if n < 1:
            return []
        recs = list(self._explains)[-n:]
        return [r.build() for r in recs]

    def export_trace_jsonl(self, path_or_file) -> int:
        """Dump the tracer ring as JSONL (0 spans when tracing is off)."""
        return self.obs.tracer.export_jsonl(path_or_file)

    def close(self) -> None:
        """Quiesce the micro-batcher, release the exposition endpoint and
        stop counting collector pauses (idempotent)."""
        self.stop()
        if self._http is not None:
            self._http.close()
        gcwatch.HOOK.unsubscribe(self.obs)

    def warmup(self):
        """Compile every bucket shape up front (one trace per bucket)."""
        operands, _, summ, idx = self._backing_arrays()
        if self._route_fn is not None:
            packed = self._packed_for(summ)
            iops = self._index_ops_for(idx) if self._indexed else ()
            for b in self.cfg.bucket_sizes:
                q = np.zeros((b, self.dim), np.float32)
                l_arr = np.zeros(b, np.int32)
                _readback(self._route_fn(operands, packed, *iops, q, l_arr,
                                         self._base_key))()
            return
        if self._ensemble_fn is not None:
            eops = operands
            if self.cfg.route == "pruned":
                eops = eops + (np.ones(self.k, bool),)
            for b in self.cfg.bucket_sizes:
                q = np.zeros((b, self.dim), np.float32)
                kl = np.zeros(b, np.int32)
                jax.block_until_ready(self._ensemble_fn(*eops, q, kl))
        if self._indexed:
            operands = operands + (np.ones(self.k * self.m_local, bool),)
        if self.cfg.route == "pruned":
            operands = operands + (np.ones(self.k, bool),)
        for b in self.cfg.bucket_sizes:
            q = np.zeros((b, self.dim), np.float32)
            l_arr = np.zeros(b, np.int32)
            _readback(self._fn(*operands, q, l_arr, self._base_key))()

    # ---- store passthrough ----------------------------------------------
    # The server is most callers' only handle on the serving stack, so
    # the store's mutation and payload APIs are exposed here 1:1 (same
    # signatures, same atomic-batch semantics).  Static servers raise:
    # their point set is immutable by construction.

    def _require_store(self, op: str):
        if self._store is None:
            raise ValueError(f"{op}() needs a store-backed server "
                             f"(construct with store=)")
        return self._store

    def insert(self, points, ids=None, values=None, labels=None):
        """Stage point insertions on the backing store; returns the
        assigned global ids (see MutableStore.insert — ``values`` needs
        with_values, ``labels`` needs with_labels)."""
        return self._require_store("insert").insert(
            points, ids=ids, values=values, labels=labels)

    def update(self, ids, points, labels=None):
        """Stage in-place point overwrites; omitted ``labels`` keep the
        current label payload (MutableStore.update)."""
        return self._require_store("update").update(ids, points,
                                                    labels=labels)

    def delete(self, ids):
        """Stage deletions by global id (MutableStore.delete)."""
        return self._require_store("delete").delete(ids)

    def flush_store(self) -> int:
        """Apply staged mutations as one epoch swap; returns the new
        generation (MutableStore.flush)."""
        return self._require_store("flush_store").flush()

    @property
    def with_values(self) -> bool:
        """Whether answers carry the int payload table (store
        with_values, or the static ``values=`` argument)."""
        return (self._store.with_values if self._store is not None
                else self._values is not None)

    @property
    def with_labels(self) -> bool:
        """Whether a label payload is attached (store with_labels, or
        the static ``labels=`` argument)."""
        return (self._store.with_labels if self._store is not None
                else self._labels is not None)

    def values_for(self, ids):
        """Map global ids to int payload values, -1 where absent."""
        if self._store is not None:
            return self._store.values_for(ids)
        if self._values is None:
            raise RuntimeError("server has no value payload")
        ids = np.asarray(ids)
        safe = np.clip(ids, 0, len(self._values) - 1)
        return np.where(ids == _ID_SENTINEL, -1, self._values[safe])

    def labels_for(self, ids):
        """Map global ids to label payloads, NaN where absent."""
        if self._store is not None:
            return self._store.labels_for(ids)
        if self._labels_host is None:
            raise RuntimeError("server has no label payload")
        ids = np.asarray(ids)
        safe = np.clip(ids, 0, len(self._labels_host) - 1)
        return np.where(ids == _ID_SENTINEL, np.nan,
                        self._labels_host[safe]).astype(np.float32)

    # ---- request path ---------------------------------------------------

    def submit(self, query, l: Optional[int] = None) -> Future:
        """Enqueue one query; the Future resolves to a QueryResult."""
        l = self.cfg.l if l is None else int(l)
        if not 1 <= l <= self.cfg.l_max:
            raise ValueError(f"l={l} outside [1, l_max={self.cfg.l_max}]")
        query = np.asarray(query, np.float32)
        if query.shape != (self.dim,):
            raise ValueError(f"query shape {query.shape} != ({self.dim},)")
        t_enq = time.perf_counter()
        # Root span of this request's trace, opened at the enqueue
        # timestamp so the retroactive "queued" child always nests.
        span = self.obs.tracer.begin("request", t0=t_enq, l=l,
                                     same_thread=False)
        rec = _Pending(query, l, t_enq, Future(), span)
        with self._cv:
            self._pending.append(rec)
            self._cv.notify()
        return rec.future

    def query_batch(self, queries, ls=None) -> list[QueryResult]:
        """Synchronous convenience: submit all, flush, collect."""
        queries = np.asarray(queries, np.float32)
        if ls is None:
            ls = [None] * len(queries)
        futs = [self.submit(q, l) for q, l in zip(queries, ls)]
        self.flush()
        return [f.result() for f in futs]

    def flush(self):
        """Drain the queue now, bucket by bucket (synchronous path)."""
        while True:
            with self._cv:
                if not self._pending:
                    return
                chunk = self._take_chunk_locked()
            self._dispatch(chunk)

    def _take_chunk_locked(self) -> list[_Pending]:
        n = min(len(self._pending), self.cfg.bucket_sizes[-1])
        chunk, self._pending = self._pending[:n], self._pending[n:]
        return chunk

    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.bucket_sizes:
            if b >= n:
                return b
        return self.cfg.bucket_sizes[-1]

    def _accounting(self, iterations: int,
                    touched: int) -> tuple[int, int]:
        """k-machine (rounds, messages) for one dispatched batch.

        ``touched`` is the batch's touched-shard count (k when
        route="exact"): a pruned shard holds no candidates, so it never
        sends — the leader tree carries ``touched - 1`` peers' payloads
        per round instead of ``k - 1``.

        Ensemble prediction replaces the whole selection pipeline: one
        local pass, one O(C) answer per routed shard, zero collectives —
        1 round, exactly ``touched`` messages (the contract bench_serve
        hard-asserts per query).  Exact prediction adds the class
        histogram / value-sum psum on top of selection: +1 round,
        +(touched − 1) messages.
        """
        t = max(int(touched), 1)
        if self._ensemble:
            return 1, t
        if self.cfg.sampler == "gather":
            # one all-gather whose per-peer payload is l_max scalars
            return 1, (t - 1) * self.cfg.l_max
        rounds = 2 * iterations            # pivot all_gather + count psum
        rounds += 2 if self.cfg.use_sampling else 0   # sample + verify
        rounds += 2                        # result gather: count + pack
        messages = (t - 1) * rounds
        if self._predict:
            rounds += 1                    # the exact-predict psum
            messages += t - 1
        return rounds, messages

    def _unpack_outputs(self, out):
        """One executable's host outputs (``_run``) as ``(d, i, iters,
        surv, pred)`` where ``pred`` is the ``(label, confidence)`` pair
        when the config predicts and ``()`` otherwise (the executable's
        output arity follows the same flag)."""
        d, i, iters, surv = out[:4]
        return d, i, int(iters), surv, tuple(out[4:])

    def _ensemble_call(self, kspan, operands, active, q, l_arr, touched):
        """Serve one micro-batch in ensemble mode: local-k split on the
        host, one collective-free launch (under ``kspan``, as ``_run``),
        host aggregation.

        Returns ``(d, i, iters, surv, pred, payload, votes, kl,
        readback_s)`` shaped like the exact path's outputs so the
        dispatch tail is shared: ``d``/``i`` are all-sentinel (no point
        identity ever leaves its shard — that is the mode's bill),
        ``payload`` the (k, B, C) per-shard answers for the explain vote
        table, ``votes`` the (B, C) shard-vote tally (classification
        only), ``kl`` the per-row local-k actually used, ``readback_s``
        ``_run``'s pair.
        """
        cfg = self.cfg
        kl = predict_mod.local_k_for(l_arr, touched, cfg.local_k,
                                     cfg.l_max)
        ops = operands if active is None else operands + (active,)
        payload, readback_s = self._run(kspan, self._ensemble_fn, *ops, q,
                                        kl)
        act = (np.ones(self.k, bool) if active is None
               else np.asarray(active, bool))
        if cfg.predict == "vote":
            label, conf, votes = predict_mod.aggregate_vote(payload, act)
        else:
            label, conf = predict_mod.aggregate_regress(payload, act)
            votes = None
        b = q.shape[0]
        d = np.full((b, cfg.l_max), np.inf, np.float32)
        i = np.full((b, cfg.l_max), _ID_SENTINEL, np.int32)
        surv = np.zeros(b, np.int32)
        return d, i, 0, surv, (label, conf), payload, votes, kl, readback_s

    def _run(self, kspan, fn, *args):
        """One launch of ``fn(*args)`` and its readback, as ``kspan``'s
        leaves ``kernel.launch`` (the call returning, the copy home
        started) and ``kernel.readback`` (``_readback``); returns
        the outputs as host arrays and the readback's ``(wait_s,
        copy_s)``: the seconds blocked on the device, then the seconds
        to the host arrays."""
        tracer = self.obs.tracer
        with tracer.span("kernel.launch", parent=kspan):
            collect = _readback(fn(*args))
        with tracer.span("kernel.readback", parent=kspan):
            out, wait_s, copy_s = collect()
        return out, (wait_s, copy_s)

    def _dispatch(self, chunk: list[_Pending]):
        tracer = self.obs.tracer
        t_take = time.perf_counter()
        cpu0 = time.thread_time()
        pspan = tracer.begin("dispatch.prologue")
        n = len(chunk)
        bucket = self._bucket_for(n)
        q = np.zeros((bucket, self.dim), np.float32)
        l_arr = np.zeros(bucket, np.int32)      # padding rows keep l=0
        for row, rec in enumerate(chunk):
            q[row] = rec.query
            l_arr[row] = rec.l

        # _dispatch may run concurrently from the micro-batcher thread and
        # a caller's flush(); counter and stats updates go under the lock.
        with self._cv:
            batch_id = self._batch_counter
            self._batch_counter += 1
        key = jax.random.fold_in(self._base_key, batch_id)
        pspan.end()
        t_dispatch = time.perf_counter()
        # Per-batch trace root; request trees point at it through their
        # "serve" child's batch attribute (cross-tree reference by
        # attribute, never by parent link — trees stay single-rooted).
        dspan = tracer.begin("dispatch", t0=t_dispatch, batch=batch_id,
                             bucket=bucket, n_real=n)
        env = self._env_by_bucket[bucket]
        batch_spans = [dspan]        # every begun span, ended on error too
        # Stage boundaries are stamped explicitly (not read back off the
        # spans) so the per-stage histograms stay populated with tracing
        # off — the no-op span carries no clock.
        try:
            t_snap0 = time.perf_counter()
            sspan = tracer.begin("snapshot", parent=dspan, t0=t_snap0)
            batch_spans.append(sspan)
            if self._store is not None:
                waited0 = self._store.lock_wait_s()
            operands, generation, summ, idx = self._backing_arrays()
            if self._store is not None:
                n_live = int(self._store.live_per_shard.sum())
                maint0 = self._store.maint_commit_clock()
                lock_wait = self._store.lock_wait_s() - waited0
            else:
                n_live = self.m_local * self.k
                maint0 = (0, None)
            sspan.end(generation=generation, n_live=n_live)
            t_snap1 = time.perf_counter()
            t_route0 = t_route1 = None
            cand_frac = None       # search="approx" kept-live fraction
            keep_arr = None        # (k, b) batch-union bucket keep
            active_arr = None      # (k,) batch-union shard keep
            pred = ()              # (label, conf) when predicting
            epayload = evotes = kl = None    # ensemble-mode extras
            kattrs = dict(path=env["path"], l2_path=env["l2_path"],
                          fallback=env["fallback_reason"] or "")
            if self._route_fn is not None:
                # Device routing: the Pallas prologue computes the
                # touched-shard union inside the same launch as the
                # query; ``active`` comes back with the batch — so the
                # routing decision has no separate interval and its span
                # is recorded over the fused launch.
                t_kern0 = time.perf_counter()
                kspan = tracer.begin("kernel", parent=dspan, t0=t_kern0,
                                     route_compute="device", **kattrs)
                batch_spans.append(kspan)
                packed = self._packed_for(summ)
                if self._indexed:
                    iops = self._index_ops_for(idx)
                    (*out, active_arr, keep_any), readback_s = self._run(
                        kspan, self._route_fn, operands, packed, *iops, q,
                        l_arr, key)
                    keep_arr = keep_any.reshape(self.k, idx.num_buckets)
                    cand_frac = index_mod.candidate_fraction(
                        idx, keep_arr)
                else:
                    (*out, active_arr), readback_s = self._run(
                        kspan, self._route_fn, operands, packed, q, l_arr,
                        key)
                d, i, iters, surv, pred = self._unpack_outputs(out)
                touched = int(active_arr.sum())
                kspan.end(touched=touched)
                t_kern1 = time.perf_counter()
                tracer.record("route", t_kern0, t_kern1, parent=dspan,
                              compute="device", fused=True,
                              touched=touched, slack=self.cfg.route_slack)
            elif self.cfg.route == "pruned":
                # Touched-shard set for this micro-batch: the union over
                # real rows of the summary lower-bound survivors (padding
                # rows carry l=0 and route nowhere).  One collective pass
                # serves the whole batch, so the device mask is the union;
                # accounting charges only the touched subset.
                t_route0 = time.perf_counter()
                rspan = tracer.begin("route", parent=dspan, t0=t_route0,
                                     compute="host",
                                     slack=self.cfg.route_slack)
                batch_spans.append(rspan)
                active_rows = summaries_mod.route_shards(
                    summ, q, l_arr, slack=self.cfg.route_slack)
                active = active_rows.any(axis=0)
                active_arr = active
                touched = int(active.sum())
                extra = ()
                if self._indexed:
                    # Second prologue stage, bucket granularity: the
                    # per-row shard keep gates which buckets can
                    # compete, the batch-union bucket keep becomes the
                    # (n,) per-slot candidate operand (store/index.py).
                    pcand, cand_frac, keep_arr = self._host_candidates(
                        idx, q, l_arr, active_rows)
                    extra = (pcand,)
                rspan.end(touched=touched)
                t_route1 = time.perf_counter()
                kspan = tracer.begin("kernel", parent=dspan, t0=t_route1,
                                     route_compute="host", **kattrs)
                batch_spans.append(kspan)
                if self._ensemble:
                    (d, i, iters, surv, pred, epayload, evotes, kl,
                     readback_s) = self._ensemble_call(
                         kspan, operands, active, q, l_arr, touched)
                else:
                    out, readback_s = self._run(
                        kspan, self._fn, *operands, *extra, active, q,
                        l_arr, key)
                    d, i, iters, surv, pred = self._unpack_outputs(out)
                kspan.end()
                t_kern0, t_kern1 = t_route1, time.perf_counter()
            else:
                touched = self.k
                extra = ()
                if self._indexed:
                    t_route0 = time.perf_counter()
                    rspan = tracer.begin("route", parent=dspan,
                                         t0=t_route0, compute="host",
                                         indexed=True)
                    batch_spans.append(rspan)
                    pcand, cand_frac, keep_arr = self._host_candidates(
                        idx, q, l_arr, None)
                    extra = (pcand,)
                    rspan.end()
                    t_route1 = time.perf_counter()
                t_kern0 = time.perf_counter()
                kspan = tracer.begin("kernel", parent=dspan, t0=t_kern0,
                                     **kattrs)
                batch_spans.append(kspan)
                if self._ensemble:
                    (d, i, iters, surv, pred, epayload, evotes, kl,
                     readback_s) = self._ensemble_call(
                         kspan, operands, None, q, l_arr, touched)
                else:
                    out, readback_s = self._run(
                        kspan, self._fn, *operands, *extra, q, l_arr, key)
                    d, i, iters, surv, pred = self._unpack_outputs(out)
                kspan.end()
                t_kern1 = time.perf_counter()
        except Exception as exc:
            # A failed dispatch must never strand its futures (the chunk
            # already left the queue), kill the micro-batcher thread, or
            # leave torn spans behind.
            self._m["errors"].inc()
            for rec in chunk:
                _resolve(rec.future, error=exc)
                if rec.span is not None:
                    rec.span.end(error=type(exc).__name__)
            for sp in reversed(batch_spans):      # Span.end is idempotent
                sp.end(error=type(exc).__name__)
            return
        t_done = time.perf_counter()

        rounds, messages = self._accounting(iters, touched)
        self.stats.observe(
            bucket, n,
            touched=touched if self.cfg.route == "pruned" else None)
        l_real = max((rec.l for rec in chunk), default=1)
        # Theorem-1 contract: always-on envelope check.  The gather
        # sampler's bill charges the static buffer width l_max per peer,
        # so its envelope is checked against the same width.
        audit_l = (self.cfg.l_max if self.cfg.sampler == "gather"
                   else l_real)
        contract_ok = self._contract.check(
            l_max=audit_l, n_live=n_live, rounds=rounds, messages=messages,
            use_sampling=self.cfg.use_sampling, sampler=self.cfg.sampler,
            generation=generation)
        if self._store is not None:
            maint1 = self._store.maint_commit_clock()
            head_generation = self._store.generation
        else:
            maint1 = (0, None)
            head_generation = generation
        if self._slo is not None:
            self._slo.measure("contract", 0.0 if contract_ok else 1.0)
        # One capture per dispatch: references to what the dispatch
        # already holds (frozen summaries/index, its own padded query
        # block) plus the scalars above — the explain reports assemble
        # lazily from it (obs/explain.py).
        pmode = ("none" if not self._predict
                 else "ensemble" if self._ensemble else "exact")
        capture = BatchCapture(
            batch_id=batch_id, bucket=bucket, n_real=n,
            generation=generation, route=self.cfg.route,
            route_compute=("device" if self._route_fn is not None
                           else "host"),
            search=self.cfg.search, slack=self.cfg.route_slack,
            oversample=self.cfg.index_oversample,
            queries=q, ls=l_arr, summaries=summ, index=idx,
            active=active_arr, keep_any=keep_arr, touched=touched,
            candidate_fraction=cand_frac,
            predict=self.cfg.predict, predict_mode=pmode,
            labels=(pred[0] if pred else None),
            confidences=(pred[1] if pred else None),
            local_k=kl, shard_answers=epayload, votes=evotes,
            timings={
                "snapshot_s": t_snap1 - t_snap0,
                "route_s": (t_route1 - t_route0
                            if t_route0 is not None else None),
                "kernel_s": t_kern1 - t_kern0,
            },
            maint_before=maint0[0], maint_after=maint1[0],
            maint_last=maint1[1], contract_ok=contract_ok)
        # Shadow-exact audit: replay every Nth pruned/indexed batch
        # through the same executable with every shard active and every
        # slot a candidate — the exact collective at this generation
        # with this key.  For pruned exact routing the contract is
        # byte-identity (tests/test_routing.py as a production signal);
        # for search="approx" the auditor instead measures recall@l
        # against cfg.recall_floor.
        if (self._shadow is not None
                and (self.cfg.route == "pruned" or self._indexed
                     or self._ensemble)
                and self._shadow.due()):
            with tracer.span("shadow_audit", parent=dspan,
                             generation=generation) as aspan:
                all_on = (np.ones(self.k, bool)
                          if self.cfg.route == "pruned" else None)
                if self._ensemble:
                    # Accuracy mode: replay through the exact-fold
                    # executable (all shards active, same generation/key)
                    # and measure ensemble-vs-exact label agreement over
                    # the batch's real rows.
                    ok = self._shadow.check_labels(
                        pred[0], l_arr,
                        lambda: self._exact_label_replay(
                            operands, all_on, q, l_arr, key),
                        generation=generation, batch_id=batch_id,
                        touched=touched)
                    if (self._slo is not None
                            and self._shadow.last_agreement is not None):
                        self._slo.measure("label_agreement",
                                          self._shadow.last_agreement)
                else:
                    ok = self._shadow.check(
                        d, i, lambda: self._exact_replay(operands, all_on,
                                                         q, l_arr, key),
                        generation=generation, batch_id=batch_id,
                        touched=touched)
                    if (self._slo is not None
                            and self._shadow.mode == "recall"
                            and self._shadow.last_min_recall is not None):
                        self._slo.measure("recall_min",
                                          self._shadow.last_min_recall)
                aspan.annotate(diverged=not ok)

        t_res0 = time.perf_counter()
        vspan = tracer.begin("resolve", parent=dspan, t0=t_res0)
        for row, rec in enumerate(chunk):
            # ascending by distance (gather_selected packs by shard rank,
            # not by distance; l is small, so sort host-side — this also
            # keeps the selection and gather A/B paths byte-identical in
            # ordering)
            order = np.argsort(d[row, :rec.l], kind="stable")
            dists = d[row, order]
            ids = i[row, order]
            values = None
            if self._store is not None and self._store.with_values:
                # the store's id -> value map is monotone (entries outlive
                # deletion), so the lookup is valid for any generation's ids
                values = self._store.values_for(ids)
            elif self._values is not None:
                # sentinel slots (fewer than l finite points) map to -1;
                # clip both ends — np.where evaluates the lookup branch
                # for sentinel ids too.
                safe = np.clip(ids, 0, len(self._values) - 1)
                values = np.where(ids == _ID_SENTINEL, -1,
                                  self._values[safe])
            xrec = ExplainRecord(
                capture, row, l=rec.l, dists=dists, ids=ids,
                queued_s=t_dispatch - rec.t_enqueue,
                latency_s=t_done - rec.t_enqueue)
            self._explains.append(xrec)
            _resolve(rec.future, result=QueryResult(
                dists=dists, ids=ids, values=values, l=rec.l,
                iterations=iters, rounds=rounds, messages=messages,
                survivors=int(surv[row]), bucket=bucket,
                queued_s=t_dispatch - rec.t_enqueue,
                latency_s=t_done - rec.t_enqueue,
                generation=generation, shards_touched=touched,
                recall_mode="approx" if self._indexed else "exact",
                explain_ref=xrec,
                label=(float(pred[0][row]) if pred else None),
                confidence=(float(pred[1][row]) if pred else None),
                predict_mode=pmode))
            if rec.span is not None:
                tracer.record("queued", rec.t_enqueue, t_dispatch,
                              parent=rec.span)
                tracer.record("serve", t_dispatch, t_done,
                              parent=rec.span, batch=batch_id)
                rec.span.end(bucket=bucket, generation=generation,
                             route=self.cfg.route, touched=touched,
                             rounds=rounds)
            self._m["queued"].observe(t_dispatch - rec.t_enqueue)
            self._m["latency"].observe(
                time.perf_counter() - rec.t_enqueue)
            if self._slo is not None:
                self._slo.measure("latency_p99",
                                  time.perf_counter() - rec.t_enqueue)
                self._slo.measure("staleness",
                                  head_generation - generation)
        vspan.end()
        dspan.end(touched=touched, generation=generation)
        t_res1 = time.perf_counter()
        cpu_s = time.thread_time() - cpu0
        m = self._m
        m["prologue"].observe(t_dispatch - t_take)
        m["snapshot"].observe(t_snap1 - t_snap0)
        if self._store is not None:
            m["lock_wait"].observe(lock_wait)
        m["kernel"].observe(t_kern1 - t_kern0)
        m["wait"].observe(readback_s[0])
        m["copy"].observe(readback_s[1])
        if t_route0 is not None:
            m["route"].observe(t_route1 - t_route0)
        m["resolve"].observe(t_res1 - t_res0)
        m["dispatch"].observe(t_res1 - t_dispatch)
        m["cpu"].observe(cpu_s)
        m["rounds"].observe(rounds)
        m["messages"].observe(messages)
        # Defensive (satellite of the -1 sentinel fix): a negative
        # touched count is QueryResult's "never routed" sentinel, not an
        # observation — it must never enter the serving histograms.
        if touched >= 0:
            m["touched"].observe(touched)
        if cand_frac is not None:
            m["cand_frac"].observe(cand_frac)
        # Explain reports assemble only after the dispatch completes, so
        # this late fill is always visible to them.
        capture.timings["resolve_s"] = t_res1 - t_res0
        if self._slo is not None:
            self._slo.evaluate()

    def _exact_replay(self, operands, all_on, q, l_arr, key):
        """The exact collective for one dispatched batch: the same
        executable, operands, and key, with every shard active
        (``all_on``; None when the server routes exact) and — for an
        indexed server — every slot a candidate.  Answers are host
        arrays ready for the shadow comparison."""
        ops = list(operands)
        if self._indexed:
            ops.append(np.ones(self.k * self.m_local, bool))
        if all_on is not None:
            ops.append(all_on)
        return _readback(self._fn(*ops, q, l_arr, key))()[0][:2]

    def _exact_label_replay(self, operands, all_on, q, l_arr, key):
        """The exact-mode prediction for one ensemble batch: the
        exact-fold executable at the same generation and key with every
        shard active — the oracle the accuracy shadow audit compares the
        one-message-per-shard answer against."""
        ops = list(operands)
        if all_on is not None:
            ops.append(all_on)
        return _readback(self._fn(*ops, q, l_arr, key))()[0][4]

    def _host_candidates(self, idx, q, l_arr, shard_keep):
        """Host-path bucket prologue for one micro-batch: the (n,)
        per-slot candidate operand, the kept-live fraction, and the
        (k, b) batch-union bucket keep itself (the explain capture
        reports it and cross-checks it against the recomputed rule) —
        store/index.py ``bucket_keep`` -> union across rows ->
        ``candidate_mask``; ``shard_keep`` is the per-row routing
        decision, None = all shards compete."""
        keep = index_mod.bucket_keep(
            idx, q, l_arr, shard_keep=shard_keep,
            oversample=self.cfg.index_oversample)
        keep_any = keep.any(axis=0)
        pcand = index_mod.candidate_mask(idx, keep_any, self.m_local)
        return (pcand, index_mod.candidate_fraction(idx, keep_any),
                keep_any)

    # ---- background micro-batcher ---------------------------------------

    def start(self):
        """Run the micro-batcher thread (linger-then-dispatch loop)."""
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="knn-microbatcher",
                                        daemon=True)
        self._thread.start()

    def stop(self):
        """Quiesce the micro-batcher and drain the queue.

        Contract (tests/test_knn_server.py::test_server_stop_drains):

        * every request pending at stop() entry has its Future resolved
          by the time stop() returns — none stranded;
        * each request is dispatched exactly once (the batcher takes a
          chunk under the lock before dispatching, so the final
          ``flush()`` can never re-dispatch a request the exiting
          batcher already took);
        * FIFO order is preserved through the drain;
        * stop() is idempotent and safe to race with itself — the
          thread handle is captured-and-cleared under the lock, so
          exactly one caller joins it and a second concurrent stop()
          just flushes.
        """
        with self._cv:
            self._running = False
            self._cv.notify_all()
            t, self._thread = self._thread, None
        if t is not None:
            t.join()
        self.flush()          # leave no request stranded

    def serving(self):
        return _Serving(self)

    def _serve_loop(self):
        linger = self.cfg.max_wait_ms / 1e3
        full = self.cfg.bucket_sizes[-1]
        tracer = self.obs.tracer
        while True:
            wspan = tracer.begin("batcher.wait")
            with self._cv:
                while self._running and not self._pending:
                    self._cv.wait(timeout=0.1)
                if not self._running:
                    wspan.end()
                    return
                # Linger: give the batch a chance to fill before paying a
                # datastore pass for a mostly-padded bucket.
                deadline = self._pending[0].t_enqueue + linger
                while (self._running and len(self._pending) < full
                       and time.perf_counter() < deadline):
                    self._cv.wait(timeout=max(
                        deadline - time.perf_counter(), 1e-4))
                chunk = self._take_chunk_locked()
            wspan.end(n=len(chunk))
            if chunk:
                self._dispatch(chunk)


def _distances_fn(cfg: KnnServiceConfig):
    if cfg.distance_impl == "auto":
        # masked-aware: pushes a store's valid mask down into the
        # kernels layer (core/knn._masked_distances convention)
        def fn(q, p, valid=None):
            return kops.l2_distance(q, p, valid=valid)
        fn.supports_valid = True
        return fn
    # plain jnp path: _masked_distances applies the mask when needed
    return knn_mod.squared_l2_distances


def build_query_program(cfg: KnnServiceConfig, mesh, axis_name: str, *,
                        masked: bool, predicting: bool, indexed: bool):
    """The service's jitted query program for one backing layout.

    ``masked`` adds the store's (n,) valid-mask operand (store-backed
    servers; static servers keep the unmasked program — no per-query
    masking cost for a point set that can never change).  The mesh only
    fixes the shard_map, so the program can be lowered against a
    described device topology without constructing a server.
    """
    axis = axis_name
    l_max = cfg.l_max
    distances_fn = _distances_fn(cfg)
    # route="pruned" adds one (k,) bool operand; in_spec P(axis) hands
    # each shard its own flag, which core/knn folds into the valid mask
    # ahead of the fused distance+top-l kernel.
    routed = cfg.route == "pruned"
    # search="approx" (``indexed``) adds one (n,) bool per-slot candidate
    # operand — the bucket index's keep decision, folded into the same
    # mask (core/knn point_candidates); P(axis) hands each shard its own
    # slots.  cfg.predict (``predicting``) adds one (n,) f32 per-slot
    # label operand carried through the local top-l permutation (core/knn
    # local_top_l extra=), and two replicated outputs: the predicted
    # label and its confidence, folded from the winner mask inside the
    # same program (predict/vote.py — one extra psum).

    if cfg.sampler == "selection":
        def body(pts, pids, pvalid, plabels, pcand, active, q, l_arr, key):
            res = knn_mod.knn_query_batched(
                pts, pids, q, l_max, l_arr, key, axis_name=axis,
                distances_fn=distances_fn,
                use_sampling=cfg.use_sampling,
                num_pivots=cfg.num_pivots,
                point_valid=pvalid, shard_active=active,
                point_candidates=pcand, point_labels=plabels)
            out = (res.dists, res.ids, res.selection.iterations,
                   res.prune.survivors)
            if plabels is None:
                return out
            label, conf, _detail = predict_mod.exact_predict(
                res, l_arr, predict=cfg.predict,
                num_classes=cfg.num_classes, axis_name=axis)
            return out + (label, conf)
    elif cfg.sampler == "gather":
        def body(pts, pids, pvalid, plabels, pcand, active, q, l_arr, key):
            sd, si = knn_mod.knn_simple(
                pts, pids, q, l_max, axis_name=axis,
                distances_fn=distances_fn, point_valid=pvalid,
                shard_active=active, point_candidates=pcand)
            # per-request l: slots at rank >= l[b] are masked to the
            # sentinel (knn_simple returns ascending order).
            keep = jnp.arange(l_max)[None, :] < l_arr[:, None]
            sd = jnp.where(keep, sd, jnp.inf)
            si = jnp.where(keep, si, _ID_SENTINEL)
            zeros = jnp.zeros(q.shape[:1], jnp.int32)
            return sd, si, jnp.int32(0), zeros
    else:
        raise ValueError(f"unknown sampler {cfg.sampler!r}")

    # Operand layout composes by flag, always in this order:
    #   pts, pids, [pvalid], [plabels], [pcand], [active], q, l_arr, key
    # — every present optional operand is sharded P(axis).  The
    # dispatch/warmup/replay sites assemble operands in the same order
    # from the same flags.
    def fn(*a):
        it = iter(a)
        pts, pids = next(it), next(it)
        pvalid = next(it) if masked else None
        plabels = next(it) if predicting else None
        pcand = next(it) if indexed else None
        active = next(it) if routed else None
        q, l_arr, key = next(it), next(it), next(it)
        return body(pts, pids, pvalid, plabels, pcand, active, q, l_arr,
                    key)

    n_sharded = (2 + int(masked) + int(predicting) + int(indexed)
                 + int(routed))
    in_specs = (P(axis),) * n_sharded + (P(None), P(None), P(None))
    out_specs = (P(None), P(None), P(), P(None))
    if predicting:
        out_specs = out_specs + (P(None), P(None))

    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))


class _Packed(NamedTuple):
    """One launch of a ``_OneBuffer`` program: its packed device buffer
    and the shape and dtype of each output packed in it."""
    buf: jax.Array
    layout: tuple


class _OneBuffer:
    """A program whose tuple of replicated 32-bit or bool outputs leaves
    the device packed bit-exactly in one int32 buffer (floats bitcast,
    bools widened), so a launch has one copy home; ``_readback`` splits
    it on the host. ``inner`` is the unpacked program, for composing
    into another program. Each traced signature records its layout,
    keyed by the buffer's length: every signature of one program is a
    bucket size, and the outputs grow with the bucket's rows."""

    def __init__(self, inner):
        self.inner = inner
        self._layouts = {}

        def packed(*args):
            outs = tuple(inner(*args))
            words = [x.astype(jnp.int32) if x.dtype == jnp.bool_
                     else x if x.dtype == jnp.int32
                     else lax.bitcast_convert_type(x, jnp.int32)
                     for x in outs]
            buf = jnp.concatenate([w.reshape(-1) for w in words])
            self._layouts[buf.shape[0]] = tuple(
                (x.shape, np.dtype(x.dtype)) for x in outs)
            return buf

        # The XLA module keeps the unpacked program's name (``jit_fn``
        # for the query program): the benchmark's trace readers find the
        # query program's device time by it.
        packed.__name__ = inner.__name__
        self.packed = jax.jit(packed)

    def __call__(self, *args):
        buf = self.packed(*args)
        return _Packed(buf, self._layouts[buf.shape[0]])


def _unpack(buf, layout):
    """The outputs a ``_OneBuffer`` program packed into ``buf``."""
    out, o = [], 0
    for shape, dtype in layout:
        n = math.prod(shape)
        v = buf[o:o + n]
        out.append((v != 0 if dtype == np.bool_ else v.view(dtype))
                   .reshape(shape))
        o += n
    return tuple(out)


def _readback(out):
    """Start the copy home of one launch's output, ``out`` (a
    ``_Packed`` buffer or an array), and return ``collect``.

    Called as soon as the launch returns: the runtime starts the copy
    when the program that writes the array ends, without waiting for the
    host. ``collect()`` blocks until the output is ready, then takes it
    as a numpy array (a ``_Packed`` buffer split into its outputs); it
    returns that, the seconds of the block and the seconds from its end
    to the arrays in hand. Every read of an executable's outputs goes
    through here."""
    dev = out.buf if isinstance(out, _Packed) else out
    dev.copy_to_host_async()

    def collect():
        t0 = time.perf_counter()
        dev.block_until_ready()
        t1 = time.perf_counter()
        host = np.asarray(dev)
        if isinstance(out, _Packed):
            host = _unpack(host, out.layout)
        return host, t1 - t0, time.perf_counter() - t1

    return collect


def _resolve(future: Future, result=None, error=None):
    """Resolve a future, tolerating client-side cancellation."""
    try:
        if error is not None:
            future.set_exception(error)
        else:
            future.set_result(result)
    except Exception:
        pass      # already cancelled/resolved by the client — nothing owed


class _Serving:
    def __init__(self, server: KnnServer):
        self._server = server

    def __enter__(self):
        self._server.start()
        return self._server

    def __exit__(self, *exc):
        self._server.stop()
        return False
