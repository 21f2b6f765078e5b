"""Observability plane (src/repro/obs/): recorder, registry, auditors.

What this suite pins, layer by layer:

* **Histogram quantiles** stay within one geometric bucket (~2.2%
  relative, asserted at 5%) of a sorted oracle with O(1) observes — the
  property that fixed ``StepWatchdog.observe``'s per-step re-sort.
* **Span trees are well-formed under racing** — the
  test_async_maintenance.py-style harness (mutator thread + background
  maintenance worker + micro-batcher) must quiesce with zero torn
  spans, every exported tree reassembling cleanly, complete request
  trees, and maintenance cycles interleaved in the same ring.
* **The auditors audit.**  The Theorem-1 contract envelope passes on
  real serving and trips on absurd bills; the shadow-exact auditor
  catches an injected routing corruption (a monkeypatched router that
  silently drops shards) and stays silent on a clean run.
* **The recorder is affordable**: instrumented-vs-off on the same smoke
  workload within the 10% budget (DESIGN.md §12).
"""

import io
import json
import math
import statistics
import threading
import time

import numpy as np
import pytest

from repro.configs.knn_service import CONFIG
from repro.obs import ObsPlane
from repro.obs.audit import ContractAuditor, ShadowAuditor
from repro.obs.metrics import (GROWTH, Histogram, MetricsRegistry,
                               default_registry)
from repro.obs.trace import NULL_TRACER, Tracer, build_trees
from repro.runtime import KnnServer
from repro.runtime.metrics import StepWatchdog
from repro.store import MutableStore

DIM = 8
L_MAX = 16


# ---- metrics registry ----------------------------------------------------

def test_histogram_quantiles_vs_sorted_oracle(rng):
    h = Histogram()
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=20_000)
    for v in samples:
        h.observe(float(v))
    s = np.sort(samples)
    for q in (0.50, 0.90, 0.99):
        exact = float(s[min(int(math.ceil(q * len(s))) - 1, len(s) - 1)])
        approx = h.quantile(q)
        assert abs(approx - exact) / exact < 0.05, (q, approx, exact)
    snap = h.snapshot()
    assert snap["count"] == len(samples)
    assert snap["min"] == float(samples.min())
    assert snap["max"] == float(samples.max())
    assert abs(snap["mean"] - samples.mean()) / samples.mean() < 1e-9


def test_histogram_identical_values_exact_and_edge_cases():
    h = Histogram()
    assert math.isnan(h.quantile(0.5))
    for _ in range(9):
        h.observe(0.1)
    # all-identical observations: clamping to [min, max] makes every
    # quantile exact — the property StepWatchdog's flagging rests on
    assert h.quantile(0.5) == pytest.approx(0.1)
    assert h.quantile(0.99) == pytest.approx(0.1)
    h.observe(0.0)                     # underflow bucket -> reported min
    assert h.quantile(0.01) == 0.0
    # any quantile is within one bucket (~GROWTH) of the true value
    assert GROWTH < 1.05


def test_histogram_empty_explicit_and_full_key_snapshot():
    """Empty-histogram oracle: quantile is NaN at *every* q (never the
    +inf/-inf min/max seeds), and snapshot carries the full key set so
    readers indexing ["p99"]/["mean"] unconditionally never KeyError on
    a histogram that simply hasn't fired yet (e.g. serve.route_s under
    route="exact")."""
    h = Histogram()
    for q in (0.0, 0.01, 0.5, 0.99, 1.0):
        assert math.isnan(h.quantile(q)), q
    snap = h.snapshot()
    assert snap["count"] == 0
    assert set(snap) == {"count", "sum", "mean", "min", "max",
                         "p50", "p90", "p99"}
    assert snap["sum"] == 0.0 and snap["mean"] == 0.0
    assert snap["min"] == 0.0 and snap["max"] == 0.0      # seeds hidden
    assert all(math.isnan(snap[k]) for k in ("p50", "p90", "p99"))
    assert not any(math.isinf(v) for v in snap.values()
                   if isinstance(v, float))


def test_histogram_single_observation_and_extreme_q_oracle():
    """Nearest-rank edges against the sorted oracle: one observation
    answers every q with itself; q=0.0 is the min and q=1.0 the max of
    any sample."""
    h = Histogram()
    h.observe(0.25)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == pytest.approx(0.25), q
    snap = h.snapshot()
    assert snap["count"] == 1
    assert snap["min"] == snap["max"] == 0.25
    assert snap["mean"] == pytest.approx(0.25)
    samples = [0.003, 0.5, 0.02, 0.11, 7.0]
    h2 = Histogram()
    for v in samples:
        h2.observe(v)
    # multi-sample edges: within one geometric bucket (~2.2%) of the
    # true order statistic, and never outside the observed range
    assert h2.quantile(0.0) == pytest.approx(min(samples), rel=0.05)
    assert h2.quantile(1.0) == pytest.approx(max(samples), rel=0.05)
    assert min(samples) <= h2.quantile(0.0) <= max(samples)
    assert min(samples) <= h2.quantile(1.0) <= max(samples)


def test_registry_create_or_get_and_type_collision():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    c.inc()
    assert reg.counter("a.count") is c
    assert reg.value("a.count") == 1
    assert reg.value("missing", default=7) == 7
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("a.count")
    reg.gauge("a.gauge").set(2.5)
    reg.histogram("a.hist").observe(1.0)
    snap = reg.snapshot(prefix="a.")
    assert set(snap) == {"a.count", "a.gauge", "a.hist"}
    buf = io.StringIO()
    assert reg.export_jsonl(buf) == 3
    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert {ln["metric"] for ln in lines} == set(snap)


def test_step_watchdog_streaming_semantics():
    w = StepWatchdog(factor=3.0, warmup=3)
    for _ in range(10):
        assert not w.observe(0.1)
    assert w.observe(1.0)              # 10x the p50 -> flagged
    assert w.flagged
    assert not w.observe(0.1)          # recovery is not sticky
    # registry-backed: the same flagging, counted
    reg = MetricsRegistry()
    w2 = StepWatchdog(factor=3.0, warmup=2, registry=reg)
    for _ in range(4):
        w2.observe(0.05)
    w2.observe(0.5)
    assert reg.value("watchdog.step_s.flagged") == 1
    assert reg.get("watchdog.step_s").count == 5


# ---- tracer --------------------------------------------------------------

def test_tracer_span_tree_and_retroactive_record():
    tr = Tracer(capacity=64)
    root = tr.begin("request", l=4)
    t_mid = time.perf_counter()
    with tr.span("kernel", parent=root, path="oracle"):
        time.sleep(0.001)
    tr.record("queued", root.t0, t_mid, parent=root)
    root.end(route="pruned")
    assert tr.active_count() == 0
    recs = tr.spans()
    assert [r["name"] for r in recs] == ["kernel", "queued", "request"]
    trees = build_trees(recs)
    assert len(trees) == 1
    by_name = {r["name"]: r for r in recs}
    assert by_name["kernel"]["parent"] == by_name["request"]["span"]
    assert by_name["request"]["attrs"] == {"l": 4, "route": "pruned"}
    # idempotent end: a second end must not double-record
    root.end()
    assert len(tr.spans()) == 3


def test_tracer_ring_eviction_and_export():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.begin(f"s{i}").end()
    assert len(tr.spans()) == 4
    assert tr.dropped == 6
    assert [r["name"] for r in tr.spans()] == ["s6", "s7", "s8", "s9"]
    buf = io.StringIO()
    assert tr.export_jsonl(buf) == 4
    assert tr.stats()["recorded"] == 4
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_null_tracer_is_inert():
    sp = NULL_TRACER.begin("x", parent=None, l=1)
    assert sp.end() is sp and sp.span_id == 0
    with NULL_TRACER.span("y"):
        pass
    assert NULL_TRACER.spans() == []
    assert NULL_TRACER.active_count() == 0
    assert NULL_TRACER.export_jsonl(io.StringIO()) == 0
    assert NULL_TRACER.stats()["enabled"] is False


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enters/exits."""

    enabled = True
    log: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    @staticmethod
    def is_enabled():
        return _FakeAnnotation.enabled

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture()
def fake_sink(monkeypatch):
    from repro.obs import trace as trace_mod
    _FakeAnnotation.log = []
    _FakeAnnotation.enabled = True
    monkeypatch.setattr(trace_mod, "_profiler", _FakeAnnotation)
    return _FakeAnnotation


@pytest.mark.parametrize("tracer", ["ring", "null"])
def test_profiler_sink_mirrors_same_thread_spans(fake_sink, tracer):
    """While a session records, same-thread spans of either tracer are
    also ``knn.``-prefixed annotations, begin to end; cross-thread and
    retroactive spans are not; with no session nothing is mirrored and
    the disabled tracer hands out its shared no-op span."""
    from repro.obs.trace import _NULL_SPAN
    tr = Tracer(capacity=16) if tracer == "ring" else NULL_TRACER
    with tr.span("kernel", bucket=4):
        inner = tr.begin("kernel.launch")
        inner.end()
        inner.end()                       # a second end exits nothing
    tr.begin("request", same_thread=False).end()
    tr.record("queued", 0.0, 1.0)
    assert fake_sink.log == [("enter", "knn.kernel", {"bucket": 4}),
                             ("enter", "knn.kernel.launch", {}),
                             ("exit", "knn.kernel.launch"),
                             ("exit", "knn.kernel")]
    fake_sink.enabled = False
    fake_sink.log = []
    sp = tr.begin("snapshot")
    sp.end()
    assert fake_sink.log == []
    if tracer == "null":
        assert sp is _NULL_SPAN
    else:
        assert [r["name"] for r in tr.spans()] == [
            "kernel.launch", "kernel", "request", "queued", "snapshot"]
        assert tr.active_count() == 0


def test_build_trees_rejects_malformed_forests():
    def rec(span, parent, t0, t1, trace=1, name="s"):
        return {"trace": trace, "span": span, "parent": parent,
                "name": name, "t0": t0, "t1": t1}

    with pytest.raises(ValueError, match="unfinished"):
        build_trees([rec(1, None, 0.0, None)])
    with pytest.raises(ValueError, match="orphaned"):
        build_trees([rec(2, 99, 0.0, 1.0)])
    with pytest.raises(ValueError, match="ends before"):
        build_trees([rec(1, None, 5.0, 1.0)])
    with pytest.raises(ValueError, match="outside parent"):
        build_trees([rec(1, None, 0.0, 1.0),
                     rec(2, 1, 0.0, 2.0)])
    with pytest.raises(ValueError, match="crosses traces"):
        build_trees([rec(1, None, 0.0, 1.0),
                     rec(2, 1, 0.0, 0.5, trace=7)])
    # well-formed forest: two roots, nested children
    ok = [rec(1, None, 0.0, 1.0), rec(2, 1, 0.2, 0.8),
          rec(3, None, 0.0, 1.0, trace=3)]
    assert set(build_trees(ok)) == {1, 3}


def test_obs_plane_from_config():
    on = ObsPlane.from_config(CONFIG.replace(obs_trace=True,
                                             obs_trace_capacity=32))
    assert on.tracer.enabled and on.tracer.capacity == 32
    off = ObsPlane.from_config(CONFIG)
    assert off.tracer is NULL_TRACER
    assert off.snapshot()["trace"]["enabled"] is False


def test_compaction_evaluate_publishes_registry():
    from repro.store import compaction
    reg = MetricsRegistry()
    live = np.array([10, 10, 10, 10])
    used = np.array([20, 10, 10, 10])   # 10 dead of 50 used
    d = compaction.evaluate(live, used, 32, tombstone_frac=0.1,
                            imbalance_frac=0.5, registry=reg)
    assert d.compact and "tombstone" in d.reason
    assert reg.value("store.compact_trigger.tombstone") == 1
    assert reg.value("store.tombstone_density") == pytest.approx(0.2)
    d2 = compaction.evaluate(live, np.array([30, 10, 10, 10]), 32,
                             tombstone_frac=0.9, imbalance_frac=0.5,
                             registry=reg)
    assert not d2.compact               # gauges refresh even when quiet
    assert reg.value("store.tombstone_density") == pytest.approx(1 / 3)
    # registry-less calls stay pure (the store without an attached plane)
    assert compaction.evaluate(live, used, 32, tombstone_frac=0.1,
                               imbalance_frac=0.5).compact


# ---- contract auditor ----------------------------------------------------

def test_contract_auditor_bounds_and_verdicts():
    reg = MetricsRegistry()
    a = ContractAuditor(reg, k=8)
    # monotone in l, barely sensitive to n (the w.h.p. loglog term)
    r1 = a.rounds_bound(1, 10_000, use_sampling=True, sampler="selection")
    r128 = a.rounds_bound(128, 10_000, use_sampling=True,
                          sampler="selection")
    assert r1 < r128
    big_n = a.rounds_bound(1, 10_000_000, use_sampling=True,
                           sampler="selection")
    assert big_n - r1 < 6.0            # loglog growth, not log
    # gather is exact: 1 round, (k-1)*l_max messages
    assert a.rounds_bound(16, 10_000, use_sampling=True,
                          sampler="gather") == 1.0
    assert a.messages_bound(16, 10_000, use_sampling=True,
                            sampler="gather") == 7 * 16
    # a realistic bill passes; an absurd one (the deterministic
    # iteration cap, ~8*log2(n) rounds) is flagged
    assert a.check(l_max=8, n_live=10_000, rounds=24, messages=7 * 24,
                   use_sampling=True, sampler="selection")
    assert not a.check(l_max=8, n_live=10_000, rounds=280,
                       messages=7 * 280, use_sampling=True,
                       sampler="selection")
    snap = a.snapshot()
    assert snap["checks"] == 2 and snap["violations"] == 1
    assert snap["details"][0]["rounds"] == 280
    # Theorem 2.2 regime (no sampling): O(log n) rounds are in-envelope
    assert a.check(l_max=8, n_live=10_000, rounds=60, messages=7 * 60,
                   use_sampling=False, sampler="selection")


def test_shadow_auditor_sampling_and_divergence():
    reg = MetricsRegistry()
    s = ShadowAuditor(reg, every=3)
    assert [s.due() for _ in range(7)] == [True, False, False,
                                           True, False, False, True]
    d = np.arange(4, dtype=np.float32)
    i = np.arange(4, dtype=np.int32)
    assert s.check(d, i, lambda: (d.copy(), i.copy()))
    assert not s.check(d, i, lambda: (d + 1, i.copy()), batch_id=5)
    snap = s.snapshot()
    assert snap["checks"] == 2 and snap["divergences"] == 1
    assert snap["details"][0]["batch_id"] == 5
    with pytest.raises(ValueError):
        ShadowAuditor(reg, every=0)
    with pytest.raises(ValueError, match="mode"):
        ShadowAuditor(reg, every=1, mode="fuzzy")


def test_shadow_auditor_recall_mode():
    """mode="recall" (the search="approx" contract): per-row recall@l
    against the exact replay's finite ids, minimum over rows, floored.
    Sentinel-only rows (padding / l=0) are vacuous; the measured
    minimum lands in the snapshot's recall histogram."""
    sent = 2**31 - 1
    reg = MetricsRegistry()
    s = ShadowAuditor(reg, every=1, mode="recall", floor=0.75)
    exact_i = np.array([[1, 2, 3, 4],
                        [10, 11, sent, sent],
                        [sent, sent, sent, sent]], np.int32)
    d = np.zeros_like(exact_i, np.float32)
    # row recalls 4/4, 2/2 -> min 1.0: passes
    assert s.check(exact_i.copy(), exact_i.copy(),
                   lambda: (d, exact_i.copy()))
    # row0 drops one true id (3/4 = 0.75, at the floor): still passes
    near = exact_i.copy()
    near[0, 3] = 99
    assert s.check(d, near, lambda: (d, exact_i.copy()))
    # row1 misses both true ids -> min 0.0: flagged with the measurement
    bad = exact_i.copy()
    bad[1, :2] = [98, 99]
    assert not s.check(d, bad, lambda: (d, exact_i.copy()), batch_id=3)
    snap = s.snapshot()
    assert snap["mode"] == "recall" and snap["floor"] == 0.75
    assert snap["checks"] == 3 and snap["divergences"] == 1
    assert snap["details"][0]["min_recall"] == 0.0
    assert snap["details"][0]["batch_id"] == 3
    assert snap["recall"]["count"] == 3
    assert snap["recall"]["min"] == 0.0


# ---- serving integration -------------------------------------------------

def _clustered_server(mesh8, *, obs_trace=True, audit_every=0,
                      route_compute="host", seed=0, per_shard=24):
    from repro.data import sharded_clusters
    pts, centers = sharded_clusters(8, per_shard, DIM, seed=seed)
    cfg = CONFIG.replace(dim=DIM, l=4, l_max=L_MAX, bucket_sizes=(1, 2, 4),
                         sampler="selection", route="pruned",
                         route_compute=route_compute,
                         obs_trace=obs_trace, obs_audit_every=audit_every)
    srv = KnnServer(pts, cfg=cfg, mesh=mesh8, axis_name="x")
    srv.warmup()
    return srv, centers


def test_request_trace_complete_and_audits_clean(mesh8):
    """One traced, audited serving pass: every request tree is complete
    (queued + serve children), every dispatch tree carries the
    snapshot/route/kernel/resolve stages, both auditors ran and stayed
    clean, and the per-stage histograms populated."""
    srv, centers = _clustered_server(mesh8, audit_every=2)
    rng = np.random.default_rng(1)
    for wave in range(5):
        qs = (centers[wave % len(centers)]
              + rng.normal(size=(3, DIM))).astype(np.float32)
        srv.query_batch(qs, [1 + wave % 4] * 3)
    assert srv.obs.tracer.active_count() == 0
    recs = srv.obs.tracer.spans()
    build_trees(recs)
    kids = {}
    for r in recs:
        if r["parent"] is not None:
            kids.setdefault(r["parent"], set()).add(r["name"])
    requests = [r for r in recs if r["name"] == "request"]
    assert len(requests) == 15
    assert all(kids[r["span"]] == {"queued", "serve"} for r in requests)
    dispatches = [r for r in recs if r["name"] == "dispatch"]
    assert dispatches
    for d in dispatches:
        assert {"snapshot", "route", "kernel", "resolve"} <= kids[d["span"]]
    # the serve child names its dispatch batch (cross-tree reference by
    # attribute, never by parent link)
    batches = {d["attrs"]["batch"] for d in dispatches}
    serves = [r for r in recs if r["name"] == "serve"]
    assert all(r["attrs"]["batch"] in batches for r in serves)

    snap = srv.obs_snapshot()
    assert snap["audit"]["contract"]["checks"] == len(dispatches)
    assert snap["audit"]["contract"]["violations"] == 0
    assert snap["audit"]["shadow"]["checks"] >= 1
    assert snap["audit"]["shadow"]["divergences"] == 0
    for stage in ("serve.snapshot_s", "serve.route_s", "serve.kernel_s",
                  "serve.resolve_s", "serve.latency_s", "serve.queued_s"):
        assert snap["metrics"][stage]["count"] > 0, stage
    assert snap["metrics"]["serve.rounds"]["count"] == len(dispatches)
    # the kernels dispatcher counted its envelope builds (and any
    # fallbacks) in the process-wide registry
    assert default_registry().value("kernel.envelopes") > 0
    # and the L2 kernel's form: 24 points a shard at width 8 lie
    # column-major on a TPU, so the kernel reads their (d, m) view
    assert snap["kernel"]["kernel.l2_distance.form.cols"] > 0


def test_device_routed_trace_has_fused_route_span(mesh8):
    srv, centers = _clustered_server(mesh8, route_compute="device",
                                     audit_every=2, seed=3)
    qs = (centers[0] + np.random.default_rng(2)
          .normal(size=(2, DIM))).astype(np.float32)
    srv.query_batch(qs, [4, 4])
    recs = srv.obs.tracer.spans()
    build_trees(recs)
    routes = [r for r in recs if r["name"] == "route"]
    assert routes and all(r["attrs"]["fused"] for r in routes)
    kernels = [r for r in recs if r["name"] == "kernel"]
    assert all(r["attrs"]["route_compute"] == "device" for r in kernels)
    snap = srv.obs_snapshot()
    assert snap["audit"]["shadow"]["checks"] >= 1
    assert snap["audit"]["shadow"]["divergences"] == 0
    assert snap["audit"]["contract"]["violations"] == 0


def test_shadow_auditor_catches_injected_routing_corruption(mesh8):
    """Corrupt the router (drop every shard but the query's worst) and
    the sampled shadow-exact replay must flag byte divergence — the
    offline bit-identity invariant as a live tripwire."""
    from repro.store import summaries as summaries_mod
    srv, centers = _clustered_server(mesh8, audit_every=1, seed=4)
    real_route = summaries_mod.route_shards

    def corrupt_route(summ, q, l_arr, slack):
        mask = real_route(summ, q, l_arr, slack=slack)
        out = np.zeros_like(mask)
        out[:, 0] = True               # only shard 0, whatever the query
        return out

    try:
        summaries_mod.route_shards = corrupt_route
        rng = np.random.default_rng(5)
        # queries near non-shard-0 clusters: the exact answer lives on a
        # shard the corrupted router just dropped
        for c in (3, 5, 7):
            qs = (centers[c] + rng.normal(size=(2, DIM))) \
                .astype(np.float32)
            srv.query_batch(qs, [4, 4])
    finally:
        summaries_mod.route_shards = real_route
    snap = srv.obs_snapshot()
    assert snap["audit"]["shadow"]["checks"] >= 3
    assert snap["audit"]["shadow"]["divergences"] >= 1
    assert snap["audit"]["shadow"]["details"][0]["batch_id"] >= 0


def test_racing_span_forest_well_formed(mesh8):
    """The concurrency bar: a mutator thread and the background
    maintenance worker race a traced server, and the ring still holds a
    clean forest — no torn spans after quiesce, every tree
    reassembles, request trees complete, and maintenance
    plan/prepare/commit cycles interleave with query spans in the same
    export."""
    centers = np.random.default_rng(11).normal(scale=20.0, size=(16, DIM))
    cfg = CONFIG.replace(dim=DIM, l=4, l_max=L_MAX, bucket_sizes=(1, 2, 4),
                         route="pruned", summary_pivots=2,
                         use_sampling=False, max_wait_ms=2.0,
                         placement="affinity", redeal="proximity",
                         retighten_every=3, split_radius_factor=1.2,
                         maintenance="background",
                         store_capacity_per_shard=192, store_staging_size=64,
                         obs_trace=True, obs_audit_every=3)
    store = MutableStore(DIM, mesh=mesh8, axis_name="x",
                         **cfg.store_kwargs())
    srv = KnnServer(store=store, cfg=cfg)
    rng = np.random.default_rng(12)

    def draw(n, c=None):
        c = int(rng.integers(0, len(centers))) if c is None else c
        return (centers[c] + rng.normal(size=(n, DIM))).astype(np.float32)

    store.insert(draw(40, 0))
    store.insert(draw(40, 1))
    store.flush()
    srv.warmup()

    errors = []

    def mutator():
        try:
            for _ in range(10):
                store.insert(draw(12))
                store.flush()
                live = store.live_arrays()[0]
                if len(live) > 90:
                    store.delete(np.random.default_rng(1)
                                 .permutation(live)[:8])
                    store.flush()
                time.sleep(0.003)
        except Exception as exc:     # surfaced below, not swallowed
            errors.append(exc)

    t = threading.Thread(target=mutator, daemon=True)
    pending = []
    with srv.serving():
        t.start()
        for wave in range(8):
            for _ in range(3):
                pending.append(srv.submit(draw(1)[0],
                                          1 + wave % 4))
            time.sleep(0.004)
        t.join()
        for f in pending:
            f.result(timeout=120)
    store.close()
    assert not errors, errors

    assert srv.obs.tracer.active_count() == 0, "torn spans after quiesce"
    recs = srv.obs.tracer.spans()
    trees = build_trees(recs)
    names = {r["name"] for r in recs}
    assert {"request", "queued", "serve", "dispatch", "snapshot",
            "kernel", "resolve", "store.apply"} <= names
    ws = store.maintenance_stats()["worker"]
    assert ws["errors"] == 0
    assert ws["commits"] > 0
    assert {"maint.cycle", "maint.prepare", "maint.commit"} <= names
    kids = {}
    for r in recs:
        if r["parent"] is not None:
            kids.setdefault(r["parent"], set()).add(r["name"])
    requests = [r for r in recs if r["name"] == "request"]
    assert len(requests) == len(pending)
    assert all(kids[r["span"]] == {"queued", "serve"} for r in requests)
    assert len(trees) >= len(requests)
    snap = srv.obs_snapshot()
    assert snap["audit"]["contract"]["violations"] == 0
    assert snap["audit"]["shadow"]["checks"] >= 1
    assert snap["audit"]["shadow"]["divergences"] == 0


def test_instrumentation_overhead_within_budget(mesh8):
    """Tracing + contract auditing must cost <= 10% of obs-off
    throughput on the smoke workload (DESIGN.md §12 budget).  The arms
    run the identical seeded load *interleaved*, in alternating order,
    and each pass is read as the process's CPU time: the serving
    thread's and the XLA threads' work, which is what throughput costs,
    without the time spent waiting for a core that the other test
    workers sharing the machine hold (the wall clock's reading swings
    by tens of percent under them).  The overhead is the median of the
    per-round on/off ratios.  Both arms carry the always-on clocks, the
    collector hook and the profiler sink's check."""
    servers = {}
    for obs_trace in (False, True):
        srv, centers = _clustered_server(mesh8, obs_trace=obs_trace,
                                         seed=6)
        servers[obs_trace] = srv
    rng = np.random.default_rng(7)
    qs_waves = [(centers[w % 8] + rng.normal(size=(4, DIM)))
                .astype(np.float32) for w in range(6)]

    def one_pass(srv):
        t0 = time.process_time()
        for qs in qs_waves:
            srv.query_batch(qs, [4] * 4)
        return time.process_time() - t0

    for srv in servers.values():       # warm the whole path, both arms
        one_pass(srv)
    ratios = []
    for r in range(15):
        order = (False, True) if r % 2 == 0 else (True, False)
        cost = {obs_trace: one_pass(servers[obs_trace])
                for obs_trace in order}
        ratios.append(cost[True] / cost[False])
    for srv in servers.values():
        srv.close()
    overhead = statistics.median(ratios) - 1.0
    assert overhead <= 0.10, f"obs overhead {overhead:.1%} > 10%"


# ---- profiler clock, collector pauses, host counters ---------------------

def _store_server(mesh8, *, obs_trace=False):
    cfg = CONFIG.replace(dim=DIM, l=4, l_max=L_MAX, bucket_sizes=(1, 2, 4),
                         max_wait_ms=1.0, store_capacity_per_shard=32,
                         obs_trace=obs_trace)
    store = MutableStore(DIM, mesh=mesh8, axis_name="x",
                         **cfg.store_kwargs())
    rng = np.random.default_rng(21)
    store.insert(rng.normal(size=(64, DIM)).astype(np.float32))
    store.flush()
    srv = KnnServer(store=store, cfg=cfg)
    srv.warmup()
    return srv, store, rng


def _host_spans(log_dir):
    """{line id: [(name, start_ns, end_ns)]} of the ``knn.`` events on
    the host plane of the one trace under ``log_dir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, ln in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in ln.events if e.name.startswith("knn.")]
            if evs:
                lines[i] = sorted(evs, key=lambda e: e[1])
    return lines


def test_profiler_session_holds_serving_and_write_spans(mesh8, tmp_path):
    """With the ring off, a recording jax.profiler session gets the
    serving thread's leaves and the writer's spans on the host plane,
    ``knn.``-prefixed; the serving thread's leaves follow one another
    without overlapping, and the cross-thread request span is absent."""
    import jax
    srv, store, rng = _store_server(mesh8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with srv.serving():
            futs = [srv.submit(q, 4) for q in
                    rng.normal(size=(3, DIM)).astype(np.float32)]
            for f in futs:
                f.result(timeout=120)
        srv.insert(rng.normal(size=(4, DIM)).astype(np.float32))
        srv.flush_store()
    finally:
        jax.profiler.stop_trace()
        srv.close()
    lines = _host_spans(str(tmp_path))
    names = {e[0] for evs in lines.values() for e in evs}
    assert {"knn.batcher.wait", "knn.dispatch.prologue", "knn.dispatch",
            "knn.snapshot", "knn.kernel", "knn.kernel.launch",
            "knn.kernel.readback", "knn.resolve", "knn.store.stage",
            "knn.store.apply"} <= names
    assert "knn.request" not in names
    serving, = [evs for evs in lines.values()
                if any(e[0] == "knn.batcher.wait" for e in evs)]
    leaves = [e for e in serving if e[0] in (
        "knn.batcher.wait", "knn.dispatch.prologue", "knn.snapshot",
        "knn.kernel.launch", "knn.kernel.readback", "knn.resolve")]
    assert len(leaves) >= 6
    for prev, nxt in zip(leaves, leaves[1:]):
        assert nxt[1] >= prev[2], (prev, nxt)


def test_collector_pauses_counted_while_the_server_is_open(mesh8):
    """A forced full collection under an open server is one more
    collection and one more pause in its registry, and a ``gc`` span of
    generation 2 in its ring; close() takes the server's plane off the
    hook."""
    import gc
    from repro.obs import gcwatch
    srv, _ = _clustered_server(mesh8, obs_trace=True, seed=8)
    reg = srv.obs.metrics
    n0 = reg.value("runtime.gc_collections")
    p0 = reg.histogram("runtime.gc_pause_s").count
    assert gcwatch.HOOK.installed and gcwatch.HOOK.subscribers() >= 1
    gc.collect()
    assert reg.value("runtime.gc_collections") >= n0 + 1
    pauses = reg.histogram("runtime.gc_pause_s")
    assert pauses.count >= p0 + 1 and pauses.total > 0
    spans = [r for r in srv.obs.tracer.spans() if r["name"] == "gc"]
    assert spans and spans[-1]["attrs"]["generation"] == 2
    srv.close()
    srv.close()                               # idempotent
    assert gcwatch.HOOK.installed == (gcwatch.HOOK.subscribers() > 0)
    count = pauses.count
    gc.collect()
    assert pauses.count == count


def test_collector_hook_leaves_with_its_last_subscriber():
    """The hook is installed by the first subscriber and removed by the
    last; a plane dropped without unsubscribing falls out."""
    import gc
    from repro.obs import gcwatch
    hook = gcwatch.GcHook()
    a, b = ObsPlane(), ObsPlane()
    hook.subscribe(a)
    hook.subscribe(b)
    hook.subscribe(b)
    gc.disable()                  # only the collections asked for below
    try:
        assert hook.installed and hook.subscribers() == 2
        gc.collect(1)
        for reg in (a.metrics, b.metrics):
            assert reg.value("runtime.gc_collections") == 1
            assert reg.histogram("runtime.gc_pause_s").count == 1
        gc.collect(0)
        assert a.metrics.value("runtime.gc_collections") == 2
        assert a.metrics.histogram("runtime.gc_pause_s").count == 1
        hook.unsubscribe(a)
        assert hook.installed and hook.subscribers() == 1
        del b, reg
        gc.collect()
        hook.unsubscribe(a)                   # publishes: b is gone
        assert not hook.installed and hook.subscribers() == 0
    finally:
        gc.enable()
        if hook.installed:
            gc.callbacks.remove(hook._callback)


def test_host_counters_fill_with_tracing_off(mesh8):
    """prologue_s, wait_s, cpu_s and the store's lock_wait_s get one
    observation per dispatch with the ring off; a reader held off the
    store lock by a writer counts the wait, and the serving thread's
    CPU time stays within its wall time."""
    srv, store, rng = _store_server(mesh8)
    reg = srv.obs.metrics
    names = ("serve.prologue_s", "serve.wait_s", "serve.cpu_s",
             "store.lock_wait_s", "serve.dispatch_s")
    before = {n: reg.histogram(n).count for n in names}
    srv.query_batch(rng.normal(size=(3, DIM)).astype(np.float32), [4] * 3)
    held, release = threading.Event(), threading.Event()

    def writer():
        with store._lock:
            held.set()
            release.wait(timeout=10)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    assert held.wait(timeout=10)
    timer = threading.Timer(0.2, release.set)
    timer.start()
    srv.query_batch(rng.normal(size=(2, DIM)).astype(np.float32), [4] * 2)
    t.join(timeout=10)
    assert not t.is_alive()
    srv.close()
    snap = reg.snapshot()
    for n in names:
        assert snap[n]["count"] == before[n] + 2, n
    assert snap["store.lock_wait_s"]["max"] >= 0.1
    wall = snap["serve.prologue_s"]["sum"] + snap["serve.dispatch_s"]["sum"]
    assert 0 < snap["serve.cpu_s"]["sum"] <= wall
    assert 0 < snap["serve.wait_s"]["sum"] <= snap["serve.dispatch_s"]["sum"]


def test_dispatch_allocates_no_span_with_tracing_off(mesh8, monkeypatch):
    """Ring off and no profiler session: every span the serving and
    write paths ask for is the shared no-op span, so a dispatch builds
    no span object."""
    from repro.obs import trace as trace_mod
    handed = []
    begin = trace_mod.NullTracer.begin

    def counting_begin(self, name, **kw):
        sp = begin(self, name, **kw)
        handed.append((name, sp))
        return sp

    srv, _, rng = _store_server(mesh8)
    monkeypatch.setattr(trace_mod.NullTracer, "begin", counting_begin)
    with srv.serving():
        f = srv.submit(rng.normal(size=DIM).astype(np.float32), 4)
        assert f.result(timeout=120).ids.shape == (4,)
    srv.query_batch(rng.normal(size=(2, DIM)).astype(np.float32), [4] * 2)
    srv.insert(rng.normal(size=(2, DIM)).astype(np.float32))
    srv.flush_store()
    srv.close()
    names = {name for name, _ in handed}
    assert {"request", "batcher.wait", "dispatch.prologue", "dispatch",
            "snapshot", "kernel", "kernel.launch", "kernel.readback",
            "resolve", "store.stage", "store.apply"} <= names
    assert all(sp is trace_mod._NULL_SPAN for _, sp in handed)
