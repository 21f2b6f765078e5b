# Local fallback for the CI entrypoints (.github/workflows/ci.yml).
PYTHONPATH_PREFIX := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-cov test-threads deps bench bench-serve smoke-artifacts \
	bench-smoke obs-smoke perf-history examples

# Shared smoke artifacts (one bench_serve --smoke run feeds BOTH CI
# gates below).
SMOKE_BENCH := /tmp/BENCH_serve_smoke.json
SMOKE_TRACE := /tmp/BENCH_trace_smoke.jsonl
SMOKE_PROM  := /tmp/BENCH_prom_smoke.txt

deps:
	pip install -r requirements-dev.txt

# tier-1 verify (ROADMAP.md)
test:
	$(PYTHONPATH_PREFIX) python -m pytest -x -q

# coverage gate for the query-path packages (ci.yml coverage job):
# store (mutable/compaction/summaries/placement/adaptive — and, since
# ISSUE 8, the in-shard bucket index in store/index.py, exercised by
# tests/test_index.py) and core (Algorithms 1 & 2) must stay above the
# floor so the routing, placement, adaptive-maintenance, and approx-
# index paths can't silently rot untested.  The job runs the full
# suite, so the ISSUE-8 regression tests ride in it too: the
# empty-histogram snapshot oracle (tests/test_obs.py) and the
# shards_touched=-1 sentinel guards (tests/test_knn_server.py).
# repro.obs joined the gate with ISSUE 9: the operator layer (explain
# reports, the SLO burn-rate engine, the Prometheus/OTLP exporters) is
# pure-python control logic whose failure modes are exactly the kind a
# coverage floor catches.
test-cov:
	$(PYTHONPATH_PREFIX) python -m pytest -q \
		--cov=repro.store --cov=repro.core --cov=repro.obs \
		--cov-report=term-missing --cov-fail-under=85

# thread-sanity gate (ci.yml thread-sanity job): the concurrency suites
# — background-maintenance harness (including the racing search="approx"
# recall-floor race), stop()-drain contract, ServerStats hammer,
# device-routing parity — run 3x under a faulthandler timeout,
# so a rare-interleaving deadlock dumps every thread's stack instead of
# hanging CI silently.
test-threads:
	for i in 1 2 3; do \
		$(PYTHONPATH_PREFIX) python -m pytest -q \
			-o faulthandler_timeout=300 \
			tests/test_async_maintenance.py tests/test_knn_server.py \
			tests/test_routing.py || exit 1; \
	done

bench:
	$(PYTHONPATH_PREFIX):. python -m benchmarks.run

bench-serve:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHONPATH_PREFIX):. python benchmarks/bench_serve.py

# The single serve-smoke run both gates below validate.  bench-smoke
# and obs-smoke used to run *identical* bench_serve --smoke invocations
# back to back (~2x the CI minutes for zero extra signal); the run now
# happens once here, emitting every artifact either gate needs — the
# JSON report, the flight-recorder trace, the HTTP-fetched Prometheus
# text — and appending the run's summary row to the tracked perf
# ledger.  `make bench-smoke obs-smoke` in one invocation runs it once.
smoke-artifacts:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHONPATH_PREFIX):. python benchmarks/bench_serve.py --smoke \
		--out $(SMOKE_BENCH) \
		--trace-out $(SMOKE_TRACE) \
		--prom-out $(SMOKE_PROM) \
		--history BENCH_history.jsonl

# CI dry-run: tiny-size bench_serve + bench_ingest end to end, JSON to /tmp —
# proves the benchmark scripts can't silently rot (ci.yml smoke step).
# bench_serve's placement section exercises placement="affinity" +
# redeal="proximity" (store/placement.py) in smoke mode too, so the
# locality-aware write path and the Lloyd re-deal run in CI on every push;
# its adaptive section drives the drifting-cluster store with
# summary_pivots=2 and hard-asserts one forced re-tighten and one forced
# split on a tiny store (store/adaptive.py), so both maintenance
# triggers fire in CI on every push.  bench_ingest's under_ingest
# section is the quiet-vs-ingest serve-latency A/B over a
# maintenance="background" store with device-side routing — it
# hard-asserts that a background re-tighten AND split fired mid-run.
# bench_serve's index section runs the search="approx" A/B on the
# clustered and drifting workloads with the recall floor and the 3x
# candidate-reduction target hard-asserted inline (store/index.py).
# The bench-regression sentinel rides here too (ISSUE 9): check_perf
# first proves its own bounds on a synthetic ledger (--self-test, where
# an injected 2x p99 regression must FAIL), then judges the smoke run
# against the rolling baseline in the tracked BENCH_history.jsonl.
bench-smoke: smoke-artifacts
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHONPATH_PREFIX):. python benchmarks/bench_ingest.py --smoke \
		--out /tmp/BENCH_ingest_smoke.json
	$(PYTHONPATH_PREFIX):. python benchmarks/check_perf.py --self-test
	$(PYTHONPATH_PREFIX):. python benchmarks/check_perf.py \
		--report $(SMOKE_BENCH) --history BENCH_history.jsonl

# Observability gate (ci.yml smoke step): validate the shared smoke
# artifacts — zero Theorem-1 contract violations, zero shadow-exact
# divergences (with both auditors demonstrably active), the approx
# recall floor + 3x reduction, a well-formed span export containing a
# complete routed-query tree racing a committed maintenance cycle, and
# (ISSUE 9) the operator layer: a well-formed query-explain report
# whose kept-bucket set matches the recomputed keep rule, the
# forced-breach SLO fired AND cleared (slo.* spans in the trace), the
# Prometheus exposition parsing under the strict round-trip parser,
# and (ISSUE 10) the label-prediction contract: exact arm
# oracle-identical, ensemble arms holding the accuracy floor at
# messages == shards_touched with a clean accuracy-mode shadow audit.
obs-smoke: smoke-artifacts
	$(PYTHONPATH_PREFIX):. python benchmarks/check_obs.py \
		--bench $(SMOKE_BENCH) \
		--trace $(SMOKE_TRACE) \
		--prom $(SMOKE_PROM)

# Full-size perf row: run the real bench_serve, append its summary row
# to the tracked ledger, and judge it against the rolling full-size
# baseline.  Run before cutting a release commit; commit the ledger.
perf-history:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHONPATH_PREFIX):. python benchmarks/bench_serve.py \
		--out BENCH_serve.json --trace-out BENCH_trace.jsonl \
		--prom-out BENCH_prom.txt --history BENCH_history.jsonl
	$(PYTHONPATH_PREFIX):. python benchmarks/check_perf.py \
		--report BENCH_serve.json --history BENCH_history.jsonl

examples:
	$(PYTHONPATH_PREFIX) python examples/quickstart.py
