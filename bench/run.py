"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration (``BENCHMARK.json`` -> ``bench/configs``)
and traffic mix (``bench/traffic``), makes the points from ``--seed``,
builds the service through its public entry points (``MutableStore``
``insert``/``flush`` and ``KnnServer``, or ``KnnServer(points=...)``),
warms up the bucket shapes the traffic uses and the write path, then
drives ``KnnServer.submit`` (and ``insert``/``delete``/``flush_store``)
for ``--seconds``.  After the window it frees the service and compares a
sample of the answers, drawn from the seed, with the float64 reference
(``bench/reference.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the window and the result
carries the per-layer metrics, ``busy_s``/``window_s`` and a breakdown.
Each metric is computed by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object; the numbers the
correctness check compared, each with its limit, are the last lines of
standard error and the last key of that object.  The run exits non-zero
and prints no result off TPU or with fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import data, reference, spec, tracing, traffic  # noqa: E402

WARM_WRITES = 3          # write batches in set-up (compiles the scatter)
DRAIN_S = 60.0           # how long answers may come after the window


def setup_compile_cache() -> None:
    """``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
    otherwise one fixed directory in the checkout; every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def require_chips(n: int):
    """The chips to run on; exits without a result unless JAX is on TPU
    with ``n`` chips and the kernels dispatch as kernels."""
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"bench: JAX backend is {jax.default_backend()!r}, "
                 f"not 'tpu'")
    from repro.kernels import ops
    if ops._mode() != "kernel":
        sys.exit(f"bench: kernel mode is {ops._mode()!r}, not 'kernel'")
    devices = jax.devices()
    if len(devices) < n:
        sys.exit(f"bench: {n} chips needed, {len(devices)} found")
    return devices[:n]


class System:
    """The service under test, built from the configuration and seed."""

    def __init__(self, cell: spec.Cell, seed: int, devices):
        from jax.sharding import Mesh
        from repro.configs.knn_service import CONFIG
        from repro.runtime.knn_server import KnnServer
        from repro.store import MutableStore

        c = cell.config
        self.dim = int(c["dim"])
        self.centers = data.centers(seed, c["clusters"], self.dim,
                                    c["center_scale"])
        points, self.labels = data.cluster_points(
            seed, "points", c["n_points"], self.centers, with_labels=True)
        self.cfg = CONFIG.replace(dim=self.dim, **c["service"])
        mesh = Mesh(np.array(devices), ("knn",))
        self.store = None
        if c["backing"] == "store":
            self.store = MutableStore(self.dim, mesh=mesh,
                                      **self.cfg.store_kwargs())
            step = int(c["load_chunk"])
            for s in range(0, len(points), step):
                e = min(s + step, len(points))
                self.store.insert(points[s:e], ids=np.arange(s, e))
            self.store.flush()
            self.server = KnnServer(store=self.store, cfg=self.cfg,
                                    seed=seed % 2**31)
            gen0 = self.store.generation
        elif c["backing"] == "static":
            self.server = KnnServer(points=points, cfg=self.cfg, mesh=mesh,
                                    seed=seed % 2**31)
            gen0 = 0
        else:
            raise ValueError(f"unknown backing {c['backing']!r}")
        self.points_per_chip = len(points) // len(devices)
        self.live = reference.LiveSet(points, gen0)
        self.logged = 0          # write batches the reference has seen

    def close(self) -> None:
        self.server.close()
        if self.store is not None:
            self.store.close()
        self.server = self.store = None
        gc.collect()


class Load:
    """The cell's traffic for one window, made from the seed."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 system: System):
        t = cell.traffic
        self.loop = t["loop"]
        self.server = system.server
        buckets = list(system.cfg.bucket_sizes)
        if self.loop == "open":
            due = traffic.arrivals(data.rng(seed, "arrivals"),
                                   t["rate_qps"], seconds)
            n = len(due)
            self.queries = data.cluster_points(seed, "queries", max(n, 1),
                                               system.centers)
            ls = traffic.l_values(data.rng(seed, "l"), t["l_mix"], n)
            self.driver = traffic.OpenLoop(
                self.server, self.queries,
                [traffic.Request(row=i, l=int(ls[i]), due=float(due[i]))
                 for i in range(n)])
        elif self.loop == "closed":
            pool = int(t["query_pool"])
            self.queries = data.cluster_points(seed, "queries", pool,
                                               system.centers)
            ls = traffic.l_values(data.rng(seed, "l"), t["l_mix"], pool)
            self.driver = traffic.ClosedLoop(self.server, self.queries, ls,
                                             int(t["clients"]))
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        # every bucket: a closed loop's callers can fall behind the
        # server and leave a batch part full
        self.warm_buckets = buckets
        self.warm_ls = [int(l) for l, _ in t["l_mix"]]
        self.writer = None
        w = t.get("writer")
        if w is not None:
            batches = WARM_WRITES + math.ceil(seconds * 1e3 / w["every_ms"])
            order = data.rng(seed, "write_order").permutation(
                len(system.centers))
            per = int(w["batches_per_cluster"])
            labels = traffic.Writer.insert_labels(order, batches,
                                                  w["inserts"], per)
            pool = data.cluster_points(seed, "inserts", len(labels),
                                       system.centers, labels=labels)
            self.writer = traffic.Writer(
                self.server, pool, labels, system.labels, order,
                w["every_ms"], w["inserts"], w["deletes"], per)

    def warm_up(self, seed: int, centers: np.ndarray) -> None:
        """Compile and run every shape the window will use."""
        top = max(self.warm_buckets)
        q = data.cluster_points(seed, "warm", top, centers)
        for _ in range(2):
            for b in self.warm_buckets:
                ls = [self.warm_ls[i % len(self.warm_ls)] for i in range(b)]
                self.server.query_batch(q[:b], ls)
        if self.writer is not None:
            for _ in range(WARM_WRITES):
                self.writer.apply(self.writer.make_batch(0.0))
            self.server.query_batch(q[:top], [self.warm_ls[0]] * top)

    def release(self) -> None:
        """Drop every handle on the service, so closing it frees it."""
        self.server = self.driver.server = None
        if self.writer is not None:
            self.writer.server = None

    def run(self, seconds: float, trace_dir=None) -> types.SimpleNamespace:
        """Drive the window; returns what the metric readers read."""
        server = self.server
        warm_writes = len(self.writer.batches) if self.writer else 0
        win = types.SimpleNamespace(seconds=seconds, trace_window_s=None)
        win.registry = [server.obs.metrics.snapshot()]
        win.stats = [server.stats.snapshot()]
        if trace_dir is not None:
            tracing.start(trace_dir)
            t_on = time.perf_counter()
        if self.loop == "open":
            server.start()
            win.t0 = time.perf_counter()
            self.driver.start(win.t0)
        else:
            win.t0 = time.perf_counter()
            self.driver.start(win.t0, seconds)
            server.start()
        if self.writer is not None:
            self.writer.start(win.t0, seconds)
        time.sleep(max(win.t0 + seconds - time.perf_counter(), 0.0))
        win.t_close = time.perf_counter()
        win.registry.append(server.obs.metrics.snapshot())
        win.stats.append(server.stats.snapshot())
        if trace_dir is not None:
            win.trace_window_s = time.perf_counter() - t_on
            tracing.stop()
        self.driver.join()
        if self.writer is not None:
            self.writer.join()
        reqs = self.driver.requests
        deadline = win.t_close + DRAIN_S
        while (any(math.isnan(r.done) for r in reqs)
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        server.stop()
        traffic.collect(reqs)
        win.requests = [r for r in reqs if r.due < seconds]
        win.writes = (self.writer.batches[warm_writes:]
                      if self.writer else [])
        win.flushes = ([b for b in self.writer.batches if b.error is None]
                       if self.writer else [])
        return win


def log_writes(system: System, load: Load) -> None:
    """Hand the reference the write batches it has not seen yet."""
    if load.writer is None:
        return
    done = [b for b in load.writer.batches if b.error is None]
    system.live.log((b.generation, b.ins_ids, b.ins_pts, b.del_ids)
                    for b in done[system.logged:])
    system.logged = len(done)


def stale(gen0: int, flushes: list, requests: list) -> int:
    """Answers older than what their query was due to see: a query
    submitted after a flush had returned generation g must be answered
    at g or later (the server captures the snapshot at dispatch)."""
    done = np.array([b.done for b in flushes])
    head = np.maximum.accumulate(
        np.array([gen0] + [b.generation for b in flushes], np.int64))
    answered = [r for r in requests if r.result is not None]
    seen = np.searchsorted(done, [r.submitted for r in answered])
    return int(sum(r.result.generation < head[k]
                   for r, k in zip(answered, seen)))


def sample_answers(load: Load, win, seed: int, sample: int) -> list:
    """``(query, l, generation, ids, dists)`` of answers drawn from the
    seed among the window's answered queries."""
    answered = [r for r in win.requests if r.result is not None]
    pick = data.rng(seed, "sample").choice(
        len(answered), min(sample, len(answered)), replace=False)
    return [(load.queries[r.row], r.l, r.result.generation, r.result.ids,
             r.result.dists) for r in (answered[i] for i in np.sort(pick))]


def truth(system: System, answers: list, dist=reference.f64_distances):
    """The reference's answers to the same queries at the same
    generations (``dist`` in float64, or the control's)."""
    return reference.search(system.live, [a[0] for a in answers],
                            [a[2] for a in answers],
                            [a[1] for a in answers], dist)


def check(system: System, load: Load, win, seed: int, limits: dict,
          sample: int) -> dict:
    """The numbers ``correct`` is decided on, each with its limit."""
    log_writes(system, load)
    missing = (sum(1 for r in win.requests if r.result is None)
               + sum(1 for b in win.writes if b.error is not None))
    answers = sample_answers(load, win, seed, sample)
    numbers = {"missing": missing,
               "stale": stale(system.live.gen0, win.flushes, win.requests)}
    numbers.update(reference.compare(system.live, answers,
                                     truth(system, answers)))
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def settle() -> None:
    """End set-up: free its garbage, so the window starts with the
    collector's counts at nought in every run."""
    gc.collect()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, keep_trace=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    system = System(cell, seed, devices)
    load = Load(cell, seed, seconds, system)
    load.warm_up(seed, system.centers)
    settle()
    setup_s = time.perf_counter() - T_START
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        win = load.run(seconds, trace_dir)
        reduced = None
        if trace:
            xspace = tracing.read_xspace(trace_dir)
            if keep_trace:
                Path(keep_trace).write_bytes(xspace)
            reduced = tracing.reduce(xspace, len(devices))
            reduced["window_s"] = win.trace_window_s
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes(devices)}
    ctx = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, device_kind=d.device_kind,
        dim=system.dim, points_per_chip=system.points_per_chip,
        setup_s=setup_s, trace=reduced, **vars(win))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lag = [r.submitted - (win.t0 + r.due) for r in win.requests
           if not math.isnan(r.submitted)]
    load.release()
    system.close()
    c = cell.config["check"]
    checked = check(system, load, win, seed, c["limits"], c["sample"])
    correct = all(v["value"] <= v["limit"] for v in checked.values())
    result = {"correct": correct,
              "attempted": len(win.requests) + len(win.writes),
              "failed": checked["missing"]["value"],
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["generator_lag_p99_ms"] = (float(np.percentile(lag, 99)) * 1e3
                                      if lag else None)
    result["check"] = checked
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the raw .xplane.pb here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = require_chips(cell.chips)
    setup_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, keep_trace=args.keep_trace)
    sys.stdout.flush()
    for name, v in result["check"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
