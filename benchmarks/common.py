"""Shared benchmark plumbing.

The k-machine-model benchmarks need k >= 2 host devices; the benchmark
entrypoint claims its own process-local device count — nothing leaks
into tests or other runs.
"""

from __future__ import annotations

import os

K_MACHINES = 8

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={K_MACHINES} "
        + os.environ.get("XLA_FLAGS", ""))

import datetime  # noqa: E402
import subprocess  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from repro.parallel.compat import make_mesh


def kmachine_mesh(k: int = K_MACHINES):
    return make_mesh((k,), ("x",))


def stamp(report: dict) -> dict:
    """Attach provenance metadata to a BENCH_*.json report (in place).

    Every emitted report carries ``meta.git_commit`` / ``meta.timestamp``
    / ``meta.jax_version`` so the benchmark trajectory across PRs is
    reconstructable from the JSON artifacts alone.
    """
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        commit = ""
    report["meta"] = {
        "git_commit": commit or "unknown",
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "jax_version": jax.__version__,
    }
    return report


def time_fn(fn, *args, repeats: int = 20, warmup: int = 3) -> float:
    """Median wall seconds per call (fn must return jax arrays)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def obs_section(srv) -> dict:
    """Compact ``obs`` payload for a BENCH_*.json, read off one
    ``KnnServer.obs_snapshot()``: audit verdicts (Theorem-1 contract +
    shadow-exact), the per-stage p50/p99 latency breakdown from the
    unified registry (src/repro/obs/metrics.py), kernel-fallback
    counters, and tracer ring stats.  ``make obs-smoke``
    (benchmarks/check_obs.py) asserts on these fields."""
    snap = srv.obs_snapshot()
    stages = {}
    for name, payload in snap["metrics"].items():
        if (name.startswith(("serve.", "maint.", "store."))
                and isinstance(payload, dict) and "p50" in payload):
            stages[name] = {"count": payload["count"],
                            "mean": payload["mean"],
                            "p50": payload["p50"],
                            "p99": payload["p99"]}
    contract = snap["audit"]["contract"]
    shadow = snap["audit"]["shadow"]
    return {
        "stages": stages,
        "contract_checks": contract["checks"],
        "contract_violations": contract["violations"],
        "contract_details": contract["details"],
        "shadow_every": shadow["every"],
        "shadow_checks": shadow["checks"],
        "shadow_divergences": shadow["divergences"],
        "kernel_fallbacks": snap["kernel"],
        "trace": snap["trace"],
    }
