"""Run the store-backed kNN service end to end on TPU and check every answer.

    python3 chip_smoke.py [--seed N] [--chips 1|4]

Default (one chip): load 2^22 points of dim 128 (``gaussian_clusters`` from
``--seed``) into a ``MutableStore`` on one chip, then serve them through
``KnnServer``: warm up every bucket, answer bursts that fill every bucket
from 1 to 32 with l in {1, 10, 100, 128}, apply a write wave while serving
(1,024 inserts, 1,024 deletes that include current winners), and answer
again at the new generation.

``--chips 4`` runs only the sharded phase: the same 2^22 points over a
four-chip mesh (2^20 per chip), checks that each chip holds its own
quarter, and answers the same bursts through Algorithm 2
(``sampler="selection"``) and through the paper's gather baseline.

Every answer is compared with a float64 brute force over the store's host
mirror at the generation the answer reports.  Ids must match rank for
rank; a rank may differ only where the two points' float64 distances lie
within the float32 rounding bound of the distance expansion (reported as
tie exceptions).  Each returned distance must lie within that bound of
its float64 value.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}`` and appears only when every check
passed.  The script exits non-zero, before loading anything, when JAX
finds no TPU or the kernels would not run in kernel mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_POINTS = 1 << 22      # the paper's points per machine
DIM = 128               # SIFT's width (ANN-Benchmarks)
L_MAX = 128
L_VALUES = (1, 10, 100, 128)
WAVE = 1024             # inserts and deletes in the write wave
LOAD_CHUNK = 1 << 18    # points per insert call while loading
BURSTS = (1, 2, 4, 8, 16, 32)
N_QUERIES = sum(BURSTS)
# float32 rounding bound of ||q||^2 - 2 q.p + ||p||^2 over DIM terms:
# |error| <= GAMMA * (||q|| + ||p||)^2 with GAMMA = gamma_{DIM+2}.
_U = 2.0 ** -24
GAMMA = (DIM + 2) * _U / (1 - (DIM + 2) * _U)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def setup_compile_cache() -> None:
    """JAX_COMPILATION_CACHE_DIR wins when set (JAX reads it itself);
    otherwise the cache sits at one fixed path inside the checkout."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))


def require_chip(chips: int):
    """The devices to run on; exits before any work unless JAX is on TPU,
    the kernels dispatch in kernel mode, and ``chips`` chips are present."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'")
    from repro.kernels import ops as kops
    if kops._mode() != "kernel":
        sys.exit(f"chip_smoke: kernel mode is {kops._mode()!r}, not "
                 f"'kernel' (REPRO_KERNEL_MODE overrides it)")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"chip_smoke: {chips} chips needed, {len(devices)} found")
    return devices[:chips]


def make_data(seed: int):
    """(load points, wave points, queries) from one gaussian_clusters draw;
    the first queries' near-duplicates join the wave, so inserted ids must
    surface in the answers after it."""
    from repro.configs.knn_service import CONFIG
    from repro.data.synthetic import gaussian_clusters
    pts, _ = gaussian_clusters(N_POINTS + WAVE + N_QUERIES, DIM,
                               CONFIG.num_classes, seed=seed)
    load, wave = pts[:N_POINTS], pts[N_POINTS:N_POINTS + WAVE].copy()
    queries = pts[N_POINTS + WAVE:]
    rng = np.random.default_rng(seed + 1)
    wave[:N_QUERIES] = queries + rng.normal(
        scale=1e-2, size=queries.shape).astype(np.float32)
    return load, wave, queries


def request_ls(n: int) -> np.ndarray:
    return np.array([L_VALUES[i % len(L_VALUES)] for i in range(n)])


def load_store(store, points) -> dict:
    t0 = time.perf_counter()
    for c in range(0, len(points), LOAD_CHUNK):
        store.insert(points[c:c + LOAD_CHUNK])
    gen = store.flush()
    return {"points": int(store.live_count), "dim": DIM,
            "load_s": time.perf_counter() - t0, "generation": gen}


def serve_bursts(server, queries, ls, errors: list) -> list:
    """Submit ``queries`` as bursts of BURSTS sizes (each burst drained
    before the next, so each fills one bucket); returns one (row, result)
    pair per answered request and appends failures to ``errors``."""
    answered = []
    row = 0
    for size in BURSTS:
        rows = range(row, row + size)
        futs = [(r, server.submit(queries[r], int(ls[r]))) for r in rows]
        for r, fut in futs:
            try:
                answered.append((r, fut.result(timeout=600)))
            except Exception as e:          # a failed dispatch
                errors.append(f"{type(e).__name__}: {e}")
        row += size
    return answered


class Reference:
    """float64 brute force over one generation's host mirror."""

    def __init__(self, store):
        self.ids, self.pts = store.live_arrays()       # ascending ids

    def top(self, queries: np.ndarray, l: int):
        """(nq, l) ids and f64 distances, ascending by (distance, id)."""
        q = queries.astype(np.float64)
        q2 = (q * q).sum(1)
        keep = l + 32
        cands = []
        for c in range(0, len(self.pts), LOAD_CHUNK):
            p = self.pts[c:c + LOAD_CHUNK].astype(np.float64)
            d = (p * p).sum(1)[:, None] - 2.0 * (p @ q.T) + q2[None, :]
            k = min(keep, len(p) - 1)
            cands.append(c + np.argpartition(d, k, axis=0)[:keep])
        cands = np.concatenate(cands, axis=0).T           # (nq, C)
        out_ids = np.empty((len(q), l), np.int64)
        out_d = np.empty((len(q), l))
        for j in range(len(q)):
            rows = cands[j]
            d = self.exact(q[j], rows)
            order = np.lexsort((self.ids[rows], d))[:l]
            out_ids[j], out_d[j] = self.ids[rows[order]], d[order]
        return out_ids, out_d

    def rows_of(self, ids: np.ndarray) -> np.ndarray:
        """Mirror rows of ``ids``; -1 where an id is not live."""
        rows = np.searchsorted(self.ids, ids)
        rows = np.minimum(rows, len(self.ids) - 1)
        return np.where(self.ids[rows] == ids, rows, -1)

    def exact(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        diff = self.pts[rows].astype(np.float64) - q
        return (diff * diff).sum(1)

    def bound(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        pn = np.linalg.norm(self.pts[rows].astype(np.float64), axis=1)
        return GAMMA * (np.linalg.norm(q) + pn) ** 2


def check_answers(answered, queries, refs: dict, forbidden=()) -> dict:
    """Compare each answer with the float64 reference at its generation."""
    tally = {"requests": len(answered), "id_mismatches": 0,
             "dist_mismatches": 0, "tie_exceptions": 0,
             "forbidden_ids": 0, "wrong_generation": 0}
    forbidden = np.asarray(sorted(forbidden), np.int64)
    by_gen: dict = {}
    for r, res in answered:
        by_gen.setdefault(res.generation, []).append((r, res))
    for gen, items in by_gen.items():
        ref = refs.get(gen)
        if ref is None:
            tally["wrong_generation"] += len(items)
            continue
        rows_q = sorted({r for r, _ in items})
        top_ids, top_d = ref.top(queries[rows_q], L_MAX)
        pos = {r: i for i, r in enumerate(rows_q)}
        for r, res in items:
            q = queries[r].astype(np.float64)
            l = res.l
            ids = np.asarray(res.ids, np.int64)
            tally["forbidden_ids"] += int(np.isin(ids, forbidden).sum())
            rows = ref.rows_of(ids)
            dead = rows < 0
            if dead.any() or len(set(ids.tolist())) != l:
                tally["id_mismatches"] += int(max(dead.sum(), 1))
                continue
            d_sys = ref.exact(q, rows)
            bound = ref.bound(q, rows)
            tally["dist_mismatches"] += int(
                (np.abs(np.asarray(res.dists, np.float64) - d_sys)
                 > bound).sum())
            want_ids = top_ids[pos[r], :l]
            want_d = top_d[pos[r], :l]
            differ = ids != want_ids
            if differ.any():
                want_bound = ref.bound(q, ref.rows_of(want_ids[differ]))
                tie = (np.abs(d_sys[differ] - want_d[differ])
                       <= bound[differ] + want_bound)
                tally["tie_exceptions"] += int(tie.sum())
                tally["id_mismatches"] += int((~tie).sum())
    return tally


def server_health(server, store) -> dict:
    snap = server.obs_snapshot()
    kernel = {k: v for k, v in snap["kernel"].items()
              if k.startswith("kernel.fallback.")}
    health = {
        "server_errors": int(snap["metrics"].get("serve.dispatch_errors",
                                                 0)),
        "contract_checks": int(snap["audit"]["contract"]["checks"]),
        "contract_violations": int(
            snap["audit"]["contract"]["violations"]),
        "kernel_fallbacks": kernel,
        "bucket_counts": {str(b): n for b, n in sorted(
            snap["server"]["bucket_counts"].items())},
    }
    worker = store.maintenance_stats().get("worker")
    if worker is not None:
        health["maintenance_errors"] = int(worker["errors"])
    return health


def health_failures(health: dict, buckets) -> list:
    fails = []
    if health["server_errors"]:
        fails.append("server dispatch errors")
    if health["contract_violations"]:
        fails.append("contract violations")
    if health["kernel_fallbacks"].get("kernel.fallback.l2_distance", 0):
        fails.append("l2_distance fell back from the kernel")
    if health.get("maintenance_errors", 0):
        fails.append("background maintenance errors")
    missing = [b for b in buckets if str(b) not in health["bucket_counts"]]
    if missing:
        fails.append(f"buckets never served: {missing}")
    return fails


def answer_failures(tally: dict, errors: list) -> list:
    fails = [f"{k}={tally[k]}" for k in ("id_mismatches", "dist_mismatches",
                                         "forbidden_ids", "wrong_generation")
             if tally[k]]
    if errors:
        fails.append(f"{len(errors)} failed futures: {errors[:3]}")
    return fails


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def run_one_chip(devices, seed: int) -> list:
    from jax.sharding import Mesh
    from repro.configs.knn_service import CONFIG
    from repro.runtime.knn_server import KnnServer
    from repro.store import MutableStore

    fails = []
    load, wave, queries = make_data(seed)
    ls = request_ls(N_QUERIES)
    cfg = CONFIG.replace(dim=DIM, l_max=L_MAX,
                         store_capacity_per_shard=N_POINTS + WAVE,
                         store_staging_size=4 * WAVE)
    mesh = Mesh(np.array(devices[:1]), ("knn",))
    store = MutableStore(DIM, mesh=mesh, **cfg.store_kwargs())
    stats = load_store(store, load)
    del load
    emit("load", **stats)
    refs = {store.generation: Reference(store)}

    server = KnnServer(store=store, cfg=cfg)
    t0 = time.perf_counter()
    server.warmup()
    emit("warmup", compile_s=time.perf_counter() - t0,
         buckets=list(cfg.bucket_sizes), peak_bytes=peak_bytes(devices))

    errors: list = []
    with server.serving():
        before = serve_bursts(server, queries, ls, errors)
        # the write wave: delete current winners and random points,
        # insert the wave (near-duplicates of the queries among it)
        winners = sorted({int(i) for _, res in before
                          for i in res.ids[:min(res.l, 8)]})[:WAVE // 4]
        rng = np.random.default_rng(seed + 2)
        others = rng.choice(np.setdiff1d(np.arange(N_POINTS), winners),
                            WAVE - len(winners), replace=False)
        deleted = np.concatenate([winners, others]).astype(np.int64)
        t0 = time.perf_counter()
        inserted = server.insert(wave)
        server.delete(deleted)
        gen = server.flush_store()
        emit("write_wave", inserted=len(inserted), deleted=len(deleted),
             deleted_winners=len(winners), generation=gen,
             apply_s=time.perf_counter() - t0)
        refs[gen] = Reference(store)
        after = serve_bursts(server, queries, ls, errors)

    tally_before = check_answers(before, queries, refs)
    tally_after = check_answers(after, queries, refs, forbidden=deleted)
    new_ids = sum(int(np.isin(res.ids, inserted).any()) for _, res in after)
    emit("answers", generation=list(refs), before=tally_before,
         after=tally_after, answers_with_new_ids=new_ids,
         failed_futures=len(errors), peak_bytes=peak_bytes(devices))
    fails += answer_failures(tally_before, errors)
    fails += answer_failures(tally_after, [])
    if new_ids == 0:
        fails.append("no inserted id surfaced after the write wave")

    health = server_health(server, store)
    emit("health", **health)
    fails += health_failures(health, cfg.bucket_sizes)
    server.close()
    store.close()
    return fails


def run_four_chips(devices, seed: int) -> list:
    from jax.sharding import Mesh
    from repro.configs.knn_service import CONFIG
    from repro.runtime.knn_server import KnnServer
    from repro.store import MutableStore

    fails = []
    k = len(devices)
    load, _, queries = make_data(seed)
    ls = request_ls(N_QUERIES)
    cfg = CONFIG.replace(dim=DIM, l_max=L_MAX,
                         store_capacity_per_shard=N_POINTS // k,
                         store_staging_size=4 * WAVE)
    mesh = Mesh(np.array(devices), ("knn",))
    store = MutableStore(DIM, mesh=mesh, **cfg.store_kwargs())
    # Buffer shapes are fixed by capacity, so both samplers compile on the
    # empty store, before the long host-side load.
    servers, compile_s = {}, {}
    for sampler in ("selection", "gather"):
        servers[sampler] = KnnServer(store=store,
                                     cfg=cfg.replace(sampler=sampler))
        t0 = time.perf_counter()
        servers[sampler].warmup()
        compile_s[sampler] = time.perf_counter() - t0
    emit("warmup", compile_s=compile_s, buckets=list(cfg.bucket_sizes))
    stats = load_store(store, load)
    del load
    shards = store.snapshot().points.addressable_shards
    placed = {
        "shard_devices": sorted(s.device.id for s in shards),
        "shard_rows": [int(s.data.shape[0]) for s in shards],
        "live_per_shard": [int(v) for v in store.live_per_shard],
    }
    emit("load", chips=k, **stats, **placed)
    if (placed["shard_devices"] != sorted(d.id for d in devices)
            or set(placed["shard_rows"]) != {N_POINTS // k}
            or set(placed["live_per_shard"]) != {N_POINTS // k}):
        fails.append("points are not one quarter per chip")
    refs = {store.generation: Reference(store)}

    for sampler, server in servers.items():
        errors: list = []
        with server.serving():
            answered = serve_bursts(server, queries, ls, errors)
        tally = check_answers(answered, queries, refs)
        health = server_health(server, store)
        emit("answers", sampler=sampler, **tally,
             failed_futures=len(errors), peak_bytes=peak_bytes(devices),
             **health)
        fails += [f"{sampler}: {f}" for f in answer_failures(tally, errors)
                  + health_failures(health, cfg.bucket_sizes)]
        server.close()
    store.close()
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    setup_compile_cache()
    devices = require_chip(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        fails = run_four_chips(devices, args.seed)
    else:
        fails = run_one_chip(devices, args.seed)
    emit("done", wall_s=time.perf_counter() - t0, failures=fails)
    if fails:
        print("chip_smoke: FAILED: " + "; ".join(fails), file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
