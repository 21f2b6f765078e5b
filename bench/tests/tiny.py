"""Cells at a size a CPU test run can hold."""

import dataclasses

from bench import run, spec

SEED = 2**31 + 12345
SECONDS = 1.0


def tiny(name: str) -> spec.Cell:
    """The cell at 2,048 points and 100 queries/s; everything else as
    committed."""
    full = spec.load_cell(name)
    c = dict(full.config, n_points=2048, load_chunk=1024)
    service = dict(c["service"])
    if "store_capacity_per_shard" in service:
        service["store_capacity_per_shard"] = 2048 + 4096
    c["service"] = service
    c["check"] = dict(c["check"], sample=64)
    t = dict(full.traffic)
    if "rate_qps" in t:
        t["rate_qps"] = 100
    return dataclasses.replace(full, config=c, traffic=t)


def devices(cell):
    import jax
    return jax.devices("cpu")[:cell.chips]


def run_tiny(name: str) -> dict:
    c = tiny(name)
    return run.run_cell(c, SEED, SECONDS, False, devices(c))
