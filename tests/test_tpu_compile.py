"""Compile the main path's kernels and the service query program for a
described TPU v5e, with no chip attached.

Each test lowers with ``interpret=False`` (kernel mode) against devices of
a described ``v5e:2x2`` topology and asserts that the compiled program
holds a Mosaic kernel (``tpu_custom_call``).  This catches what interpret
mode cannot: block shapes Mosaic refuses, VMEM overruns, and programs that
do not fit the chip's memory.  Nothing runs, so these tests say nothing
about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.knn_service import CONFIG
from repro.kernels import ops as kops
from repro.runtime import knn_server

DIM = 128
QUERY_BLOCK = 128
M = 1 << 20                       # points per kernel call
V5E_HBM = 16 * 2**30
# chip_smoke.py's one-chip store: 2^22 loaded points plus a 1,024-point
# insert wave, in one shard.
SERVICE_SLOTS = (1 << 22) + 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described device's compile lands in the persistent cache but can
    # never be read back without the chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def kernel_mode(monkeypatch):
    monkeypatch.setattr(kops, "_MODE", "kernel")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_l2_distance_compiles(one_chip, kernel_mode):
    _compile(lambda q, p: kops.l2_distance(q, p),
             _spec(one_chip, (QUERY_BLOCK, DIM)), _spec(one_chip, (M, DIM)))


@pytest.mark.parametrize("l", [128, 256])
def test_masked_distance_topk_compiles(one_chip, kernel_mode, l):
    _compile(lambda q, p, v: kops.distance_topk(q, p, l, valid=v),
             _spec(one_chip, (QUERY_BLOCK, DIM)), _spec(one_chip, (M, DIM)),
             _spec(one_chip, (M,), jnp.bool_))


@pytest.mark.parametrize("l", [128, 256])
def test_local_topk_compiles(one_chip, kernel_mode, l):
    _compile(lambda x: kops.local_topk(x, l),
             _spec(one_chip, (QUERY_BLOCK, M)))


def test_route_mask_compiles(one_chip, kernel_mode):
    # lane-aligned summaries (dim 128, k = 128 shards): the only shape
    # ops.route_mask sends to the kernel; 8 sketch directions, one pivot.
    k, r = 128, 8
    packed = [_spec(one_chip, s) for s in (
        (DIM, k), (1, k), (1, k), (r, k), (r, k), (DIM, k), (1, k),
        (1, k), (1, k), (1, 1), (DIM, r))]
    _compile(lambda q, ls, *pk: kops.route_mask(q, ls, pk),
             _spec(one_chip, (QUERY_BLOCK, DIM)),
             _spec(one_chip, (QUERY_BLOCK,), jnp.int32), *packed)


def test_index_mask_compiles(one_chip, kernel_mode):
    # k·b = 16 shards × 8 buckets = 128 lanes
    k, b = 16, 8
    packed = [_spec(one_chip, s) for s in ((DIM, k * b), (1, k * b),
                                           (1, k * b))]
    _compile(lambda q, ls, rows, *pk: kops.index_mask(q, ls, rows, pk),
             _spec(one_chip, (QUERY_BLOCK, DIM)),
             _spec(one_chip, (QUERY_BLOCK,), jnp.int32),
             _spec(one_chip, (QUERY_BLOCK, k), jnp.bool_), *packed)


def _service_operands(mesh, slots, bucket):
    sharded = NamedSharding(mesh, P("knn"))
    rep = NamedSharding(mesh, P())
    return (_spec(sharded, (slots, DIM)),
            _spec(sharded, (slots,), jnp.int32),
            _spec(sharded, (slots,), jnp.bool_),
            _spec(rep, (bucket, DIM)),
            _spec(rep, (bucket,), jnp.int32),
            _spec(rep, (2,), jnp.uint32))


def test_service_query_program_fits_one_chip(topo, kernel_mode):
    """The store-backed service's query program at chip_smoke.py's one-chip
    size compiles with the L2 kernel and fits the chip's memory."""
    mesh = Mesh(np.array(topo.devices[:1]), ("knn",))
    cfg = CONFIG.replace(dim=DIM, l_max=128)
    program = knn_server.build_query_program(
        cfg, mesh, "knn", masked=True, predicting=False, indexed=False)
    compiled = program.lower(*_service_operands(
        mesh, SERVICE_SLOTS, cfg.bucket_sizes[-1])).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= SERVICE_SLOTS * DIM * 4
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM


@pytest.mark.parametrize("sampler", ["selection", "gather"])
def test_service_query_program_compiles_on_four_chips(topo, kernel_mode,
                                                      sampler):
    """chip_smoke.py --chips 4: 2^22 points sharded 2^20 per chip."""
    mesh = Mesh(np.array(topo.devices[:4]), ("knn",))
    cfg = CONFIG.replace(dim=DIM, l_max=128, sampler=sampler)
    program = knn_server.build_query_program(
        cfg, mesh, "knn", masked=True, predicting=False, indexed=False)
    compiled = program.lower(*_service_operands(
        mesh, 1 << 22, cfg.bucket_sizes[-1])).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "all-gather" in compiled.as_text()
