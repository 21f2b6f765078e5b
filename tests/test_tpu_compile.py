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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.knn_service import CONFIG
from repro.kernels import ops as kops
from repro.obs.metrics import default_registry
from repro.runtime import knn_server

DIM = 128
QUERY_BLOCK = 128
M = 1 << 20                       # points per kernel call
V5E_HBM = 16 * 2**30
# chip_smoke.py's one-chip store: 2^22 loaded points plus a 1,024-point
# insert wave, in one shard.
SERVICE_SLOTS = (1 << 22) + 1024
# the msturing100-store cells' store: capacity 2^20 + 2^17 slots of 100-d
# float32, l_max 10, buckets of 1 to 32 rows.
STORE_SLOTS = (1 << 20) + (1 << 17)
STORE_DIM = 100


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


def _point_consumers(text, index):
    """Opcodes of the entry computation's consumers of parameter
    ``index``, looking through bitcasts (a bitcast is a free view)."""
    start = text.index("\nENTRY")
    entry = text[start:text.index("\n}\n", start)]
    names = {re.search(rf"%(\S+) = \S+ parameter\({index}\)", entry)[1]}
    ops_ = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9_-]*)\((.*)",
                     line)
        if m is None:
            continue
        used = set(re.findall(r"%([\w.-]+)", m[3].split("),")[0]))
        if used & names:
            if m[2] == "bitcast":
                names.add(m[1])
            else:
                ops_.append(m[2])
    return ops_


def _form_counts():
    reg = default_registry()
    return {f: reg.value(f"kernel.l2_distance.form.{f}")
            for f in ("rows", "cols")}


def _assert_reads_points_in_place(compiled, point_index, b, m):
    """The L2 kernel is the only reader of the point buffer (no copy,
    pad or transpose of it), and the temporaries stay near the (B, m)
    distance matrix."""
    assert _point_consumers(compiled.as_text(), point_index) == [
        "custom-call"]
    b_pad = -(-b // 8) * 8
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        1.2 * b_pad * m * 4)


@pytest.mark.parametrize("b,m,d,form", [
    (1, STORE_SLOTS, STORE_DIM, "cols"),
    (32, STORE_SLOTS, STORE_DIM, "cols"),
    (32, M, DIM, "rows"),
])
def test_l2_distance_reads_points_in_place(one_chip, kernel_mode, b, m, d,
                                           form):
    before = _form_counts()
    compiled = _compile(lambda q, p: kops.l2_distance(q, p),
                        _spec(one_chip, (b, d)), _spec(one_chip, (m, d)))
    _assert_reads_points_in_place(compiled, 1, b, m)
    after = _form_counts()
    other = "rows" if form == "cols" else "cols"
    assert after[form] == before[form] + 1
    assert after[other] == before[other]


@pytest.mark.parametrize("b", [1, 32])
def test_store_query_program_reads_points_in_place(topo, kernel_mode, b):
    """The msturing100-store cells' query program: the points parameter
    goes straight into the kernel, in the cols form."""
    mesh = Mesh(np.array(topo.devices[:1]), ("knn",))
    cfg = CONFIG.replace(dim=STORE_DIM, l_max=10)
    before = _form_counts()
    program = knn_server.build_query_program(
        cfg, mesh, "knn", masked=True, predicting=False, indexed=False)
    compiled = program.lower(*_service_operands(
        mesh, STORE_SLOTS, b, STORE_DIM)).compile()
    _assert_reads_points_in_place(compiled, 0, b, STORE_SLOTS)
    assert _form_counts()["cols"] > before["cols"]


@pytest.mark.parametrize("b", [1, 32])
def test_store_query_program_returns_one_buffer(topo, kernel_mode, b):
    """The cells' query program as the server launches it: its outputs
    leave the device as one int32 buffer (one copy home a launch), its
    module keeps the name the benchmark's trace readers look for, and
    the points parameter still goes straight into the kernel."""
    mesh = Mesh(np.array(topo.devices[:1]), ("knn",))
    cfg = CONFIG.replace(dim=STORE_DIM, l_max=10)
    program = knn_server._OneBuffer(knn_server.build_query_program(
        cfg, mesh, "knn", masked=True, predicting=False, indexed=False))
    compiled = program.packed.lower(*_service_operands(
        mesh, STORE_SLOTS, b, STORE_DIM)).compile()
    out = compiled.out_info
    assert isinstance(out, jax.ShapeDtypeStruct)
    # dists and ids (b, 10), the iteration count, survivors (b,)
    assert out.dtype == jnp.int32 and out.shape == (2 * b * 10 + 1 + b,)
    assert compiled.as_text().startswith("HloModule jit_fn,")
    _assert_reads_points_in_place(compiled, 0, b, STORE_SLOTS)


@pytest.mark.parametrize("m,d", [
    (STORE_SLOTS, STORE_DIM), (M, DIM), (96, 100), (777, 100),
    (2048, 1000), (777, 1000), (4096, 64), (4096, 200), (4096, 768),
    (SERVICE_SLOTS, DIM)])
def test_points_layout_rule_matches_device(one_chip, m, d):
    """ops.points_transposed agrees with the layout the compiler gives an
    (m, d) float32 buffer, so the cols form's p.T is a free view."""
    compiled = jax.jit(lambda p: p * 2).lower(
        _spec(one_chip, (m, d))).compile()
    layout = re.search(r"entry_computation_layout=\{\(?f32\[[^\]]*\]"
                       r"\{([0-9,]+)", compiled.as_text())[1]
    assert (layout == "0,1") == kops.points_transposed(m, d)


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


def _service_operands(mesh, slots, bucket, dim=DIM):
    sharded = NamedSharding(mesh, P("knn"))
    rep = NamedSharding(mesh, P())
    return (_spec(sharded, (slots, dim)),
            _spec(sharded, (slots,), jnp.int32),
            _spec(sharded, (slots,), jnp.bool_),
            _spec(rep, (bucket, dim)),
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
