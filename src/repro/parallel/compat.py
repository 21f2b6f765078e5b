"""One import site for the mesh / shard_map API surface.

Thin aliases over the installed jax (>= 0.9): ``jax.shard_map`` and
``jax.make_mesh`` with explicit ``Auto`` axis types.  Call sites across
the core algorithms, the serving runtime, benchmarks and tests import
these names from here, so a future API rename is absorbed in one file.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``; ``check_vma`` toggles the "outputs claimed
    replicated must be provably replicated" verifier."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    """Mesh of the process's devices with ``Auto`` axis types everywhere."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(tuple(axis_names)))

