"""Small collective utilities used across the framework.

JAX's varying-manual-axes (vma) checker does not infer `all_gather` outputs
as replicated, even though they are identical on every shard.  `replicate`
re-derives provable invariance with one psum of shard 0's copy — O(size)
flops, no extra bytes beyond the psum itself — so library functions can hand
back replicated results to shard_maps running with full vma checking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def replicate(x: jax.Array, axis_name) -> jax.Array:
    """Make a semantically-replicated value *provably* invariant over axis.

    Correct only if ``x`` already holds the same value on every shard of
    ``axis_name`` (true for anything derived from all_gather-ed data through
    shard-independent computation).  Handles +/-inf and bool payloads.
    """
    if x.dtype == jnp.bool_:
        return replicate(x.astype(jnp.int32), axis_name).astype(jnp.bool_)
    picked = jnp.where(lax.axis_index(axis_name) == 0, x, jnp.zeros_like(x))
    return lax.psum(picked, axis_name)


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis."""
    return int(lax.axis_size(axis_name))
