"""Logical-axis sharding: one rule table maps every tensor dim to mesh axes.

MaxText-style: model code annotates tensors with *logical* axis names; the
rule table (swappable per experiment — the long-context cells override
``kv_seq``) resolves them to mesh axes.  GSPMD propagates the rest.

Mesh axes (launch/mesh.py):
  pod   — data parallelism across pods (multi-pod mesh only)
  data  — FSDP: batch AND parameter/optimizer sharding (ZeRO-3 style)
  model — tensor/expert parallelism: heads, d_ff, vocab, experts

Non-divisible cases (40 heads / 16, 40 experts / 16) rely on GSPMD padding;
the waste shows up in the roofline's MODEL_FLOPS / HLO_FLOPs ratio and is a
recorded hillclimb lever (EXPERIMENTS.md Section Perf).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Rules:
    batch: tuple | str | None = ("pod", "data")
    seq: Optional[str] = None            # activation sequence axis
    kv_seq: Optional[str] = None         # KV-cache sequence axis ("data" for
                                         # the long-context cells: SP decode)
    embed: Optional[str] = "data"        # parameter d_model axis (FSDP)
    heads: Optional[str] = "model"
    qkv: Optional[str] = "model"         # fused (head, head_dim) param axis
    mlp: Optional[str] = "model"         # d_ff
    vocab: Optional[str] = "model"
    experts: Optional[str] = "model"
    expert_cap: Optional[str] = None
    stack: Optional[str] = None          # stacked-layer leading axis
    none: Optional[str] = None


_CURRENT = Rules()


def current_rules() -> Rules:
    return _CURRENT


@contextlib.contextmanager
def use_rules(rules: Rules):
    global _CURRENT
    prev, _CURRENT = _CURRENT, rules
    try:
        yield
    finally:
        _CURRENT = prev


def _mesh_axis_names():
    from repro.parallel.compat import ambient_mesh_axis_names
    return ambient_mesh_axis_names()


def spec(*logical_axes: Optional[str]) -> P:
    """Resolve logical axis names to a PartitionSpec under current rules.

    Mesh axes referenced by the rules but absent from the active mesh are
    dropped (e.g. "pod" on the single-pod mesh), so one rule table serves
    every mesh shape.
    """
    r = _CURRENT
    names = _mesh_axis_names()
    out = []
    for ax in logical_axes:
        resolved = None if ax is None else getattr(r, ax)
        if names is not None and resolved is not None:
            if isinstance(resolved, tuple):
                resolved = tuple(a for a in resolved if a in names) or None
                if resolved is not None and len(resolved) == 1:
                    # 1-tuples and bare names are distinct to old-jax
                    # PartitionSpec equality; normalize to the bare name.
                    resolved = resolved[0]
            elif resolved not in names:
                resolved = None
        out.append(resolved)
    return P(*out)


def divisible(pspec: P, shape, mesh) -> P:
    """Drop mesh axes that do not evenly divide their dimension.

    Input/output placements (ShapeDtypeStruct shardings, device_put) must
    tile evenly — unlike internal with_sharding_constraint, where GSPMD
    pads.  Where a dim is not divisible (40 heads / 16, batch 1, stacked
    layer counts) the offending axes are dropped: the tensor arrives
    replicated on those axes and the first internal constraint reshards it.
    """
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    out = []
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            n = sizes.get(a, 1)
            if dim % (prod * n) == 0:
                kept.append(a)
                prod *= n
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    return P(*out)


def batch_shards() -> int:
    """Number of mesh shards the batch ("data"/"pod") axes span under the
    active mesh — the MoE dispatch group count (moe.py)."""
    names = _mesh_axis_names()
    if not names:
        return 1
    from repro.parallel.compat import ambient_mesh_axis_sizes
    sizes = ambient_mesh_axis_sizes()
    if sizes is None:
        return 1
    rule = _CURRENT.batch
    axes = rule if isinstance(rule, tuple) else (rule,)
    out = 1
    for a in axes:
        if a in names:
            out *= sizes.get(a, 1)
    return max(out, 1)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint if we're under a mesh, else a no-op.

    Lets the same model code run in single-device tests and under the
    production mesh.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        empty = mesh.empty if mesh is not None else True
    except Exception:
        empty = True
    if empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec(*logical_axes))
