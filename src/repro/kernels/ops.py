"""Jitted, shape-general entry points for the Pallas kernels.

Responsibilities:
  * pad arbitrary shapes up to block multiples (+inf-padding points so padded
    rows never win a top-l slot), slice results back — except the L2
    distance's points, which the kernel reads as they lie on the device
    (``points_transposed``, ``_l2_blocks``);
  * route to the jnp oracle when a shape is outside a kernel's
    specialization envelope (l > MAX_L, VMEM budget exceeded) or when the
    backend has no Mosaic support (this CPU container -> interpret mode for
    tests, oracle for performance paths);
  * expose one flag (`REPRO_KERNEL_MODE`) so the whole framework can be
    flipped between kernel / oracle / interpret for A-B testing.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import l2_distance as _l2
from repro.kernels import distance_topk as _dtk
from repro.kernels import local_topk as _ltk
from repro.kernels import routing as _routing
from repro.obs import metrics as _obs_metrics

# kernel  : pl.pallas_call compiled for the backend (TPU target)
# interpret: kernel body executed in Python (CPU-correctness mode)
# oracle  : pure-jnp reference (fast on CPU, also the fallback)
_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")

# v5e VMEM is ~128 MiB/core but Mosaic's practical per-kernel budget is far
# smaller; stay well under 16 MiB of live scratch + operands.
_VMEM_BUDGET = 12 * 2**20


def _mode() -> str:
    if _MODE != "auto":
        return _MODE
    return "kernel" if jax.default_backend() == "tpu" else "oracle"


def _pad_to(x, mult, axis, value):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def _sublanes(dtype) -> int:
    """Rows of one (sublane, lane) VMEM tile: 8 for 32-bit, 16 for bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def points_transposed(m: int, d: int) -> bool:
    """Whether the L2 kernel reads an (m, d) point buffer as its (d, m)
    view: true where the TPU keeps the buffer column-major.

    The device lays a 2-d array out in (8, 128) tiles along whichever
    orientation pads it less (d = 100: 104 sublanes against 128 lanes;
    d = 128: no pad either way, row-major kept), so ``p.T`` is a free
    view exactly when this holds.  ``tests/test_tpu_compile.py`` checks
    the rule against the compiler's own choice.
    """
    cols = _ceil_mult(d, 8) * _ceil_mult(m, 128)
    rows = _ceil_mult(m, 8) * _ceil_mult(d, 128)
    return cols < rows


def _l2_vmem(bb, bm, bk, p_itemsize, cols):
    """VMEM estimate of one L2 grid step: double-buffered q, point and
    output tiles, three f32 working copies of the point tile and the
    (bb, bm) products.  Conservative: Mosaic compiles v5e tiles this puts
    at about twice the budget."""
    q_tile = bb * _ceil_mult(bk, 128) * 4
    p_elems = (_ceil_mult(bk, 8) * bm if cols
               else _ceil_mult(bm, 8) * _ceil_mult(bk, 128))
    out_tile = bb * _ceil_mult(bm, 128) * 4
    return (2 * q_tile + p_elems * (2 * p_itemsize + 3 * 4)
            + 4 * out_tile)


_L2_MAX_BLOCK_B = 256
_L2_BLOCK_M = (4096, 2048, 1024, 512, 256, 128)
_L2_WIDE_BLOCK_K = 512


def _l2_blocks(B, m, d, q_dtype, p_dtype, cols):
    """(block_b, block_m, block_k) for one L2 call: the query block is the
    batch rounded up to the sublane multiple (split only past 256 rows),
    the width one block whenever the tiles fit ``_VMEM_BUDGET``, and
    block_m the largest that fits (m itself when m is smaller)."""
    sub = _sublanes(q_dtype)
    b_pad = _ceil_mult(max(B, 1), sub)
    nb = -(-b_pad // _L2_MAX_BLOCK_B)
    bb = _ceil_mult(-(-b_pad // nb), sub)
    item = jnp.dtype(p_dtype).itemsize
    for bk in (d, _L2_WIDE_BLOCK_K):
        for bm in _L2_BLOCK_M:
            bm = min(bm, m)
            if _l2_vmem(bb, bm, bk, item, cols) <= _VMEM_BUDGET:
                return bb, bm, bk
    return bb, min(128, m), _L2_WIDE_BLOCK_K


@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "block_k",
                                              "cols", "interpret"))
def _l2_padded(q, p, block_b, block_m, block_k, cols, interpret):
    """Pads the queries (and, past the VMEM budget, the width) only: the
    points go in as they lie, viewed as (d, m) in the cols form."""
    B = q.shape[0]
    qp = _pad_to(_pad_to(q, block_b, 0, 0.0), block_k, 1, 0.0)
    pv = p.T if cols else p
    pv = _pad_to(pv, block_k, 0 if cols else 1, 0.0)
    out = _l2.l2_distance(qp, pv, block_b=block_b, block_m=block_m,
                          block_k=block_k, points_transposed=cols,
                          interpret=interpret)
    return out[:B]


def l2_distance(queries, points, *, valid=None, block_b=None, block_m=None,
                block_k=None):
    """General-shape squared-L2 distance matrix (see kernels/l2_distance.py).

    ``valid`` (optional (m,) bool — the mutable store's live-slot mask)
    forces masked columns to +inf.  The unfused kernel computes the full
    matrix and masks after (the top-l reduction happens at the caller); the
    fused :func:`distance_topk` masks *inside* its running merge.
    """
    mode = _mode()
    if mode == "oracle":
        _count_fallback("l2_distance", "mode_oracle")
        if valid is not None:
            return ref.masked_l2_distance_ref(queries, points, valid)
        return ref.l2_distance_ref(queries, points)
    m, d = points.shape
    cols = points_transposed(m, d)
    bb, bm, bk = _l2_blocks(queries.shape[0], m, d, queries.dtype,
                            points.dtype, cols)
    _obs_metrics.default_registry().counter(
        f"kernel.l2_distance.form.{'cols' if cols else 'rows'}").inc()
    out = _l2_padded(queries, points, block_b or bb, min(block_m or bm, m),
                     block_k or bk, cols, mode == "interpret")
    if valid is not None:
        out = jnp.where(valid[None, :].astype(jnp.bool_), out, jnp.inf)
    return out


@functools.partial(jax.jit,
                   static_argnames=("l", "block_b", "block_m", "block_k",
                                    "interpret"))
def _dtk_padded(q, p, l, block_b, block_m, block_k, interpret):
    B, m = q.shape[0], p.shape[0]
    qp = _pad_to(_pad_to(q, block_b, 0, 0.0), block_k, 1, 0.0)
    # Padded point rows are zero-filled; the kernel itself excludes ids >= m
    # from the top-l (a zero row's distance ||q||^2 can be competitive, so
    # post-hoc masking would lose genuine winners).
    pp = _pad_to(_pad_to(p, block_m, 0, 0.0), block_k, 1, 0.0)
    v, i = _dtk.distance_topk(qp, pp, l, block_b=block_b, block_m=block_m,
                              block_k=block_k, m_real=m, interpret=interpret)
    i = jnp.where(jnp.isfinite(v), i, 2**31 - 1)
    return v[:B], i[:B]


@functools.partial(jax.jit,
                   static_argnames=("l", "block_b", "block_m", "block_k",
                                    "interpret"))
def _dtk_padded_masked(q, p, valid, l, block_b, block_m, block_k, interpret):
    B, m = q.shape[0], p.shape[0]
    qp = _pad_to(_pad_to(q, block_b, 0, 0.0), block_k, 1, 0.0)
    pp = _pad_to(_pad_to(p, block_m, 0, 0.0), block_k, 1, 0.0)
    # Layout-padding slots are masked the same way tombstones are (0.0).
    vp = _pad_to(valid.astype(jnp.float32)[None, :], block_m, 1, 0.0)
    v, i = _dtk.distance_topk(qp, pp, l, block_b=block_b, block_m=block_m,
                              block_k=block_k, m_real=m, valid=vp,
                              interpret=interpret)
    i = jnp.where(jnp.isfinite(v), i, 2**31 - 1)
    return v[:B], i[:B]


def _count_fallback(entry: str, kind: str) -> None:
    """Tally one dispatcher fallback in the process-wide metrics registry
    (src/repro/obs/metrics.py) so silent oracle/jnp reroutes surface in
    ``KnnServer.obs_snapshot()`` and the bench JSONs instead of only in a
    returned string nobody reads.  Dispatcher bodies run at trace time,
    so jitted callers tally once per compiled specialization — the count
    answers "did this deployment ever fall back, and why", not "how many
    launches"."""
    reg = _obs_metrics.default_registry()
    reg.counter(f"kernel.fallback.{entry}").inc()
    reg.counter(f"kernel.fallback.{entry}.{kind}").inc()


def _reason_kind(reason: str) -> str:
    """Stable metric-suffix classification of a _fused_gate reason."""
    if reason.startswith("l="):
        return "max_l"
    if reason.startswith("vmem"):
        return "vmem"
    return "dim"


def _fused_gate(l, dim, bb, bm, bk):
    """The distance_topk routing gate: (vmem estimate, fallback reason).

    Single source of truth shared by the dispatcher below and
    :func:`service_envelope`, so the pre-flight report cannot drift from
    the actual routing.
    """
    vmem = 4 * (bb * bk + bm * bk + bb * bm + 2 * bb * l) + 8 * bm
    if l > _dtk.MAX_L:
        return vmem, f"l={l} > MAX_L={_dtk.MAX_L}"
    if vmem > _VMEM_BUDGET:
        return vmem, f"vmem {vmem} > budget {_VMEM_BUDGET}"
    if dim < 1:
        return vmem, "dim < 1"
    return vmem, None


def distance_topk(queries, points, l, *, valid=None, block_b=None,
                  block_m=None, block_k=None):
    """General-shape fused distance+top-l (see kernels/distance_topk.py).

    ``valid`` (optional (m,) bool) excludes masked point rows from the
    top-l — inside the kernel's running merge on the fused path, via the
    masked oracle on fallbacks.  On the masked path, +inf slots always
    report the INT32_MAX sentinel id (tombstoned ids never surface).
    """
    mode = _mode()
    bb = block_b or _dtk.DEFAULT_BLOCK_B
    bm = block_m or _dtk.DEFAULT_BLOCK_M
    bk = block_k or 512
    d = queries.shape[-1]
    _, reason = _fused_gate(l, d, bb, bm, bk)
    if mode == "oracle" or reason is not None:
        _count_fallback("distance_topk",
                        "mode_oracle" if reason is None
                        else _reason_kind(reason))
        if valid is not None:
            return ref.masked_distance_topk_ref(queries, points, valid, l)
        return ref.distance_topk_ref(queries, points, l)
    bk = min(bk, _ceil_mult(d, 128))
    if valid is not None:
        return _dtk_padded_masked(queries, points, valid, l, bb, bm, bk,
                                  mode == "interpret")
    return _dtk_padded(queries, points, l, bb, bm, bk, mode == "interpret")


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def service_envelope(bucket_b: int, m_local: int, dim: int, l: int) -> dict:
    """Pre-flight dispatch check for one service bucket shape — no compile.

    The micro-batched kNN service (runtime/knn_server.py) compiles one
    executable per bucket (B, l_max) shape; this reports, per bucket and
    *before* paying a compile, which path each kernel entry point routes
    to for that shape:

    * ``l2_path`` — :func:`l2_distance`, the distance step the service's
      executables actually run today (mode flag only);
    * ``path`` — :func:`distance_topk`, the fused distance+top-l hot
      path, evaluated through the same ``_fused_gate`` the dispatcher
      uses (default blocks, ``bk=512`` pre-clamp) so capacity planning
      for a fused service deployment reads true.

    ``fallback_reason`` explains a fused-path oracle fallback (if any).
    """
    mode = _mode()
    bb = _dtk.DEFAULT_BLOCK_B
    bm = _dtk.DEFAULT_BLOCK_M
    bk = 512                       # distance_topk gates on the pre-clamp bk
    vmem, reason = _fused_gate(l, dim, bb, bm, bk)
    path = mode if reason is None else "oracle"
    _obs_metrics.default_registry().counter("kernel.envelopes").inc()
    if reason is not None:
        _count_fallback("envelope", _reason_kind(reason))
    return {
        "bucket_b": bucket_b, "m_local": m_local, "dim": dim, "l": l,
        "path": path, "l2_path": mode, "vmem_bytes": vmem,
        "fallback_reason": reason,
        # padded shape the fused kernel would actually run (grid-aligned)
        "padded_b": _ceil_mult(max(bucket_b, 1), bb),
        "padded_m": _ceil_mult(max(m_local, 1), bm),
    }


@functools.partial(jax.jit,
                   static_argnames=("l", "block_b", "block_m", "interpret"))
def _ltk_padded(x, l, block_b, block_m, interpret):
    B, m = x.shape
    xp = _pad_to(_pad_to(x, block_b, 0, jnp.inf), block_m, 1, jnp.inf)
    v, i = _ltk.local_topk(xp, l, block_b=block_b, block_m=block_m,
                           interpret=interpret)
    i = jnp.where(i < m, i, 2**31 - 1)
    return v[:B], i[:B]


def local_topk(values, l, *, block_b=None, block_m=None):
    """General-shape l-smallest per row (see kernels/local_topk.py)."""
    mode = _mode()
    if mode == "oracle" or l > _dtk.MAX_L:
        _count_fallback("local_topk",
                        "mode_oracle" if l <= _dtk.MAX_L else "max_l")
        return ref.local_topk_ref(values, l)
    bb = block_b or _ltk.DEFAULT_BLOCK_B
    bm = block_m or _ltk.DEFAULT_BLOCK_M
    return _ltk_padded(values, l, bb, bm, mode == "interpret")


@functools.partial(jax.jit, static_argnames=("dim_real", "slack"))
def _route_ref_jit(q, ls2, *packed, dim_real, slack):
    return _routing.route_mask_ref(q, ls2, *packed, dim_real=dim_real,
                                   slack=slack)


@functools.partial(jax.jit, static_argnames=("dim_real", "slack",
                                             "block_b", "interpret"))
def _route_padded(q, ls2, *packed, dim_real, slack, block_b, interpret):
    B = q.shape[0]
    # padding rows carry l=0 and route nowhere, exactly like the
    # micro-batcher's own bucket padding
    qp = _pad_to(q, block_b, 0, 0.0)
    lp = _pad_to(ls2, block_b, 0, 0)
    out = _routing.route_mask(qp, lp, *packed, dim_real=dim_real,
                              slack=slack, block_b=block_b,
                              interpret=interpret)
    return out[:B]


def route_mask(queries, ls, packed, *, slack=1e-4):
    """(B, k) bool active mask — the route_shards decision on device
    (see kernels/routing.py).

    ``packed`` is the operand tuple from ``routing.pack_summaries`` (one
    pack per store generation; the server caches it).  Traceable: the
    service executable calls this in its prologue so routing rides the
    batch's own launch.  Mode routing mirrors the other entry points —
    oracle runs the shared jnp math core directly; a Mosaic-hostile
    shape (lane dims not 128-aligned — always true at the repo's k=8)
    ALSO takes the jnp core, which still fuses into the same XLA program
    and stays device-side; only the aligned case pays a pallas_call.
    """
    mode = _mode()
    q = jnp.asarray(queries, jnp.float32)
    ls2 = jnp.asarray(ls, jnp.int32).reshape(-1, 1)
    dim_real = q.shape[1]
    k = packed[1].shape[1]
    if mode != "interpret" and (mode == "oracle"
                                or dim_real % 128 or k % 128):
        _count_fallback("route_mask",
                        "mode_oracle" if mode == "oracle" else "unaligned")
        out = _route_ref_jit(q, ls2, *packed, dim_real=dim_real,
                             slack=slack)
    else:
        out = _route_padded(q, ls2, *packed, dim_real=dim_real,
                            slack=slack, block_b=_routing.DEFAULT_BLOCK_B,
                            interpret=mode == "interpret")
    return out != 0


@functools.partial(jax.jit, static_argnames=("oversample",))
def _index_ref_jit(q, ls2, rows, *packed, oversample):
    return _routing.index_mask_ref(q, ls2, rows, *packed,
                                   oversample=oversample)


@functools.partial(jax.jit, static_argnames=("oversample", "block_b",
                                             "interpret"))
def _index_padded(q, ls2, rows, *packed, oversample, block_b, interpret):
    B = q.shape[0]
    qp = _pad_to(q, block_b, 0, 0.0)
    lp = _pad_to(ls2, block_b, 0, 0)      # padding rows keep no bucket
    rp = _pad_to(rows, block_b, 0, 0)
    out = _routing.index_mask(qp, lp, rp, *packed, oversample=oversample,
                              block_b=block_b, interpret=interpret)
    return out[:B]


def index_mask(queries, ls, rows, packed, *, oversample=2.0):
    """(B, k·b) bool bucket-keep mask — the search="approx" in-shard
    candidate decision on device (see kernels/routing.py and
    store/index.py).

    ``rows`` is the (B, k) routing keep mask (bool or int32; all-ones
    under route="exact"); ``packed`` is the tuple from
    ``routing.pack_index`` (one pack per store generation; the server
    caches it).  Traceable — the service executable calls this right
    after ``route_mask`` in its prologue.  Mode routing mirrors
    route_mask: oracle and Mosaic-hostile shapes take the shared jnp
    core (still fused device-side); only lane-aligned shapes pay a
    pallas_call.
    """
    mode = _mode()
    q = jnp.asarray(queries, jnp.float32)
    ls2 = jnp.asarray(ls, jnp.int32).reshape(-1, 1)
    rows2 = jnp.asarray(rows, jnp.int32)
    dim_real = q.shape[1]
    kb = packed[1].shape[1]
    if mode != "interpret" and (mode == "oracle"
                                or dim_real % 128 or kb % 128):
        _count_fallback("index_mask",
                        "mode_oracle" if mode == "oracle" else "unaligned")
        out = _index_ref_jit(q, ls2, rows2, *packed,
                             oversample=float(oversample))
    else:
        out = _index_padded(q, ls2, rows2, *packed,
                            oversample=float(oversample),
                            block_b=_routing.DEFAULT_BLOCK_B,
                            interpret=mode == "interpret")
    return out != 0
