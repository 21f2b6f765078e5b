"""Device idle time put down to the program's own spans.

The program mirrors its spans into a recording profiler session as
``knn.<span>`` annotations on the host plane, one line per thread
(``src/repro/obs/trace.py``).  ``idle_by_span`` reads such a trace and
returns, for device 0, the idle seconds between its first and its last
XLA op, per the innermost ``knn.`` span open at the time on the serving
thread's line (the line with the most ``knn.dispatch`` spans), keyed by
the span's name without the prefix.  A ``knn.gc`` span on any line takes
precedence, since a collection halts every Python thread; idle time with
no such span open is ``unnamed``.

``longest_gaps`` names, for the longest idle gaps, the innermost span
open in the middle of each on every line that holds program spans (the
serving thread's, the writer's).  Both reduce the same trace as
``tracing.reduce``, which does not call them yet.  On a trace kept by
``bench/run.py --keep-trace <path>``:

    python3 -m bench.idle_spans <path>
"""

from __future__ import annotations

import gzip
import json
import sys

import numpy as np

from bench import tracing

PREFIX = "knn."
UNNAMED = "unnamed"


def _intervals(events) -> np.ndarray:
    return np.array([(s, s + d) for _, s, d in events],
                    np.float64).reshape(-1, 2)


def _parse(xspace: bytes) -> tuple:
    """(device 0's idle gaps as (start, end) ns rows, the host lines that
    hold program spans as lists of (name without prefix, start, length),
    the serving thread's line or [])."""
    from jax.profiler import ProfileData
    ops, lines = [], []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if plane.name == "/device:TPU:0":
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    ops = tracing._events(ln)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                spans = [(n[len(PREFIX):], s, d)
                         for n, s, d in tracing._events(ln)
                         if n.startswith(PREFIX)]
                if spans:
                    lines.append(spans)
    busy = tracing.union(_intervals(ops))
    gaps = np.stack([busy[:-1, 1], busy[1:, 0]], 1) if len(busy) > 1 \
        else np.zeros((0, 2))
    serving = max(lines, key=lambda ln: sum(n == "dispatch"
                                            for n, _, _ in ln), default=[])
    if not any(n == "dispatch" for n, _, _ in serving):
        serving = []
    return gaps, lines, serving


def idle_by_span(xspace: bytes) -> dict:
    """``{span name: idle seconds}`` on device 0, largest first, with an
    ``unnamed`` entry; see the module docstring."""
    gaps, lines, serving = _parse(xspace)
    collections = tracing.union(_intervals(
        [e for ln in lines for e in ln if e[0] == "gc"]))

    # Elementary segments between every edge; each takes the label of the
    # innermost serving span over it (spans sorted by start, the longer
    # first at a tie, so a nested span overwrites its parent), then gc.
    spans = sorted(serving, key=lambda e: (e[1], -e[2]))
    span_iv = _intervals(spans)
    edges = np.unique(np.concatenate(
        [gaps.ravel(), span_iv.ravel(), collections.ravel()]))
    names = [UNNAMED] + sorted({n for n, _, _ in spans} | {"gc"})
    code = {n: i for i, n in enumerate(names)}
    label = np.zeros(max(len(edges) - 1, 0), np.int64)
    for (name, _, _), a, b in zip(spans,
                                  np.searchsorted(edges, span_iv[:, 0]),
                                  np.searchsorted(edges, span_iv[:, 1])):
        label[a:b] = code[name]
    for a, b in zip(np.searchsorted(edges, collections[:, 0]),
                    np.searchsorted(edges, collections[:, 1])):
        label[a:b] = code["gc"]
    mids = (edges[:-1] + edges[1:]) / 2
    gap = np.searchsorted(gaps[:, 0], mids, side="right") - 1
    idle = (gap >= 0) & (mids < gaps[np.maximum(gap, 0), 1]) \
        if len(gaps) else np.zeros(len(mids), bool)
    seconds = np.bincount(label[idle], weights=np.diff(edges)[idle],
                          minlength=len(names)) * 1e-9
    out = {n: float(v) for n, v in zip(names, seconds)
           if v > 0 or n == UNNAMED}
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def longest_gaps(xspace: bytes, top: int = 10) -> list:
    """The ``top`` longest idle gaps of device 0, longest first, each as
    ``[seconds, [innermost span open at its midpoint on each line that
    holds program spans, or None]]``, the serving thread's line first."""
    gaps, lines, serving = _parse(xspace)
    lines = ([serving] if serving else []) + [ln for ln in lines
                                              if ln is not serving]
    out = []
    for s, e in gaps[np.argsort(gaps[:, 0] - gaps[:, 1],
                                 kind="stable")][:top]:
        mid = (s + e) / 2
        names = []
        for ln in lines:
            open_ = [(st, n) for n, st, d in ln if st <= mid < st + d]
            names.append(max(open_)[1] if open_ else None)
        out.append([float(e - s) * 1e-9, names])
    return out


def main(argv=None) -> int:
    for path in (sys.argv[1:] if argv is None else argv):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            xspace = f.read()
        by_span = idle_by_span(xspace)
        print(json.dumps({"trace": path, "idle_s": sum(by_span.values()),
                          "idle_by_span": by_span,
                          "longest_gaps": longest_gaps(xspace)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
