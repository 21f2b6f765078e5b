"""Profiler window and the reduction from its trace to numbers.

A traced run records the measured window with ``jax.profiler`` (Python
tracer off) and reduces the ``.xplane.pb`` it writes to:

* ``busy_s``: per device, the union of the intervals in which an XLA op
  ran ("XLA Ops" line of each ``/device:TPU:<n>`` plane), averaged over
  the devices the cell uses;
* ``modules``: on device 0, launches and device seconds per XLA module
  ("XLA Modules" line), keyed by module name without its ``(id)``;
* ``collective_s``: on device 0, device seconds of collective ops;
* ``top_ops``: on device 0, the ops with the most device seconds;
* ``idle_gaps``: on device 0, the longest gaps between busy intervals,
  each named by the host event that overlaps it most.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP = 10
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_KIND = re.compile(r" ([a-z][a-z0-9-]*)\(")      # " copy(" after the type


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def read_xspace(log_dir: str) -> bytes:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    with open(paths[0], "rb") as f:
        return f.read()


def _events(line):
    return [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=np.float64)


def _base(name: str) -> str:
    return name.split("(")[0]


def _op(name: str) -> tuple:
    """(label, kind) of an "XLA Ops" event.  On TPU the name is the HLO
    instruction, ``%copy.3 = f32[...]{...} copy(operands), attrs``; the
    label keeps its name, shape and kind, without operands."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    m = _KIND.search(rest)
    if not m:
        return head, head.split(".")[0]
    return f"{head} = {rest[:m.end() - 1]}", m.group(1)


def _collective(name: str) -> bool:
    label, kind = _op(name)
    return kind.startswith(COLLECTIVES) or label.startswith(COLLECTIVES)


def reduce(xspace: bytes, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    devices, host = {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            devices[int(m.group(1))] = {ln.name: _events(ln)
                                        for ln in plane.lines}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend(_events(ln))
    if len(devices) != n_devices:
        raise RuntimeError(f"trace holds devices {sorted(devices)}, "
                           f"expected {n_devices}")

    def intervals(evs):
        return np.array([(s, s + d) for _, s, d in evs],
                        np.float64).reshape(-1, 2)

    busy = []
    for lines in devices.values():
        merged = union(intervals(lines.get("XLA Ops", [])))
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)

    d0 = devices[0]
    modules: dict = {}
    for name, _, dur in d0.get("XLA Modules", []):
        entry = modules.setdefault(_base(name), [0, 0.0])
        entry[0] += 1
        entry[1] += dur * 1e-9
    ops: dict = {}
    collective_s = 0.0
    for name, _, dur in d0.get("XLA Ops", []):
        label = _op(name)[0]
        ops[label] = ops.get(label, 0.0) + dur * 1e-9
        if _collective(name):
            collective_s += dur * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]

    merged = union(intervals(d0.get("XLA Ops", [])))
    gaps = np.stack([merged[:-1, 1], merged[1:, 0]], 1) if len(merged) > 1 \
        else np.zeros((0, 2))
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:TOP]
    host_iv = intervals(host)
    idle = []
    for s, e in longest:
        overlap = (np.minimum(host_iv[:, 1], e)
                   - np.maximum(host_iv[:, 0], s)) if len(host) else []
        if len(overlap) and overlap.max() > 0:
            label = f"host: {host[int(np.argmax(overlap))][0]}"
        else:
            label = "host: no event"
        idle.append([label, float(e - s) * 1e-9])
    return {
        "busy_s": float(np.mean(busy)),
        "modules": {k: {"launches": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "collective_s": collective_s,
        "top_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": idle,
    }
