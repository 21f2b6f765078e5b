"""Collector pauses, counted inside the program.

A full collection of a loaded store rescans every Python object it
holds, and halts every Python thread while it does.  One
``gc.callbacks`` hook per process turns each collection into numbers in
the registry of every subscribed :class:`~repro.obs.ObsPlane` (each
``KnnServer`` subscribes its own while it is open):

* ``runtime.gc_collections`` (counter): collections of every
  generation.  A generation-0 collection only bumps it, so the hook
  stays cheap at the collector's most frequent rate.
* ``runtime.gc_pause_s`` (histogram): one observation per collection of
  generation 1 or 2, from its ``start`` callback to its ``stop``.

A collection of generation 1 or 2 is also a ``gc`` span (attribute
``generation``): in each subscriber's ring, and once, on the collecting
thread's line, in a recording profiler session (``knn.gc``, through the
disabled tracer's profiler sink, obs/trace.py).

The hook is installed when the first plane subscribes and removed when
the last one leaves.  Stdlib only, like the rest of the plane.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref

from repro.obs.trace import NULL_TRACER


class GcHook:
    """The ``gc.callbacks`` hook and its subscribers; the process has one,
    :data:`HOOK`.  Collections never overlap, so the hook keeps the open
    collection's clock and span on itself."""

    def __init__(self):
        self._lock = threading.Lock()      # subscribe/unsubscribe only
        self._subs: dict = {}              # id(plane) -> subscriber entry
        self._view: tuple = ()             # what the callback iterates
        self._t0 = None
        self._span = None

    @property
    def installed(self) -> bool:
        return self._callback in gc.callbacks

    def subscribers(self) -> int:
        return len(self._view)

    def subscribe(self, plane) -> None:
        """Count collections into ``plane`` from now on (idempotent)."""
        reg = plane.metrics
        entry = (weakref.ref(plane), reg.counter("runtime.gc_collections"),
                 reg.histogram("runtime.gc_pause_s"))
        with self._lock:
            self._subs[id(plane)] = entry
            self._publish_locked()

    def unsubscribe(self, plane) -> None:
        """Stop counting into ``plane`` (idempotent); the last one out
        removes the hook."""
        with self._lock:
            self._subs.pop(id(plane), None)
            self._publish_locked()

    def _publish_locked(self) -> None:
        # Planes dropped without unsubscribing fall out here.
        self._subs = {k: e for k, e in self._subs.items()
                      if e[0]() is not None}
        self._view = tuple(self._subs.values())
        if self._view and not self.installed:
            gc.callbacks.append(self._callback)
        elif not self._view and self.installed:
            gc.callbacks.remove(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if gen == 0:
            if phase == "stop":
                for _, collections, _ in self._view:
                    collections.inc()
            return
        if phase == "start":
            self._span = NULL_TRACER.begin("gc", generation=gen)
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        t0, self._t0 = self._t0, None
        span, self._span = self._span, None
        if t0 is None:          # installed while this collection ran
            return
        span.end()
        for ref, collections, pauses in self._view:
            collections.inc()
            pauses.observe(t1 - t0)
            plane = ref()
            if plane is not None:
                plane.tracer.record("gc", t0, t1, generation=gen)


HOOK = GcHook()
