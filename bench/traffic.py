"""One general traffic generator, driven by a mix's data file.

A mix (``bench/traffic/<mix>.json``) sets:

* ``loop``: ``"open"`` (independent users: arrivals on a schedule,
  whatever the server does) or ``"closed"`` (``clients`` callers, each
  with one query outstanding);
* ``rate_qps`` (open loop): the offered rate;
* ``clients`` (closed loop);
* ``l_mix``: ``[[l, share], ...]``, the neighbour counts asked for;
* ``writer`` (optional): ``{"every_ms", "inserts", "deletes",
  "batches_per_cluster"}``, one write batch per period: inserts of new
  points, deletes of live ids, then ``flush_store()``.  The writer goes
  cluster by cluster (see ``Writer``).

Every seed gets the same amount of work in another order: an open loop
offers exactly ``round(rate_qps * seconds)`` queries, their arrival times
uniform order statistics over the window (a Poisson process conditioned
on its count), and the l mix is met exactly, shuffled by the seed.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
from concurrent.futures import Future
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Request:
    row: int                   # query row in the run's query pool
    l: int
    due: float                 # seconds after the window opened
    submitted: float = math.nan    # time.perf_counter() readings
    done: float = math.nan
    future: Optional[Future] = None
    result: object = None      # the server's QueryResult
    error: Optional[str] = None


@dataclasses.dataclass
class WriteBatch:
    due: float                 # seconds after the window opened
    ins_ids: np.ndarray
    ins_pts: np.ndarray
    del_ids: np.ndarray
    done: float = math.nan     # time.perf_counter() reading
    generation: int = -1
    error: Optional[str] = None


def arrivals(rng: np.random.Generator, rate: float,
             seconds: float) -> np.ndarray:
    """Sorted arrival offsets of an open loop over ``seconds``."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def l_values(rng: np.random.Generator, l_mix, n: int) -> np.ndarray:
    """``n`` neighbour counts meeting the mix's shares exactly, shuffled."""
    ls = [int(l) for l, _ in l_mix]
    shares = np.array([float(s) for _, s in l_mix])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argmax(shares)] += n - counts.sum()
    out = np.repeat(ls, counts)
    rng.shuffle(out)
    return out


def _note_done(req: Request, answered=None):
    """The done callback.  It runs on the server's thread, so it only
    notes the time (and hands the request to a caller thread)."""
    def cb(fut):
        req.done = time.perf_counter()
        if answered is not None:
            answered.put(req)
    return cb


def _submit(server, queries: np.ndarray, req: Request, answered=None):
    req.future = server.submit(queries[req.row], req.l)
    req.future.add_done_callback(_note_done(req, answered))


def collect(requests: list) -> None:
    """Read the answer or failure of every resolved request."""
    for req in requests:
        fut = req.future
        if fut is None or not fut.done() or req.result is not None:
            continue
        exc = fut.exception()
        if exc is None:
            req.result = fut.result()
        else:                       # a failed dispatch counts as failed
            req.error = f"{type(exc).__name__}: {exc}"


class OpenLoop:
    """Submits ``requests`` at their due times from one thread."""

    def __init__(self, server, queries: np.ndarray, requests: list):
        self.server = server
        self.queries = queries
        self.requests = requests
        self._thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        name="bench-open-loop")
        self._thread.start()

    def _run(self, t0: float) -> None:
        for req in self.requests:
            wait = t0 + req.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req.submitted = time.perf_counter()
            _submit(self.server, self.queries, req)

    def join(self) -> None:
        self._thread.join()


class ClosedLoop:
    """``clients`` callers; each answer submits that caller's next query
    until the window closes.  Queries cycle through the pool.

    The callers run on one thread of their own, as remote clients would
    run apart from the server: an answer's done callback only queues the
    request, and the caller thread submits the next query."""

    def __init__(self, server, queries: np.ndarray, ls: np.ndarray,
                 clients: int):
        self.server = server
        self.queries = queries
        self.ls = ls
        self.clients = clients
        self.requests: list = []
        self._lock = threading.Lock()
        self._answered: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._t0 = 0.0
        self._t_end = 0.0

    def start(self, t0: float, seconds: float) -> None:
        self._t0, self._t_end = t0, t0 + seconds
        for _ in range(self.clients):
            self._submit(t0)
        self._thread = threading.Thread(target=self._run,
                                        name="bench-closed-loop")
        self._thread.start()

    def _submit(self, due: float) -> None:
        with self._lock:
            row = len(self.requests) % len(self.queries)
            req = Request(row=row, l=int(self.ls[row]), due=due - self._t0,
                          submitted=time.perf_counter())
            self.requests.append(req)
        _submit(self.server, self.queries, req, self._answered)

    def _run(self) -> None:
        while True:
            req = self._answered.get()
            if req is None:
                return
            if req.done < self._t_end:
                self._submit(req.done)

    def join(self) -> None:
        """Stop the callers; answers after this submit nothing new."""
        self._answered.put(None)
        self._thread.join()

    def outstanding(self) -> int:
        with self._lock:
            return sum(1 for r in self.requests if math.isnan(r.done))


class Writer:
    """Write batches on a fixed period from one thread, cluster by
    cluster.

    As in the streaming track's clustered runbook, inserts and deletes go
    cluster by cluster, so the live set's distribution shifts during a
    run.  Batch ``k`` inserts ``inserts`` new points around the centre
    ``order[(k // per_cluster) % C]`` (the pool is made so) and deletes
    the ``deletes`` oldest live ids around the centre half an order
    further on, moving to the next centre when one runs out.

    The writer owns the ids: the store is loaded with ids ``0 .. n-1``
    and every insert takes the next unused id.  A delete takes only ids
    that were live after the previous batch, so no dead id is ever
    deleted, and the live count never changes when inserts == deletes.
    """

    def __init__(self, server, pool: np.ndarray, pool_labels: np.ndarray,
                 base_labels: np.ndarray, order: np.ndarray,
                 every_ms: float, inserts: int, deletes: int,
                 per_cluster: int):
        self.server = server
        self.pool = pool
        self.pool_labels = pool_labels
        self.every = every_ms / 1e3
        self.inserts = inserts
        self.deletes = deletes
        self.per_cluster = per_cluster
        self.delete_order = np.roll(order, -(len(order) // 2))
        # live ids per centre, oldest first
        by = np.argsort(base_labels, kind="stable")
        cuts = np.searchsorted(base_labels[by], np.arange(len(order) + 1))
        self.queues = [collections.deque([by[cuts[c]:cuts[c + 1]]])
                       for c in range(len(order))]
        self.n_live = len(base_labels)
        self.next_id = len(base_labels)
        self.made = 0
        self.batches: list = []
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def insert_labels(order: np.ndarray, batches: int, inserts: int,
                      per_cluster: int) -> np.ndarray:
        """The centre of each point of the insert pool, batch by batch."""
        k = np.arange(batches)
        return np.repeat(order[(k // per_cluster) % len(order)], inserts)

    def live_ids(self) -> np.ndarray:
        return np.concatenate([a for q in self.queues for a in q])

    def _take(self, c: int, n: int) -> list:
        """Up to ``n`` of centre ``c``'s oldest live ids."""
        out, q = [], self.queues[c]
        while n and q:
            a = q[0]
            k = min(n, len(a))
            out.append(a[:k])
            n -= k
            if k == len(a):
                q.popleft()
            else:
                q[0] = a[k:]
        return out

    def make_batch(self, due: float) -> WriteBatch:
        k = self.made
        s = k * self.inserts
        pts = self.pool[s:s + self.inserts]
        if len(pts) < self.inserts:
            raise RuntimeError("write pool exhausted")
        first = (k // self.per_cluster) % len(self.delete_order)
        gone, need = [], self.deletes
        for j in range(len(self.delete_order)):
            c = self.delete_order[(first + j) % len(self.delete_order)]
            got = self._take(c, need)
            gone += got
            need -= sum(len(a) for a in got)
            if not need:
                break
        if need:
            raise RuntimeError("no live ids left to delete")
        gone = np.concatenate(gone).astype(np.int64)
        ids = np.arange(self.next_id, self.next_id + self.inserts)
        labels = self.pool_labels[s:s + self.inserts]
        for c in np.unique(labels):
            self.queues[c].append(ids[labels == c])
        self.next_id += self.inserts
        self.n_live += self.inserts - self.deletes
        self.made += 1
        return WriteBatch(due=due, ins_ids=ids, ins_pts=pts, del_ids=gone)

    def apply(self, batch: WriteBatch) -> None:
        try:
            self.server.insert(batch.ins_pts, ids=batch.ins_ids)
            self.server.delete(batch.del_ids)
            batch.generation = self.server.flush_store()
        except Exception as e:
            batch.error = f"{type(e).__name__}: {e}"
        batch.done = time.perf_counter()
        self.batches.append(batch)

    def start(self, t0: float, seconds: float) -> None:
        self._thread = threading.Thread(target=self._run,
                                        args=(t0, seconds),
                                        name="bench-writer")
        self._thread.start()

    def _run(self, t0: float, seconds: float) -> None:
        for k in range(int(math.ceil(seconds / self.every))):
            due = k * self.every
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.apply(self.make_batch(due))

    def join(self) -> None:
        self._thread.join()
