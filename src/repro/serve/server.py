"""Request batcher in front of the jitted sharded top-k.

:class:`EmbeddingServer` coalesces individual neighbour/analogy requests
into fixed-size padded batches — the serving analogue of the training
kernel's minibatching: one device dispatch amortizes the table sweep
over the whole batch, and a *fixed* batch shape means the jitted
:func:`~repro.serve.query.make_topk_fn` compiles once per
``(placement, mode, k)`` and never again.

Batch-cut policy (DESIGN.md §10): a batch closes when it reaches
``batch_size`` query rows **or** ``deadline_ms`` after its first request
arrived, whichever comes first — bounded latency under light traffic,
full batches under heavy. Requests of different kinds (nn vs analogy)
never share a device call; a kind change closes the batch and the odd
request carries into the next one.

Snapshot discipline: the dispatcher takes **one** index reference per
batch, so every query in a batch is answered from a single coherent
snapshot even while :class:`~repro.serve.snapshot.SnapshotWatcher` flips
the pointer underneath. Each result records ``snapshot_step`` — the
chaos harness's torn-query check recomputes the oracle for that exact
step.

``close()`` drains the queue before the dispatcher exits: a request
accepted by :meth:`submit` is always answered (zero dropped queries);
requests arriving *after* close raise immediately instead of hanging.

Tracing (``repro.tracing``): each request's wait from submit to its
batch's top-k call is one ``repro.server.queue`` interval keyed by the
request's id and carrying its batch's id; each batch's co-batching wait,
top-k call (to the results on the host) and hand-back are the spans
``repro.server.collect``, ``repro.server.topk`` and
``repro.server.resolve``, keyed by the batch's id. The top-k span
carries ``ranked``, the candidates a shard's final sort ranks per query
row (``make_topk_fn``'s ``fn.ranked``; None for a top-k without it).
"""
from __future__ import annotations

import dataclasses
import itertools
import logging
import queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import tracing
from repro.serve.index import EmbeddingIndex
from repro.serve.query import make_topk_fn

log = logging.getLogger("repro.serve.server")

# process-wide, so trace keys of different servers never collide
_REQUEST_IDS = itertools.count()
_BATCH_IDS = itertools.count()


@dataclasses.dataclass
class QueryResult:
    """One answered request: global-id/score top-k plus provenance."""

    ids: np.ndarray                 # (n, k) int32 global vocabulary ids
    scores: np.ndarray              # (n, k) f32 cosine scores
    snapshot_step: Optional[int]    # checkpoint step that answered it
    latency_us: float               # submit -> resolve wall time


class _Request:
    __slots__ = ("id", "kind", "ids", "k", "t0_ns", "t0", "event",
                 "result", "error")

    def __init__(self, kind: str, ids: np.ndarray, k: int):
        self.id = next(_REQUEST_IDS)
        self.kind = kind
        self.ids = ids
        self.k = k
        self.t0_ns = time.perf_counter_ns()
        self.t0 = self.t0_ns / 1e9          # perf_counter seconds
        self.event = threading.Event()
        self.result: Optional[QueryResult] = None
        self.error: Optional[BaseException] = None

    def resolve(self, result: QueryResult) -> None:
        self.result = result
        self.event.set()

    def fail(self, exc: BaseException) -> None:
        self.error = exc
        self.event.set()

    def wait(self, timeout: Optional[float]) -> QueryResult:
        if not self.event.wait(timeout):
            raise TimeoutError("query not answered in time")
        if self.error is not None:
            raise self.error
        assert self.result is not None
        return self.result


class EmbeddingServer:
    """Deadline/max-batch query coalescer over a (possibly hot-swapped)
    :class:`EmbeddingIndex`.

    Parameters
    ----------
    source : an :class:`EmbeddingIndex` (static snapshot) or anything
        with a ``current() -> EmbeddingIndex`` method (a
        :class:`~repro.serve.snapshot.SnapshotWatcher` for live serving).
    batch_size : padded device batch — also the per-request row cap.
    deadline_ms : max time the first request in a batch waits for
        co-riders before the batch is cut short.
    k : neighbours returned per query (fixed per server: one compiled
        kernel per mode).
    """

    def __init__(self, source, batch_size: int = 32,
                 deadline_ms: float = 2.0, k: int = 5):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        self._source = source
        self.batch_size = int(batch_size)
        self.deadline_s = float(deadline_ms) / 1e3
        self.k = int(k)
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._carry: Optional[_Request] = None
        self._fns: Dict[Tuple, object] = {}   # (placement, mode) -> jitted fn
        self._closed = False
        self._lock = threading.Lock()
        self.served = 0
        self.batches = 0
        self.latencies_us: List[float] = []
        self._thread = threading.Thread(
            target=self._dispatch_loop, name="embedding-server", daemon=True)
        self._thread.start()

    # -- public API ----------------------------------------------------------
    def current_index(self) -> EmbeddingIndex:
        """The snapshot the *next* batch would be served from."""
        if isinstance(self._source, EmbeddingIndex):
            return self._source
        return self._source.current()

    def submit(self, kind: str, ids, k: Optional[int] = None) -> _Request:
        """Enqueue a request; returns a waitable handle. ``ids`` is
        ``(n,)`` for ``kind="nn"``, ``(n, 3)`` rows ``(a, b, c)`` for
        ``kind="analogy"``; ``n <= batch_size``."""
        if kind not in ("nn", "analogy"):
            raise ValueError(f"unknown query kind {kind!r} (nn | analogy)")
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if kind == "analogy":
            ids = ids.reshape(-1, 3)
        n = ids.shape[0]
        if n < 1 or n > self.batch_size:
            raise ValueError(
                f"request has {n} queries; allowed 1..{self.batch_size}")
        k = self.k if k is None else int(k)
        if k > self.k:
            raise ValueError(f"k={k} exceeds server k={self.k}")
        req = _Request(kind, ids, k)
        with self._lock:
            if self._closed:
                raise RuntimeError("server is closed")
            self._queue.put(req)
        return req

    def neighbors(self, ids, k: Optional[int] = None,
                  timeout: float = 60.0) -> QueryResult:
        """Synchronous nearest-neighbour query for global ids ``(n,)``."""
        return self.submit("nn", ids, k=k).wait(timeout)

    def analogy(self, triples, k: Optional[int] = None,
                timeout: float = 60.0) -> QueryResult:
        """Synchronous ``a − b + c`` analogy query for rows ``(n, 3)``."""
        return self.submit("analogy", triples, k=k).wait(timeout)

    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting requests, answer everything already accepted,
        then stop the dispatcher — zero dropped queries by construction."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._thread.join(timeout=timeout)

    def __enter__(self) -> "EmbeddingServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- dispatcher ----------------------------------------------------------
    def _take_first(self) -> Optional[_Request]:
        if self._carry is not None:
            first, self._carry = self._carry, None
            return first
        try:
            return self._queue.get(timeout=0.01)
        except queue.Empty:
            return None

    def _collect_batch(self) -> Optional[Tuple[int, List[_Request]]]:
        """Block for a first request, then co-batch same-kind arrivals
        until the row budget or the deadline runs out. Returns the new
        batch's id and its requests."""
        first = self._take_first()
        if first is None:
            return None
        with tracing.span("repro.server.collect",
                          key=next(_BATCH_IDS)) as sp:
            batch, rows = [first], first.ids.shape[0]
            deadline = first.t0 + self.deadline_s
            while rows < self.batch_size:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if (nxt.kind != first.kind
                        or rows + nxt.ids.shape[0] > self.batch_size):
                    self._carry = nxt          # rides the next batch
                    break
                batch.append(nxt)
                rows += nxt.ids.shape[0]
        return sp.key, batch

    def _fn_for(self, index: EmbeddingIndex, mode: str):
        key = (index.placement, mode, self.k, self.batch_size)
        fn = self._fns.get(key)
        if fn is None:
            fn = make_topk_fn(index.placement, index.mesh, mode=mode,
                              k=self.k)
            self._fns[key] = fn
        return fn

    def _serve_batch(self, batch_id: int, batch: List[_Request]) -> None:
        index = self.current_index()       # ONE snapshot for the batch
        kind = batch[0].kind
        ids = np.concatenate([r.ids for r in batch], axis=0)
        n = ids.shape[0]
        pad = self.batch_size - n
        if pad:                            # fixed shape: compile once
            fill = np.zeros((pad,) + ids.shape[1:], np.int32)
            ids = np.concatenate([ids, fill], axis=0)
        fn = self._fn_for(index, kind)
        with tracing.span("repro.server.topk", key=batch_id,
                          requests=len(batch), rows=n,
                          ranked=getattr(fn, "ranked", None)) as sp:
            out_ids, out_scores = fn(index.hot, index.cold, ids)
            out_ids = np.asarray(out_ids)[:n]
            out_scores = np.asarray(out_scores)[:n]
        now = time.perf_counter()
        for r in batch:
            tracing.interval("repro.server.queue", r.t0_ns, sp.start_ns,
                             key=r.id, batch=batch_id)
        self.batches += 1
        with tracing.span("repro.server.resolve", key=batch_id):
            off = 0
            for r in batch:
                m = r.ids.shape[0]
                lat = (now - r.t0) * 1e6
                r.resolve(QueryResult(
                    ids=out_ids[off:off + m, :r.k],
                    scores=out_scores[off:off + m, :r.k],
                    snapshot_step=index.step, latency_us=lat))
                off += m
                self.served += m
                self.latencies_us.append(lat)

    def _dispatch_loop(self) -> None:
        while True:
            got = self._collect_batch()
            if got is None:
                if self._closed and self._carry is None \
                        and self._queue.empty():
                    return                 # drained: safe to exit
                continue
            batch_id, batch = got
            try:
                self._serve_batch(batch_id, batch)
            except BaseException as e:  # noqa: BLE001 — fail the batch,
                for r in batch:             # never strand its futures
                    r.fail(e)
                log.exception("batch of %d %s queries failed",
                              len(batch), batch[0].kind)
