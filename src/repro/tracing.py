"""Host spans and counters of the program, on the profiler's clock.

``span(name, key=None)`` times a stretch of host code. It enters a
``jax.profiler.TraceAnnotation`` (its key as a ``key`` stat), so a
profiler trace shows the span on its thread's line of the host plane, on
the same clock as the device's operations, and on exit it appends one
:class:`Record` to a bounded ring
kept per name. ``count(name, n)`` adds to a counter the same way, one
record per call. ``interval`` records a stretch that starts on one thread
and ends on another (a request waiting in a queue): it goes to the ring
only, since a trace annotation cannot cross threads.

A record's ``key`` names the unit of work it belongs to: a training batch
is ``(epoch, index)``, a request its id, a server batch its id. A span or
count given no key takes the key of the innermost span open on its
thread, and its ``parent`` is that span's id. ``recent`` and ``keyed``
read the rings.

Tracing is always on: a span costs a few microseconds, and the program
records a few per batch or request. No span stays open across a
``yield``: a generator times the work between its yields. Records made
in a worker process stay in that process's rings.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, Hashable, Iterable, List, Optional

import jax

# records kept per name: a few windows of batches or requests
RING = 4096


class Record:
    """One closed span or one count. ``value`` is the span's seconds or
    the amount counted; ``start_ns``/``end_ns`` are ``perf_counter_ns``
    (equal for a count)."""

    __slots__ = ("name", "key", "thread", "start_ns", "end_ns", "id",
                 "parent", "value", "attrs")

    def __init__(self, name, key, thread, start_ns, end_ns, id_, parent,
                 value, attrs):
        self.name = name
        self.key = key
        self.thread = thread
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.id = id_
        self.parent = parent
        self.value = value
        self.attrs = attrs

    def __repr__(self) -> str:
        return (f"Record({self.name!r}, key={self.key!r}, "
                f"value={self.value!r}, id={self.id}, "
                f"parent={self.parent}, attrs={self.attrs!r})")


class Span:
    """An open span: the ``with`` block may set ``key`` and ``attrs``
    before it closes (a key known only once the work is done); after it
    closes, ``seconds`` is its duration."""

    __slots__ = ("_tracer", "name", "key", "attrs", "id", "parent",
                 "start_ns", "end_ns", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, key, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.key = key
        self.attrs = attrs
        self.id = 0
        self.parent: Optional[int] = None
        self.start_ns = self.end_ns = 0
        self._annotation = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        tracer = self._tracer
        self.key, self.parent = tracer._inherit(self.key)
        self.id = next(tracer._ids)
        tracer._stack().append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        key=str(self.key))
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._tracer._stack().pop()
        self._tracer._append(Record(
            self.name, self.key, threading.get_ident(), self.start_ns,
            self.end_ns, self.id, self.parent, self.seconds, self.attrs))


class Tracer:
    """The rings of one process: one ``deque(maxlen=ring)`` per name."""

    def __init__(self, ring: int = RING):
        self.ring = ring
        self._rings: Dict[str, Deque[Record]] = {}
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, rec: Record) -> None:
        with self._lock:
            ring = self._rings.get(rec.name)
            if ring is None:
                ring = self._rings[rec.name] = collections.deque(
                    maxlen=self.ring)
            ring.append(rec)

    def _inherit(self, key):
        """(key, parent id): the innermost open span's where not given."""
        stack = self._stack()
        if not stack:
            return key, None
        top = stack[-1]
        return (top.key if key is None else key), top.id

    def span(self, name: str, key: Hashable = None, **attrs) -> Span:
        """A span over a ``with`` block (see the module docstring)."""
        return Span(self, name, key, attrs)

    def count(self, name: str, n: float, key: Hashable = None,
              **attrs) -> None:
        """Add ``n`` to counter ``name``, as one record."""
        key, parent = self._inherit(key)
        now = time.perf_counter_ns()
        self._append(Record(name, key, threading.get_ident(), now, now,
                            next(self._ids), parent, n, attrs))

    def interval(self, name: str, start_ns: int, end_ns: int,
                 key: Hashable = None, **attrs) -> None:
        """Record a span that began at ``start_ns`` (``perf_counter_ns``,
        maybe on another thread) and ends at ``end_ns``."""
        key, parent = self._inherit(key)
        self._append(Record(name, key, threading.get_ident(), start_ns,
                            end_ns, next(self._ids), parent,
                            (end_ns - start_ns) / 1e9, attrs))

    def recent(self, name: str, n: Optional[int] = None,
               thread: Optional[int] = None) -> List[Record]:
        """The newest ``n`` records of ``name`` (all the ring holds when
        ``n`` is None), oldest first; with ``thread``, only the records
        made on that thread (``threading.get_ident()``)."""
        if n is not None and n <= 0:
            return []
        with self._lock:
            ring = self._rings.get(name, ())
            if thread is None:
                recs = list(ring)
            else:   # newest first, stopping at n: a thread's last record
                recs = []
                for r in reversed(ring):
                    if r.thread == thread:
                        recs.append(r)
                        if len(recs) == n:
                            break
                recs.reverse()
        return recs if n is None else recs[-n:]

    def keyed(self, name: str, keys: Iterable[Hashable]
              ) -> Dict[Hashable, float]:
        """The values of ``name``'s records summed by key, for the keys
        given that the ring holds: seconds of a span, amounts of a
        counter."""
        want = set(keys)
        out: Dict[Hashable, float] = {}
        for r in self.recent(name):
            if r.key in want:
                out[r.key] = out.get(r.key, 0.0) + r.value
        return out


_tracer = Tracer()
span = _tracer.span
count = _tracer.count
interval = _tracer.interval
recent = _tracer.recent
keyed = _tracer.keyed
