"""The per-layer metrics that read the program's own spans and counters
(``repro.tracing``), scoped to the measured window.

* Training: the window's steps are the session's newest
  ``len(rec["steps"])`` ``repro.session.step`` records (set-up ends before
  the window, and the harness closes ``stream()`` right after it). The
  pipeline's records are joined to them by batch key ``(epoch, index)``,
  not by recency: batches finalized ahead, past the window's end, do not
  count.
* Serving: the window's batches are the server's newest ``rec["batches"]``
  ``repro.server.topk`` records; their requests are the
  ``repro.server.queue`` records that carry those batch ids.

A reader returns None where the program has no such records (a program
without ``repro.tracing``) or the rings hold fewer than the window has.
"""
from __future__ import annotations

import statistics


def _tracing():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def window_steps(rec):
    """The ``repro.session.step`` records of the window's steps, or
    None."""
    tr = _tracing()
    n = len(rec["steps"]) if rec["kind"] == "train" else 0
    if tr is None or n == 0:
        return None
    steps = tr.recent("repro.session.step", n)
    return steps if len(steps) == n else None


def _per_step(rec, names):
    """Per window step, the summed values of ``names``' records keyed by
    its batch; None unless every step has a record of every name."""
    steps = window_steps(rec)
    if steps is None:
        return None
    keys = [s.key for s in steps]
    tr = _tracing()
    totals = dict.fromkeys(keys, 0.0)
    for name in names:
        got = tr.keyed(name, keys)
        if len(got) != len(keys):
            return None
        for k, v in got.items():
            totals[k] += v
    return [totals[k] for k in keys]


def mean_ms(rec, *names):
    """The mean, over the window's steps, of the summed seconds of the
    spans ``names`` keyed by each step's batch, in milliseconds."""
    per = _per_step(rec, names)
    return None if per is None else 1e3 * statistics.fmean(per)


def per_word(rec, counter: str):
    """Counter ``counter`` over the window's batches, per real word they
    hold."""
    per = _per_step(rec, [counter])
    if per is None:
        return None
    words = sum(s["words"] for s in rec["steps"])
    return sum(per) / words if words > 0 else None


def window_batches(rec):
    """The ``repro.server.topk`` records of the window's batches, or
    None."""
    tr = _tracing()
    n = rec["batches"] if rec["kind"] == "serve" else 0
    if tr is None or n <= 0:
        return None
    batches = tr.recent("repro.server.topk", n)
    return batches if len(batches) == n else None


def topk_ms(rec):
    """The median ``repro.server.topk`` span of the window's batches, in
    milliseconds."""
    batches = window_batches(rec)
    if batches is None:
        return None
    return 1e3 * statistics.median(b.value for b in batches)


def queue_ms(rec):
    """The median ``repro.server.queue`` interval of the window's
    requests, in milliseconds."""
    batches = window_batches(rec)
    if batches is None:
        return None
    ids = {b.key for b in batches}
    waits = [q.value for q in _tracing().recent("repro.server.queue")
             if q.attrs.get("batch") in ids]
    if len(waits) != sum(b.attrs["requests"] for b in batches):
        return None
    return 1e3 * statistics.median(waits)
