"""Faults planted in the program under test for the calibration and the
harness's tests; the benchmark's own runs never use them. Each is a
context manager that breaks one guarantee the configuration states and
restores the program on exit: the host pipeline's (``PIPELINE``) and a
training step's write outside the rows it trains (``STEP``)."""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    real = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield real
    finally:
        setattr(obj, name, real)


@contextlib.contextmanager
def subsampling_off():
    """Every word kept, whatever ``subsample_t`` says."""
    from repro.data.vocab import Vocab
    with _patched(Vocab, "subsample_ids", lambda self, ids, t, rng: ids):
        yield


@contextlib.contextmanager
def negatives_unigram1():
    """Negatives drawn from the unigram law, not unigram^0.75."""
    from repro.data.vocab import Vocab
    with _patched(Vocab, "unigram_weights",
                  lambda self, power=0.75: self.counts.astype(float)):
        yield


@contextlib.contextmanager
def token_altered():
    """One token of every batch altered where the pipeline produces it."""
    from repro.data import batching, prefetch
    real = prefetch.finalize_packed

    def altered(packed, cfg, sampler, *args, **kwargs):
        batch = real(packed, cfg, sampler, *args, **kwargs)
        batch.tokens[0, 1] = (batch.tokens[0, 1] + 1) % sampler.vocab
        return batch
    with _patched(prefetch, "finalize_packed", altered), \
            _patched(batching, "finalize_packed", altered):
        yield


PIPELINE = {"fault_subsampling_off": subsampling_off,
            "fault_negatives_unigram1": negatives_unigram1,
            "fault_token_produced_altered": token_altered}


def _step_ids(tables, step) -> np.ndarray:
    """The distinct ids of a step's real tokens and their negatives, in
    the table's ids (a vocab-sharded step carries them remapped into each
    shard's working rows: the hot head, then the shard's cold requests)."""
    tok, neg, ln = (np.asarray(x) for x in (step.tokens, step.negs,
                                            step.lengths))
    row, pos = np.nonzero(np.arange(tok.shape[1])[None, :] < ln[:, None])
    ids = np.concatenate([tok[row, pos], neg[row, pos].ravel()])
    pl = tables.placement
    if pl is None:
        return np.unique(ids)
    cold = np.asarray(step.cold_ids)
    shard = np.concatenate([row, np.repeat(row, neg.shape[2])]) // (
        tok.shape[0] // cold.shape[0])
    return np.unique(np.where(
        ids < pl.hot, ids, cold[shard, np.maximum(ids - pl.hot, 0)]))


def _add_to_row(tables, row: int, delta: float):
    pl = tables.placement
    if pl is None or row < pl.hot:
        return dataclasses.replace(tables, w_in=tables.w_in.at[row].add(delta))
    at = int(pl.owner_of(row) * pl.cold_per_shard + pl.local_row(row))
    return dataclasses.replace(tables,
                               cold_in=tables.cold_in.at[at].add(delta))


@contextlib.contextmanager
def stray_write(steps: int = 3):
    """At the ``steps``-th training step (the last the check compares),
    the input row of the largest id that none of the first ``steps``
    batches' real tokens or negatives touch gains 1: a write outside the
    rows the check reads."""
    from repro.kernels import ops
    real, seen = ops.step, []

    def stepped(tables, step, cfg, **kw):
        out = real(tables, step, cfg, **kw)
        if len(seen) >= steps:
            return out
        seen.append(_step_ids(tables, step))
        if len(seen) < steps:
            return out
        vocab = (tables.w_in.shape[0] if tables.placement is None
                 else tables.placement.vocab_size)
        free = np.setdiff1d(np.arange(vocab), functools.reduce(np.union1d,
                                                               seen))
        if not len(free):
            raise RuntimeError("the first steps touch every row: no row "
                               "is left to write to")
        return _add_to_row(out, int(free[-1]), 1.0)
    with _patched(ops, "step", stepped):
        yield


STEP = {"fault_stray_write": stray_write}
