"""Faults of the host pipeline, planted in the program under test for the
calibration and the harness's tests; the benchmark's own runs never use
them. Each is a context manager that breaks one guarantee the
configuration states and restores the program on exit."""
from __future__ import annotations

import contextlib


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
