"""Paper Table 1 analogue: host batching speed in words/sec (vocab encode +
subsample + pack + negative pre-sampling, no device work).

Rows use ``BatchingPipeline.stats``, which clocks *steady-state batching
only* — the timer starts at the first batch, so vocab/alias construction
never dilutes words/sec. The async rows exercise
``data/prefetch.py::AsyncBatchingPipeline`` with the same seed and record
the speedup, the bounded-queue depth profile, and a bitwise-match witness
against the synchronous stream (1.0 = every batch identical).
"""
from __future__ import annotations

import dataclasses
import os
from typing import List, Optional

import numpy as np

from benchmarks.common import bench_cfg, fmt_row
from repro.data.batching import BatchingPipeline
from repro.data.corpus import synthetic_zipf_corpus
from repro.data.prefetch import AsyncBatchingPipeline

# modest parallelism: CI runners have 2-4 cores; more workers than cores
# only adds contention to the numbers
BENCH_WORKERS = max(2, min(4, os.cpu_count() or 2))


def _consume(pipe: BatchingPipeline, epoch: int = 0,
             reference: Optional[list] = None):
    """Drain one epoch; returns (batches, words_per_sec, n_batches,
    bitwise_match_vs_reference)."""
    batches = list(pipe.batches(pad_len=64, epoch=epoch))
    match = 1.0
    if reference is not None:
        match = float(len(batches) == len(reference) and all(
            np.array_equal(a.tokens, b.tokens)
            and np.array_equal(a.negs, b.negs)
            and np.array_equal(a.lengths, b.lengths)
            for a, b in zip(batches, reference)))
    return batches, pipe.stats.words_per_sec, len(batches), match


def run() -> List[str]:
    cfg = bench_cfg(sentences_per_batch=512)
    # ~24 batches: long enough to amortize pool start-up and measure the
    # pipelines in steady state
    corpus = synthetic_zipf_corpus(vocab_size=20_000, n_sentences=12_288,
                                   mean_len=24, seed=0)
    # one vocab for every pipeline: the rows measure batching, not build
    vocab = BatchingPipeline(corpus, cfg).vocab

    rows = []
    sync = BatchingPipeline(corpus, cfg, vocab=vocab)
    ref, wps_sync, n, _ = _consume(sync)
    rows.append(fmt_row(
        "batching/standard", sync.stats.seconds / n * 1e6,
        f"words_per_sec={wps_sync:.0f}"))

    cfg_pack = dataclasses.replace(cfg, ignore_delimiters=True)
    packed = BatchingPipeline(corpus, cfg_pack, vocab=vocab)
    _, wps_pack, n_pack, _ = _consume(packed)
    rows.append(fmt_row(
        "batching/stream_packed", packed.stats.seconds / n_pack * 1e6,
        f"words_per_sec={wps_pack:.0f}"))

    for mode in ("thread", "process"):
        apipe = AsyncBatchingPipeline(corpus, cfg, vocab=vocab,
                                      workers=BENCH_WORKERS, depth=4,
                                      mode=mode)
        _, wps, n_async, match = _consume(apipe, reference=ref)
        rows.append(fmt_row(
            f"batching/async_{mode}", apipe.stats.seconds / n_async * 1e6,
            f"words_per_sec={wps:.0f} "
            f"speedup_vs_sync={wps / max(wps_sync, 1e-9):.2f} "
            f"workers={BENCH_WORKERS} "
            f"max_in_flight={apipe.prefetch.max_in_flight} "
            f"bitwise_match_sync={match:.0f}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
