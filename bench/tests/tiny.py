"""Cells shrunk to CPU size, holding the real cells' limits, traffic
kinds and per-layer metrics: the harness's fault and control tests drive
whole runs of them with the timed path broken underneath."""
import dataclasses
import time

import jax

from harness import serve, spec, train


def train_cell(name="w2v-text8.stream") -> spec.Cell:
    """The training cell at CPU size; its vocabulary is large enough that
    the checked steps leave some rows untouched."""
    cell = spec.load_cell(name)
    config = dict(cell.config, vocab_size=20_000, corpus_words=5_000_000,
                  w2v=dict(cell.config["w2v"], dim=32, max_sentence_len=64,
                           sentences_per_batch=64, prefetch_workers=2))
    traffic = dict(cell.traffic, run_words=40_000, line_words=1000)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def serve_cell(name="w2v-1bw.serve") -> spec.Cell:
    cell = spec.load_cell(name)
    config = dict(cell.config, vocab_size=4000,
                  w2v=dict(cell.config["w2v"], dim=32))
    traffic = dict(cell.traffic, rate_qps=200, warmup_requests=20,
                   check_sample=64)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run(cell, seed=2**31 + 17, seconds=1.0):
    drv = train if cell.traffic["kind"] == "train" else serve
    return drv.run(cell, seed, seconds, time.perf_counter(),
                   jax.devices()[:1], log=lambda *a: None)
