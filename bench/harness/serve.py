"""Serving cells: open-loop nearest-neighbour queries against an
``EmbeddingServer``.

The table's rows are made on the device from the seed (word2vec's
initialisation, not normalised), split by the program's own placement
(``VocabPlacement.plan`` with ``SERVE_HOT_FRAC``, one shard) and staged
by the program (``EmbeddingIndex._stage``: placed on the device and
normalised there), as it stages a snapshot it serves. Single-id queries,
their ids drawn from the configuration's Zipf law, arrive open-loop at
the mix's fixed rate with exponential gaps
(``openloop.exponential_schedule``: the same gaps for every seed, dealt
into blocks of ``arrival_block_s`` of equal load, in the seed's order);
the generator submits each when it is due, late or not, and each
request's latency runs from its due time. After the window the harness
waits for every request (``drain_s`` at most), then compares a sample of
the answers, drawn from the seed, with the plain dense top-k.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import device, openloop, reference, trace, zipf


def make_index(config: dict, seed: int, devices):
    from jax.sharding import Mesh

    from repro.distributed.vocab_placement import VocabPlacement
    from repro.serve.index import SERVE_HOT_FRAC, EmbeddingIndex

    v, d = int(config["vocab_size"]), int(config["w2v"]["dim"])
    counts = zipf.ZipfLaw(v, config["zipf_exponent"]).counts(
        int(config["corpus_words"]))
    placement = VocabPlacement.plan(counts, n_shards=1,
                                    hot_frac=SERVE_HOT_FRAC)
    rows = np.asarray(reference.serve_rows(
        jax.random.PRNGKey(zipf.program_seed(seed)), v, d))
    hot, cold = placement.split(rows)
    del rows
    return EmbeddingIndex._stage(
        placement, hot, cold, Mesh(np.array(devices[:1]), ("data",)),
        step=0)


def drive(server, ids: np.ndarray, due: np.ndarray, t0: float):
    """Submit request i at ``t0 + due[i]`` (at once when late). Returns
    the handles and how late each submit was."""
    handles, late = [], np.empty(len(due))
    for i, (q, t) in enumerate(zip(ids, due)):
        wait = t0 + t - time.perf_counter()
        if wait > 0:
            with trace.span("bench.idle"):
                time.sleep(wait)
        late[i] = time.perf_counter() - (t0 + t)
        handles.append(server.submit("nn", q[None]))
    return handles, late


def collect(handles, deadline: float):
    """Completion time (perf_counter) and answer of each request; NaN and
    None for one that failed or did not come back by ``deadline``."""
    done = np.full(len(handles), np.nan)
    answers = [None] * len(handles)
    for i, h in enumerate(handles):
        try:
            res = h.wait(max(0.0, deadline - time.perf_counter()))
        except Exception:  # noqa: BLE001 - a failed request is missing
            continue
        # the server times each request from its submit (h.t0)
        done[i] = h.t0 + res.latency_us / 1e6
        answers[i] = res
    return done, answers


def compare(table, ids, answers, k: int) -> dict:
    """The numbers that decide ``correct``, over the sampled answers:

    * ``rank_gap``: the widest gap by which a served neighbour's exact
      score lies below the exact score of the reference's neighbour at the
      same rank;
    * ``score_err``: the largest difference between a served score and
      the exact score of the id it was served for;
    * ``bad_ids``: served ids that are out of range, repeated within an
      answer or the query itself.
    """
    v = table.shape[0]
    served = np.stack([a.ids[0] for a in answers]).astype(np.int64)
    scores = np.stack([a.scores[0] for a in answers])
    bad = (served < 0) | (served >= v) | (served == ids[:, None])
    bad |= np.array([[x in row[:j] for j, x in enumerate(row)]
                     for row in served])
    _, ref_s = reference.dense_topk(table, ids, k)
    exact = reference.pair_scores(table, ids, np.clip(served, 0, v - 1))
    return {"rank_gap": float(np.max(ref_s - exact)),
            "score_err": float(np.max(np.abs(scores - exact))),
            "bad_ids": float(bad.sum())}


def run(cell, seed: int, seconds: float, t_process: float, devices,
        trace_dir=None, log=print, rate: float = None) -> dict:
    from repro.serve import EmbeddingServer

    clock = device.CompileClock()
    config, traffic = cell.config, cell.traffic
    rate = float(rate or traffic["rate_qps"])
    index = make_index(config, seed, devices)
    law = zipf.ZipfLaw(int(config["vocab_size"]), config["zipf_exponent"])
    block_s = float(traffic["arrival_block_s"])
    due = openloop.exponential_schedule(zipf.rng_for(seed, zipf.ARRIVAL_TAG),
                                        rate, seconds, block_s)
    ids = law.draw(zipf.rng_for(seed, zipf.QUERY_TAG), len(due))
    n_warm = int(traffic["warmup_requests"])
    warm_due = openloop.exponential_schedule(
        zipf.rng_for(seed + 1, zipf.ARRIVAL_TAG), rate, n_warm / rate,
        block_s)
    warm_ids = law.draw(zipf.rng_for(seed + 1, zipf.QUERY_TAG),
                        len(warm_due))
    k = int(traffic["k"])
    server = EmbeddingServer(index, batch_size=int(traffic["batch_size"]),
                             deadline_ms=float(traffic["deadline_ms"]), k=k)
    try:
        # warm-up at the cell's rate: compiles the one batch shape
        handles, _ = drive(server, warm_ids, warm_due, time.perf_counter())
        collect(handles, time.perf_counter() + float(traffic["drain_s"]))
        setup_s = time.perf_counter() - t_process
        log(f"set-up: {setup_s} s, {len(warm_due)} warm-up requests, "
            f"compiles {clock.count} ({clock.seconds} s)")
        compiles0 = clock.count
        served0, batches0 = server.served, server.batches
        if trace_dir:
            trace.start(trace_dir)
        with trace.span(trace.WINDOW):
            t0 = time.perf_counter()
            handles, late = drive(server, ids, due, t0)
            with trace.span("bench.drain"):
                done, answers = collect(
                    handles, t0 + seconds + float(traffic["drain_s"]))
            t1 = time.perf_counter()
        if trace_dir:
            trace.stop()
        served, batches = server.served - served0, server.batches - batches0
    finally:
        server.close(timeout=float(traffic["drain_s"]))
    memory = device.peak_bytes(devices)
    lat = openloop.latencies(t0 + due, done)
    failed = int(np.isnan(done).sum())
    log(f"window: {len(due)} requests due in {seconds} s at {rate}/s, "
        f"{batches} batches, {failed} failed or unanswered; generator "
        f"lateness p50 {np.median(late)} s, max {late.max()} s; compiles "
        f"inside the window: {clock.count - compiles0}")
    rec = {"kind": "serve", "window_s": t1 - t0, "chips": len(devices),
           "config": config, "traffic": traffic, "queries": len(due),
           "served": served, "batches": batches,
           "p95_ms": openloop.percentile(lat, 95) * 1e3}
    del index
    table = reference.serve_table(
        jax.random.PRNGKey(zipf.program_seed(seed)),
        int(config["vocab_size"]), int(config["w2v"]["dim"]))
    ok = np.flatnonzero(~np.isnan(done))
    pick = np.sort(zipf.rng_for(seed, zipf.SAMPLE_TAG).choice(
        ok, min(len(ok), int(traffic["check_sample"])), replace=False))
    numbers = compare(table, ids[pick], [answers[i] for i in pick], k)
    numbers["unanswered"] = float(failed)
    e2e = {"query_p50_ms": openloop.percentile(lat, 50) * 1e3,
           "query_p95_ms": openloop.percentile(lat, 95) * 1e3}
    return {"setup_s": setup_s, "e2e": e2e, "attempted": len(due),
            "failed": failed, "numbers": numbers, "rec": rec,
            "memory_peak_bytes": memory, "latencies": lat}
