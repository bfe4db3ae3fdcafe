#!/usr/bin/env python3
"""Bring-up check: FULL-W2V trains on a TPU through its normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # vocab-sharded training on four chips

One chip. A seeded Zipf corpus over a 400k-row vocabulary (two f32
tables, 410 MB) trains at the paper's widths -- d=128, W=5, N=5, L=1000
(``max_sentence_len``), 10k sentences per batch -- through
``make_pipeline`` -> ``TrainSession`` -> ``train``, in three phases:

  (a) backend "auto" at T=1, which must resolve to ``pallas_pipelined``
  (b) backend "pallas" at T=1
  (c) backend "auto" at T=8, which must resolve to ``pallas_tiled``

After each phase the trained tables take one more step on a fresh
10k-sentence batch, on the chip kernel (the program the phase trained
with, timed) and on the jnp oracle (``jnp`` / ``jnp_tiled``, f32
matmuls); the two must agree within ``ATOL`` / ``RTOL``. Phase (c)'s tables are then
checkpointed, loaded into an ``EmbeddingIndex`` and served: 64
nearest-neighbour queries must return the ids ``serve.query.dense_topk``
returns.

Four chips (``--chips 4``) runs only the vocab-sharded path -- ``--tables
shards=4`` f32 with ``pallas_tiled`` (fused gather, exact ``all_to_all``
exchange) -- and its reference, ``jnp_tiled`` with the dense exchange,
on the same mesh and batches (``SHARD_SENTENCES`` per batch). The
tables must agree within the DESIGN.md §8 tolerance.

Earlier lines report resolved backends, compile seconds, words/s, the
oracle differences and device memory. The last line is one JSON object,
``{"ok": true, "device": {...}}``. With no TPU, without the repository
around it, or when any check fails, the script exits non-zero and prints
no such line. Everything runs in this one process (the host pipeline's
workers are threads), so the chip is never shared.
"""
import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

VOCAB = 400_000          # rows per table, as examples/train_100m_w2v.py
SENTENCES = 22_000       # > 2 batches of 10k after subsampling drops some
MEAN_LEN = 25            # words per sentence, One-Billion-Words-like
ZIPF_A = 1.1
SEED = 0
TIMED_BATCHES = 2        # per phase, after one warm-up batch
ATOL, RTOL = 2e-5, 1e-4  # kernel vs oracle, as the interpret-mode tests
# Four chips: the jnp_tiled reference loops over each chip's sentences one
# window tile at a time across all L=1000 positions, so a batch is cut to
# 1024 sentences (~9k words); two timed batches after the warm-up.
SHARD_SENTENCES = 1024
SHARD_BATCHES = 2
SHARD_ATOL, SHARD_RTOL = 1e-6, 1e-5   # DESIGN.md §8 parity tolerance
QUERIES, TOPK = 64, 10
CKPT_DIR = os.path.join(ROOT, ".chip_smoke")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, flush=True)


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (explicit: ``assert`` vanishes under
    ``python -O``)."""
    if not ok:
        raise AssertionError(what)


class CompileClock:
    """Seconds spent in XLA backend compiles, persistent-cache reads
    included (a cache hit replaces the compile inside the same span)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs


def paper_cfg(**kw):
    """The paper's configuration (W2VConfig defaults: d=128, W=5, N=5,
    max_sentence_len=1000, S=10k, subsample 1e-4) for one pass over a
    pre-built vocabulary; host batching runs on worker threads."""
    from repro.configs.w2v import W2VConfig
    base = dict(epochs=1, seed=SEED, prefetch_workers=4,
                prefetch_mode="thread")
    base.update(kw)
    return W2VConfig(**base)


def make_corpus(vocab_size: int, sentences: int):
    """Seeded Zipf corpus, and a vocabulary with a row for every one of
    ``vocab_size`` ids: the corpus's words by frequency, then the ids this
    sample never drew, with count 0 (rows that exist, as in a vocabulary
    built on a larger corpus, but are never sampled or hot)."""
    import numpy as np
    from repro.data.corpus import synthetic_zipf_corpus
    from repro.data.vocab import Vocab
    corpus = synthetic_zipf_corpus(vocab_size=vocab_size,
                                   n_sentences=sentences,
                                   mean_len=MEAN_LEN, zipf_a=ZIPF_A,
                                   seed=SEED)
    seen = Vocab.build(corpus.sentences, min_count=1)
    ids = dict(seen.ids)
    for w in range(vocab_size):
        ids.setdefault(w, len(ids))
    counts = np.zeros(vocab_size, np.int64)
    counts[:seen.size] = seen.counts
    return corpus, Vocab(ids=ids, counts=counts, total=seen.total)


def train_phase(label, corpus, vocab, cfg, backend, clock,
                timed_batches=TIMED_BATCHES, **session_kw):
    """Train one warm-up batch (compiles the step), then time
    ``timed_batches`` batches of a fresh ``train`` call. Returns the
    session and the compile seconds inside the timed call."""
    from repro.core.trainer import TrainSession
    from repro.data.prefetch import make_pipeline
    sess = TrainSession(make_pipeline(corpus, cfg, vocab), cfg,
                        backend=backend, **session_kw)
    c0, t0 = clock.seconds, time.perf_counter()
    sess.train(max_batches=1)
    warm = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    words0, c1 = sess.state.words_seen, clock.seconds
    sess.train(max_batches=timed_batches)
    timed_compile_s = clock.seconds - c1
    log(f"{label}: backend={sess.backend} compile_s={compile_s} "
        f"warmup_s={warm} words_per_s={sess.words_per_sec} "
        f"(train: {timed_batches} batches, "
        f"{sess.state.words_seen - words0} words, "
        f"wall_s={sess.wall_seconds}, host_wait_s={sess.fetch_seconds}, "
        f"timed_compile_s={timed_compile_s})")
    return sess, timed_compile_s


def probe_batch(corpus, vocab, cfg):
    """One batch the sessions have not trained on (epoch 1, batch 0)."""
    from repro.data.batching import BatchingPipeline
    pipe = BatchingPipeline(corpus, cfg, vocab)
    return next(pipe.batches(pad_len=cfg.resolved_pad_len, epoch=1))


def unpadded(batch, tile: int):
    """The same batch with its padding cut to the longest sentence
    (rounded up to whole tiles): every sentence, word, negative and tile
    plan entry stays. The oracle loops over all L positions of every
    sentence, and a position past the sentence's length changes nothing,
    so this gives the oracle's result at a fraction of its cost."""
    step = max(8, tile)
    pad = -(-int(batch.lengths.max()) // step) * step
    plan = batch.plan
    if plan is not None:
        nt = pad // plan.tile
        plan = dataclasses.replace(
            plan, uniq=plan.uniq[:, :nt], scatter=plan.scatter[:, :nt],
            ucount=plan.ucount[:, :nt], strict=plan.strict[:, :nt])
    return dataclasses.replace(batch, tokens=batch.tokens[:, :pad],
                               negs=batch.negs[:, :pad], plan=plan)


def _table_copy(sess):
    """Copies of the session's tables: ``ops.step`` donates its inputs,
    the session keeps its own."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.tables import Tables
    tables = Tables(w_in=jnp.copy(sess.state.w_in),
                    w_out=jnp.copy(sess.state.w_out))
    return jax.block_until_ready(tables)


def step_check(label, sess, batch, oracle):
    """One step on a whole batch from identical tables: the chip kernel
    (the program the phase trained with, already compiled; timed twice,
    words/s of the device step alone) and the jnp oracle must agree
    within ATOL/RTOL. The oracle runs with f32 matmuls: XLA's default
    TPU matmul precision is not f32."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    lr = sess.current_lr()
    step = jax.block_until_ready(batch.step_inputs(lr))
    times = []
    for _ in range(2):
        tables = _table_copy(sess)
        t0 = time.perf_counter()
        got = ops.step(tables, step, sess.cfg, backend=sess.backend)
        jax.block_until_ready((got.w_in, got.w_out))
        times.append(time.perf_counter() - t0)
    log(f"{label}: device step {batch.n_words} words in seconds {times} "
        f"-> words_per_s={batch.n_words / min(times)}")
    del step
    ref = unpadded(batch, sess.cfg.tile_windows)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        want = ops.step(_table_copy(sess), ref.step_inputs(lr), sess.cfg,
                        backend=oracle)
        jax.block_until_ready((want.w_in, want.w_out))
    ok = True
    for name, a, b, before in (("w_in", got.w_in, want.w_in, sess.state.w_in),
                               ("w_out", got.w_out, want.w_out,
                                sess.state.w_out)):
        diff = float(jnp.max(jnp.abs(a - b)))
        moved = float(jnp.max(jnp.abs(b - before)))
        within = bool(jnp.all(jnp.abs(a - b) <= ATOL + RTOL * jnp.abs(b)))
        finite = bool(jnp.all(jnp.isfinite(a)))
        log(f"{label}: {sess.backend} vs {oracle} on the whole batch "
            f"({len(batch.lengths)} sentences, {batch.n_words} words; oracle "
            f"at L={ref.tokens.shape[1]}, {time.perf_counter() - t0} s): "
            f"{name} max_abs_diff={diff} max_update={moved} "
            f"within(atol={ATOL}, rtol={RTOL})={within}")
        ok &= within and finite and moved > 0.0
    require(ok, f"{label}: {sess.backend} disagrees with {oracle}")


def serve_check(sess, seed=SEED):
    """Checkpoint -> EmbeddingIndex -> server; nearest-neighbour ids must
    equal the dense oracle's."""
    import numpy as np
    from repro.serve import EmbeddingIndex, EmbeddingServer
    from repro.serve.query import dense_topk
    path = sess.save_checkpoint()
    index = EmbeddingIndex.load(sess.ckpt_dir)
    ids = np.random.default_rng(seed).choice(
        index.vocab_size, QUERIES, replace=False).astype(np.int32)
    with EmbeddingServer(index, batch_size=QUERIES, k=TOPK) as server:
        res = server.neighbors(ids)
    want_ids, want_sc = dense_topk(index.dense_embeddings(), ids, k=TOPK)
    same = bool(np.array_equal(res.ids, want_ids))
    close = bool(np.allclose(res.scores, want_sc, atol=1e-5))
    log(f"serve: checkpoint {path} -> index vocab={index.vocab_size} "
        f"shards={index.n_shards}; {QUERIES} nn queries k={TOPK}: "
        f"id_parity={same} score_parity={close}")
    require(same and close, "served neighbours differ from dense_topk")


def native(name: str) -> bool:
    return not (name.endswith("_interpret") or name.startswith("jnp"))


# (label, backend requested, backend it must resolve to, T, oracle)
PHASES = (("a", "auto", "pallas_pipelined", 1, "jnp"),
          ("b", "pallas", "pallas", 1, "jnp"),
          ("c", "auto", "pallas_tiled", 8, "jnp_tiled"))


def one_chip(clock, phases=PHASES, vocab_size=VOCAB, sentences=SENTENCES,
             require_native=True, **cfg_kw) -> int:
    """Phases a-c and serving on the default device. Returns the number of
    devices used (1)."""
    corpus, vocab = make_corpus(vocab_size, sentences)
    cfg = paper_cfg(**cfg_kw)
    log(f"corpus: {len(corpus.sentences)} sentences, vocab={vocab.size}, "
        f"table_bytes={2 * vocab.size * cfg.dim * 4}, "
        f"L={cfg.resolved_pad_len}, S={cfg.sentences_per_batch}")
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    probes = {}
    for label, backend, expect, tile, oracle in phases:
        cfg = paper_cfg(tile_windows=tile, **cfg_kw)
        if tile not in probes:
            probes[tile] = probe_batch(corpus, vocab, cfg)
        last = label == phases[-1][0]
        ckpt = dict(ckpt_dir=CKPT_DIR, resume=False) if last else {}
        sess, timed_compile_s = train_phase(f"phase {label}", corpus, vocab,
                                            cfg, backend, clock, **ckpt)
        # every batch has the same (S, L) shape: the warm-up compiled it all
        require(timed_compile_s == 0.0,
                f"phase {label}: {timed_compile_s} s of compilation after "
                f"the warm-up")
        require(sess.backend == expect,
                f"phase {label}: resolved {sess.backend}, expected {expect}")
        require(native(sess.backend) or not require_native,
                f"phase {label}: {sess.backend} is not a native kernel")
        step_check(f"phase {label}", sess, probes[tile], oracle)
    try:
        serve_check(sess)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    return 1


def four_chips(clock, sentences_per_batch=SHARD_SENTENCES,
               batches=SHARD_BATCHES, vocab_size=VOCAB, backend="auto",
               expect="pallas_tiled", **cfg_kw) -> int:
    """Vocab-sharded pallas_tiled (exact exchange) vs jnp_tiled (dense
    exchange) on one mesh over every device, same batches. Returns the
    number of devices used."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model=1)
    n = int(mesh.shape["data"])
    corpus, vocab = make_corpus(vocab_size,
                                sentences_per_batch * (batches + 1))
    cfg = paper_cfg(tile_windows=8, tables=f"shards={n}",
                    sentences_per_batch=sentences_per_batch, **cfg_kw)
    path, path_compile_s = train_phase(f"{n}-chip path", corpus, vocab, cfg,
                                       backend, clock, timed_batches=batches,
                                       mesh=mesh)
    require(path.backend == expect and path.exchange == "exact",
            f"sharded path resolved {path.backend}/{path.exchange}")
    for leaf in (path.state.cold_in, path.state.cold_out):
        require(len(leaf.sharding.device_set) == n,
                f"cold table not spread over {n} devices: {leaf.sharding}")
    if path_compile_s:
        # exchange lists are padded to a few widths, not one: a batch whose
        # width the warm-up did not see compiles the step again
        log(f"{n}-chip path: words_per_s above is NOT steady state: it "
            f"includes {path_compile_s} s of compilation")
    pl = path.placement
    log(f"placement: hot={pl.hot} cold={pl.cold} shards={pl.n_shards} "
        f"rows_per_device={pl.rows_per_device}")
    with jax.default_matmul_precision("float32"):
        ref, _ = train_phase(f"{n}-chip reference", corpus, vocab, cfg,
                             "jnp_tiled", clock, timed_batches=batches,
                             mesh=mesh, exchange="dense")
    ok = True
    got, want = path.state.params(), ref.state.params()
    for name in sorted(want):
        a, b = np.asarray(got[name]), np.asarray(want[name])
        diff = float(np.abs(a - b).max())
        within = bool(np.allclose(a, b, atol=SHARD_ATOL, rtol=SHARD_RTOL))
        log(f"{n}-chip parity {name} {a.shape}: max_abs_diff={diff} "
            f"within(atol={SHARD_ATOL}, rtol={SHARD_RTOL})={within}")
        ok &= within and bool(np.isfinite(a).all())
    require(ok, "sharded pallas_tiled disagrees with the dense-exchange "
                "reference")
    return n


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: training phases a-c + serving on one chip; "
                         "4: only vocab-sharded training over four chips")
    args = ap.parse_args()
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}, compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    used = one_chip(clock) if args.chips == 1 else four_chips(clock)
    stats = dev.memory_stats() or {}
    log(f"done in {time.perf_counter() - t0} s; compile_s_total="
        f"{clock.seconds}; peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": used}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
