"""Async host pipeline (DESIGN.md §4.1): worker-count invariance,
bit-identity with the synchronous pipeline, bounded-queue backpressure,
clean shutdown, steady-state stats, and exact mid-epoch resume with
prefetch enabled."""
import time

import numpy as np
import pytest

from repro.configs.w2v import smoke
from repro.data.batching import BatchingPipeline
from repro.data.corpus import synthetic_zipf_corpus
from repro.data.prefetch import AsyncBatchingPipeline, make_pipeline


def _corpus(n=600, seed=0):
    return synthetic_zipf_corpus(vocab_size=300, n_sentences=n,
                                 mean_len=12, seed=seed)


def _cfg(**kw):
    base = dict(sentences_per_batch=64, max_sentence_len=32)
    base.update(kw)
    return smoke(**base)


def _same_stream(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.tokens, y.tokens)
        assert np.array_equal(x.negs, y.negs)
        assert np.array_equal(x.lengths, y.lengths)
        assert x.n_words == y.n_words
        assert (x.plan is None) == (y.plan is None)
        if x.plan is not None:
            assert np.array_equal(x.plan.uniq, y.plan.uniq)
            assert np.array_equal(x.plan.scatter, y.plan.scatter)
            assert np.array_equal(x.plan.ucount, y.plan.ucount)
            assert np.array_equal(x.plan.strict, y.plan.strict)
        assert (x.exchange is None) == (y.exchange is None)
        if x.exchange is not None:
            ex, ey = x.exchange, y.exchange
            assert ex.placement == ey.placement
            for f in ("tokens", "negs", "cold_ids", "bucket_ids",
                      "bucket_pos"):
                assert np.array_equal(getattr(ex, f), getattr(ey, f)), f


def test_async_bitwise_equals_sync_any_worker_count():
    cfg = _cfg()
    corpus = _corpus()
    sync = BatchingPipeline(corpus, cfg)
    ref = list(sync.batches(pad_len=32, epoch=0))
    assert len(ref) >= 3
    for workers in (1, 4):
        apipe = AsyncBatchingPipeline(corpus, cfg, vocab=sync.vocab,
                                      workers=workers, depth=3)
        _same_stream(ref, list(apipe.batches(pad_len=32, epoch=0)))


def test_async_tiled_stream_packed_equals_sync():
    """The relaxed modes compose: tile plans + stream packing survive the
    async path bit-for-bit (plan arrays included)."""
    cfg = _cfg(tile_windows=2, ignore_delimiters=True)
    corpus = _corpus()
    sync = BatchingPipeline(corpus, cfg)
    ref = list(sync.batches(pad_len=32, epoch=1))
    assert ref[0].plan is not None
    apipe = AsyncBatchingPipeline(corpus, cfg, vocab=sync.vocab,
                                  workers=3, depth=2)
    _same_stream(ref, list(apipe.batches(pad_len=32, epoch=1)))


@pytest.mark.parametrize("mode", ["thread", "process"])
def test_async_carries_worker_planned_exchange(mode):
    """A placement-aware pipeline attaches the vocab-sharding exchange plan
    (request lists + capacity buckets) in the finalize workers — both
    worker kinds — bit-identically to the synchronous pipeline."""
    from repro.distributed.vocab_placement import VocabPlacement

    cfg = _cfg(vocab_shard=True)
    corpus = _corpus()
    sync = BatchingPipeline(corpus, cfg)
    sync.placement = VocabPlacement.plan(sync.vocab.counts, 2, hot_frac=0.2)
    ref = list(sync.batches(pad_len=32, epoch=0))
    assert ref[0].exchange is not None
    assert ref[0].exchange.bucket_ids is not None
    apipe = AsyncBatchingPipeline(corpus, cfg, vocab=sync.vocab,
                                  workers=2, depth=2, mode=mode)
    apipe.placement = sync.placement
    _same_stream(ref, list(apipe.batches(pad_len=32, epoch=0)))


def test_epochs_draw_distinct_randomness():
    cfg = _cfg()
    pipe = BatchingPipeline(_corpus(), cfg)
    b0 = next(pipe.batches(pad_len=32, epoch=0))
    b1 = next(pipe.batches(pad_len=32, epoch=1))
    b0_again = next(pipe.batches(pad_len=32, epoch=0))
    assert not np.array_equal(b0.negs, b1.negs)
    assert np.array_equal(b0.negs, b0_again.negs)


def test_skip_batches_is_exact_suffix():
    cfg = _cfg()
    corpus = _corpus()
    for pipe in (BatchingPipeline(corpus, cfg),
                 AsyncBatchingPipeline(corpus, cfg, workers=2, depth=2)):
        full = list(pipe.batches(pad_len=32, epoch=3))
        part = list(pipe.batches(pad_len=32, epoch=3, skip_batches=2))
        assert len(part) == len(full) - 2
        _same_stream(full[2:], part)


def test_backpressure_bounds_in_flight_batches():
    cfg = _cfg()
    apipe = AsyncBatchingPipeline(_corpus(1200), cfg, workers=2, depth=2)
    n = 0
    for _ in apipe.batches(pad_len=32, epoch=0):
        time.sleep(0.02)   # slow consumer: producer must hit the bound
        n += 1
    assert n >= 6
    assert 1 <= apipe.prefetch.max_in_flight <= 2
    assert len(apipe.prefetch.depth_samples) == n


def test_worker_exception_propagates_and_shuts_down(monkeypatch):
    import repro.data.prefetch as prefetch_mod

    def boom(packed, cfg, sampler, epoch, placement=None, bag_table=None):
        if packed.index >= 2:
            raise RuntimeError("injected finalize failure")
        return prefetch_mod.finalize_packed.__wrapped__(
            packed, cfg, sampler, epoch, placement, bag_table)

    boom.__wrapped__ = prefetch_mod.finalize_packed
    monkeypatch.setattr(prefetch_mod, "finalize_packed", boom)
    cfg = _cfg()
    apipe = AsyncBatchingPipeline(_corpus(), cfg, workers=2, depth=2)
    with pytest.raises(RuntimeError, match="injected finalize failure"):
        list(apipe.batches(pad_len=32, epoch=0))
    apipe._producer.join(timeout=5.0)
    assert not apipe._producer.is_alive()
    # the pipeline is reusable after a failed epoch
    monkeypatch.setattr(prefetch_mod, "finalize_packed",
                        boom.__wrapped__)
    assert len(list(apipe.batches(pad_len=32, epoch=0))) >= 3


def test_early_close_joins_producer():
    cfg = _cfg()
    apipe = AsyncBatchingPipeline(_corpus(1200), cfg, workers=2, depth=2)
    it = apipe.batches(pad_len=32, epoch=0)
    next(it)
    next(it)
    it.close()
    apipe._producer.join(timeout=5.0)
    assert not apipe._producer.is_alive()


def test_stats_clock_starts_at_first_batch():
    """BatchingStats measures steady-state batching only: pipeline/vocab
    construction and idle time before the first batch never count."""
    cfg = _cfg()
    for pipe in (BatchingPipeline(_corpus(), cfg),
                 AsyncBatchingPipeline(_corpus(), cfg, workers=2, depth=2)):
        time.sleep(0.25)                    # idle after construction
        t0 = time.perf_counter()
        batches = list(pipe.batches(pad_len=32, epoch=0))
        consumed = time.perf_counter() - t0
        assert batches
        assert 0 < pipe.stats.seconds <= consumed + 0.05
        assert pipe.stats.words == sum(b.n_words for b in batches)
        assert np.isfinite(pipe.stats.words_per_sec)


def test_make_pipeline_selects_by_config():
    sync = make_pipeline(_corpus(), _cfg())
    assert type(sync) is BatchingPipeline
    apipe = make_pipeline(_corpus(), _cfg(prefetch_workers=3,
                                          prefetch_depth=5))
    assert isinstance(apipe, AsyncBatchingPipeline)
    assert apipe.workers == 3 and apipe.depth == 5


def test_process_mode_matches_sync(subproc):
    """Process workers (fresh interpreters, no shared state) still emit the
    bit-identical stream. Run in a subprocess with no jax imported."""
    r = subproc("""
        import numpy as np
        from repro.configs.w2v import smoke
        from repro.data.batching import BatchingPipeline
        from repro.data.corpus import synthetic_zipf_corpus
        from repro.data.prefetch import AsyncBatchingPipeline

        cfg = smoke(sentences_per_batch=32, max_sentence_len=32,
                    tile_windows=2)
        corpus = synthetic_zipf_corpus(vocab_size=200, n_sentences=200,
                                       mean_len=12, seed=0)
        sync = BatchingPipeline(corpus, cfg)
        ref = list(sync.batches(pad_len=32, epoch=0))
        apipe = AsyncBatchingPipeline(corpus, cfg, vocab=sync.vocab,
                                      workers=2, depth=2, mode="process")
        got = list(apipe.batches(pad_len=32, epoch=0))
        assert len(ref) == len(got) and len(ref) >= 2
        for a, b in zip(ref, got):
            assert np.array_equal(a.tokens, b.tokens)
            assert np.array_equal(a.negs, b.negs)
            assert np.array_equal(a.plan.uniq, b.plan.uniq)
        print("PROCESS_MODE_OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "PROCESS_MODE_OK" in r.stdout


def test_killed_process_worker_heals_bit_identical(subproc):
    """SIGKILLing a process-pool worker breaks the whole pool
    (BrokenProcessPool): the pipeline must rebuild it and recompute the
    owed batches — the emitted stream stays bit-identical to sync
    (DESIGN.md §9). Run in a subprocess with no jax imported."""
    r = subproc("""
        import os, signal
        import numpy as np
        from repro.configs.w2v import smoke
        from repro.data.batching import BatchingPipeline
        from repro.data.corpus import synthetic_zipf_corpus
        from repro.data.prefetch import AsyncBatchingPipeline

        cfg = smoke(sentences_per_batch=32, max_sentence_len=32)
        corpus = synthetic_zipf_corpus(vocab_size=200, n_sentences=200,
                                       mean_len=12, seed=0)
        sync = BatchingPipeline(corpus, cfg)
        ref = list(sync.batches(pad_len=32, epoch=0))
        assert len(ref) >= 4

        apipe = AsyncBatchingPipeline(corpus, cfg, vocab=sync.vocab,
                                      workers=2, depth=2, mode="process")
        got = []
        for i, b in enumerate(apipe.batches(pad_len=32, epoch=0)):
            got.append(b)
            if i == 0:
                pids = apipe.worker_pids()
                assert pids, "process pool has no live workers"
                os.kill(pids[0], signal.SIGKILL)
        assert apipe.prefetch.heals >= 1, "pool was never healed"
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            assert np.array_equal(a.tokens, b.tokens)
            assert np.array_equal(a.negs, b.negs)
            assert np.array_equal(a.lengths, b.lengths)
        print("HEAL_OK heals=%d" % apipe.prefetch.heals)
    """)
    assert r.returncode == 0, r.stderr
    assert "HEAL_OK" in r.stdout


# set in the test process only: a spawned worker imports this module fresh
# and sees False, a forked one would inherit True
_PARENT_ONLY = False


def _worker_probe():
    """Runs inside a pool worker after real finalize work."""
    import os
    import sys
    jax_backend = False
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        jax_backend = xla_bridge.backends_are_initialized()
    return _PARENT_ONLY, jax_backend, os.environ.get("JAX_PLATFORMS")


def test_process_workers_spawn_and_never_start_jax(monkeypatch):
    """Process workers start from a fresh interpreter (spawn, not fork —
    the parent may hold the accelerator) and finalize batches without ever
    initialising a JAX backend; any JAX use there is pinned to the CPU."""
    from repro.data import prefetch
    from repro.distributed.vocab_placement import VocabPlacement

    # the worker must pin itself, not inherit the test run's setting
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    globals()["_PARENT_ONLY"] = True
    try:
        cfg = _cfg(vocab_shard=True)
        apipe = AsyncBatchingPipeline(_corpus(), cfg, workers=1, depth=1,
                                      mode="process")
        # a placement makes the worker import repro.distributed (and jax)
        apipe.placement = VocabPlacement.plan(apipe.vocab.counts, 2,
                                              hot_frac=0.2)
        packed = next(apipe._packed(32, 0))
        ex = apipe._make_executor()
        try:
            batch = ex.submit(prefetch._proc_finalize, packed, 0).result()
            assert batch.exchange is not None
            inherited, jax_backend, platforms = ex.submit(
                _worker_probe).result()
        finally:
            ex.shutdown(wait=True)
    finally:
        globals()["_PARENT_ONLY"] = False
    assert inherited is False, "worker was forked from the parent"
    assert jax_backend is False, "worker initialised a JAX backend"
    assert platforms == "cpu"


def test_dead_producer_surfaces_as_pipeline_fault(monkeypatch):
    """A producer thread that dies without delivering its end-of-epoch
    sentinel must surface as a recoverable PipelineFault within the
    consumer's bounded poll — never a hang."""
    import queue as queue_mod

    import repro.data.prefetch as prefetch_mod

    class SentinelEatingQueue(queue_mod.Queue):
        # drop the end-of-epoch marker: exactly what the consumer sees
        # when the producer is killed between queue puts
        def put(self, item, *a, **kw):
            if isinstance(item, prefetch_mod._EndOfEpoch):
                return
            super().put(item, *a, **kw)

    monkeypatch.setattr(prefetch_mod.queue, "Queue", SentinelEatingQueue)
    cfg = _cfg()
    apipe = AsyncBatchingPipeline(_corpus(), cfg, workers=2, depth=2)
    with pytest.raises(prefetch_mod.PipelineFault, match="producer"):
        list(apipe.batches(pad_len=32, epoch=0))
    apipe._producer.join(timeout=5.0)
    assert not apipe._producer.is_alive()


def test_pipeline_cursor_roundtrip():
    from repro.train.checkpoint import PipelineCursor

    c = PipelineCursor(epoch=2, epoch_batch=7, prefetch_workers=4)
    extra = {"words_seen": 123, **c.to_extra()}
    back = PipelineCursor.from_extra(extra)
    assert back == c
    assert PipelineCursor.from_extra({}) == PipelineCursor()


def test_checkpoint_resume_mid_epoch_with_prefetch(tmp_path):
    """Interrupt mid-epoch, resume with prefetch enabled: final tables are
    bit-identical to the uninterrupted run (keyed randomness + cursor
    fast-forward), and identical to the all-synchronous run."""
    import jax  # noqa: F401  (deferred: keep pipeline tests jax-free)

    from repro.core.trainer import TrainSession

    corpus = _corpus(n=300)
    cfg = _cfg(dim=16, epochs=2, prefetch_workers=2, prefetch_depth=2)
    cfg_sync = _cfg(dim=16, epochs=2)

    def fresh(c):
        return make_pipeline(corpus, c), c

    # uninterrupted, synchronous reference
    pipe, c = fresh(cfg_sync)
    ref = TrainSession(pipe, c, backend="jnp").train()
    ref_in = np.asarray(ref.w_in)

    # uninterrupted with prefetch
    pipe, c = fresh(cfg)
    full = TrainSession(pipe, c, backend="jnp").train()
    assert np.array_equal(ref_in, np.asarray(full.w_in))

    # interrupted mid-epoch + resumed, prefetch on both sides
    ckpt = str(tmp_path / "ckpt")
    pipe, c = fresh(cfg)
    TrainSession(pipe, c, backend="jnp", ckpt_dir=ckpt,
                 ckpt_every=1).train(max_batches=3)
    pipe, c = fresh(cfg)
    resumed = TrainSession(pipe, c, backend="jnp", ckpt_dir=ckpt,
                           ckpt_every=0)
    assert resumed.resumed_step == 3
    resumed.train()
    assert np.array_equal(ref_in, np.asarray(resumed.state.w_in))
