"""Host spans and counters (``repro.tracing``): bounded rings, parent ids
and inherited keys within and across threads, program spans on the host
plane of a profiler trace, the records a training session and a server
leave per step and per request, and the program names the benchmark's
trace reduction matches."""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import tracing
from repro.configs.w2v import smoke
from repro.data.corpus import synthetic_zipf_corpus
from repro.data.prefetch import AsyncBatchingPipeline


def test_ring_keeps_the_newest_records_only():
    tr = tracing.Tracer(ring=8)
    for i in range(20):
        with tr.span("repro.test.ring", key=i):
            pass
        tr.count("repro.test.counter", 1, key=i)
    spans = tr.recent("repro.test.ring")
    assert [r.key for r in spans] == list(range(12, 20))
    assert len(tr.recent("repro.test.counter")) == 8
    assert [r.key for r in tr.recent("repro.test.ring", 3)] == [17, 18, 19]
    assert tr.recent("repro.test.ring", 0) == []
    assert tr.keyed("repro.test.counter", [3, 15, 19]) == {15: 1.0, 19: 1.0}


def test_parents_and_keys_nest_within_a_thread_not_across():
    tr = tracing.Tracer()
    seen = {}

    def other():
        with tr.span("repro.test.other") as sp:
            tr.count("repro.test.n", 2)
        seen["other"] = sp

    with tr.span("repro.test.outer", key=(0, 7)) as outer:
        with tr.span("repro.test.inner") as inner:
            tr.count("repro.test.n", 5)
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert outer.parent is None
    assert inner.parent == outer.id and inner.key == (0, 7)
    # a span on another thread does not nest under this thread's spans
    assert seen["other"].parent is None and seen["other"].key is None
    counts = {r.parent: r for r in tr.recent("repro.test.n")}
    assert counts[inner.id].key == (0, 7) and counts[inner.id].value == 5
    assert counts[seen["other"].id].key is None
    rec = tr.recent("repro.test.inner")[0]
    assert rec.thread == threading.get_ident()
    assert rec.start_ns >= outer.start_ns and rec.end_ns <= outer.end_ns
    assert rec.value == pytest.approx((rec.end_ns - rec.start_ns) / 1e9)
    assert tr.keyed("repro.test.n", [(0, 7)]) == {(0, 7): 5.0}


def test_span_key_and_attrs_set_inside_the_block():
    tr = tracing.Tracer()
    with tr.span("repro.test.late") as sp:
        sp.key = (1, 2)
        sp.attrs["depth"] = 3
    (rec,) = tr.recent("repro.test.late")
    assert rec.key == (1, 2) and rec.attrs == {"depth": 3}
    tr.interval("repro.test.wait", 100, 2_000_100, key=9, batch=4)
    (w,) = tr.recent("repro.test.wait")
    assert w.value == pytest.approx(2e-3) and w.attrs == {"batch": 4}


def _corpus(n=600):
    return synthetic_zipf_corpus(vocab_size=300, n_sentences=n,
                                 mean_len=12, seed=0)


def test_program_spans_on_the_host_plane_of_a_trace(tmp_path):
    cfg = smoke(sentences_per_batch=64, max_sentence_len=32)
    pipe = AsyncBatchingPipeline(_corpus(), cfg, workers=2, depth=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        n = sum(1 for _ in pipe.batches(pad_len=32, epoch=0))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"repro.pipeline.produce", "repro.pipeline.finalize",
            "repro.pipeline.negatives", "repro.pipeline.handover"} <= names
    assert not any(x.startswith("bench.") for x in names)
    assert n >= 3


@pytest.fixture
def fresh(monkeypatch):
    """Empty rings for one test: other tests in this process key their
    batches and requests the same way."""
    tr = tracing.Tracer()
    for name in ("span", "count", "interval", "recent", "keyed"):
        monkeypatch.setattr(tracing, name, getattr(tr, name))
    return tr


def test_session_records_one_step_per_batch_with_pipeline_keys(fresh):
    from repro.core.trainer import TrainSession

    cfg = smoke(dim=16, sentences_per_batch=64, max_sentence_len=32,
                prefetch_workers=2, prefetch_depth=2, epochs=1)
    pipe = AsyncBatchingPipeline(_corpus(), cfg)
    sess = TrainSession(pipe, cfg, backend="jnp")
    sess.train(max_batches=4)
    steps = tracing.recent("repro.session.step", 4)
    keys = [r.key for r in steps]
    assert keys == [(0, i) for i in range(4)]
    assert all(r.attrs["words"] > 0 for r in steps)
    for name in ("repro.pipeline.produce", "repro.pipeline.finalize",
                 "repro.pipeline.negatives", "repro.pipeline.handover",
                 "repro.session.put", "repro.session.dispatch"):
        got = tracing.keyed(name, keys)
        assert set(got) == set(keys), name
        assert all(v >= 0 for v in got.values())
    # dispatch nests under its step; put runs a step ahead, outside it
    by_id = {r.id: r for r in steps}
    disp = tracing.recent("repro.session.dispatch")
    assert len(disp) == 4
    assert all(by_id[r.parent].key == r.key for r in disp)
    assert all(r.parent is None for r in tracing.recent("repro.session.put"))
    # every rejection round draws the whole (S, L, N) block
    drawn = tracing.keyed("repro.neg.drawn", keys)
    rounds = tracing.keyed("repro.neg.rounds", keys)
    block = 64 * 32 * cfg.negatives
    assert all(drawn[k] == rounds[k] * block for k in keys)
    report = sess.host_report(4)
    assert report["finalize_ms"] > 0
    words = sum(r.attrs["words"] for r in steps)
    assert report["neg_draws_per_word"] == pytest.approx(
        sum(drawn.values()) / words)


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _index(v=64, hot=12, d=16):
    from repro.distributed.vocab_placement import VocabPlacement
    from repro.serve import EmbeddingIndex

    placement = VocabPlacement(vocab_size=v, hot=hot, n_shards=1)
    table = np.random.default_rng(0).standard_normal((v, d)).astype(
        np.float32)
    h, c = placement.split(table)
    return EmbeddingIndex._stage(placement, h, c, _mesh1(), step=0)


def test_server_records_one_queue_interval_per_request(fresh):
    from repro.serve import EmbeddingServer

    with EmbeddingServer(_index(), batch_size=4, deadline_ms=5.0,
                         k=3) as srv:
        handles = [srv.submit("nn", np.array([i])) for i in range(10)]
        for h in handles:
            h.wait(60.0)
        n_batches = srv.batches
    topk = tracing.recent("repro.server.topk", n_batches)
    batch_ids = {r.key for r in topk}
    assert len(batch_ids) == n_batches
    assert sum(r.attrs["requests"] for r in topk) == 10
    # a 64-row table: the top-k ranks all its candidates
    assert {r.attrs["ranked"] for r in topk} == {64}
    queued = {r.key: r for r in tracing.recent("repro.server.queue")
              if r.key in {h.id for h in handles}}
    assert len(queued) == 10
    assert {r.attrs["batch"] for r in queued.values()} == batch_ids
    for h in handles:
        q = queued[h.id]
        assert q.start_ns == h.t0_ns and q.value >= 0
        # the wait ends where its batch's top-k call starts
        (t,) = [r for r in topk if r.key == q.attrs["batch"]]
        assert q.end_ns == t.start_ns
    assert set(tracing.keyed("repro.server.collect", batch_ids)) == batch_ids
    assert set(tracing.keyed("repro.server.resolve", batch_ids)) == batch_ids


def _step_module(monkeypatch):
    """The module the single-device f32 step dispatches."""
    from repro.kernels import ops
    from repro.kernels.tables import Tables
    from repro.data.batching import BatchingPipeline

    made = []
    real = ops._jitted_update

    def spy(*args):
        made.append(real(*args))
        return made[-1]

    monkeypatch.setattr(ops, "_jitted_update", spy)
    cfg = smoke(dim=8, sentences_per_batch=4, max_sentence_len=16)
    pipe = BatchingPipeline(_corpus(40), cfg)
    batch = next(pipe.batches(pad_len=16, epoch=0))
    v = pipe.vocab.size
    tables = Tables(w_in=jnp.zeros((v, 8)), w_out=jnp.zeros((v, 8)))
    step = batch.step_inputs(0.025)
    ops.step(tables, step, cfg, backend="jnp")
    (fn,) = made
    return fn.lower(jnp.zeros((v, 8)), jnp.zeros((v, 8)), step)


def _topk_module(monkeypatch):
    """The module ``make_topk_fn`` jits."""
    from repro.serve import make_topk_fn

    idx = _index()
    fn = make_topk_fn(idx.placement, idx.mesh, mode="nn", k=3)
    return fn.lower(idx.hot, idx.cold, jnp.zeros((4,), jnp.int32))


@pytest.mark.parametrize("lower,module", [(_step_module, "jit_run"),
                                          (_topk_module, "jit_local")])
def test_program_names_the_benchmark_reduction_matches(monkeypatch, lower,
                                                        module):
    """``step_roofline_pct.*`` and ``topk_roofline_pct.serve`` find the
    step and the top-k in a device trace by these XLA module names; a
    rename would silence them."""
    header = lower(monkeypatch).compile().as_text().split()[:2]
    assert header == ["HloModule", module + ","]
