"""Serving subsystem (DESIGN.md §10): index loading, sharded top-k
parity, snapshot hot-swap, request batching, and the serve chaos bar."""
import os
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.distributed.vocab_placement import VocabPlacement
from repro.serve import (EmbeddingIndex, EmbeddingServer, SnapshotWatcher,
                         dense_topk, make_topk_fn)
from repro.serve.chaos import SCHEDULES, _publish, run_serve_chaos
from repro.serve.index import _restripe
from repro.serve.query import _block_rank, _rank
from repro.train import checkpoint as ckpt

V, HOT, D = 64, 12, 16


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _table(seed=0, v=V, d=D):
    return np.random.default_rng(seed).standard_normal(
        (v, d)).astype(np.float32)


def _index(seed=0, v=V, hot=HOT, d=D, step=0):
    placement = VocabPlacement(vocab_size=v, hot=hot, n_shards=1)
    h, c = placement.split(_table(seed, v, d))
    return EmbeddingIndex._stage(placement, h, c, _mesh1(), step=step)


# -- index construction -------------------------------------------------------
def test_index_rows_normalized():
    idx = _index()
    dense = idx.dense_embeddings()
    np.testing.assert_allclose(np.linalg.norm(dense, axis=1),
                               np.ones(V), atol=1e-5)


def test_index_load_split_checkpoint_without_merge(tmp_path, monkeypatch):
    """Loading a split checkpoint restores only the input-table leaves
    and never calls VocabPlacement.merge (the no-(V,d)-reassembly
    contract)."""
    d = str(tmp_path)
    table = _table(1)
    placement = VocabPlacement(vocab_size=V, hot=HOT, n_shards=1)
    _publish(d, 30, table, placement)

    def boom(*a, **k):
        raise AssertionError("serving load reassembled the full table")
    monkeypatch.setattr(VocabPlacement, "merge", boom)
    idx = EmbeddingIndex.load(d)
    assert idx.step == 30 and idx.vocab_size == V
    assert idx.placement == placement
    monkeypatch.undo()
    norm = table / np.maximum(
        np.linalg.norm(table, axis=1, keepdims=True), 1e-12)
    np.testing.assert_allclose(idx.dense_embeddings(), norm, atol=1e-6)


def test_index_load_replicated_checkpoint(tmp_path):
    """A replicated (w_in/w_out) checkpoint is split under a prefix-head
    placement at load time."""
    d = str(tmp_path)
    table = _table(2)
    ckpt.save(d, 5, {"w_in": table, "w_out": table * 0.5})
    idx = EmbeddingIndex.load(d, hot_frac=0.25)
    assert idx.placement.hot == 16 and idx.n_shards == 1
    norm = table / np.maximum(
        np.linalg.norm(table, axis=1, keepdims=True), 1e-12)
    np.testing.assert_allclose(idx.dense_embeddings(), norm, atol=1e-6)


def test_restripe_permutes_between_layouts():
    """Elastic serving: re-striping cold rows between shard counts is a
    pure permutation — merge(src) == merge(dst) row for row."""
    table = _table(3)
    src = VocabPlacement(vocab_size=V, hot=HOT, n_shards=4)
    dst = VocabPlacement(vocab_size=V, hot=HOT, n_shards=2)
    hot, cold_src = src.split(table)
    cold_dst = _restripe(cold_src, src, dst)
    np.testing.assert_array_equal(dst.merge(hot, cold_dst), table)


def test_index_load_restripes_on_shard_count_change(tmp_path):
    """A checkpoint written on 2 shards serves on 1 without reassembly:
    the dense views agree exactly."""
    d = str(tmp_path)
    table = _table(4)
    _publish(d, 7, table, VocabPlacement(vocab_size=V, hot=HOT, n_shards=2))
    idx = EmbeddingIndex.load(d)      # 1-device mesh -> 1-shard layout
    assert idx.n_shards == 1
    norm = table / np.maximum(
        np.linalg.norm(table, axis=1, keepdims=True), 1e-12)
    np.testing.assert_allclose(idx.dense_embeddings(), norm, atol=1e-6)


def test_index_load_no_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        EmbeddingIndex.load(str(tmp_path / "empty"))


# -- sharded top-k parity -----------------------------------------------------
def test_topk_parity_boundary_ids_1shard():
    idx = _index()
    dense = idx.dense_embeddings()
    # hot/cold boundary: last hot id, first/second cold ids, edges
    ids = np.array([0, HOT - 1, HOT, HOT + 1, V - 1], np.int32)
    fn = make_topk_fn(idx.placement, idx.mesh, mode="nn", k=6)
    got_ids, got_sc = fn(idx.hot, idx.cold, ids)
    want_ids, want_sc = dense_topk(dense, ids, k=6, mode="nn")
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got_sc), want_sc, atol=1e-6)


def test_topk_analogy_parity_1shard():
    idx = _index(5)
    dense = idx.dense_embeddings()
    triples = np.array([[0, 1, 2], [HOT - 1, HOT, HOT + 1],
                        [V - 1, 0, HOT]], np.int32)
    fn = make_topk_fn(idx.placement, idx.mesh, mode="analogy", k=4)
    got_ids, got_sc = fn(idx.hot, idx.cold, triples)
    want_ids, want_sc = dense_topk(dense, triples, k=4, mode="analogy")
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got_sc), want_sc, atol=1e-6)


def test_topk_ties_break_by_id():
    """Duplicate rows produce tied scores; both paths must rank the
    lower id first (the lexicographic tie-break parity depends on)."""
    table = _table(6)
    table[HOT + 3] = table[2]           # a cold duplicate of a hot row
    placement = VocabPlacement(vocab_size=V, hot=HOT, n_shards=1)
    h, c = placement.split(table)
    idx = EmbeddingIndex._stage(placement, h, c, _mesh1())
    ids = np.array([5, 40], np.int32)
    fn = make_topk_fn(placement, idx.mesh, mode="nn", k=V - 1)
    got_ids, _ = fn(idx.hot, idx.cold, ids)
    want_ids, _ = dense_topk(idx.dense_embeddings(), ids, k=V - 1)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)


def test_topk_excludes_query_words():
    idx = _index(7)
    ids = np.arange(8, dtype=np.int32)
    fn = make_topk_fn(idx.placement, idx.mesh, mode="nn", k=5)
    got_ids, _ = fn(idx.hot, idx.cold, ids)
    for q, row in zip(ids, np.asarray(got_ids)):
        assert q not in row


def test_topk_k_too_large_raises():
    idx = _index()
    with pytest.raises(ValueError):
        make_topk_fn(idx.placement, idx.mesh, k=V + 1)
    with pytest.raises(ValueError):
        make_topk_fn(idx.placement, idx.mesh, mode="cosmul")


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1),     # seed
       st.integers(24, 80),           # vocab
       st.integers(2, 16),            # hot head
       st.integers(1, 8),             # k
       st.integers(1, 6))             # query batch
def test_topk_parity_property_1shard(seed, v, hot, k, b):
    rng = np.random.default_rng(seed)
    hot = min(hot, v - 2)
    table = rng.standard_normal((v, 8)).astype(np.float32)
    placement = VocabPlacement(vocab_size=v, hot=hot, n_shards=1)
    h, c = placement.split(table)
    idx = EmbeddingIndex._stage(placement, h, c, _mesh1())
    ids = rng.integers(v, size=b).astype(np.int32)
    fn = make_topk_fn(placement, idx.mesh, mode="nn", k=k)
    got_ids, got_sc = fn(idx.hot, idx.cold, ids)
    want_ids, want_sc = dense_topk(idx.dense_embeddings(), ids, k=k)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got_sc), want_sc, atol=1e-6)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_topk_parity_multishard(subproc, n_shards):
    """Property-style parity across real shard counts (fake devices):
    random ids plus the hot/cold boundary, nn and analogy."""
    r = subproc(f"""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.distributed.vocab_placement import VocabPlacement
        from repro.serve.index import EmbeddingIndex
        from repro.serve.query import dense_topk, make_topk_fn
        n = {n_shards}
        mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
        for seed in range(3):
            rng = np.random.default_rng(seed)
            v = int(rng.integers(40, 90)); hot = int(rng.integers(4, 14))
            table = rng.standard_normal((v, 8)).astype(np.float32)
            pl = VocabPlacement(vocab_size=v, hot=hot, n_shards=n)
            h, c = pl.split(table)
            idx = EmbeddingIndex._stage(pl, h, c, mesh)
            dense = idx.dense_embeddings()
            ids = rng.integers(v, size=9).astype(np.int32)
            ids[:4] = [hot - 1, hot, hot + 1, v - 1]
            fn = make_topk_fn(pl, mesh, mode="nn", k=6)
            gi, gs = fn(idx.hot, idx.cold, ids)
            wi, ws = dense_topk(dense, ids, k=6)
            assert np.array_equal(np.asarray(gi), wi), (seed, gi, wi)
            assert np.allclose(np.asarray(gs), ws, atol=1e-6)
            tri = rng.integers(v, size=(4, 3)).astype(np.int32)
            fa = make_topk_fn(pl, mesh, mode="analogy", k=5)
            gi, gs = fa(idx.hot, idx.cold, tri)
            wi, ws = dense_topk(dense, tri, k=5, mode="analogy")
            assert np.array_equal(np.asarray(gi), wi), (seed, gi, wi)
            assert np.allclose(np.asarray(gs), ws, atol=1e-6)
        print("MULTISHARD_PARITY_OK")
    """, n_devices=n_shards)
    assert "MULTISHARD_PARITY_OK" in r.stdout, r.stdout + r.stderr


# -- block-max prefilter ------------------------------------------------------
# (candidates C, k, block g, distinct score values, share of -inf scores)
_PREFILTER_CASES = {
    "few_values_maxima_tie": (300, 4, 8, 3, 0.0),
    "more_dead_than_c_minus_k": (96, 6, 8, 4, 0.97),
    "c_not_multiple_of_g": (101, 3, 8, 50, 0.2),
    "kg_just_below_c": (41, 5, 8, 6, 0.1),
    "kg_at_c": (40, 5, 8, 6, 0.1),
    "k1": (200, 1, 32, 5, 0.3),
    "all_dead": (64, 3, 4, 2, 1.0),
}


@pytest.mark.parametrize("case", sorted(_PREFILTER_CASES))
def test_block_rank_matches_full_rank(case):
    """The prefilter ranks exactly as the full ``(score desc, id asc)``
    sort, ids and scores, on tie-heavy rows with a forced small block."""
    c, k, g, values, dead = _PREFILTER_CASES[case]
    for seed in range(4):
        rng = np.random.default_rng(seed)
        scores = rng.integers(values, size=(6, c)).astype(np.float32)
        scores[rng.random((6, c)) < dead] = -np.inf
        # ascending ids with gaps, as a shard's strided cold ids are
        ids = np.sort(rng.choice(10 * c, size=c, replace=False)).astype(
            np.int32)
        want = _rank(jnp.asarray(scores),
                     jnp.broadcast_to(ids, scores.shape), k)
        got = _block_rank(jnp.asarray(scores), jnp.asarray(ids), k, g)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


PV, PHOT = 4000, 100      # C = 4,000 candidates: k=5 prefilters by g=128


def _prefilter_index():
    """A table whose duplicate rows straddle the block boundaries at
    candidate 128 and 256 (one shard: candidate index == id)."""
    table = _table(8, PV)
    table[124:132] = table[5] + 0.05 * table[9]        # 8 tied, across 128
    table[254:258] = table[300] + 0.05 * table[11]     # 4 tied, across 256
    placement = VocabPlacement(vocab_size=PV, hot=PHOT, n_shards=1)
    h, c = placement.split(table)
    return EmbeddingIndex._stage(placement, h, c, _mesh1())


@pytest.mark.parametrize("mode", ["nn", "analogy"])
def test_topk_prefilter_parity_with_dense(mode):
    idx = _prefilter_index()
    rng = np.random.default_rng(1)
    if mode == "nn":
        ids = np.concatenate([[5, 300, 124, 131, 256, PHOT - 1, PHOT],
                              rng.integers(PV, size=9)]).astype(np.int32)
    else:
        ids = np.concatenate([[[5, 9, 300], [300, 11, 5], [127, 3, 128]],
                              rng.integers(PV, size=(5, 3))]).astype(np.int32)
    fn = make_topk_fn(idx.placement, idx.mesh, mode=mode, k=5)
    assert fn.ranked == 5 * 128
    got_ids, got_sc = fn(idx.hot, idx.cold, ids)
    want_ids, want_sc = dense_topk(idx.dense_embeddings(), ids, k=5,
                                   mode=mode)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got_sc), want_sc, atol=1e-6)
    if mode == "nn":      # the ties resolve by id across the boundary
        np.testing.assert_array_equal(want_ids[0], np.arange(124, 129))
        np.testing.assert_array_equal(want_ids[1][:4], np.arange(254, 258))


@pytest.mark.parametrize("v,k,ranked", [(PV, 5, 5 * 128), (PV, 1, 128),
                                        (V, 3, V), (PV, 40, PV)])
def test_topk_ranked_counts_the_final_sort(v, k, ranked):
    """``fn.ranked``: k·g where the prefilter engages, all C candidates
    where k blocks would hold them all."""
    placement = VocabPlacement(vocab_size=v, hot=12, n_shards=1)
    assert make_topk_fn(placement, _mesh1(), k=k).ranked == ranked


@pytest.mark.parametrize("v,full_sort", [(PV, False), (V, True)])
def test_topk_prefilter_sorts_no_full_candidate_row(v, full_sort):
    """The compiled top-k sorts a (B, C)-wide operand only where the
    shape rule falls back to the full rank."""
    import re

    idx = _prefilter_index() if v == PV else _index()
    fn = make_topk_fn(idx.placement, idx.mesh, mode="nn", k=5)
    text = fn.lower(idx.hot, idx.cold, np.zeros(8, np.int32)).compile(
        ).as_text()
    widths = {int(w) for line in text.splitlines() if " sort(" in line
              for w in re.findall(r"\[8,(\d+)\]", line)}
    assert widths, "no sort found"
    assert (v in widths) == full_sort, widths


def test_topk_prefilter_parity_multishard(subproc):
    """Each shard prefilters its own strided candidates; the merge of the
    shards' partials stays exact."""
    r = subproc(f"""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.distributed.vocab_placement import VocabPlacement
        from repro.serve.index import EmbeddingIndex
        from repro.serve.query import dense_topk, make_topk_fn
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        rng = np.random.default_rng(0)
        v, hot = {PV}, {PHOT}
        table = rng.standard_normal((v, 8)).astype(np.float32)
        table[250:262] = table[7]          # ties on both shards
        pl = VocabPlacement(vocab_size=v, hot=hot, n_shards=2)
        h, c = pl.split(table)
        idx = EmbeddingIndex._stage(pl, h, c, mesh)
        ids = np.concatenate([[7, 250, hot - 1, hot, v - 1],
                              rng.integers(v, size=11)]).astype(np.int32)
        fn = make_topk_fn(pl, mesh, mode="nn", k=5)
        assert fn.ranked == 5 * 128 < hot + pl.cold_per_shard
        gi, gs = fn(idx.hot, idx.cold, ids)
        wi, ws = dense_topk(idx.dense_embeddings(), ids, k=5)
        assert np.array_equal(np.asarray(gi), wi), (gi, wi)
        assert np.allclose(np.asarray(gs), ws, atol=1e-6)
        print("PREFILTER_MULTISHARD_OK")
    """, n_devices=2)
    assert "PREFILTER_MULTISHARD_OK" in r.stdout, r.stdout + r.stderr


# -- session accessors --------------------------------------------------------
def _tiny_session(vocab_shard):
    from repro.configs.w2v import smoke
    from repro.core.trainer import TrainSession
    from repro.data.batching import BatchingPipeline
    from repro.data.corpus import synthetic_cluster_corpus

    cfg = smoke(epochs=1, dim=16, vocab_shard=vocab_shard)
    corpus = synthetic_cluster_corpus(n_clusters=4, words_per_cluster=8,
                                      n_sentences=120, mean_len=8, seed=0)
    sess = TrainSession(BatchingPipeline(corpus, cfg), cfg, backend="jnp")
    sess.train(max_batches=2)
    return sess


def test_embeddings_sharded_no_gather():
    sess = _tiny_session(vocab_shard=True)
    hot, cold, placement = sess.embeddings_sharded()
    assert placement is sess.placement
    assert hot.shape == (placement.hot, 16)
    assert cold.shape == (placement.cold_pad, 16)
    np.testing.assert_array_equal(
        placement.merge(np.asarray(hot), np.asarray(cold)),
        sess.embeddings())


def test_embeddings_sharded_replicated_session():
    sess = _tiny_session(vocab_shard=False)
    full, cold, placement = sess.embeddings_sharded()
    assert cold is None and placement is None
    np.testing.assert_array_equal(np.asarray(full), sess.embeddings())


@pytest.mark.parametrize("vocab_shard", [False, True])
def test_from_session_matches_dense(vocab_shard):
    sess = _tiny_session(vocab_shard)
    idx = EmbeddingIndex.from_session(sess)
    e = sess.embeddings()
    norm = e / np.maximum(np.linalg.norm(e, axis=1, keepdims=True), 1e-12)
    np.testing.assert_allclose(idx.dense_embeddings(), norm, atol=1e-6)


# -- snapshot watcher ---------------------------------------------------------
def test_watcher_swaps_and_tolerates_corrupt(tmp_path):
    d = str(tmp_path)
    placement = VocabPlacement(vocab_size=V, hot=HOT, n_shards=1)
    _publish(d, 10, _table(8), placement)
    w = SnapshotWatcher(d, poll_s=0.01)
    assert w.poll_once() and w.current().step == 10

    # newer-but-corrupt checkpoint: swap refused, old snapshot serves on
    _publish(d, 20, _table(9), placement)
    npz = os.path.join(d, "step_00000020", "arrays.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)
    w.poll_once()
    assert w.current().step == 10 and w.load_failures >= 1

    # a good one after it is picked up (corrupt step was quarantined)
    _publish(d, 30, _table(10), placement)
    assert w.poll_once() and w.current().step == 30
    assert w.swaps == 2


def test_watcher_crash_and_restart(tmp_path):
    d = str(tmp_path)
    placement = VocabPlacement(vocab_size=V, hot=HOT, n_shards=1)
    _publish(d, 10, _table(11), placement)
    w = SnapshotWatcher(d, poll_s=0.01)
    with w:
        w.wait_ready(timeout=30)
        w.inject_crash()
        deadline = time.monotonic() + 10
        while w.alive and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not w.alive and w.crashes == 1
        assert w.current().step == 10        # serving survives the crash
        _publish(d, 20, _table(12), placement)
        w.start()                            # restart picks up missed step
        deadline = time.monotonic() + 10
        while w.current().step != 20 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert w.current().step == 20


def test_watcher_current_before_ready_raises(tmp_path):
    w = SnapshotWatcher(str(tmp_path), poll_s=0.01)
    with pytest.raises(RuntimeError):
        w.current()


# -- server batching ----------------------------------------------------------
def test_server_coalesces_and_answers(tmp_path):
    idx = _index(13, step=42)
    dense = idx.dense_embeddings()
    with EmbeddingServer(idx, batch_size=8, deadline_ms=20.0,
                         k=4) as server:
        reqs = [server.submit("nn", np.array([i], np.int32))
                for i in range(8)]
        results = [r.wait(30.0) for r in reqs]
        # a full row budget arriving within the deadline rides one batch
        assert server.batches <= 2
        for i, res in enumerate(results):
            assert res.snapshot_step == 42
            want_ids, want_sc = dense_topk(dense, np.array([i], np.int32),
                                           k=4)
            np.testing.assert_array_equal(res.ids, want_ids)
            np.testing.assert_allclose(res.scores, want_sc, atol=1e-6)


def test_server_mixed_kinds_never_share_a_batch():
    idx = _index(14)
    with EmbeddingServer(idx, batch_size=16, deadline_ms=5.0,
                         k=3) as server:
        nn = server.submit("nn", np.array([1, 2], np.int32))
        an = server.submit("analogy", np.array([[1, 2, 3]], np.int32))
        r_nn, r_an = nn.wait(30.0), an.wait(30.0)
        assert r_nn.ids.shape == (2, 3)
        assert r_an.ids.shape == (1, 3)
        assert server.batches == 2


def test_server_close_drains_pending():
    idx = _index(15)
    server = EmbeddingServer(idx, batch_size=4, deadline_ms=1.0, k=3)
    reqs = [server.submit("nn", np.array([i % V], np.int32))
            for i in range(25)]
    server.close()
    assert all(r.event.is_set() for r in reqs)          # zero dropped
    assert server.served == 25
    with pytest.raises(RuntimeError):
        server.submit("nn", np.array([0], np.int32))


def test_server_rejects_bad_requests():
    idx = _index(16)
    with EmbeddingServer(idx, batch_size=4, k=3) as server:
        with pytest.raises(ValueError):
            server.submit("nn", np.arange(5, dtype=np.int32))   # > batch
        with pytest.raises(ValueError):
            server.submit("cosmul", np.array([0], np.int32))
        with pytest.raises(ValueError):
            server.neighbors(np.array([0], np.int32), k=99)


def test_server_concurrent_submitters():
    idx = _index(17)
    dense = idx.dense_embeddings()
    errors = []

    def client(seed):
        try:
            rng = np.random.default_rng(seed)
            with_ids = rng.integers(V, size=3).astype(np.int32)
            res = server.neighbors(with_ids, timeout=30.0)
            want_ids, _ = dense_topk(dense, with_ids, k=5)
            assert np.array_equal(res.ids, want_ids)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    with EmbeddingServer(idx, batch_size=8, deadline_ms=2.0,
                         k=5) as server:
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    assert not errors, errors


# -- chaos bar ----------------------------------------------------------------
def test_serve_chaos_ci_schedule_zero_dropped_zero_torn():
    rep = run_serve_chaos(SCHEDULES["ci"], timeout=30.0)
    assert rep["dropped"] == 0, rep
    assert rep["torn"] == 0, rep
    assert rep["errors"] == 0, rep
    assert rep["crashes"] == len(SCHEDULES["ci"].crash_at)
    assert rep["swaps"] >= 2                  # live swap + post-restart swap
    assert rep["steps_served"] >= 2           # answers from >1 snapshot
    assert rep["final_step_served"] == 10 * len(SCHEDULES["ci"].publish_at)
