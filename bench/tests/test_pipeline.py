"""The pipeline checks on batches made here: in order and at the laws'
rates they read small; reordered, unsubsampled or from the wrong law
they read large."""
import numpy as np
import pytest

from harness import pipeline, zipf

V, T = 3000, 1e-4


def _law():
    law = zipf.ZipfLaw(V, 1.0)
    return law, law.counts(2_000_000)


def _batches(rows, width, negs=None, epoch=0):
    tok = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        tok[i, :len(r)] = r
    lengths = np.array([len(r) for r in rows], np.int32)
    if negs is None:
        negs = np.zeros(tok.shape + (5,), np.int32)
    return [pipeline.Handed(tok, negs, lengths, epoch, 0.0, 0.0)]


def _subsampled(corpus, counts, seed):
    p = pipeline.keep_probs(counts, T)
    rng = np.random.default_rng(seed)
    return [s[rng.random(len(s)) < p[s]] for s in corpus]


@pytest.mark.parametrize("stream", [False, True])
def test_sound_rows_are_in_order_at_the_keep_rate(stream):
    law, counts = _law()
    corpus = (zipf.stream(law, 1, 60_000, 1000) if stream
              else zipf.sentences(law, 1, 60_000, 25, 1000))
    kept = _subsampled(corpus, counts, 2)
    if stream:
        flat = np.concatenate(kept)
        rows = [flat[i:i + 500] for i in range(0, len(flat), 500)]
    else:
        rows = [s for s in kept if len(s) > 1]
    handed = _batches(rows, 500)
    breaks, got, covered = pipeline.order(handed, corpus, stream, V)
    assert breaks == 0
    assert pipeline.subsample_z(got, covered, counts, T) < 5
    # the same rows with two tokens swapped, or kept without subsampling
    swapped = [r.copy() for r in rows]
    swapped[3][[0, 1]] = swapped[3][[1, 0]]
    if swapped[3][0] != rows[3][0]:
        assert pipeline.order(_batches(swapped, 500), corpus, stream,
                              V)[0] > 0
    full = [s for s in corpus if len(s) > 1]
    if stream:
        flat = np.concatenate(corpus)
        full = [flat[i:i + 500] for i in range(0, len(flat), 500)]
    _, got, covered = pipeline.order(_batches(full, 1000), corpus, stream,
                                     V)
    assert pipeline.subsample_z(got, covered, counts, T) > 100


def test_negatives_against_the_law():
    law, counts = _law()
    rng = np.random.default_rng(3)
    targets = law.draw(rng, 40_000).astype(np.int64)
    sound = pipeline.draw_negatives(rng, counts, targets, 5)
    assert not pipeline._conflicts(targets, sound).any()
    rows = [targets[i:i + 400] for i in range(0, len(targets), 400)]

    def handed(negs):
        return _batches(rows, 400, negs.reshape(len(rows), 400, 5))

    conflicts, z = pipeline.negatives(handed(sound), counts,
                                      np.random.default_rng(4))
    assert conflicts == 0 and abs(z) < 5
    wrong = pipeline.draw_negatives(rng, counts ** (1 / 0.75), targets, 5)
    assert pipeline.negatives(handed(wrong), counts,
                              np.random.default_rng(4))[1] > 50
    same = sound.copy()
    same[7, 2] = targets[7]
    assert pipeline.negatives(handed(same), counts,
                              np.random.default_rng(4))[0] == 1
