"""Checks of the batches the host pipeline handed to the session, against
the corpus the harness generated and the laws the configuration states.

* ``order_breaks``: rows whose real tokens are not an in-order
  subsequence of the corpus. In the sentence layout a row lies in one
  sentence, after the previous row's (or continues it, after a full
  row); in the stream layout it continues the corpus from where the
  previous row ended, each token within ``LOOKAHEAD`` positions of the
  last. Each epoch starts again at the corpus's start.
* ``subsample_z``: over the corpus the rows covered, the words kept in
  each bin of ranks against word2vec's keep probability
  ``min(1, sqrt(t / f))`` (Mikolov et al. 2013) at the vocabulary's
  frequencies: the largest ``|kept - expected| / sqrt(variance + 1)``.
* ``neg_conflicts``: real windows whose negatives include their target, a
  repeat, or an id outside the vocabulary.
* ``neg_chi2``: the negatives of a sample of real windows, drawn from the
  seed, against the harness's own draws for the same targets (unigram^0.75
  at the vocabulary's counts, every slot that equals the target or an
  earlier slot drawn again until none does): a two-sample chi-square over
  bins of ranks, as a z-score.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

# corpus positions between two kept tokens of a row in the stream layout:
# a sound run drops ~40% of positions, so a gap of 256 has odds ~0.4^256
LOOKAHEAD = 256
# sentences a row may skip: a sentence subsampled to fewer than two words
# gives no row (odds ~3e-6 a sentence at 1BW's lengths)
SKIP_SENTENCES = 4
NEG_SAMPLE_WINDOWS = 400_000
NEG_POWER = 0.75


@dataclasses.dataclass
class Handed:
    """One batch as the pipeline handed it to the session (its arrays,
    not copies), with when it came and how long the session waited."""
    tokens: np.ndarray
    negs: np.ndarray
    lengths: np.ndarray
    epoch: int
    t: float
    wait: float

    @property
    def words(self) -> int:
        return int(self.lengths.sum())


def keep_probs(counts: np.ndarray, t: float) -> np.ndarray:
    f = counts / counts.sum()
    return np.minimum(1.0, np.sqrt(t / f))


def rank_bins(vocab: int, ratio: float, singles: int) -> np.ndarray:
    """Bin edges over ids (``rank - 1``): the first ``singles`` ranks one
    a bin, then bins growing by ``ratio``, the last ending at ``vocab``."""
    edges = list(range(min(singles, vocab) + 1))
    while edges[-1] < vocab:
        edges.append(min(vocab, max(edges[-1] + 1,
                                    int(edges[-1] * ratio))))
    return np.asarray(edges)


def _match(row: List[int], flat: List[int], j: int, end: int,
           lookahead: int):
    """Greedy in-order match of ``row`` in ``flat[j:end]``, each token
    within ``lookahead`` of the last: the position after the last match,
    or None."""
    index = flat.index
    try:
        for x in row:
            j = index(x, j, j + lookahead) + 1
    except ValueError:
        return None
    return j if j <= end else None


def order(handed: Sequence[Handed], corpus: Sequence[np.ndarray],
          stream: bool, vocab: int):
    """``(order_breaks, kept, covered)``: rows out of order, and the
    counts per id of the rows' tokens and of the corpus they covered."""
    flat_np = np.concatenate(corpus)
    flat = flat_np.tolist()
    ends = (np.array([len(flat)]) if stream
            else np.cumsum([len(s) for s in corpus]))
    starts = np.concatenate([[0], ends[:-1]])
    matched = [np.zeros(0, np.int32)]
    covered = np.zeros(vocab, np.int64)
    breaks, epoch, q, j, full = 0, None, -1, 0, False

    def close():
        if epoch is not None and q >= 0:
            covered[:] += np.bincount(flat_np[:j if stream else ends[q]],
                                      minlength=vocab)

    for b in handed:
        if b.epoch != epoch:
            close()
            epoch, q, j, full = b.epoch, (0 if stream else -1), 0, False
        width = b.tokens.shape[1]
        for row_np, ln in zip(b.tokens, b.lengths):
            if ln <= 0:
                continue
            row = row_np[:ln].tolist()
            if stream:
                tries = [(0, j, ends[0], LOOKAHEAD)]
            else:
                tries = [(q, j, ends[q], len(flat))] if full else []
                tries += [(s, starts[s], ends[s], len(flat))
                          for s in range(q + 1, min(len(ends),
                                                    q + 1 + SKIP_SENTENCES))]
            for s, start, end, look in tries:
                got = _match(row, flat, start, end, look)
                if got is not None:
                    q, j, full = s, got, ln == width
                    matched.append(row_np[:ln])
                    break
            else:
                breaks += 1
    close()
    return breaks, np.bincount(np.concatenate(matched), minlength=vocab), \
        covered


def subsample_z(kept: np.ndarray, covered: np.ndarray, counts: np.ndarray,
                t: float) -> float:
    p = keep_probs(counts, t)
    mean, var = covered * p, covered * p * (1.0 - p)
    worst = 0.0
    edges = rank_bins(len(counts), 2.0, 1)
    for lo, hi in zip(edges[:-1], edges[1:]):
        z = (kept[lo:hi].sum() - mean[lo:hi].sum()) / np.sqrt(
            var[lo:hi].sum() + 1.0)
        worst = max(worst, abs(float(z)))
    return worst


def _conflicts(targets: np.ndarray, negs: np.ndarray) -> np.ndarray:
    """(M, N) bool: a slot equal to its window's target or to an earlier
    slot of the window."""
    bad = negs == targets[:, None]
    for k in range(1, negs.shape[1]):
        bad[:, k] |= (negs[:, k:k + 1] == negs[:, :k]).any(1)
    return bad


def draw_negatives(rng: np.random.Generator, counts: np.ndarray,
                   targets: np.ndarray, n: int) -> np.ndarray:
    """``n`` negatives per target from unigram^0.75 by inverse CDF, every
    slot that equals the target or an earlier slot drawn again."""
    cdf = np.cumsum(counts.astype(np.float64) ** NEG_POWER)
    cdf /= cdf[-1]
    top = len(counts) - 1

    def draw(shape):
        # sorted uniforms search fast; a permutation makes them iid again
        u = np.sort(rng.random(int(np.prod(shape))))
        ids = np.minimum(np.searchsorted(cdf, u, side="right"), top)
        return rng.permutation(ids).reshape(shape)

    negs = draw((len(targets), n))
    for _ in range(1000):
        bad = _conflicts(targets, negs)
        if not bad.any():
            return negs
        negs = np.where(bad, draw(negs.shape), negs)
    raise RuntimeError("negatives did not become distinct")


def negatives(handed: Sequence[Handed], counts: np.ndarray,
              rng: np.random.Generator):
    """``(neg_conflicts, neg_chi2)`` over the handed batches."""
    vocab = len(counts)
    total = sum(b.words for b in handed)
    take = rng.random(total) < NEG_SAMPLE_WINDOWS / max(1, total)
    conflicts, tg, ng, at = 0, [], [], 0
    for b in handed:
        real = np.arange(b.tokens.shape[1])[None, :] < b.lengths[:, None]
        t, n = b.tokens[real], b.negs[real]
        bad = _conflicts(t, n).any(1) | ((n < 0) | (n >= vocab)).any(1)
        conflicts += int(bad.sum())
        pick = take[at:at + len(t)]
        at += len(t)
        tg.append(t[pick])
        ng.append(n[pick])
    targets, prog = np.concatenate(tg), np.concatenate(ng)
    own = draw_negatives(rng, counts, targets, prog.shape[1])
    edges = rank_bins(vocab, 1.5, 32)
    x, y = (np.bincount(np.searchsorted(edges, a.ravel(), side="right") - 1,
                        minlength=len(edges) - 1)
            for a in (np.clip(prog, 0, vocab - 1), own))
    seen = (x + y) > 0
    chi2 = float((((x - y) ** 2)[seen] / (x + y)[seen]).sum())
    df = max(1, int(seen.sum()) - 1)
    return conflicts, float((chi2 - df) / np.sqrt(2.0 * df))


def check(handed: Sequence[Handed], corpus: Sequence[np.ndarray],
          stream: bool, counts: np.ndarray, t: float,
          rng: np.random.Generator) -> dict:
    breaks, kept, covered = order(handed, corpus, stream, len(counts))
    conflicts, chi2 = negatives(handed, counts, rng)
    return {"order_breaks": float(breaks),
            "subsample_z": subsample_z(kept, covered, counts, t),
            "neg_conflicts": float(conflicts), "neg_chi2": chi2}
