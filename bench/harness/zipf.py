"""Bounded Zipf law and the corpora and query streams drawn from it.

Word ranks follow ``p(r) ∝ r^-a`` for ``r = 1..V``, normalised over the
vocabulary and sampled by inverse CDF, so no tail mass is clamped onto
one id. Ids are ``rank - 1``, which is also the frequency-sorted dense
id the vocabulary gives them.

The vocabulary's counts are the law's expectation at the source corpus
size, not counts of the sample a run draws: subsampling and the
unigram^0.75 negative table then see the frequencies a full corpus
would give them.
"""
from __future__ import annotations

from typing import List

import numpy as np

# domain tags: the corpus, the query ids, the arrival times and the
# checks' samples of one seed are independent streams
(CORPUS_TAG, LENGTH_TAG, QUERY_TAG, ARRIVAL_TAG, SAMPLE_TAG,
 NEGATIVE_TAG) = range(1, 7)


def rng_for(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's own keyed randomness (its RNG keys
    take 32-bit integers; the benchmark's seeds may be larger)."""
    return int(np.random.SeedSequence([int(seed), 0]).generate_state(1)[0]
               & 0x7FFFFFFF)


class ZipfLaw:
    def __init__(self, vocab: int, exponent: float):
        if vocab < 2 or exponent <= 0:
            raise ValueError(f"bad Zipf law: V={vocab}, a={exponent}")
        self.vocab = int(vocab)
        self.exponent = float(exponent)
        w = np.arange(1, self.vocab + 1, dtype=np.float64) ** -self.exponent
        self.probs = w / w.sum()
        self.cdf = np.cumsum(self.probs)
        self.cdf[-1] = 1.0

    def counts(self, corpus_words: int) -> np.ndarray:
        """Expected occurrences of each id in a corpus of that size."""
        return np.maximum(1, np.rint(self.probs * corpus_words)
                          ).astype(np.int64)

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        ids = np.searchsorted(self.cdf, rng.random(n), side="right")
        return np.minimum(ids, self.vocab - 1).astype(np.int32)


def sentences(law: ZipfLaw, seed: int, words: int, mean_len: float,
              max_len: int) -> List[np.ndarray]:
    """Sentences with Poisson lengths (at least 1, at most ``max_len``)
    covering about ``words`` words."""
    n = max(1, int(round(words / mean_len)))
    lens = np.clip(rng_for(seed, LENGTH_TAG).poisson(mean_len, n), 1,
                   max_len)
    flat = law.draw(rng_for(seed, CORPUS_TAG), int(lens.sum()))
    return np.split(flat, np.cumsum(lens)[:-1])


def stream(law: ZipfLaw, seed: int, words: int,
           line_words: int) -> List[np.ndarray]:
    """One undelimited token stream of ``words`` words, cut into lines of
    ``line_words`` only so that it can be handed over as a corpus."""
    flat = law.draw(rng_for(seed, CORPUS_TAG), int(words))
    return np.split(flat, np.arange(line_words, flat.size, line_words))
