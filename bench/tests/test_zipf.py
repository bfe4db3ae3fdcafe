import numpy as np

from harness import zipf


def test_bounded_law_has_no_clamp_spike_and_keeps_top_shares():
    law = zipf.ZipfLaw(10_000, 1.1)
    ids = law.draw(np.random.default_rng(0), 2_000_000)
    share = np.bincount(ids, minlength=law.vocab) / ids.size
    # the last id gets what the law gives it, not the mass of the tail
    assert share[-1] < 3 * law.probs[-1] + 1e-5
    np.testing.assert_allclose(share[:5], law.probs[:5], rtol=0.01)
    assert ids.min() >= 0 and ids.max() < law.vocab


def test_the_clamped_generator_would_spike():
    # what the bounded law replaces: ranks past V piled onto id V-1
    ranks = np.random.default_rng(0).zipf(1.1, 500_000)
    clamped = np.minimum(ranks - 1, 10_000 - 1)
    assert np.mean(clamped == 10_000 - 1) > 0.2


def test_counts_follow_the_law_at_corpus_size():
    law = zipf.ZipfLaw(71_290, 1.0)
    counts = law.counts(16_718_845)
    assert counts.min() >= 5          # every row survives min_count=5
    assert abs(counts.sum() - 16_718_845) < 71_290
    assert np.all(np.diff(counts) <= 0)


def test_corpora_are_a_function_of_the_seed():
    law = zipf.ZipfLaw(1000, 1.0)
    a = zipf.sentences(law, 2**33 + 5, 10_000, 25, 1000)
    b = zipf.sentences(law, 2**33 + 5, 10_000, 25, 1000)
    assert len(a) == len(b) and all(np.array_equal(x, y)
                                    for x, y in zip(a, b))
    s = zipf.stream(law, 7, 25_000, 10_000)
    assert [len(x) for x in s] == [10_000, 10_000, 5_000]
    assert zipf.program_seed(2**33 + 5) < 2**31
