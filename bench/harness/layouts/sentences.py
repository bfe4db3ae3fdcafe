"""Sentence-delimited text from a bounded Zipf law: Poisson lengths
around ``mean_sentence_len`` (at most ``max_sentence_len``), about
``run_words`` words; the vocabulary's counts are the law's at the source
corpus size (the configuration's ``zipf_exponent`` and ``corpus_words``).
"""
from __future__ import annotations

from harness import zipf
from harness.layouts import Corpus


def corpus(config: dict, traffic: dict, seed: int, cfg) -> Corpus:
    v = int(config["vocab_size"])
    law = zipf.ZipfLaw(v, config["zipf_exponent"])
    sents = zipf.sentences(law, seed, traffic["run_words"],
                           traffic["mean_sentence_len"],
                           cfg.max_sentence_len)
    return Corpus(sents, v, law.counts(int(config["corpus_words"])),
                  stream=False)
