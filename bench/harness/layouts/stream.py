"""One undelimited token stream from a bounded Zipf law, ``run_words``
words handed over in lines of ``line_words`` and packed by the program
into full rows; the vocabulary's counts are the law's at the source
corpus size (the configuration's ``zipf_exponent`` and ``corpus_words``).
"""
from __future__ import annotations

from harness import zipf
from harness.layouts import Corpus


def corpus(config: dict, traffic: dict, seed: int, cfg) -> Corpus:
    v = int(config["vocab_size"])
    law = zipf.ZipfLaw(v, config["zipf_exponent"])
    sents = zipf.stream(law, seed, traffic["run_words"],
                        traffic["line_words"])
    return Corpus(sents, v, law.counts(int(config["corpus_words"])),
                  stream=True)
