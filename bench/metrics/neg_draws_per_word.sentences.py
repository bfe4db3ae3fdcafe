"""Negative ids drawn for the window's batches (``repro.neg.drawn``:
each rejection round redraws the whole block, padding included) per real
word they hold; N when every position is real and no round repeats
(layer: host pipeline, ``data/negatives.py``), read in the
sentence-delimited cells, which report ``words_per_s.sentences``."""
from harness import spans


def read(rec):
    return spans.per_word(rec, "repro.neg.drawn")
