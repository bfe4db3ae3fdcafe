"""``harness.readers.mfu_pct``, read in the sentence-delimited
cells, which report ``words_per_s.sentences``."""
from harness import readers


def read(rec):
    return readers.mfu_pct(rec)
