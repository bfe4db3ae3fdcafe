"""``harness.readers.pad_fill_pct``, read in the training cells
that report ``words_per_s``."""
from harness import readers


def read(rec):
    return readers.pad_fill_pct(rec)
