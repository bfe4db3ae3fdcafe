"""``harness.readers.host_wait_pct``, read in the training cells
that report ``words_per_s``."""
from harness import readers


def read(rec):
    return readers.host_wait_pct(rec)
