"""Share of the traced training window in which no operation ran on the
device (layer: device), read in the sentence-delimited cells, which
report ``words_per_s.sentences``."""
from harness import readers


def read(rec):
    return readers.idle_pct(rec, "train")
