"""Share of the traced training window in which no operation ran on the
device (layer: device), read in the training cells that report
``words_per_s``."""
from harness import readers


def read(rec):
    return readers.idle_pct(rec, "train")
