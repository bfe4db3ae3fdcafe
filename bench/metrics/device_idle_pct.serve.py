"""Share of the traced serving window in which no operation ran on the
device (layer: device)."""
from harness import readers


def read(rec):
    return readers.idle_pct(rec, "serve")
