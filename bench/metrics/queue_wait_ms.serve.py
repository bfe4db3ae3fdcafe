"""The median wait of the window's requests from submit to their batch's
top-k call, the ``repro.server.queue`` intervals, in ms (layer: server,
``serve/server.py``: the queue and the coalescer)."""
from harness import spans


def read(rec):
    return spans.queue_ms(rec)
