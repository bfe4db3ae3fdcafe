"""Mean host producer work per window batch: the
``repro.pipeline.produce`` span (encode, subsample and pack) keyed by
the batch, in ms (layer: host pipeline, ``data/batching.py``), read in
the sentence-delimited cells, which report ``words_per_s.sentences``."""
from harness import spans


def read(rec):
    return spans.mean_ms(rec, "repro.pipeline.produce")
