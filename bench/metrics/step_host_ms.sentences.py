"""Mean host time of the session per window step: the batch's
``repro.session.put`` (its lift onto the device) plus its
``repro.session.dispatch`` (the ``ops.step`` call), in ms (layer:
session, ``core/trainer.py``), read in the sentence-delimited cells,
which report ``words_per_s.sentences``."""
from harness import spans


def read(rec):
    return spans.mean_ms(rec, "repro.session.put",
                         "repro.session.dispatch")
