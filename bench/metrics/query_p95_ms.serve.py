"""The 95th percentile of the window's request latencies, each timed
from its due time (layer: server). Its spread between runs of one seed
is wider than an end-to-end bound may be: one host stall of a second
moves it by half, so it is read here, beside the median it explains."""


def read(rec):
    if rec["kind"] != "serve" or "p95_ms" not in rec:
        return None
    return rec["p95_ms"]
