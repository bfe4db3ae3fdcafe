"""The median host time of the window's top-k calls, from the call to its
results on the host, the ``repro.server.topk`` spans, in ms (layer:
serving kernels, ``serve/query.py::make_topk_fn`` as the server calls
it)."""
from harness import spans


def read(rec):
    return spans.topk_ms(rec)
