"""The readers of the program's spans and counters scope to the window:
the newest steps or batches, joined by key, and None where the rings
hold fewer records than the window has or the program has none."""
import pytest

from harness import spans, spec
from repro import tracing

MS = 1_000_000


def train_rings():
    """Five trained batches; the last two are the window's. Batch (0, 5)
    was finalized ahead, past the window's end, and must not count."""
    tr = tracing.Tracer()
    for i in range(6):
        key = (0, i)
        tr.interval("repro.pipeline.produce", 0, (i + 1) * MS, key=key)
        tr.interval("repro.pipeline.finalize", 0, 10 * (i + 1) * MS,
                    key=key)
        tr.count("repro.neg.drawn", 1000 * (i + 1), key=key)
        if i == 5:
            break
        tr.interval("repro.session.put", 0, 2 * MS, key=key)
        tr.interval("repro.session.dispatch", 0, 3 * MS, key=key)
        tr.interval("repro.session.step", 0, 4 * MS, key=key)
    return tr


def train_rec(n):
    return {"kind": "train", "steps": [{"words": 100}] * n}


def serve_rings():
    """Four batches of two requests each; the last three are the
    window's."""
    tr = tracing.Tracer()
    for b in range(4):
        for j in range(2):
            r = 2 * b + j
            tr.interval("repro.server.queue", 0, (r + 1) * MS, key=r,
                        batch=b)
        tr.interval("repro.server.topk", 0, (b + 1) * 10 * MS, key=b,
                    requests=2)
    return tr


def serve_rec(n):
    return {"kind": "serve", "batches": n}


@pytest.mark.parametrize("metric,rec,want", [
    ("produce_ms.sentences", train_rec(2), (4 + 5) / 2),
    ("finalize_ms.sentences", train_rec(2), (40 + 50) / 2),
    ("neg_draws_per_word.sentences", train_rec(2), (4000 + 5000) / 200),
    ("step_host_ms.sentences", train_rec(2), 5.0),
    # requests 2..7: waits 3..8 ms
    ("queue_wait_ms.serve", serve_rec(3), 5.5),
    ("topk_host_ms.serve", serve_rec(3), 30.0),
])
def test_reader_scopes_to_the_window(monkeypatch, metric, rec, want):
    tr = train_rings() if rec["kind"] == "train" else serve_rings()
    monkeypatch.setattr(spans, "_tracing", lambda: tr)
    assert spec.reader(metric)(rec) == pytest.approx(want)


@pytest.mark.parametrize("read,n,want", [
    (lambda r: spans.mean_ms(r, "repro.pipeline.produce"), 3,
     (3 + 4 + 5) / 3),
    (lambda r: spans.mean_ms(r, "repro.pipeline.finalize"), 1, 50.0),
    (lambda r: spans.per_word(r, "repro.neg.drawn"), 3,
     (3000 + 4000 + 5000) / 300),
    (lambda r: spans.mean_ms(r, "repro.session.put",
                             "repro.session.dispatch",
                             "repro.session.step"), 5, 9.0),
])
def test_helper_scopes_to_the_newest_steps(monkeypatch, read, n, want):
    """The shared helpers take the window's size from the record alone:
    the newest ``n`` steps, and never batch (0, 5), finalized ahead."""
    tr = train_rings()
    monkeypatch.setattr(spans, "_tracing", lambda: tr)
    assert read(train_rec(n)) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "produce_ms.sentences", "finalize_ms.sentences",
    "neg_draws_per_word.sentences", "step_host_ms.sentences",
    "queue_wait_ms.serve", "topk_host_ms.serve"])
def test_reader_is_none_with_too_few_records(monkeypatch, metric):
    serve = metric.endswith(".serve")
    tr = serve_rings() if serve else train_rings()
    monkeypatch.setattr(spans, "_tracing", lambda: tr)
    read = spec.reader(metric)
    assert read(serve_rec(5) if serve else train_rec(6)) is None
    # the program has no tracing module: nothing to read, nothing raised
    monkeypatch.setattr(spans, "_tracing", lambda: None)
    assert read(serve_rec(3) if serve else train_rec(2)) is None


def test_reader_is_none_where_a_batch_lost_its_record(monkeypatch):
    """A window step whose pipeline records left the ring, or a window
    request whose wait did, makes no reading."""
    tr = tracing.Tracer(ring=2)
    for i in range(3):
        tr.interval("repro.pipeline.produce", 0, MS, key=(0, i))
    tr.interval("repro.session.step", 0, MS, key=(0, 0))
    tr.interval("repro.session.step", 0, MS, key=(0, 1))
    monkeypatch.setattr(spans, "_tracing", lambda: tr)
    assert spec.reader("produce_ms.sentences")(train_rec(2)) is None
    tr = tracing.Tracer()
    tr.interval("repro.server.queue", 0, MS, key=0, batch=0)
    tr.interval("repro.server.topk", 0, MS, key=0, requests=2)
    monkeypatch.setattr(spans, "_tracing", lambda: tr)
    assert spec.reader("queue_wait_ms.serve")(serve_rec(1)) is None
