"""Training cells: a ``TrainSession`` over a generated corpus, timed with
its host pipeline running.

Set-up builds the corpus and the session, then drives the session's own
``stream()`` through its first ``CHECK_STEPS`` steps (the first compiles)
as the window drives it: no wait on the device between them and at most
``RUN_AHEAD`` steps in flight. The tables after each are copied on the
device and fetched to the host only where the window would wait for that
step. Set-up steps on until the pipeline is in its steady state: the
batch handed over last had to be waited for (the prefetch queue is empty,
as it stays while the host is the bottleneck), or ``depth + 1`` further
steps did not wait (the queue refills faster than the device drains it).
No batch finalized ahead during set-up is then left for the window to
take for free.

The window starts with the device idle, right after a hand-over, and runs
the same ``stream()``; the host may dispatch at most ``RUN_AHEAD`` steps
ahead of the device (the session itself never waits for the device, so
it would otherwise queue steps for as long as the pipeline feeds it). It
stops on the first step boundary after ``seconds`` at which its step
count is a multiple of the prefetch depth: with ``depth`` batches in
flight the pipeline hands batches over in ``depth`` interleaved lanes
(batch k + depth starts when batch k is handed over), which may bunch, and
whole rounds hold whole cycles of every lane whatever their phases. It
ends when the tables are ready. Its words are the real words of the
batches its steps trained, counted from each batch's ``lengths``.

Once it has closed, the reference retrains the first steps' batches from
its own initialisation, and :mod:`harness.pipeline` reads every batch
handed over against the generated corpus; the numbers of :func:`compare`
and :func:`pipeline.check` decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import checks, device, pipeline, reference, trace, zipf

CHECK_STEPS = 3
STEADY_WAIT_S = 0.05
# steps the host may have dispatched ahead of the device: enough that the
# device never waits for a dispatch, few enough that the window ends
# within two steps of its last dispatch
RUN_AHEAD = 2
LEAVES = ("w_in", "w_out")


@jax.jit
def _marker(w_out):
    """One element of a step's output: ready when that step is done."""
    return w_out[0, 0]


@jax.jit
def _copy(w_in, w_out):
    return jnp.copy(w_in), jnp.copy(w_out)


class Recorder:
    """The session's pipeline, seen through: a span around each wait for
    a batch and around each hand-over to the session, and every batch
    handed over, kept (its arrays, not copies) with when it came."""

    def __init__(self, inner):
        object.__setattr__(self, "_inner", inner)
        object.__setattr__(self, "handed", [])

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __setattr__(self, name, value):
        setattr(self._inner, name, value)

    def batches(self, *args, **kwargs):
        it = self._inner.batches(*args, **kwargs)
        try:
            while True:
                t0 = time.perf_counter()
                with trace.span("bench.fetch"):
                    batch = next(it, None)
                if batch is None:
                    return
                t1 = time.perf_counter()
                self.handed.append(pipeline.Handed(
                    batch.tokens, batch.negs, batch.lengths, batch.epoch,
                    t1, t1 - t0))
                with trace.span("bench.session"):
                    yield batch
        finally:
            it.close()


def make_session(config: dict, traffic: dict, seed: int):
    """The corpus of this seed, its vocabulary (counts from the law at
    the source corpus size) and a session over the recorded pipeline."""
    from repro.configs.w2v import W2VConfig
    from repro.core.trainer import TrainSession
    from repro.data.corpus import Corpus
    from repro.data.prefetch import make_pipeline
    from repro.data.vocab import Vocab

    v = int(config["vocab_size"])
    law = zipf.ZipfLaw(v, config["zipf_exponent"])
    counts = law.counts(int(config["corpus_words"]))
    fields = dict(config["w2v"], **traffic.get("w2v", {}))
    if counts.min() < fields["min_count"]:
        raise ValueError(f"the law gives ids {int(counts.min())} "
                         f"occurrences, under min_count: V is too large")
    cfg = W2VConfig(**fields, seed=zipf.program_seed(seed),
                    epochs=int(traffic["epochs"]))
    if traffic["layout"] == "sentences":
        sents = zipf.sentences(law, seed, traffic["run_words"],
                               traffic["mean_sentence_len"],
                               cfg.max_sentence_len)
    elif traffic["layout"] == "stream":
        sents = zipf.stream(law, seed, traffic["run_words"],
                            traffic["line_words"])
    else:
        raise ValueError(f"unknown corpus layout {traffic['layout']!r}")
    vocab = Vocab(ids={i: i for i in range(v)}, counts=counts,
                  total=int(counts.sum()))
    pipe = Recorder(make_pipeline(Corpus(sents, v), cfg, vocab))
    sess = TrainSession(pipe, cfg, backend=config.get("backend", "auto"))
    return sess, pipe, cfg, sents, counts


def _tables(sess):
    return (sess.state.w_in, sess.state.w_out)


def _snapshot(tables):
    """The tables on the host."""
    return tuple(np.asarray(x) for x in tables)


def _copy_out(tables):
    """A copy of the tables on the device, already on its way to the
    host: the step after may take the originals."""
    out = _copy(*tables)
    for x in out:
        x.copy_to_host_async()
    return out


def reference_states(seed: int, vocab: int, dim: int, w_f: int, kept, lrs,
                     mm=reference.mm_f32):
    """The reference's tables before and after each kept batch (on the
    host), from its own initialisation with the program's seed."""
    cur = reference.init_tables(jax.random.PRNGKey(seed), vocab, dim)
    states = [_snapshot(cur)]
    for (tok, neg, ln), lr in zip(kept, lrs):
        cur = reference.sgns_step(cur[0], cur[1], jnp.asarray(tok),
                                  jnp.asarray(neg), jnp.asarray(ln),
                                  jnp.float32(lr), w_f, mm)
        states.append(_snapshot(cur))
    return states


def _norms(a, b):
    return {leaf: float(np.linalg.norm((x - y).astype(np.float64)))
            for leaf, x, y in zip(LEAVES, a, b)}


def compare(prog, ref, kept, w_f: int) -> dict:
    """The numbers that decide ``correct``: ``prog`` and ``ref`` are the
    tables before and after each of the first steps.

    * ``loss_gap``: the largest relative gap, over the steps, between the
      SGNS loss of the step's batch at the program's tables after it and
      at the reference's;
    * ``grad1_gap``: the first step's change (its gradient times the
      learning rate), as a norm gap of the worst leaf;
    * ``change3_gap``: the same for the change after the last step;
    * ``table_gap``: the largest elementwise difference between the
      tables after the last step, over the reference's largest change.
    """
    out = {}
    losses = []
    for k, batch in enumerate(kept, start=1):
        idx = reference.window_index(*batch, w_f)
        lp = reference.sgns_loss(*prog[k], idx)
        lr = reference.sgns_loss(*ref[k], idx)
        losses.append(abs(lp - lr) / max(abs(lr), 1e-30))
    out["loss_gap"] = max(losses)
    out["grad1_gap"] = checks.norm_gap(_norms(prog[1], prog[0]),
                                       _norms(ref[1], ref[0]))
    out["change3_gap"] = checks.norm_gap(_norms(prog[-1], prog[0]),
                                         _norms(ref[-1], ref[0]))
    gap = 0.0
    for p, r, r0 in zip(prog[-1], ref[-1], ref[0]):
        moved = float(np.max(np.abs(r - r0)))
        gap = max(gap, float(np.max(np.abs(p - r))) / max(moved, 1e-30))
    out["table_gap"] = gap
    return out


@dataclasses.dataclass
class Started:
    """A session driven through its first steps: the tables before and
    after each of them, the steps' metrics, and what the reference and
    the pipeline checks read."""
    sess: object
    pipe: Recorder
    cfg: object
    stream: object
    prog: list
    warm: list
    seed: int
    corpus: list
    counts: np.ndarray

    def kept(self):
        """The checked steps' batches, cut to their longest sentence."""
        return [tuple(np.ascontiguousarray(a) for a in reference.unpad(
            b.tokens, b.negs, b.lengths))
            for b in self.pipe.handed[:CHECK_STEPS]]


def start(config: dict, traffic: dict, seed: int) -> Started:
    sess, pipe, cfg, corpus, counts = make_session(config, traffic, seed)
    stream = sess.stream()
    prog = [_snapshot(_tables(sess))]
    warm, pending = [], []
    for _ in range(CHECK_STEPS):
        warm.append(next(stream))
        pending.append(_copy_out(_tables(sess)))
        if len(pending) > RUN_AHEAD:
            prog.append(_snapshot(pending.pop(0)))
    prog.extend(_snapshot(p) for p in pending)
    return Started(sess, pipe, cfg, stream, prog, warm, seed, corpus, counts)


def check(st: Started, config: dict, traffic: dict) -> dict:
    """The reference's states for the checked batches, compared with the
    program's, and the pipeline checks over every batch handed over (the
    session must be gone: this runs on the chip too)."""
    kept = st.kept()
    ref = reference_states(st.cfg.seed, int(config["vocab_size"]),
                           st.cfg.dim, st.cfg.fixed_window, kept,
                           [m.lr for m in st.warm[:CHECK_STEPS]])
    numbers = compare(st.prog, ref, kept, st.cfg.fixed_window)
    numbers.update(pipeline.check(
        st.pipe.handed, st.corpus, traffic["layout"] == "stream",
        st.counts, float(config["w2v"]["subsample_t"]),
        zipf.rng_for(st.seed, zipf.NEGATIVE_TAG)))
    return numbers


def run(cell, seed: int, seconds: float, t_process: float, devices,
        trace_dir=None, log=print) -> dict:
    clock = device.CompileClock()
    st = start(cell.config, cell.traffic, seed)
    sess, stream, warm, handed = st.sess, st.stream, st.warm, st.pipe.handed
    depth = int(getattr(st.pipe, "depth", 1))
    extra = 0
    while handed[-1].wait < STEADY_WAIT_S and extra < depth + 1:
        warm.append(next(stream))
        extra += 1
    # the window starts with the device idle, not with set-up's steps
    _marker(sess.state.w_out).block_until_ready()
    setup_s = time.perf_counter() - t_process
    log(f"set-up: {setup_s} s, backend {sess.backend}, {len(warm)} steps "
        f"({extra} past the checked ones; the last hand-over waited "
        f"{handed[-1].wait} s), compiles {clock.count} ({clock.seconds} s)")

    compiles0, steps, ahead = clock.count, [], []
    if trace_dir:
        trace.start(trace_dir)
    with trace.span(trace.WINDOW):
        t0 = time.perf_counter()
        while True:
            steps.append(next(stream))
            ahead.append(_marker(sess.state.w_out))
            if len(ahead) > RUN_AHEAD:
                with trace.span("bench.ahead"):
                    ahead.pop(0).block_until_ready()
            if (time.perf_counter() - t0 >= seconds
                    and len(steps) % depth == 0):
                break
        with trace.span("bench.block"):
            jax.block_until_ready(_tables(sess))
        t1 = time.perf_counter()
    if trace_dir:
        trace.stop()
    stream.close()
    memory = device.peak_bytes(devices)
    first = len(warm)
    trained = handed[first:first + len(steps)]
    words = sum(b.words for b in trained)
    gaps = np.diff([b.t for b in handed[first:first + len(steps) + 1]])
    log(f"window: {len(steps)} steps, {words} words in {t1 - t0} s; "
        f"hand-over intervals {np.round(gaps, 3).tolist()} s; "
        f"compiles inside the window: {clock.count - compiles0}")
    # the session's waits inside the window: each step's call waits for
    # the batch after it (a call that ends an epoch waits for none)
    waits = [b.wait for b in handed[first + 1:first + len(steps) + 1]]
    waits += [0.0] * (len(steps) - len(waits))
    rec = {
        "kind": "train", "window_s": t1 - t0, "chips": len(devices),
        "config": cell.config,
        "steps": [{"words": b.words, "positions": int(b.tokens.size),
                   "fetch_s": w} for b, w in zip(trained, waits)],
    }
    # free the program's state before the reference runs
    st.sess = st.stream = sess = stream = None
    numbers = check(st, cell.config, cell.traffic)
    return {"setup_s": setup_s, "e2e": {"words_per_s": words / (t1 - t0)},
            "attempted": len(steps), "failed": 0, "numbers": numbers,
            "rec": rec, "memory_peak_bytes": memory}
