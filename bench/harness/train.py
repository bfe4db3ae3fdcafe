"""Training cells: a ``TrainSession`` over a generated corpus, timed with
its host pipeline running.

Set-up builds the corpus (the module of :mod:`harness.layouts` that the
traffic's ``layout`` names) and the session, on a ``("data",)`` mesh over
the cell's chips where it has more than one, then drives the session's
own ``stream()`` through its first ``CHECK_STEPS`` steps (the first
compiles) as the window drives it: no wait on the device between them and
at most ``RUN_AHEAD`` steps in flight. After each, the rows R that the
checked batches touch (all of them are taken from the pipeline before the
second goes on to the session) are gathered on the device
(:mod:`harness.rows`) and fetched to the host only where the window would
wait for that step; after the last, a digest of every row is taken on the
device. Set-up steps on until the pipeline is in its steady state: the
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
its own initialisation on the rows they touch, and :mod:`harness.pipeline`
reads every batch handed over against the generated corpus; the numbers
of :func:`compare`, ``untouched_changed`` and :func:`pipeline.check`
decide ``correct``.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import (checks, device, layouts, pipeline, reference, rows,
                     trace, zipf)

CHECK_STEPS = 3
STEADY_WAIT_S = 0.05
# steps the host may have dispatched ahead of the device: enough that the
# device never waits for a dispatch, few enough that the window ends
# within two steps of its last dispatch
RUN_AHEAD = 2


@jax.jit
def _marker(w_out):
    """One element of a step's output: ready when that step is done."""
    return w_out[0, 0]


def _marked(state):
    """The output table every step writes: the whole of it, or the
    replicated head of a vocab-sharded session."""
    params = state.params()
    return params["w_out"] if "w_out" in params else params["hot_out"]


class Recorder:
    """The session's pipeline, seen through: a span around each wait for
    a batch and around each hand-over to the session, and every batch
    handed over, kept (its arrays, not copies) with when it came. The
    first ``checked`` batches are all taken from the pipeline before the
    second of them goes on to the session, so that the rows they touch
    are known when the state after the first step is read."""

    def __init__(self, inner, checked: int = 0):
        object.__setattr__(self, "_inner", inner)
        object.__setattr__(self, "checked", checked)
        object.__setattr__(self, "handed", [])

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __setattr__(self, name, value):
        setattr(self._inner, name, value)

    def batches(self, *args, **kwargs):
        it = self._inner.batches(*args, **kwargs)
        waiting = collections.deque()

        def take() -> bool:
            t0 = time.perf_counter()
            with trace.span("bench.fetch"):
                batch = next(it, None)
            if batch is None:
                return False
            t1 = time.perf_counter()
            self.handed.append(pipeline.Handed(
                batch.tokens, batch.negs, batch.lengths, batch.epoch,
                t1, t1 - t0))
            waiting.append(batch)
            return True

        try:
            while waiting or take():
                while 1 < len(self.handed) < self.checked and take():
                    pass
                with trace.span("bench.session"):
                    yield waiting.popleft()
        finally:
            it.close()


def make_session(config: dict, traffic: dict, seed: int, devices=None):
    """The corpus of this seed from the traffic's layout, its vocabulary
    (the layout's counts) and a session over the recorded pipeline: on a
    ``("data",)`` mesh over ``devices`` where there are more than one,
    else on the default device as the program places it."""
    from jax.sharding import Mesh
    from repro.configs.w2v import W2VConfig
    from repro.core.trainer import TrainSession
    from repro.data.corpus import Corpus
    from repro.data.prefetch import make_pipeline
    from repro.data.vocab import Vocab

    fields = dict(config["w2v"], **traffic.get("w2v", {}))
    cfg = W2VConfig(**fields, seed=zipf.program_seed(seed),
                    epochs=int(traffic["epochs"]))
    data = layouts.load(traffic["layout"]).corpus(config, traffic, seed,
                                                  cfg)
    if data.counts.min() < cfg.min_count:
        raise ValueError(f"the layout gives ids {int(data.counts.min())} "
                         f"occurrences, under min_count: V is too large")
    vocab = Vocab(ids={i: i for i in range(data.vocab)}, counts=data.counts,
                  total=int(data.counts.sum()))
    pipe = Recorder(make_pipeline(Corpus(data.sentences, data.vocab), cfg,
                                  vocab), CHECK_STEPS)
    mesh = None
    if devices is not None and len(devices) > 1:
        mesh = Mesh(np.array(devices), (rows.AXIS,))
    sess = TrainSession(pipe, cfg, backend=config.get("backend", "auto"),
                        mesh=mesh)
    return sess, pipe, cfg, data


def reference_states(start: dict, kept, lrs, w_f: int, mm=reference.mm_f32,
                     devices=None):
    """The reference's rows before and after each kept batch (on the
    host), from ``start``, the same rows of its initial tables; the kept
    batches' ids index those rows. Over more than one of ``devices``
    each batch trains data parallel, as the cell's chips train it."""
    cur = (jnp.asarray(start["w_in"]), jnp.asarray(start["w_out"]))
    states = [start]
    for (tok, neg, ln), lr in zip(kept, lrs):
        cur = reference.replicated_step(
            *cur, jnp.asarray(tok), jnp.asarray(neg), jnp.asarray(ln),
            jnp.float32(lr), w_f, mm, devices)
        states.append({leaf: rows.fetch(x)
                       for leaf, x in zip(rows.LEAVES, cur)})
    return states


def _norms(a: dict, b: dict) -> dict:
    """Each table's norm of ``a - b``. A row's squares sum in float64,
    the rows with an exactly rounded sum: equal rows add exactly nothing,
    so rows left out, equal on both sides, do not change it."""
    out = {}
    for leaf in rows.LEAVES:
        diff = (a[leaf] - b[leaf]).astype(np.float64)
        out[leaf] = math.sqrt(math.fsum((diff * diff).sum(axis=1)))
    return out


def compare(prog, ref, kept, w_f: int) -> dict:
    """The numbers that decide ``correct``: ``prog`` and ``ref`` are the
    tables' rows that the kept batches touch (each a dict of ``w_in`` and
    ``w_out``) before and after each of the first steps; rows no batch
    touches are equal on both sides and would add nothing.

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
        lp = reference.sgns_loss(prog[k]["w_in"], prog[k]["w_out"], idx)
        lr = reference.sgns_loss(ref[k]["w_in"], ref[k]["w_out"], idx)
        losses.append(abs(lp - lr) / max(abs(lr), 1e-30))
    out["loss_gap"] = max(losses)
    out["grad1_gap"] = checks.norm_gap(_norms(prog[1], prog[0]),
                                       _norms(ref[1], ref[0]))
    out["change3_gap"] = checks.norm_gap(_norms(prog[-1], prog[0]),
                                         _norms(ref[-1], ref[0]))
    gap = 0.0
    for leaf in rows.LEAVES:
        p, r, r0 = prog[-1][leaf], ref[-1][leaf], ref[0][leaf]
        moved = float(np.max(np.abs(r - r0)))
        gap = max(gap, float(np.max(np.abs(p - r))) / max(moved, 1e-30))
    out["table_gap"] = gap
    return out


@dataclasses.dataclass
class Started:
    """A session driven through its first steps: R, the rows the checked
    batches touch, and those rows after each step, the digests of every
    row after the last, the steps' metrics, and what the reference and
    the pipeline checks read."""
    sess: object
    pipe: Recorder
    cfg: object
    stream: object
    reader: rows.StateRows
    ids: np.ndarray
    prog: list
    digests: dict
    warm: list
    seed: int
    data: layouts.Corpus

    def kept(self):
        """The checked steps' batches, cut to their longest sentence."""
        return [tuple(np.ascontiguousarray(a) for a in reference.unpad(
            b.tokens, b.negs, b.lengths))
            for b in self.pipe.handed[:CHECK_STEPS]]


def _touched(handed) -> np.ndarray:
    """R: the rows that the checked batches touch."""
    if len(handed) < CHECK_STEPS:
        raise ValueError(f"an epoch of {len(handed)} batches: the check "
                         f"reads the first {CHECK_STEPS} of one epoch")
    return rows.union(rows.batch_ids(b.tokens, b.negs, b.lengths)
                      for b in handed[:CHECK_STEPS])


def start(config: dict, traffic: dict, seed: int, devices=None) -> Started:
    sess, pipe, cfg, data = make_session(config, traffic, seed, devices)
    reader = rows.StateRows(sess)
    stream = sess.stream()
    warm = [next(stream)]
    ids = _touched(pipe.handed)
    pending, prog = [reader.take(sess.state, ids)], []
    while len(warm) < CHECK_STEPS:
        warm.append(next(stream))
        pending.append(reader.take(sess.state, ids))
        if len(pending) > RUN_AHEAD:
            prog.append(pending.pop(0).host())
    digests = reader.digests(sess.state)
    prog.extend(p.host() for p in pending)
    return Started(sess, pipe, cfg, stream, reader, ids, prog, digests,
                   warm, seed, data)


def check(st: Started, config: dict, traffic: dict, devices) -> dict:
    """The reference's states for the checked batches, compared with the
    program's over the rows R they touch; the rows outside R that changed
    (``untouched_changed``); and the pipeline checks over every batch
    handed over (the session must be gone: this runs on the chip too)."""
    ids = st.ids
    kept = [(rows.positions(ids, t), rows.positions(ids, n), ln)
            for t, n, ln in st.kept()]
    init = rows.Initial(jax.random.PRNGKey(st.cfg.seed), st.data.vocab,
                        st.cfg.dim, devices)
    changed = init.untouched_changed(
        st.reader.in_id_order(st.digests, devices[0]), ids, devices[0])
    begin = init.rows(ids)
    init = st.digests = None
    prog = [begin] + st.prog
    ref = reference_states(begin, kept,
                           [m.lr for m in st.warm[:CHECK_STEPS]],
                           st.cfg.fixed_window, devices=devices)
    numbers = compare(prog, ref, kept, st.cfg.fixed_window)
    numbers["untouched_changed"] = float(changed)
    numbers.update(pipeline.check(
        st.pipe.handed, st.data.sentences, st.data.stream, st.data.counts,
        float(config["w2v"]["subsample_t"]),
        zipf.rng_for(st.seed, zipf.NEGATIVE_TAG)))
    return numbers


def run(cell, seed: int, seconds: float, t_process: float, devices,
        trace_dir=None, log=print) -> dict:
    clock = device.CompileClock()
    st = start(cell.config, cell.traffic, seed, devices)
    sess, stream, warm, handed = st.sess, st.stream, st.warm, st.pipe.handed
    depth = int(getattr(st.pipe, "depth", 1))
    extra = 0
    while handed[-1].wait < STEADY_WAIT_S and extra < depth + 1:
        warm.append(next(stream))
        extra += 1
    # the window starts with the device idle, not with set-up's steps
    _marker(_marked(sess.state)).block_until_ready()
    setup_s = time.perf_counter() - t_process
    log(f"set-up: {setup_s} s, backend {sess.backend}, {len(warm)} steps "
        f"({extra} past the checked ones; the last hand-over waited "
        f"{handed[-1].wait} s), compiles {clock.count} ({clock.seconds} s), "
        f"checked rows {len(st.ids)} of {st.data.vocab}")

    compiles0, steps, ahead = clock.count, [], []
    if trace_dir:
        trace.start(trace_dir)
    with trace.span(trace.WINDOW):
        t0 = time.perf_counter()
        while True:
            steps.append(next(stream))
            ahead.append(_marker(_marked(sess.state)))
            if len(ahead) > RUN_AHEAD:
                with trace.span("bench.ahead"):
                    ahead.pop(0).block_until_ready()
            if (time.perf_counter() - t0 >= seconds
                    and len(steps) % depth == 0):
                break
        with trace.span("bench.block"):
            jax.block_until_ready(sess.state.params())
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
    numbers = check(st, cell.config, cell.traffic, devices)
    return {"setup_s": setup_s, "e2e": {"words_per_s": words / (t1 - t0)},
            "attempted": len(steps), "failed": 0, "numbers": numbers,
            "rec": rec, "memory_peak_bytes": memory}
