"""A run with the timed path broken underneath comes out not correct;
the same run unbroken comes out correct."""
import dataclasses

import pytest

import tiny
from harness import checks, faults


def correct(cell, out):
    return checks.judge(out["numbers"], cell.limits)[0]


@pytest.fixture(params=["w2v-text8.stream", "w2v-1bw.sentences"])
def train_cell(request):
    return tiny.train_cell(request.param)


def test_sound_training_run_is_correct(train_cell):
    out = tiny.run(train_cell)
    assert correct(train_cell, out), out["numbers"]


def _unchanged(real):
    return lambda tables, step, cfg, **kw: tables


def _half_batch(real):
    def step_fn(tables, step, cfg, **kw):
        lengths = step.lengths.at[1::2].set(0)
        return real(tables, dataclasses.replace(step, lengths=lengths), cfg,
                    **kw)
    return step_fn


def _token_altered(real):
    def step_fn(tables, step, cfg, **kw):
        v = tables.w_in.shape[0]
        tokens = step.tokens.at[0, 5].set((step.tokens[0, 5] + 1) % v)
        return real(tables, dataclasses.replace(step, tokens=tokens), cfg,
                    **kw)
    return step_fn


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _token_altered])
def test_broken_training_step_is_not_correct(train_cell, fault,
                                              monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "step", fault(ops.step))
    out = tiny.run(train_cell)
    assert not correct(train_cell, out), out["numbers"]


@pytest.mark.parametrize("fault", sorted(faults.PIPELINE))
def test_broken_host_pipeline_is_not_correct(train_cell, fault):
    """Faults above the session (subsampling off, negatives from the
    wrong law, a token altered where the pipeline produces it): the
    kernel trains what it is handed, so only the pipeline's numbers see
    them."""
    with faults.PIPELINE[fault]():
        out = tiny.run(train_cell)
    assert not correct(train_cell, out), out["numbers"]


def test_sound_serving_run_is_correct():
    cell = tiny.serve_cell()
    out = tiny.run(cell)
    assert correct(cell, out), out["numbers"]


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.serve import server
    real = server.make_topk_fn

    def make(*a, **kw):
        fn = real(*a, **kw)

        def altered(hot, cold, ids):
            out_ids, scores = fn(hot, cold, ids)
            return out_ids.at[:, 0].set((out_ids[:, 0] + 1) % 4000), scores
        return altered

    monkeypatch.setattr(server, "make_topk_fn", make)
    cell = tiny.serve_cell()
    out = tiny.run(cell)
    assert not correct(cell, out), out["numbers"]
