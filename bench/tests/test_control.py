"""The control: the plain reference, one precision step down from what
the configuration states, in the program's place.

On the chip, at the cells' own sizes, it reads far above each training
limit (``bench/limits/*.json`` records the readings; PERF.md gives them
per seed). Its training error compounds over the ~10^5 sequential
windows of a batch, so at a size a CPU test holds it reads 10^3 times
less: there the test shows that every number it moves separates it from
the sound run, which reads exactly 0, and that each limit lies between
the readings it was set from. The serving control separates at any size
and must come out not correct.
"""
import dataclasses
import json
import os

import pytest

import tiny
from harness import checks, reference

LIMITS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "limits")


def test_training_control_separates_from_the_sound_run(monkeypatch):
    from repro.kernels import ops

    def control(tables, step, cfg, **kw):
        w_in, w_out = reference.sgns_step(
            tables.w_in, tables.w_out, step.tokens, step.negs, step.lengths,
            step.lr, cfg.fixed_window, reference.mm_bf16x3)
        return dataclasses.replace(tables, w_in=w_in, w_out=w_out)

    cell = tiny.train_cell()
    sound = tiny.run(cell)["numbers"]
    monkeypatch.setattr(ops, "step", control)
    got = tiny.run(cell)["numbers"]
    kernel = ("loss_gap", "grad1_gap", "change3_gap", "table_gap")
    assert all(sound[k] == 0.0 for k in kernel), sound
    assert got["table_gap"] > 0 and got["grad1_gap"] > 0, got


@pytest.mark.parametrize("cell", ["w2v-text8.stream", "w2v-1bw.sentences",
                                  "w2v-1bw.serve"])
def test_each_limit_lies_between_its_readings(cell):
    with open(os.path.join(LIMITS, cell + ".json")) as f:
        limits = json.load(f)
    for name, spec in limits.items():
        if "upper" in spec:
            assert spec["lower"] <= spec["limit"] < spec["upper"], name
            assert spec["upper"] >= 3 * spec["lower"], name


def test_serving_control_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serve import server

    def make(placement, mesh, mode="nn", k=5):
        @jax.jit
        def control(hot, cold, ids):
            table = jnp.concatenate([hot, cold])
            sc = reference.mm_fp8(table[ids], table.T)
            sc = jnp.where(jnp.arange(table.shape[0])[None, :]
                           == ids[:, None], -jnp.inf, sc)
            top_s, top_i = jax.lax.top_k(sc, k)
            return top_i, top_s
        return control

    monkeypatch.setattr(server, "make_topk_fn", make)
    cell = tiny.serve_cell()
    out = tiny.run(cell)
    assert not checks.judge(out["numbers"], cell.limits)[0], out["numbers"]
