"""The training check reads the rows the checked steps touch, R, and
nothing larger: its numbers equal the whole tables' numbers, its host
arrays hold |R| rows, a write outside R shows as ``untouched_changed``,
and corpus layouts are found by name."""
import dataclasses
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import checks, faults, layouts, reference, rows, train
from test_faults import _half_batch, _token_altered, _unchanged

KERNEL = ("loss_gap", "grad1_gap", "change3_gap", "table_gap")
SEED = 2**31 + 17


class WholeTables:
    """The program's whole tables, copied to the host where the check
    reads its rows: before the first step, and after each checked one."""

    def __init__(self, monkeypatch):
        self.states = []
        real_init, real_take = rows.StateRows.__init__, rows.StateRows.take
        states = self.states

        def init(reader, sess):
            real_init(reader, sess)
            states.append(self._copy(sess.state))

        def take(reader, state, ids):
            states.append(self._copy(state))
            return real_take(reader, state, ids)

        monkeypatch.setattr(rows.StateRows, "__init__", init)
        monkeypatch.setattr(rows.StateRows, "take", take)

    @staticmethod
    def _copy(state):
        params = state.params()
        return {leaf: np.array(params[leaf]) for leaf in rows.LEAVES}


def whole_table_numbers(st, prog):
    """The kernel's four numbers as the check computed them over whole
    tables: the program's tables against the reference's, both from the
    full initial draw."""
    vocab, dim = st.data.vocab, st.cfg.dim
    w_in, w_out = reference.init_tables(jax.random.PRNGKey(st.cfg.seed),
                                        vocab, dim)
    begin = {"w_in": np.asarray(w_in), "w_out": np.asarray(w_out)}
    kept = st.kept()
    ref = train.reference_states(begin, kept,
                                 [m.lr for m in st.warm[:train.CHECK_STEPS]],
                                 st.cfg.fixed_window)
    return train.compare(prog, ref, kept, st.cfg.fixed_window)


def both_ways(cell, monkeypatch):
    """The check's numbers, and the whole tables' numbers of the same
    run."""
    whole = WholeTables(monkeypatch)
    devices = jax.devices()[:1]
    st = train.start(cell.config, cell.traffic, SEED, devices)
    st.stream.close()
    st.sess = st.stream = None
    numbers = train.check(st, cell.config, cell.traffic, devices)
    return numbers, whole_table_numbers(st, whole.states), len(st.ids)


@pytest.fixture(params=["w2v-text8.stream", "w2v-1bw.sentences"])
def train_cell(request):
    return tiny.train_cell(request.param)


@pytest.mark.parametrize("fault", [None, _unchanged, _half_batch,
                                   _token_altered])
def test_row_numbers_equal_whole_table_numbers(train_cell, fault,
                                               monkeypatch):
    if fault is not None:
        from repro.kernels import ops
        monkeypatch.setattr(ops, "step", fault(ops.step))
    got, whole, n_rows = both_ways(train_cell, monkeypatch)
    assert n_rows < train_cell.config["vocab_size"]
    assert checks.judge(got, train_cell.limits)[0] == (fault is None)
    if got["untouched_changed"]:
        # the altered token is a row outside R (1BW's tiny cell): the
        # whole tables see its write in the gaps, the rows in the count
        assert fault is _token_altered
        assert {k: got[k] for k in KERNEL} != whole
    else:
        assert {k: got[k] for k in KERNEL} == whole


@pytest.mark.parametrize("fault", sorted(faults.PIPELINE))
def test_row_numbers_equal_under_pipeline_faults(train_cell, fault,
                                                 monkeypatch):
    with faults.PIPELINE[fault]():
        got, whole, _ = both_ways(train_cell, monkeypatch)
    assert {k: got[k] for k in KERNEL} == whole


def test_a_write_outside_the_rows_is_counted(train_cell, monkeypatch):
    """The stray write is outside R: the whole tables see it, the rows do
    not, and ``untouched_changed`` counts it."""
    with faults.stray_write():
        got, whole, _ = both_ways(train_cell, monkeypatch)
    assert all(got[k] == 0.0 for k in KERNEL), got
    assert whole["table_gap"] > 1.0, whole
    assert got["untouched_changed"] == 1
    assert not checks.judge(got, train_cell.limits)[0]


def test_host_holds_no_more_than_the_touched_rows(monkeypatch):
    cell = tiny.train_cell("w2v-text8.stream")
    cell = dataclasses.replace(cell, config=dict(
        cell.config, vocab_size=200_000, corpus_words=20_000_000,
        w2v=dict(cell.config["w2v"], dim=8)))
    fetched = []
    real_fetch = rows.fetch

    def fetch(x):
        out = real_fetch(x)
        fetched.append(out.shape)
        return out
    monkeypatch.setattr(rows, "fetch", fetch)
    devices = jax.devices()[:1]
    st = train.start(cell.config, cell.traffic, SEED, devices)
    st.stream.close()
    st.sess = st.stream = None
    numbers = train.check(st, cell.config, cell.traffic, devices)
    n_rows = len(st.ids)
    assert checks.judge(numbers, cell.limits)[0], numbers
    assert n_rows < 200_000 // 4
    assert fetched and max(s[0] for s in fetched) == n_rows
    for got in st.prog:
        assert all(len(x) == n_rows for x in got.values())


def test_positions_in_the_touched_rows():
    ids = np.array([2, 5, 9])
    got = rows.positions(ids, np.array([[9, 2], [5, 7]]))
    assert got.tolist() == [[2, 0], [1, 0]]
    assert rows.batch_ids(np.array([[4, 1, 3]]), np.array([[[6], [7], [8]]]),
                          np.array([2])).tolist() == [1, 4, 6, 7]


def test_digest_sees_every_changed_word():
    table = (jax.random.uniform(jax.random.PRNGKey(0), (64, 16)) - 0.5) / 16
    base = np.asarray(rows.digest(table))
    for r, c in [(0, 0), (17, 5), (63, 15)]:
        bits = np.asarray(table).view(np.uint32).copy()
        bits[r, c] ^= np.uint32(1 << (r % 32))
        got = np.asarray(rows.digest(jnp.asarray(bits.view(np.float32))))
        assert (got != base).any(axis=1).tolist() == [i == r
                                                      for i in range(64)]


UNIFORM = '''
"""Uniform ids over the vocabulary in one stream, equal counts."""
import numpy as np

from harness import zipf
from harness.layouts import Corpus


def corpus(config, traffic, seed, cfg):
    v = int(config["vocab_size"])
    rng = zipf.rng_for(seed, zipf.CORPUS_TAG)
    flat = rng.integers(0, v, int(traffic["run_words"])).astype(np.int32)
    lines = np.split(flat, np.arange(1000, flat.size, 1000))
    return Corpus(lines, v, np.full(v, 1000, np.int64), stream=True)
'''


def test_layout_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "uniform_test.py").write_text(textwrap.dedent(UNIFORM))
    monkeypatch.setattr(layouts, "__path__",
                        list(layouts.__path__) + [str(tmp_path)])
    cell = tiny.train_cell("w2v-text8.stream")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  layout="uniform_test"))
    out = train.run(cell, SEED, 1.0, time.perf_counter(), jax.devices()[:1],
                    log=lambda *a: None)
    assert checks.judge(out["numbers"], cell.limits)[0], out["numbers"]
    assert out["numbers"]["subsample_z"] < 10
