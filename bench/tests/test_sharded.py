"""A four-chip, vocab-sharded training cell needs only new files: on four
CPU devices (a process of its own: JAX fixes its device count when it
starts) the harness builds the session on a mesh over the cell's chips,
reads its split tables through the placement, and judges the run;
planted faults come out not correct. The reference's initial draw, in
row blocks over the devices, equals the one-device draw row for row."""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
SRC = os.path.join(os.path.dirname(BENCH), "src")

SCRIPT = r'''
import dataclasses, json, sys, time
sys.path[:0] = {paths!r}
import jax
import numpy as np

import tiny
from harness import checks, faults, reference, rows, train

SEED = 2**31 + 17
devices = jax.devices()[:4]


def emit(**kw):
    print(json.dumps(kw), flush=True)


key = jax.random.PRNGKey(7)
one = np.asarray(reference.init_tables(key, 1001, 8)[0])
init = rows.Initial(key, 1001, 8, devices)
emit(case="draw", shards=len(init.table.sharding.device_set),
     equal=bool((init.rows(np.arange(1001))["w_in"] == one).all()))

cell = tiny.train_cell("w2v-text8.stream")
cell = dataclasses.replace(cell, chips=4, config=dict(
    cell.config, w2v=dict(cell.config["w2v"], vocab_shard=True)))

st = train.start(cell.config, cell.traffic, SEED, devices)
state = st.sess.state
emit(case="session", mesh=int(st.sess.mesh.size),
     cold=len(state.cold_in.sharding.device_set),
     hot=int(st.sess.placement.hot), vocab=int(st.sess.placement.vocab_size))
st.stream.close()


def run(case):
    out = train.run(cell, SEED, 1.0, time.perf_counter(), devices,
                    log=lambda *a: None)
    emit(case=case, numbers=out["numbers"],
         correct=checks.judge(out["numbers"], cell.limits)[0])


run("sound")
from repro.kernels import ops
real = ops.step


def altered(tables, step, cfg, **kw):
    # a token of the first row, in the working rows the step carries
    v = tables.w_in.shape[0]
    tokens = step.tokens.at[0, 5].set((step.tokens[0, 5] + 1) % v)
    return real(tables, dataclasses.replace(step, tokens=tokens), cfg, **kw)


ops.step = altered
run("token_altered")
ops.step = real
with faults.stray_write():
    run("stray_write")
'''


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    code = SCRIPT.format(paths=[TESTS, BENCH, SRC])
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return {r["case"]: r for r in map(json.loads, done.stdout.splitlines())}


def test_sharded_draw_equals_one_device_draw(four):
    assert four["draw"] == {"case": "draw", "shards": 4, "equal": True}


def test_session_spans_the_cells_chips(four):
    got = four["session"]
    assert got["mesh"] == 4 and got["cold"] == 4
    assert 0 < got["hot"] < got["vocab"]


def test_sound_vocab_sharded_run_is_correct(four):
    got = four["sound"]
    assert got["correct"], got["numbers"]
    assert got["numbers"]["untouched_changed"] == 0


def test_token_altered_vocab_sharded_run_is_not_correct(four):
    got = four["token_altered"]
    assert not got["correct"], got["numbers"]


def test_stray_write_in_vocab_sharded_run_is_counted(four):
    got = four["stray_write"]
    assert got["numbers"]["untouched_changed"] >= 1
    assert not got["correct"], got["numbers"]
