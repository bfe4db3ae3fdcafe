"""Bring-up surfaces: the persistent compile cache's placement, and
``chip_smoke.py`` — refusing to run anywhere but on a TPU, and its
one- and four-chip routines rehearsed on the CPU with stand-in
backends."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_cache_dir_is_left_alone(monkeypatch, tmp_path,
                                         cache_dir_config):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_inside_the_repo(monkeypatch,
                                                    cache_dir_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache") == cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == path
    # fixed: the same path on every call, in every process
    assert cache.enable_compile_cache() == path


def test_cache_and_scratch_dirs_are_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip() for line in f}
    assert {".jax_cache/", ".chip_smoke/"} <= ignored


@pytest.mark.parametrize("entry", [
    "src/repro/launch/train.py", "src/repro/launch/serve.py",
    "benchmarks/run.py", "chip_smoke.py"])
def test_entry_points_place_the_compile_cache(entry):
    with open(os.path.join(REPO, entry)) as f:
        assert "enable_compile_cache()" in f.read()


def _smoke(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _smoke(REPO, env)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_refuses_without_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _smoke(str(tmp_path), env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_rehearse_on_cpu(monkeypatch, tmp_path, capsys):
    """The one-chip routine end to end at a tiny size, with the interpret
    kernels in place of the native ones: training, the oracle check and
    the checkpoint -> serve parity all run and pass."""
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "CKPT_DIR", str(tmp_path / "ckpt"))
    phases = (("a", "pallas_interpret", "pallas_interpret", 1, "jnp"),
              ("c", "pallas_tiled_interpret", "pallas_tiled_interpret", 8,
               "jnp_tiled"))
    smoke.one_chip(smoke.CompileClock(), phases=phases, vocab_size=3000,
                   sentences=300, require_native=False,
                   sentences_per_batch=32, max_sentence_len=64)
    out = capsys.readouterr().out
    assert out.count("within(atol=2e-05, rtol=0.0001)=True") == 4
    assert "id_parity=True score_parity=True" in out
    assert not (tmp_path / "ckpt").exists()


def test_chip_smoke_four_chip_path_rehearses_on_cpu(subproc):
    """The four-chip routine on 4 virtual CPU devices, the jnp_tiled exact
    exchange standing in for the kernel: same mesh, same batches, parity
    within the DESIGN.md §8 tolerance on every table leaf."""
    r = subproc(f"""
        import importlib.util, jax
        assert jax.device_count() == 4
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        assert smoke.four_chips(smoke.CompileClock(), sentences_per_batch=32,
                                vocab_size=3000, backend="jnp_tiled",
                                expect="jnp_tiled", max_sentence_len=32) == 4
    """, n_devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("within(atol=1e-06, rtol=1e-05)=True") == 4
    # the warm-up compiled the steady-state step: none in the timed batches
    assert r.stdout.count("timed_compile_s=0.0)") == 2
