import json
import os
import subprocess
import sys
import types

import pytest

from harness import device, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v99")
    assert peaks.peak_for("TPU v5 lite").hbm_bytes_per_s == 819e9


def test_cpu_platform_is_refused():
    with pytest.raises(device.NoChip, match="no TPU"):
        device.require_chips(1)


def test_tpu_of_unknown_kind_is_refused(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(device.NoChip, match="no published peaks"):
        device.require_chips(1)
    with pytest.raises(device.NoChip, match="asks for 4"):
        monkeypatch.setattr(jax, "devices", lambda *a: [
            types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
        device.require_chips(4)


def test_run_prints_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "w2v-text8.stream", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "no TPU" in p.stderr
