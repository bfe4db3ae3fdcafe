"""``run.py`` end to end on the CPU, its look for a chip skipped: the
result line has the contract's keys, in order, with the cell's metrics."""
import json

import jax
import pytest

import run
import tiny
from harness import device, peaks, spec, trace


@pytest.fixture
def cpu_run(monkeypatch):
    monkeypatch.setattr(device, "require_chips", lambda n: (
        jax.devices()[:n], peaks.peak_for("TPU v5 lite")))
    monkeypatch.setattr(device, "use_checkout_cache", lambda: None)
    small = {"w2v-text8.stream": tiny.train_cell("w2v-text8.stream"),
             "w2v-1bw.sentences": tiny.train_cell("w2v-1bw.sentences"),
             "w2v-1bw.serve": tiny.serve_cell("w2v-1bw.serve")}
    monkeypatch.setattr(spec, "load_cell", lambda name: small[name])


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,e2e", [
    ("w2v-text8.stream", {"words_per_s", "setup_s"}),
    ("w2v-1bw.sentences", {"words_per_s.sentences", "setup_s"}),
    ("w2v-1bw.serve", {"query_p50_ms", "setup_s"})])
def test_result_line(cpu_run, capsys, cell, e2e):
    assert run.main(["--workload", cell, "--seed", str(2**31 + 3),
                     "--seconds", "1", "--trace", "0"]) == 0
    out = _last_json(capsys)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 1


def test_traced_result_line(cpu_run, capsys, monkeypatch):
    # the CPU has no device plane: stand in a reduced trace
    fake = trace.Summary(window_s=1.0, busy_s=0.5,
                         program_s={"jit_run(1)": 0.5},
                         op_s={"%run.1": 0.5},
                         idle_gaps=[("bench.fetch", 0.5)], n_devices=1)
    monkeypatch.setattr(trace, "reduce", lambda path, n: fake)
    monkeypatch.setattr(trace, "xplane_path", lambda d: d)
    assert run.main(["--workload", "w2v-text8.stream", "--seed", "5",
                     "--seconds", "1", "--trace", "1"]) == 0
    out = _last_json(capsys)
    assert set(out["metrics"]) == {
        "host_wait_pct.train", "pad_fill_pct.train",
        "step_roofline_pct.train", "mfu_pct.train", "device_idle_pct.train"}
    assert out["device"]["busy_s"] == 0.5
    assert out["breakdown"]["idle_gaps"] == [["bench.fetch", 0.5]]
    assert list(out)[-1] == "checks"
