import os
import types

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


MS = 1_000_000


def small_trace():
    """A 100 ms window: two step programs on the device, a wait for the
    host pipeline between them, the block at the end."""
    host = plane("/host:CPU", [
        line("python", [ev(trace.WINDOW, 0, 100 * MS),
                        ev("bench.fetch", 30 * MS, 70 * MS),
                        ev("bench.block", 95 * MS, 100 * MS),
                        ev("unrelated", 0, 100 * MS)]),
    ])
    dev = plane("/device:TPU:0", [
        line(trace.MODULES_LINE, [ev("jit_run(123)", 5 * MS, 30 * MS),
                                  ev("jit_run(123)", 70 * MS, 95 * MS)]),
        line(trace.OPS_LINE, [ev("%run.1 = custom-call", 5 * MS, 29 * MS),
                              ev("%copy.4 = copy", 29 * MS, 30 * MS),
                              ev("%run.1 = custom-call", 70 * MS, 95 * MS),
                              # overlaps the previous op: counted once
                              ev("%copy.5 = copy", 90 * MS, 96 * MS)]),
        line("Steps", [ev("ignored", 0, 100 * MS)]),
    ])
    sparse = plane("/device:TPU:0 SparseCore 0", [
        line(trace.OPS_LINE, [ev("ignored", 0, 100 * MS)])])
    return [host, dev, sparse]


def test_busy_union_and_idle_gaps_charged_to_spans():
    s = trace.reduce_planes(small_trace(), n_devices=1)
    assert s.window_s == pytest.approx(0.1)
    # busy: 5-30 and 70-96 clipped to the window at 100
    assert s.busy_s == pytest.approx(0.051)
    assert s.program_s == {"jit_run(123)": pytest.approx(0.05)}
    assert s.top_ops(1) == [["%run.1 = custom-call", pytest.approx(0.049)]]
    gaps = dict((k, v) for k, v in s.top_gaps())
    assert gaps["bench.fetch"] == pytest.approx(0.04)
    assert gaps["outside bench spans"] == pytest.approx(0.005)
    assert gaps["bench.block"] == pytest.approx(0.004)


def test_a_trace_without_the_cells_devices_is_refused():
    with pytest.raises(ValueError, match="uses 4"):
        trace.reduce_planes(small_trace(), n_devices=4)
    no_window = small_trace()
    no_window[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(no_window, n_devices=1)


def test_union_seconds():
    busy, gaps = trace.union_seconds([(10, 20), (15, 30), (40, 50)], 0, 60)
    assert busy == pytest.approx(30e-9)
    assert gaps == [(0, 10), (30, 40), (50, 60)]


RECORDED = os.path.join(DATA, "tiny_v5e.xplane.pb")


def test_recorded_chip_trace():
    """Three (512, 512) matmul steps on a TPU v5e, each after a 2 ms
    ``bench.fetch`` sleep, recorded by ``trace.start``/``trace.stop``."""
    s = trace.reduce(RECORDED, n_devices=1)
    assert 0 < s.busy_s < s.window_s
    assert any(name.startswith("jit_") for name in s.program_s)
    assert {name for name, _ in s.idle_gaps} <= {
        "bench.fetch", "bench.session", "outside bench spans"}
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
