"""The profiler trace of a run, reduced to the numbers the metrics read.

The harness marks the measured window and its own calls into the
program with host spans (``bench.*`` TraceAnnotations). The reduction
reads the ``.xplane.pb`` the JAX profiler writes:

* device busy time: the union of the intervals in which an operation ran
  on a device's ``XLA Ops`` line, clipped to the window, averaged over the
  devices the cell uses;
* device time per program: the ``XLA Modules`` line, by module name;
* device time per operation: the ``XLA Ops`` line, by operation name;
* idle gaps: the stretches of the window with no operation on device 0,
  each charged to the innermost ``bench.*`` span that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Tuple

import jax
import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"
# shorter idle stretches are launch gaps between back-to-back operations
MIN_GAP_S = 100e-6


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


def start(log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # Python calls would swamp the host trace
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    jax.profiler.stop_trace()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # averaged over the devices used
    program_s: Dict[str, float]          # device 0, by XLA module name
    op_s: Dict[str, float]               # device 0, by operation name
    idle_gaps: List[Tuple[str, float]]   # (span charged, seconds), device 0
    n_devices: int

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        by_span: Dict[str, float] = collections.defaultdict(float)
        for name, sec in self.idle_gaps:
            by_span[name] += sec
        return [[k, v] for k, v in sorted(by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def union_seconds(intervals: List[Tuple[float, float]], lo: float,
                  hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``intervals`` within ``[lo, hi]`` (ns in,
    seconds out), and the gaps the union leaves there (ns)."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        busy += e - cur
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy / 1e9, gaps


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, n_devices: int) -> Summary:
    """Reduce one trace file; ``n_devices`` is how many chips the cell
    uses (devices 0..n-1)."""
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         n_devices)


def reduce_planes(planes, n_devices: int) -> Summary:
    """:func:`reduce` over planes with ``name`` and ``lines``, each line
    with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    spans, devices = [], {}
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            rest = plane.name[len(DEVICE_PLANE):]
            if not rest.isdigit():
                continue
            devices[int(rest)] = {line.name: _events(line)
                                  for line in plane.lines
                                  if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    used = [devices[i] for i in range(n_devices) if i in devices]
    if len(used) != n_devices:
        raise ValueError(f"trace holds devices {sorted(devices)}, the cell "
                         f"uses {n_devices}")
    busy, gaps0 = [], []
    for i, dev in enumerate(used):
        b, gaps = union_seconds([(s, e) for _, s, e in dev.get(OPS_LINE, [])],
                                lo, hi)
        busy.append(b)
        if i == 0:
            gaps0 = gaps
    dev0 = used[0]
    program_s: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in dev0.get(MODULES_LINE, []):
        program_s[name] += max(0, min(e, hi) - max(s, lo)) / 1e9
    op_s: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in dev0.get(OPS_LINE, []):
        op_s[name] += max(0, min(e, hi) - max(s, lo)) / 1e9
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    names = [n for n, _, _ in inner]
    starts = np.array([s for _, s, _ in inner], np.float64)
    ends = np.array([e for _, _, e in inner], np.float64)
    charged = []
    for gs, ge in gaps0:
        if (ge - gs) / 1e9 < MIN_GAP_S:
            continue
        mid = (gs + ge) / 2
        cover = np.flatnonzero((starts <= mid) & (ends >= mid))
        name = ("outside bench spans" if cover.size == 0 else
                names[cover[np.argmin(ends[cover] - starts[cover])]])
        charged.append((name, (ge - gs) / 1e9))
    return Summary(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy),
                   program_s=dict(program_s), op_s=dict(op_s),
                   idle_gaps=charged, n_devices=n_devices)
