"""Numbers the correctness check compares, held to the cell's limits.

``bench/limits/<cell>.json`` maps each compared number to
``{"limit": x, ...}``; a run is correct when every one of them was
measured, is finite and is at most its limit. Numbers a runner measures
that have no limit are printed as readings and decide nothing.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, Tuple

import numpy as np


def judge(numbers: Dict[str, float], limits: Dict[str, dict]
          ) -> Tuple[bool, Dict[str, dict]]:
    checks, ok = {}, True
    for name, spec in limits.items():
        value = numbers.get(name)
        limit = float(spec["limit"])
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def report(numbers: Dict[str, float], checks: Dict[str, dict]) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error (readings without a limit come first)."""
    for name, value in numbers.items():
        if name not in checks:
            print(f"reading {name} = {value!r} (not compared)",
                  file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()


def norm_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves the reference leaves unmoved (under a
    thousandth of the median leaf) do not count."""
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for leaf, r in ref.items():
        if r < 1e-3 * med:
            continue
        worst = max(worst, abs(prog[leaf] - r) / max(r, med))
    return worst
