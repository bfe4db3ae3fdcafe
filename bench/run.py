#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` with its configuration and
traffic files, sets up (data, tables, compiles, warm-up), measures for
``--seconds`` seconds, checks the timed path's output against the plain
reference, and prints one JSON object as the last line of standard
output. With ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer ones. Without a TPU of a kind the peak
table knows, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

from harness import checks, device, spec, trace  # noqa: E402

# a traffic kind's runner: these two by their old names, any other kind
# by the module ``harness.<kind>``
RUNNERS = {"train": "harness.train", "serve_open_loop": "harness.serve"}


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def per_layer(cell, rec, summary, peak) -> dict:
    rec = dict(rec, trace=summary, peak=peak)
    out = {}
    for m in cell.per_layer:
        value = spec.reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device.use_checkout_cache()
    try:
        devices, peak = device.require_chips(cell.chips)
    except device.NoChip as e:
        log(f"bench: {e}")
        return 1
    try:
        import repro  # noqa: F401
    except ImportError as e:
        log(f"bench: the program under test is not beside the benchmark "
            f"({e})")
        return 2
    import importlib
    kind = cell.traffic["kind"]
    runner = importlib.import_module(RUNNERS.get(kind, "harness." + kind))
    log(f"bench: {cell.name} seed {args.seed} on {devices[0].device_kind} "
        f"x{len(devices)}, compile cache {device.CACHE_DIR}")
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        out = runner.run(cell, args.seed, args.seconds, T_PROCESS, devices,
                         trace_dir=tdir if args.trace else None, log=log)
        summary = None
        if args.trace:
            summary = trace.reduce(trace.xplane_path(tdir), len(devices))
    correct, checked = checks.judge(out["numbers"], cell.limits)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        result["metrics"] = per_layer(cell, out["rec"], summary, peak)
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = dev
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps()}
    else:
        # a metric named ``<quantity>.<cells>`` reports the runner's
        # ``<quantity>``: one quantity, bounded apart in different cells
        values = dict(out["e2e"], setup_s=out["setup_s"])
        result["metrics"] = {m["name"]: {
            "value": values[m["name"].split(".")[0]], "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = dev
    result["checks"] = checked
    checks.report(out["numbers"], checked)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
