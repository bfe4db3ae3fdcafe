# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  bench_throughput  — Fig. 6/7  (training words/sec per implementation)
  bench_memory      — Table 4   (per-epoch memory demand per implementation)
  bench_quality     — Table 7   (embedding quality equivalence + tiled gate)
  bench_batching    — Table 1   (host batching speed)
  bench_roofline    — Fig. 1    (arithmetic intensity per implementation)
  bench_lm_step     — (this repo) per-arch reduced-config step timings
  bench_tile_sweep  — (this repo) DESIGN.md §4 window-tile sweep
  bench_resilience  — (this repo) DESIGN.md §9 chaos-schedule recovery
  bench_serve       — (this repo) DESIGN.md §10 serving QPS/p50/p99 + swap
  bench_workloads   — (this repo) DESIGN.md §12 per-frontend words/sec + quality

Run: PYTHONPATH=src python -m benchmarks.run [--only <name>] [--out FILE]

Besides the CSV on stdout, every run writes a ``BENCH_<step>.json``
trajectory file (step = commit count, overridable via --step/$BENCH_STEP)
with all rows parsed into key=value dicts — so future PRs can diff
throughput words/sec, quality scores, and tile-sweep reductions against
this one.
"""
import argparse
import json
import os
import subprocess
import sys
import traceback


def _git_step() -> int:
    try:
        out = subprocess.run(
            ["git", "rev-list", "--count", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        return int(out.stdout.strip())
    except Exception:  # noqa: BLE001
        return 0


def _parse_derived(derived: str) -> dict:
    """Parse 'k1=v1 k2=v2 ...' fragments of a CSV row; non k=v tokens are
    collected under 'note'."""
    out, notes = {}, []
    for tok in derived.split():
        if "=" in tok:
            key, val = tok.split("=", 1)
            try:
                out[key] = float(val.rstrip("x%"))
            except ValueError:
                out[key] = val
        else:
            notes.append(tok)
    if notes:
        out["note"] = " ".join(notes)
    return out


# suite name -> module benchmarks.bench_<name>; single registry that both
# --only's choices and the run loop derive from
SUITE_NAMES = ("roofline", "memory", "batching", "throughput", "quality",
               "tile_sweep", "lm_step", "resilience", "serve", "workloads")


def _load_suites() -> dict:
    import importlib
    return {name: importlib.import_module(f"benchmarks.bench_{name}")
            for name in SUITE_NAMES}


def _only_arg(value: str):
    """--only accepts a comma-separated subset of suites (the perf-gate
    runs batching+tile_sweep in one invocation -> one trajectory file)."""
    names = tuple(v.strip() for v in value.split(",") if v.strip())
    bad = [n for n in names if n not in SUITE_NAMES]
    if bad:
        raise argparse.ArgumentTypeError(
            f"unknown suite(s) {bad}; choose from {SUITE_NAMES}")
    return names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, type=_only_arg,
                    metavar="SUITE[,SUITE...]",
                    help=f"subset of suites to run; any of {SUITE_NAMES}")
    ap.add_argument("--step", type=int, default=None,
                    help="trajectory step id (default: $BENCH_STEP or "
                         "git commit count)")
    ap.add_argument("--out", default=None,
                    help="trajectory JSON path (default: BENCH_<step>.json "
                         "in the repo root)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    suites = _load_suites()
    step = args.step if args.step is not None else int(
        os.environ.get("BENCH_STEP", _git_step()))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # partial (--only) runs get their own file so they never clobber the
    # full trajectory future PRs diff against
    suffix = f".{'-'.join(args.only)}" if args.only else ""
    out_path = args.out or os.path.join(repo, f"BENCH_{step}{suffix}.json")

    print("name,us_per_call,derived")
    failed = 0
    traj = {"step": step, "rows": {}, "errors": []}
    for name, mod in suites.items():
        if args.only and name not in args.only:
            continue
        try:
            for row in mod.run():
                print(row)
                rname, us, derived = row.split(",", 2)
                traj["rows"][rname] = {"us_per_call": float(us),
                                       **_parse_derived(derived)}
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
            traj["errors"].append(name)
            print(f"{name},nan,ERROR")
    with open(out_path, "w") as f:
        json.dump(traj, f, indent=1, sort_keys=True)
    print(f"# trajectory -> {out_path}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
