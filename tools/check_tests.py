"""Test-coverage gate: every ``src/repro`` module needs a covering test.

"Covering" is import-level, by design: a module counts as covered when at
least one file under ``tests/`` imports it (``import repro.a.b``,
``from repro.a.b import x``, or ``from repro.a import b`` — including
imports inside the subprocess code strings the multi-device tests ship,
which is why this scans import *text*, not a loaded module graph). That
is deliberately a floor, not a substitute for assertions — its job is to
catch the failure mode where a new subsystem lands with no test file at
all, which line-coverage tooling can't do in CI without running the full
(TPU-gated) matrix.

Modules that are legitimately exercised only through higher layers live
in ``ALLOWLIST`` with a reason. The list is checked both ways: an entry
whose module has gained a covering test (or no longer exists) fails the
gate, so the list can only shrink. New subsystems must ship tests, not
allowlist entries.

    PYTHONPATH=src python tools/check_tests.py         # the CI docs job
"""
from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
TESTS = os.path.join(REPO, "tests")

# module -> why import-level coverage is waived. Only shrink this list.
ALLOWLIST = {
    # trivial re-export __init__.py facades; every submodule is tested
    # directly by its own test module
    "repro.core": "trivial re-export __init__ (submodules tested)",
    # exercised through a covered importer, not imported by tests directly
    "repro.models.moe":
        "driven through repro.models.lm (tested) + bench_lm_step",
    "repro.train.supervisor":
        "driven through repro.train.chaos (tested chaos harness)",
    # data-only model presets: dicts consumed through repro.configs.base's
    # loader, which test_models/test_train_substrate exercise
    "repro.configs.arctic_480b": "data-only preset (loader is tested)",
    "repro.configs.deepseek_67b": "data-only preset (loader is tested)",
    "repro.configs.internvl2_76b": "data-only preset (loader is tested)",
    "repro.configs.jamba_1p5_large_398b":
        "data-only preset (loader is tested)",
    "repro.configs.mamba2_1p3b": "data-only preset (loader is tested)",
    "repro.configs.moonshot_v1_16b_a3b":
        "data-only preset (loader is tested)",
    "repro.configs.musicgen_large": "data-only preset (loader is tested)",
    "repro.configs.phi3_medium_14b": "data-only preset (loader is tested)",
    "repro.configs.qwen3_8b": "data-only preset (loader is tested)",
    "repro.configs.starcoder2_3b": "data-only preset (loader is tested)",
    # CLI entry points: exercised as subprocesses by the CI smoke jobs
    # (`python -m repro.launch...`), which import-scanning can't see
    "repro.launch.dryrun": "CLI wrapper, covered by CI dry-run smoke",
    "repro.launch.report": "CLI wrapper over launch.costmodel (tested)",
    "repro.launch.serve": "CLI wrapper, covered by CI serve smoke",
    "repro.launch.train": "CLI wrapper, covered by CI train + workload smoke",
}


def src_modules() -> list:
    """Every importable module under src/repro, dotted."""
    mods = []
    for root, _dirs, files in os.walk(os.path.join(SRC, "repro")):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(root, f), SRC)
            mod = rel[:-3].replace(os.sep, ".")
            if mod.endswith(".__init__"):
                mod = mod[:-len(".__init__")]
            mods.append(mod)
    return sorted(mods)


_IMPORT = re.compile(r"\bimport\s+(repro(?:\.\w+)+)")
_FROM = re.compile(r"\bfrom\s+(repro(?:\.\w+)*)\s+import\s+"
                   r"(\([^)]*\)|[^\n]*)")


def reexport_map() -> dict:
    """(package, exported name) -> defining module, from each package
    ``__init__.py``'s own ``from repro... import name`` lines — so a test
    importing ``EmbeddingServer`` from ``repro.serve`` credits
    ``repro.serve.server``, not just the facade."""
    out = {}
    for root, _dirs, files in os.walk(os.path.join(SRC, "repro")):
        if "__init__.py" not in files:
            continue
        pkg = os.path.relpath(root, SRC).replace(os.sep, ".")
        with open(os.path.join(root, "__init__.py")) as f:
            text = f.read()
        for m in _FROM.finditer(text):
            for name in re.split(r"[,\s()]+", m.group(2)):
                if name and name.isidentifier():
                    out[(pkg, name)] = m.group(1)
    return out


def covered_modules() -> dict:
    """module -> first test file importing it. Scans raw text so imports
    inside subprocess code strings count (the multi-device idiom)."""
    reexports = reexport_map()
    got = {}
    for fname in sorted(os.listdir(TESTS)):
        if not (fname.endswith(".py") and fname.startswith("test_")):
            continue
        with open(os.path.join(TESTS, fname)) as f:
            text = f.read()
        hits = set()
        for m in _IMPORT.finditer(text):
            hits.add(m.group(1))
        for m in _FROM.finditer(text):
            parent = m.group(1)
            hits.add(parent)
            for name in re.split(r"[,\s()]+", m.group(2)):
                if name and name.isidentifier():
                    hits.add(f"{parent}.{name}")
                    if (parent, name) in reexports:
                        hits.add(reexports[(parent, name)])
        for mod in hits:
            got.setdefault(mod, fname)
    return got


def main() -> int:
    mods = src_modules()
    covered = covered_modules()
    failures = []
    for mod in mods:
        if mod in covered:
            if mod in ALLOWLIST:
                failures.append(
                    f"stale ALLOWLIST entry: {mod} is now covered by "
                    f"tests/{covered[mod]} — remove it from "
                    f"tools/check_tests.py")
            else:
                print(f"  [ok]      {mod}  <- tests/{covered[mod]}")
            continue
        if mod in ALLOWLIST:
            print(f"  [allowed] {mod}  ({ALLOWLIST[mod]})")
            continue
        failures.append(
            f"{mod} has no covering test module — add one under tests/ "
            f"(or, for modules only reachable through higher layers, an "
            f"ALLOWLIST entry with a reason in tools/check_tests.py)")
    for entry in ALLOWLIST:
        if entry not in mods:
            failures.append(
                f"stale ALLOWLIST entry: {entry} no longer exists — "
                f"remove it from tools/check_tests.py")
    if failures:
        print("\ntest-coverage gate FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"\ntest-coverage gate passed: {len(mods)} modules, "
          f"{len(ALLOWLIST)} allowlisted")
    return 0


if __name__ == "__main__":
    sys.exit(main())
