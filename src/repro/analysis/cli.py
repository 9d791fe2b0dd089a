"""``python -m repro.analysis`` — the project's static verifier CLI.

Usage::

    python -m repro.analysis src tests benchmarks
    python -m repro.analysis --format github src tests benchmarks
    python -m repro.analysis --format json --no-contracts tests/fixtures/analysis

Two layers run by default:

1. **AST passes** over every ``.py`` file under the given paths
   (donation hazards, loop-jit, ContextVar discipline, backend drift)
   plus the tuning-cache contract on every ``.json`` under the paths
   that parses as a cache file.
2. **Contract checks** (``--no-contracts`` skips them): the backend
   registry closure, the shipped control-tree family, and the
   ``BENCH_*.json`` schema under ``--artifacts`` (default
   ``artifacts/bench`` when it exists).

Exit status is the number of findings clamped to 1 — a clean tree exits
0, anything else fails CI.  Directories named ``fixtures`` are skipped
during recursive discovery (the test corpus is *supposed* to be dirty)
but analyzed when named explicitly on the command line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

from repro.analysis import ast_checks, configcheck, registry
from repro.analysis.diagnostics import (
    CODES,
    Diagnostic,
    apply_suppressions,
    render,
)

_SKIP_DIRS = frozenset(
    {"fixtures", "__pycache__", ".git", ".venv", "node_modules"}
)


def build_vocabulary() -> frozenset[str]:
    """The backend-token vocabulary, keyed off the live registries."""

    from repro.core.execution import backend_vocabulary
    from repro.tuning.measure import MEASURE_BACKEND_NAMES

    return frozenset(backend_vocabulary()) | frozenset(MEASURE_BACKEND_NAMES)


def build_fault_points() -> frozenset[str]:
    """The fault-injection point vocabulary, keyed off the live registry.

    Sourced from ``repro.runtime.faults.FAULT_POINTS`` so the drift check
    can never disagree with what ``validate_point`` accepts.
    """

    from repro.runtime.faults import FAULT_POINTS

    return frozenset(FAULT_POINTS)


def discover(paths: list[str]) -> tuple[list[str], list[str]]:
    """(.py files, .json files) under the given paths, fixtures pruned."""

    py: list[str] = []
    js: list[str] = []
    for path in paths:
        if os.path.isfile(path):
            if path.endswith(".py"):
                py.append(path)
            elif path.endswith(".json"):
                js.append(path)
            continue
        for root, dirs, files in os.walk(path):
            dirs[:] = sorted(d for d in dirs if d not in _SKIP_DIRS)
            for fname in sorted(files):
                full = os.path.join(root, fname)
                if fname.endswith(".py"):
                    py.append(full)
                elif fname.endswith(".json"):
                    js.append(full)
    return py, js


def analyze_file(
    path: str,
    vocabulary: Optional[frozenset[str]] = None,
    fault_points: Optional[frozenset[str]] = None,
) -> list[Diagnostic]:
    """All applicable AST passes + suppressions for one Python file."""

    if vocabulary is None:
        vocabulary = build_vocabulary()
    if fault_points is None:
        fault_points = build_fault_points()
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        diags = ast_checks.run_ast_checks(
            path, source, vocabulary, fault_points
        )
    except SyntaxError as e:
        # Not our diagnostic to own: surface as a hard error.
        raise SystemExit(f"{path}: cannot parse: {e}") from e
    return apply_suppressions(path, source, diags)


def analyze_paths(
    paths: list[str],
    *,
    contracts: bool = True,
    artifacts: Optional[str] = None,
    vocabulary: Optional[frozenset[str]] = None,
    fault_points: Optional[frozenset[str]] = None,
) -> list[Diagnostic]:
    """The full analyzer: AST passes over ``paths`` + contract checks."""

    if vocabulary is None:
        vocabulary = build_vocabulary()
    if fault_points is None:
        fault_points = build_fault_points()
    diags: list[Diagnostic] = []
    py_files, json_files = discover(paths)
    for path in py_files:
        diags.extend(analyze_file(path, vocabulary, fault_points))
    for path in json_files:
        diags.extend(configcheck.check_tuning_cache_file(path))
    if contracts:
        diags.extend(registry.check_registry())
        diags.extend(configcheck.check_shipped_trees())
        if artifacts is None and os.path.isdir(
            os.path.join("artifacts", "bench")
        ):
            artifacts = os.path.join("artifacts", "bench")
        if artifacts:
            diags.extend(configcheck.check_artifacts_dir(artifacts))
    return diags


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static verifier for the repo's donation, "
                    "backend-registry, VMEM-budget, and context-discipline "
                    "invariants.",
    )
    ap.add_argument(
        "paths", nargs="*", default=["src", "tests", "benchmarks"],
        help="files/directories to lint (default: src tests benchmarks)",
    )
    ap.add_argument(
        "--format", choices=("text", "github", "json"), default="text",
        help="diagnostic output format (github = PR annotations)",
    )
    ap.add_argument(
        "--no-contracts", action="store_true",
        help="skip the registry/tree/artifact contract checks (AST only)",
    )
    ap.add_argument(
        "--artifacts", default=None, metavar="DIR",
        help="bench-artifact dir for the BENCH_*.json schema check "
             "(default: artifacts/bench when present)",
    )
    ap.add_argument(
        "--list-codes", action="store_true",
        help="print the diagnostic catalogue and exit",
    )
    args = ap.parse_args(argv)

    if args.list_codes:
        print(json.dumps(CODES, indent=1, sort_keys=True))
        return 0

    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"no such path(s): {missing}", file=sys.stderr)
        return 2

    diags = analyze_paths(
        args.paths,
        contracts=not args.no_contracts,
        artifacts=args.artifacts,
    )
    out = render(diags, args.format)
    if out:
        print(out)
    if args.format != "json":
        print(
            f"repro.analysis: {len(diags)} finding(s)"
            if diags else "repro.analysis: clean",
            file=sys.stderr,
        )
    return 1 if diags else 0


if __name__ == "__main__":
    raise SystemExit(main())
