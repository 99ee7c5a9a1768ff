"""repro-lint CLI — the invariant plane's static gate (DESIGN.md §16).

    PYTHONPATH=src python -m repro.analysis.lint --strict

Lints ``src/ examples/ tests/`` (or explicit paths) with
the repo-specific rule families:

  rng-*      seeded-streams-only randomness
  det-*      no wall-clock / unordered iteration in round-loop paths
  thread-*   lock-guarded shared state + leaf-lock ordering
  pallas-*   grid↔BlockSpec consistency, alias-donation safety,
             kernel↔ref oracle wiring

Exit status: 0 clean, 1 violations (or, under ``--strict``, a
non-empty baseline), 2 usage errors. Suppressions are inline
``# repro-lint: disable=<rule> (<reason>)`` comments — the reason is
mandatory — or baseline entries; ``--strict`` (CI) accepts only the
inline, reasoned kind.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.analysis import core
from repro.analysis.core import RULE_DOCS, lint_paths

DEFAULT_PATHS = ("src", "examples", "tests")
DEFAULT_BASELINE = ".repro-lint-baseline.json"


def _find_root(start: str) -> str:
    """Walk up to the repo root (the dir holding src/repro) so the CLI
    works from any cwd inside the tree."""
    cur = os.path.abspath(start)
    while True:
        if os.path.isdir(os.path.join(cur, "src", "repro")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start)
        cur = parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="repo-specific static analysis (invariant plane)")
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint (default: "
                         f"{' '.join(DEFAULT_PATHS)} under the repo root)")
    ap.add_argument("--strict", action="store_true",
                    help="CI mode: non-empty baseline is an error")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline JSON (default: {DEFAULT_BASELINE} "
                         f"at the repo root)")
    ap.add_argument("--rule", action="append", dest="rules",
                    help="run only this rule id (repeatable)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable violation list on stdout")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        # registries populate on rule-module import
        from repro.analysis import (rules_determinism,  # noqa: F401
                                    rules_pallas, rules_rng,
                                    rules_threading)
        for rid in sorted(set(core.RULES) | set(core.PROJECT_RULES)):
            print(f"{rid:24s} {RULE_DOCS.get(rid, '')}")
        return 0

    root = _find_root(os.getcwd())
    paths = args.paths or [os.path.join(root, p) for p in DEFAULT_PATHS
                           if os.path.isdir(os.path.join(root, p))]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"repro-lint: no such path(s): {missing}", file=sys.stderr)
        return 2
    baseline = args.baseline
    if baseline is None:
        cand = os.path.join(root, DEFAULT_BASELINE)
        baseline = cand if os.path.exists(cand) else None

    report = lint_paths(paths, root=root, baseline=baseline,
                        strict=args.strict, rules=args.rules)
    if args.as_json:
        print(json.dumps([v.__dict__ for v in report.violations],
                         indent=2))
    else:
        print(report.format())
    return 0 if report.clean else 1


if __name__ == "__main__":
    sys.exit(main())
