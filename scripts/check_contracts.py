#!/usr/bin/env python
"""CI gate: the contract linter + parity-surface audit.

Runs ``anomod.analysis`` over the repo — the AST rule families
(determinism, env contract, seam discipline, lock discipline) plus the
static parity-surface audit (ServeReport fields / flight-record keys
vs their declared variant lists) — and fails on any finding that is
neither inline-suppressed (with a reason) nor in the baseline
(``scripts/lint_baseline.json``, which may only shrink).

The catalog of enforced contracts lives in docs/CONTRACTS.md; the same
run is available as ``anomod lint``.

Exit codes: 0 = clean (baselined findings ride, shrinkage reported),
1 = new contract violations (listed on stderr).
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def run(root=None) -> dict:
    """The gate body: the ONE shared
    composition ``anomod.analysis.lint.run_gate`` as a summary doc."""
    from anomod.analysis.lint import run_gate
    doc, _ = run_gate(root)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=None,
                    help="repo root to scan (tests use a fixture tree)")
    args = ap.parse_args(argv)
    doc = run(args.root)
    print(json.dumps(doc))
    if doc["status"] != "ok":
        for line in doc["new"]:
            print(f"check_contracts: {line}", file=sys.stderr)
        print("check_contracts: run `anomod lint` locally; fix the "
              "finding, add a reasoned inline suppression "
              "(# anomod-" "lint: disable=RULE — why), or baseline it "
              "deliberately", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
