#!/usr/bin/env python
"""CI gate: every ``ANOMOD_*`` env var the code reads must be covered.

"Covered" means at least one of:

- it appears in the validated ``Config`` env contract
  (``anomod/config.py`` — the typed, fail-loud home for knobs that shape
  framework behavior), or
- it is documented (``README.md`` or any ``docs/*.md`` — the contract
  for operational/driver knobs that deliberately stay out of Config).

An env read that is neither is exactly how a knob rots: it works on the
author's machine, nobody else can discover it, and a typo'd value fails
silently.  This gate greps the whole package (plus ``scripts/``) for
``ANOMOD_[A-Z0-9_]+`` tokens and fails listing every uncovered name — including any new ``ANOMOD_OBS_*`` knob someone adds
without teaching the Config/doc contract about it.

Since PR 11 the token grep is backed by the AST scanner in
``anomod.analysis.envscan`` (the E2xx lint rules' engine), which closes
this script's documented false negative: a DYNAMIC key —
``os.environ[f"ANOMOD_{name}"]``, ``os.getenv("ANOMOD_" + name)`` —
contains no complete token for the regex to match but is statically
provable to read an ``ANOMOD_*`` var.  Dynamic reads are reported as
violations in their own ``dynamic`` key (they cannot be checked against
the contract at all; route them through anomod.config).

Exit codes: 0 = every referenced var is covered and no dynamic reads,
1 = violations (listed in the JSON line and on stderr) — the exit
contract is unchanged from PR 3.
"""

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# the AST scanner lives in the package (shared with `anomod lint`)
sys.path.insert(0, str(ROOT))

_VAR = re.compile(r"ANOMOD_[A-Z0-9_]+")


def referenced_vars(root: Path) -> dict:
    """Every ANOMOD_* token in the scanned sources -> the files naming it.

    Tokens ending in ``_`` are glob-style prefixes in prose (e.g.
    ``ANOMOD_SERVE_BENCH_*`` rendered without the star) — not reads."""
    out: dict = {}
    files = sorted((root / "anomod").rglob("*.py"))
    files += sorted((root / "scripts").glob("*.py"))
    for p in files:
        if not p.is_file():
            continue
        for m in _VAR.finditer(p.read_text(errors="replace")):
            name = m.group(0)
            if name.endswith("_"):
                continue
            out.setdefault(name, set()).add(
                str(p.relative_to(root)))
    return out


def dynamic_reads(root: Path) -> dict:
    """AST pass over the same scan set: dynamic ``ANOMOD_*`` env reads
    (f-string/concat keys) the token grep cannot see — file ->
    [(line, static_prefix)].  ``anomod/config.py`` is exempt: it is the
    contract's one legitimate home for parameterized reads.  The scan
    set is ``anomod.analysis.lint.scan_files`` — ONE definition shared
    with the linter, so the two passes can never cover different
    trees."""
    from anomod.analysis.envscan import dynamic_anomod_reads
    from anomod.analysis.lint import ModuleContext, scan_files
    out: dict = {}
    # exactly anomod/config.py — the same exemption the E2xx lint rule
    # applies; a basename match would also exempt some future
    # anomod/serve/config.py and let the two gates diverge
    exempt = (root / "anomod" / "config.py").resolve()
    for p in scan_files(root):
        if p.resolve() == exempt:
            continue
        rel = str(p.relative_to(root))
        try:
            # a full ModuleContext (not a bare ast.parse): its import
            # table is what resolves `import os as _os` aliased reads
            ctx = ModuleContext(p.read_text(errors="replace"), rel)
        except SyntaxError:
            continue
        got = dynamic_anomod_reads(ctx.tree, ctx)
        if got:
            out[rel] = [[r.line, r.prefix] for r in got]
    return out


def covered_vars(root: Path) -> str:
    """The coverage corpus: the Config module + every markdown doc."""
    parts = []
    for p in [root / "anomod" / "config.py", root / "README.md",
              *sorted((root / "docs").glob("*.md"))]:
        if p.is_file():
            parts.append(p.read_text(errors="replace"))
    return "\n".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=str(ROOT),
                    help="repo root to scan (tests use a fixture tree)")
    args = ap.parse_args(argv)
    root = Path(args.root)
    refs = referenced_vars(root)
    corpus = covered_vars(root)
    missing = {name: sorted(files) for name, files in sorted(refs.items())
               if name not in corpus}
    dynamic = dynamic_reads(root)
    bad = bool(missing or dynamic)
    out = {"check": "env_contract", "n_vars": len(refs),
           "n_missing": len(missing), "n_dynamic": len(dynamic),
           "status": "ok" if not bad else "uncovered-env-vars"}
    if missing:
        out["missing"] = missing
    if dynamic:
        out["dynamic"] = dynamic
    print(json.dumps(out))
    if bad:
        for name, files in missing.items():
            print(f"check_env_contract: {name} (read in "
                  f"{', '.join(files)}) is neither in the Config env "
                  "contract (anomod/config.py) nor documented "
                  "(README.md / docs/*.md)", file=sys.stderr)
        for fname, sites in dynamic.items():
            for line, prefix in sites:
                print(f"check_env_contract: {fname}:{line} reads a "
                      f"DYNAMIC ANOMOD_* env var (key built from "
                      f"{prefix!r}...) — statically uncheckable; route "
                      "it through anomod.config", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
