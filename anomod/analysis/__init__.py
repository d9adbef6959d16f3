"""Contract-checking static analysis plane.

Every guarantee the serving plane ships — byte-identical N-shard vs
1-shard states, ``anomod audit replay`` reproducing a run from its
header, no-score-gap recovery — rests on conventions that used to be
enforced only by reviewer vigilance: no wall clock or unseeded RNG in
canonical-plane code, every ``ANOMOD_*`` read Config-validated, every
new ``ServeReport``/flight field either parity-pinned or on an explicit
variant list, always-copy at the ``get_state``/pool-gather seam, locks
around registry mutation.  PR 4 (scratch aliasing under async
dispatch), PR 5 (torn histogram scrapes) and PR 8 (``pool.put(None,
...)`` broadcast corruption) were all contracts violated silently and
found the hard way.  This package mechanizes those contracts as an
AST-based linter (``anomod lint`` / ``scripts/check_contracts.py``)
so the class of failure moves from runtime debugging to a CI gate.

Rule families (docs/CONTRACTS.md is the operator catalog):

- ``D1xx`` determinism: canonical-plane modules must not read the wall
  clock outside wall-leg timing form, call unseeded RNG, key on
  ``id()``, or feed set iteration into ordered output.
- ``E2xx`` env contract: every ``ANOMOD_*`` env read must be
  Config-validated or documented; dynamic (f-string/concat) reads are
  statically unresolvable and refused.
- ``S3xx`` seam discipline: pool-plane internals (``_slot`` /
  ``_slots`` / ``_runner``) stay inside the seam modules; gather-side
  returns never alias pool rows.
- ``P4xx`` parity surface: every ``ServeReport`` field and flight-tick
  key is either on the declared variant list or named by a test — a
  new field cannot silently widen the variant surface.
- ``L5xx`` lock discipline: classes owning ``self._lock`` mutate their
  shared state only inside ``with self._lock``.

Suppression syntax (reason REQUIRED — an unexplained suppression is
itself a finding)::

    x = time.time()   # anomod-lint: disable=D101 — forensic timestamp

The linter is pure stdlib ``ast`` + text: importing it never imports
jax or the serve plane, so the gate runs in milliseconds.
"""

from anomod.analysis.lint import (Finding, RULES, lint_repo, lint_source,
                                  load_baseline, repo_root, status_block)
from anomod.analysis.parity import run_parity_audit

__all__ = ["Finding", "RULES", "lint_repo", "lint_source",
           "load_baseline", "repo_root", "run_parity_audit",
           "status_block"]
