"""Contract-checking static analysis plane (anomod.analysis, PR 11).

Covers: the fixture corpus (one must-trip and one must-pass file per
rule family under tests/lint_fixtures/), the suppression-syntax round
trip, baseline-regression semantics (new finding fails, baselined
finding passes, stale entries ratchet out), the parity-surface audit
(incl. the synthetic un-listed ServeReport field the acceptance
criteria name), the CANONICAL ServeReport field inventory (the
forcing function: a new field must either join the variant list or be
named by a test — this literal is that naming), the repo-runs-clean
pin, the env-contract delegation (dynamic-read false negative closed) and
the sanitize-smoke verdict shapes.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from anomod.analysis import (RULES, lint_repo, lint_source, run_parity_audit,
                             status_block)
from anomod.analysis.lint import (Finding, load_baseline, save_baseline,
                                  summarize)
from anomod.analysis.parity import (FLIGHT_SPINE, audit_flight_record,
                                    audit_serve_report, flight_contract,
                                    flight_record_keys, serve_report_fields,
                                    shard_variant_fields)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).parent / "lint_fixtures"
SCRIPTS = REPO / "scripts"


def _lint_fixture(name, pretend, corpus=""):
    src = (FIXTURES / name).read_text()
    return lint_source(src, pretend, corpus)


def _active_rules(findings):
    return sorted({f.rule for f in findings if not f.suppressed})


# ---------------------------------------------------------------------------
# fixture corpus: each family demonstrably trips and passes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trip,passes,pretend,corpus,rules", [
    ("determinism_trip.py", "determinism_pass.py",
     "anomod/serve/fixture.py", "",
     ["D101", "D102", "D103", "D104", "D105"]),
    ("env_trip.py", "env_pass.py", "anomod/fixture.py",
     "ANOMOD_KNOWN_KNOB is documented here", ["E201", "E202"]),
    ("seam_trip.py", "seam_pass.py", "anomod/serve/fixture.py", "",
     ["S301"]),
    ("seam_gather_trip.py", "seam_gather_pass.py", "anomod/replay.py",
     "", ["S302"]),
    ("lock_trip.py", "lock_pass.py", "anomod/obs/registry.py", "",
     ["L501"]),
    ("commit_barrier_trip.py", "commit_barrier_pass.py",
     "anomod/serve/fixture.py", "", ["C601"]),
])
def test_fixture_family(trip, passes, pretend, corpus, rules):
    assert _active_rules(_lint_fixture(trip, pretend, corpus)) == rules
    assert _active_rules(_lint_fixture(passes, pretend, corpus)) == []


def test_scoping_is_path_based():
    """The same determinism-trip source is CLEAN outside the canonical
    modules, and the seam-trip source is clean inside a seam module —
    the contracts bind where they are declared, nowhere else."""
    src = (FIXTURES / "determinism_trip.py").read_text()
    assert _active_rules(lint_source(src, "anomod/io/fixture.py")) == []
    seam = (FIXTURES / "seam_trip.py").read_text()
    assert _active_rules(
        lint_source(seam, "anomod/serve/batcher.py")) == []


# ---------------------------------------------------------------------------
# suppression syntax
# ---------------------------------------------------------------------------

_VIOLATION = ("import time\n"
              "def f():\n"
              "    return time.time(){directive}\n")


def test_suppression_roundtrip():
    clean = _VIOLATION.format(
        directive="  # anomod-" "lint: disable=D101 — forensic stamp")
    got = lint_source(clean, "anomod/serve/x.py")
    assert _active_rules(got) == []
    sup = [f for f in got if f.suppressed]
    assert len(sup) == 1 and sup[0].rule == "D101"
    assert sup[0].reason == "forensic stamp"
    # -- and the "--" separator spelling
    clean2 = _VIOLATION.format(
        directive="  # anomod-" "lint: disable=D101 -- forensic stamp")
    assert _active_rules(lint_source(clean2, "anomod/serve/x.py")) == []


def test_suppression_requires_reason():
    bare = _VIOLATION.format(
        directive="  # anomod-" "lint: disable=D101")
    rules = _active_rules(lint_source(bare, "anomod/serve/x.py"))
    # the reasonless directive is a finding AND grants no suppression:
    # the tree cannot go green on a bare disable
    assert rules == ["D101", "LINT000"]


def test_suppression_unknown_rule_is_finding():
    bad = _VIOLATION.format(
        directive="  # anomod-" "lint: disable=NOPE — because")
    rules = _active_rules(lint_source(bad, "anomod/serve/x.py"))
    assert "LINT000" in rules and "D101" in rules


def test_suppression_statement_scope():
    """A directive-only line blesses the whole statement below it —
    including a compound statement's body (the engine's fused-gather
    branch is the real instance)."""
    src = ("import time\n"
           "def f(x):\n"
           "    # anomod-" "lint: disable=D101 — blessed block\n"
           "    if x:\n"
           "        a = time.time()\n"
           "        b = time.time()\n"
           "        return a, b\n"
           "    return time.time()\n")
    got = lint_source(src, "anomod/serve/x.py")
    active = [f for f in got if not f.suppressed]
    # lines 5 and 6 are inside the blessed if-statement; line 8 is NOT
    assert len(active) == 1 and active[0].line == 8
    assert sum(1 for f in got if f.suppressed) == 2


def test_suppression_file_wide():
    src = ("# anomod-" "lint: disable-file=D101 — fixture-wide waiver\n"
           "import time\n"
           "a = time.time()\n"
           "b = time.time()\n")
    assert _active_rules(lint_source(src, "anomod/serve/x.py")) == []


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

def test_baseline_semantics(tmp_path):
    f1 = Finding("D101", "anomod/serve/x.py", 3, "wall clock")
    f2 = Finding("L501", "anomod/obs/registry.py", 9, "unlocked")
    # new finding fails
    doc = summarize([f1, f2], [])
    assert doc["status"] == "contract-violations" \
        and doc["findings"] == 2
    # baselined finding passes; the other still fails
    doc = summarize([f1, f2], [f1.key])
    assert doc["findings"] == 1 and doc["baselined"] == 1
    # fully baselined tree is green, suppressed findings never fail
    doc = summarize([f1, Finding("D101", "a.py", 1, "x",
                                 suppressed=True, reason="why")],
                    [f1.key])
    assert doc["status"] == "ok" and doc["suppressed"] == 1
    # stale entries are reported (the shrink ratchet)
    doc = summarize([], [f1.key])
    assert doc["status"] == "ok" and doc["stale_baseline"] == [f1.key]
    # file round-trip
    p = tmp_path / "baseline.json"
    save_baseline(p, [f1.key, f2.key])
    assert load_baseline(p) == sorted([f1.key, f2.key])
    assert load_baseline(tmp_path / "absent.json") == []


def test_lint000_cannot_be_baselined(tmp_path):
    """A reasonless/malformed suppression (LINT000) can never ride the
    baseline: --update-baseline must not write its key, and even a
    hand-edited baseline entry must not silence it — otherwise the
    ratchet would launder the exact silent-disable hole the rule
    closes."""
    bad = Finding("LINT000", "anomod/serve/x.py", 3, "bare disable")
    p = tmp_path / "baseline.json"
    save_baseline(p, [bad.key, "D101|a.py|1"])
    assert load_baseline(p) == ["D101|a.py|1"]     # key dropped on save
    doc = summarize([bad], [bad.key])              # hand-edited entry
    assert doc["status"] == "contract-violations" \
        and doc["findings"] == 1


# ---------------------------------------------------------------------------
# parity-surface audit
# ---------------------------------------------------------------------------

#: THE canonical ServeReport inventory — every field that is pinned
#: byte-identical across shard counts / pipeline depths / residencies /
#: recoveries (i.e. NOT on SHARD_VARIANT_REPORT_FIELDS).  Adding a
#: ServeReport field breaks this equality until the author either adds
#: it here (naming it in a test — the parity audit's requirement) or
#: declares it variant, consciously widening the variant surface.
CANONICAL_REPORT_FIELDS = (
    "n_tenants", "duration_s", "ticks", "capacity_spans_per_s",
    "offered_spans", "admitted_spans", "served_spans", "shed_spans",
    "shed_fraction", "served_batches", "peak_backlog_spans",
    "max_backlog", "buckets", "dispatches_by_width", "fused",
    "lane_buckets", "native_staging", "serve_state", "latency",
    "per_priority", "modality_events", "n_alerts",
    "n_tenants_alerted", "fault_detection", "rca_enabled",
    "n_rca_runs", "rca_topk_hits", "rca_eligible",
    "rca_alert_to_culprit_s", "supervised", "ckpt_every",
    "n_checkpoints", "n_shard_crashes", "n_respawns",
    "n_restored_ticks", "n_quarantined", "n_migrated_tenants",
    "flight_enabled", "flight_recorded_ticks", "flight_dropped_ticks",
    # elastic policy (ISSUE-13): the policy mode and its executed
    # decision counts are seed-deterministic (and zero with the policy
    # off, so the shard fan-out parity holds trivially); peak_shards /
    # policy_wall_s are the variant topology/wall halves
    "policy", "n_scale_ups", "n_scale_downs", "n_rebalances",
    "n_policy_migrations", "brownout_ticks",
    # the fleet census (ISSUE-15): the enable bit is config, the
    # census tick count is a pure function of cadence × run length,
    # and the hot-set/Zipf census derives from coordinator admission
    # decisions alone — all three shard-invariant (pinned in
    # tests/test_census.py); the resident-bytes dict follows the
    # pool/scratch topology and lives on SHARD_VARIANT_REPORT_FIELDS
    "census_enabled", "census_ticks", "census_hot_set",
    # the deferred-commit seam (ISSUE-16): the mode bit is config and
    # the async tick count is a pure function of config × run length
    # (every served tick defers except the forced-sync checkpoint
    # cadence), so both are parity-checked; the hidden-wait wall
    # (commit_defer_wall_s) lives on SHARD_VARIANT_REPORT_FIELDS
    "async_commit", "async_ticks",
    # state tiering (ISSUE-19): the hot capacity is config and every
    # demote/spill/promote/miss count is a pure function of
    # seed+config (the deferral is deterministic, never wall-clock —
    # pinned in tests/test_serve_tiering.py); the prefetch-hidden
    # count and the tier wall are wall-clock telemetry and live on
    # SHARD_VARIANT_REPORT_FIELDS
    "tier_hot", "n_tier_demotions_warm", "n_tier_demotions_cold",
    "n_tier_promotions", "n_tier_misses")


def test_canonical_report_inventory_pinned():
    fields = serve_report_fields(REPO)
    variant = set(shard_variant_fields(REPO))
    assert set(CANONICAL_REPORT_FIELDS) == set(fields) - variant, \
        "ServeReport changed: update CANONICAL_REPORT_FIELDS (naming " \
        "the field pins it canonical) or SHARD_VARIANT_REPORT_FIELDS " \
        "(declaring it variant) — never neither"
    assert not variant - set(fields)       # no stale variant entries


def test_parity_audit_fails_on_unlisted_synthetic_field():
    fields = list(serve_report_fields(REPO)) + ["sneaky_new_field"]
    got = audit_serve_report(fields, shard_variant_fields(REPO),
                             test_corpus="nothing names it")
    assert any(f.rule == "P401" and "sneaky_new_field" in f.message
               for f in got)
    # ...and is satisfied by EITHER coverage route
    ok_by_test = audit_serve_report(
        ["sneaky_new_field"], (), test_corpus="sneaky_new_field pinned")
    assert ok_by_test == []
    ok_by_variant = audit_serve_report(
        ["sneaky_new_field"], ("sneaky_new_field",), test_corpus="")
    assert ok_by_variant == []


def test_parity_audit_stale_variant_entry():
    got = audit_serve_report(["real_field"],
                             ("real_field", "ghost_field"),
                             test_corpus="")
    assert [f.rule for f in got] == ["P402"]


def test_flight_record_audit():
    planes, variant = flight_contract(REPO)
    keys = flight_record_keys(REPO)
    # the real record is exactly spine + planes + variant
    assert audit_flight_record(keys, planes, variant) == []
    assert set(planes) <= set(keys) and set(variant) <= set(keys)
    # an undeclared key fails (P403); a missing declared key fails
    # (P404) — the every-record-carries-every-tier contract
    got = audit_flight_record(list(keys) + ["stowaway"], planes, variant)
    assert [f.rule for f in got] == ["P403"]
    got = audit_flight_record([k for k in keys if k != "fold"],
                              planes, variant)
    assert [f.rule for f in got] == ["P404"]
    assert set(FLIGHT_SPINE) == {"tick", "now_s", "final"}


# ---------------------------------------------------------------------------
# the repo itself holds its contracts
# ---------------------------------------------------------------------------

def test_repo_lint_clean():
    """`anomod lint` runs clean on the repo: zero unsuppressed findings
    (every deliberate exception carries a reasoned inline suppression)
    and the shipped baseline is EMPTY — the acceptance pin."""
    findings = lint_repo(REPO) + run_parity_audit(REPO)
    active = [f.render() for f in findings if not f.suppressed]
    assert active == [], "\n".join(active)
    assert load_baseline(SCRIPTS / "lint_baseline.json") == []
    # the deliberate exceptions exist and carry reasons
    sup = [f for f in findings if f.suppressed]
    assert sup and all(f.reason for f in sup)


def test_rule_catalog_documented():
    """Every rule id is cataloged in docs/CONTRACTS.md with its
    motivation — the operator-facing contract list cannot drift from
    the code."""
    doc = (REPO / "docs" / "CONTRACTS.md").read_text()
    for rid, rule in RULES.items():
        assert rid in doc, f"{rid} missing from docs/CONTRACTS.md"
        assert rule.family and rule.synopsis and rule.motivation


def test_status_block_shape():
    blk = status_block(REPO)
    assert blk["status"] == "ok" and blk["findings"] == 0
    assert blk["rules"] == len(RULES)
    assert blk["baseline_size"] == 0 and blk["suppressed"] >= 4


def test_lint_cli_json():
    from anomod.cli import main
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["lint", "--json", "--show-suppressed"])
    assert rc == 0
    doc = json.loads(buf.getvalue())
    assert doc["status"] == "ok" and doc["findings"] == 0
    assert all(s["reason"] for s in doc["suppressed_findings"])


# ---------------------------------------------------------------------------
# env-contract delegation: the dynamic-read false negative is closed
# ---------------------------------------------------------------------------

def test_env_contract_catches_dynamic_read(tmp_path):
    """os.environ[f"ANOMOD_{name}"] — invisible to the PR-3 token grep
    — now fails the delegating script with its exit code unchanged."""
    (tmp_path / "anomod").mkdir()
    (tmp_path / "scripts").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "anomod" / "config.py").write_text(
        'X = _env("ANOMOD_KNOWN_KNOB", "1")\n')
    (tmp_path / "anomod" / "dyn.py").write_text(
        'import os\nname = "SHARDS"\n'
        'Y = os.environ[f"ANOMOD_{name}"]\n')
    (tmp_path / "README.md").write_text("docs\n")
    r = subprocess.run(
        [sys.executable, str(SCRIPTS / "check_env_contract.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["n_dynamic"] == 1 and "anomod/dyn.py" in out["dynamic"]
    assert "DYNAMIC" in r.stderr


def test_env_rule_alias_and_concat_forms():
    """The AST scanner sees through the alias/concat spellings the grep
    inferred only by accident of the token appearing somewhere."""
    src = ("from os import environ, getenv\n"
           "name = 'X'\n"
           "a = environ['ANOMOD_ALIAS_ROGUE']\n"
           "b = getenv('ANOMOD_' + name)\n")
    rules = _active_rules(lint_source(src, "anomod/x.py"))
    assert rules == ["E201", "E202"]


# ---------------------------------------------------------------------------
# gate wiring
# ---------------------------------------------------------------------------

def test_check_contracts_gate_green_on_repo():
    sys.path.insert(0, str(SCRIPTS))
    try:
        import check_contracts
    finally:
        sys.path.pop(0)
    out = check_contracts.run()
    assert out["status"] == "ok" and out["findings"] == 0
    assert out["stale_baseline"] == []


def test_sanitize_smoke_verdict_shapes():
    """The probe returns a reasoned verdict either way; the smoke's
    skip path carries its reason (never a silent skip).  The full
    build+hammer run is `make -C native tsan` (slow path)."""
    sys.path.insert(0, str(SCRIPTS))
    try:
        import native_sanitize_smoke as nss
    finally:
        sys.path.pop(0)
    p = nss.probe("tsan")
    assert set(p) == {"ok", "reason"}
    assert p["ok"] is True or p["reason"]
    with pytest.raises(ValueError):
        nss.run("nope")
    # a box with no compiler must SKIP with the reason recorded
    missing = nss.probe("tsan", cxx="definitely-not-a-compiler")
    assert missing["ok"] is False and "compiler" in missing["reason"]


@pytest.mark.slow
def test_sanitize_smoke_full_run():
    sys.path.insert(0, str(SCRIPTS))
    try:
        import native_sanitize_smoke as nss
    finally:
        sys.path.pop(0)
    out = nss.run("tsan", workers=2, iters=8)
    assert out["status"] in ("ok", "skip")
    if out["status"] == "skip":
        assert out["reason"]
