"""The deferred-commit serve tick (ANOMOD_SERVE_ASYNC_COMMIT, ISSUE-16).

The central pin: with the knob ON, tick N's fold+score dispatch is
issued WITHOUT waiting, tick N+1's coordinator phases (admission,
drain, shed, SLO) run under the in-flight XLA work, and tick N's
results drain at a commit barrier placed just before they are first
read — and every decision plane (tenant states, alert streams, SLO,
shed, the canonical flight journal) is BYTE-identical to the
synchronous engine of the same seed.  The synchronous engine stays the
parity oracle (``ANOMOD_SERVE_ASYNC_COMMIT=0``); only wall-time
attribution moves (the hidden wait lands in ``commit_defer_wall_s``, a
consciously variant report field).

Tier-1 covers the parity core, the chaos-hook ordering across the
deferred commit (pre-mutation issue-side phases and the post-mutation
``commit`` case), elastic episodes landing mid-defer, and the env
contract; the exhaustive phase × shards × pipeline cross stays in the
supervise module.
"""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from anomod.obs.flight import diff_journals
from anomod.serve.engine import (SHARD_VARIANT_REPORT_FIELDS, ServeEngine,
                                 run_power_law)

#: the compact seeded scenario (the supervise-module idiom): 20 virtual
#: ticks, alerts firing, several checkpoints — every canonical plane
#: LIVE while commits are deferred
KW = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
          overload=2.0, duration_s=20, tick_s=1.0, seed=5,
          window_s=2.0, baseline_windows=4, fault_tenants=1,
          buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
          n_windows=16, flight_digest_every=4, ckpt_every=4,
          flight=True)

#: report fields that legitimately differ between a synchronous and a
#: deferred-commit run of the same seed: the mode bit and its tick
#: count are CONFIG state (canonical on purpose — they differ exactly
#: when the config differs); every wall leg is already shard-variant
ASYNC_REPORT_FIELDS = ("async_commit", "async_ticks")


@pytest.fixture(scope="module")
def sync_ref():
    """ONE synchronous 2-shard pipelined reference run — the parity
    oracle every async leg in this module compares against."""
    eng, rep = run_power_law(shards=2, pipeline=2, async_commit=False,
                             **KW)
    return eng, rep, eng.flight_recorder.journal()


@pytest.fixture(scope="module")
def async_run():
    eng, rep = run_power_law(shards=2, pipeline=2, async_commit=True,
                             **KW)
    return eng, rep


def assert_async_parity(reference, eng, rep, extra_skip=()):
    """Byte-identical tenant states + alert streams, identical report
    decision fields, equal canonical flight journals (the supervise
    module's no-score-gap shape, crossed over the async seam)."""
    ref_eng, ref_rep, ref_journal = reference
    for tid in sorted(ref_eng._tenant_det):
        assert [dataclasses.asdict(a) for a in ref_eng.alerts_for(tid)] \
            == [dataclasses.asdict(a) for a in eng.alerts_for(tid)], \
            f"tenant {tid} alert stream diverges"
        s1 = ref_eng._tenant_replay[tid].state
        s2 = eng._tenant_replay[tid].state
        assert np.array_equal(np.asarray(s1.agg), np.asarray(s2.agg)), \
            f"tenant {tid} agg plane diverges"
        assert np.array_equal(np.asarray(s1.hist), np.asarray(s2.hist)), \
            f"tenant {tid} hist plane diverges"
    skip = set(SHARD_VARIANT_REPORT_FIELDS) | set(ASYNC_REPORT_FIELDS) \
        | set(extra_skip)
    a = {k: v for k, v in ref_rep.to_dict().items() if k not in skip}
    b = {k: v for k, v in rep.to_dict().items() if k not in skip}
    assert a == b, sorted(k for k in a if a[k] != b[k])
    d = diff_journals(ref_journal, eng.flight_recorder.journal())
    assert d is None, d


# ---------------------------------------------------------------------------
# the parity core
# ---------------------------------------------------------------------------

def test_async_commit_byte_parity(sync_ref, async_run):
    """The headline pin: the deferred-commit engine is byte-identical
    to the synchronous oracle on every decision plane, and actually
    ran deferred (every tick but the forced-sync checkpoint-cadence
    ones took the async tail)."""
    eng, rep = async_run
    assert rep.async_commit is True and sync_ref[1].async_commit is False
    assert rep.async_ticks > 0 and sync_ref[1].async_ticks == 0
    assert rep.commit_defer_wall_s >= 0.0
    assert_async_parity(sync_ref, eng, rep)


def test_async_commit_rerun_deterministic(async_run):
    """Same seed, same knob ⇒ same canonical journal bytes — the async
    engine is as rerun-deterministic as the oracle it mirrors."""
    eng, _ = async_run
    rerun, _ = run_power_law(shards=2, pipeline=2, async_commit=True,
                             **KW)
    assert rerun.flight_recorder.canonical_bytes() \
        == eng.flight_recorder.canonical_bytes()


def test_async_header_replays_resolved(async_run, sync_ref):
    """The flight header records the RESOLVED mode (the elastic-policy
    precedent): `anomod audit replay` re-executes the run dict as-is
    and must land on the same canonical bytes — and the header's
    engine block names the seam so forensics can see which tick
    structure produced a journal."""
    eng, _ = async_run
    h = eng.flight_recorder.header
    assert h["engine"]["async_commit"] is True
    assert sync_ref[0].flight_recorder.header["engine"]["async_commit"] \
        is False
    run = dict(h["run"])
    assert run["async_commit"] is True
    run["buckets"] = tuple(run["buckets"])
    run["lane_buckets"] = tuple(run["lane_buckets"])
    run.setdefault("flight", True)
    replay, _ = run_power_law(**run)
    assert replay.flight_recorder.canonical_bytes() \
        == eng.flight_recorder.canonical_bytes()


def test_mesh_refuses_explicit_async_commit(monkeypatch):
    """The mesh plane manages its own sharded dispatch: an EXPLICIT
    async_commit=True on a mesh engine is a hard error (the
    shards-on-mesh idiom), while an env-sourced knob degrades to the
    synchronous tick so exported globals never break mesh runs."""
    from anomod.config import Config, set_config
    from anomod.parallel import make_mesh
    from anomod.replay import ReplayConfig
    from anomod.serve import PowerLawTraffic
    traffic = PowerLawTraffic(n_tenants=2, total_rate_spans_per_s=100,
                              seed=0, n_services=4)
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    with pytest.raises(ValueError, match="mesh"):
        ServeEngine(traffic.specs, traffic.services, cfg,
                    mesh=make_mesh(2), async_commit=True)
    monkeypatch.setenv("ANOMOD_SERVE_ASYNC_COMMIT", "1")
    set_config(Config())
    try:
        eng = ServeEngine(traffic.specs, traffic.services, cfg,
                          mesh=make_mesh(2))
        assert eng.async_commit is False
    finally:
        monkeypatch.delenv("ANOMOD_SERVE_ASYNC_COMMIT")
        set_config(Config())


# ---------------------------------------------------------------------------
# chaos-hook ordering across the deferred commit (satellite: the
# pre/post-mutation cases)
# ---------------------------------------------------------------------------

def test_chaos_hooks_fire_on_origin_tick_across_defer():
    """The injection-point contract: with commits deferred, the chaos
    phases still fire in the synchronous order and on the ORIGIN tick
    — ``stage``/``dispatch`` at issue time (pre-mutation), ``fold``/
    ``score``/``commit`` at the barrier (post-mutation), never keyed
    on the tick the barrier happens to land in.  Probed by recording
    every (phase, tick) hit through a live deferred run."""
    hits = []
    from anomod.serve import chaos as chaos_mod
    orig_hit = chaos_mod.ServeChaos.hit

    class _Recording(chaos_mod.ServeChaos):
        def hit(self, phase, tick, shard):
            hits.append((phase, tick, shard))
            return orig_hit(self, phase, tick, shard)

    import anomod.serve.engine as engine_mod
    orig_cls = chaos_mod.ServeChaos
    chaos_mod.ServeChaos = _Recording
    engine_orig = getattr(engine_mod, "ServeChaos", None)
    if engine_orig is not None:
        engine_mod.ServeChaos = _Recording
    try:
        # a stall is output-neutral: hooks fire, nothing recovers
        run_power_law(shards=1, chaos="stall@6:shard=0:ms=1",
                      async_commit=True, **KW)
    finally:
        chaos_mod.ServeChaos = orig_cls
        if engine_orig is not None:
            engine_mod.ServeChaos = engine_orig
    assert hits, "chaos hooks never consulted"
    by_tick = {}
    for phase, tick, shard in hits:
        by_tick.setdefault(tick, []).append(phase)
    # every scored tick ran the full synchronous phase order, keyed on
    # its OWN tick even though fold/score/commit fired one tick later
    full = [seq for seq in by_tick.values() if len(seq) >= 5]
    assert full, by_tick
    for seq in full:
        assert seq == ["stage", "dispatch", "fold", "commit"] or \
            seq[:2] == ["stage", "dispatch"] and seq[-1] == "commit", seq


def test_chaos_pre_mutation_issue_fault_recovers(sync_ref):
    """A dispatch-phase fault fires at ISSUE time (before any state
    mutation lands): the deferred tick fails inline, recovery restores
    + re-executes synchronously, and the run stays byte-identical to
    the fault-free oracle."""
    eng, rep = run_power_law(shards=2, pipeline=2,
                             chaos="crash@6:shard=0:phase=dispatch",
                             async_commit=True, **KW)
    assert rep.n_shard_crashes >= 1
    assert_async_parity(sync_ref, eng, rep,
                        extra_skip=("n_shard_crashes", "n_respawns",
                                    "n_restored_ticks"))


def test_chaos_post_mutation_commit_fault_recovers(sync_ref):
    """The post-mutation hard case: a ``commit``-phase fault fires at
    the BARRIER, after the deferred drain has already folded state
    deltas — one tick later in wall order than it was scripted.
    Recovery must key on the origin tick (a wrong key would re-trip
    the repeat=1 budget or skip the fault entirely) and restore the
    pre-mutation checkpoint, landing byte-identical to the oracle."""
    eng, rep = run_power_law(shards=2, pipeline=2,
                             chaos="except@9:shard=1:phase=commit",
                             async_commit=True, **KW)
    assert rep.n_shard_crashes >= 1 and rep.n_restored_ticks >= 1
    assert_async_parity(sync_ref, eng, rep,
                        extra_skip=("n_shard_crashes", "n_respawns",
                                    "n_restored_ticks"))


def test_chaos_every_phase_async_matches_sync_recovery(sync_ref):
    """The supervise module's five-phase campaign, re-run with commits
    deferred: the same scripted faults recover to the same bytes —
    the async seam adds no recovery divergence at ANY phase."""
    script = ("crash@6:shard=0:phase=dispatch;"
              "except@9:shard=1:phase=score;"
              "except@15:shard=1:phase=commit;"
              "crash@17:shard=0:phase=stage;"
              "stall@10:shard=0:ms=1")
    eng, rep = run_power_law(shards=2, pipeline=2, chaos=script,
                             async_commit=True, **KW)
    assert rep.n_shard_crashes == 4
    assert_async_parity(sync_ref, eng, rep,
                        extra_skip=("n_shard_crashes", "n_respawns",
                                    "n_restored_ticks"))


# ---------------------------------------------------------------------------
# elastic scaling landing mid-defer (satellite: PR-13 episodes stay
# deterministic under audit replay)
# ---------------------------------------------------------------------------

#: the policy-module surge scenario: sub-capacity base load, a 6x surge
#: forcing one scale-up and one scale-down inside the run
EL_KW = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
             overload=0.6, duration_s=24, tick_s=1.0, seed=5,
             window_s=5.0, baseline_windows=4, fault_tenants=0,
             buckets=(64, 256), lane_buckets=(1, 2, 4),
             max_backlog=1500, n_windows=16, flight_digest_every=4,
             flight=True)
SURGE = "surge@6:factor=6:ticks=6"


def _scaling_events(eng):
    return [ev for t in eng.flight_recorder.records()
            for ev in t.get("scaling", ())]


def test_elastic_episodes_mid_defer_deterministic():
    """Scale-up/down episodes landing while a commit is deferred: the
    policy executes AT the barrier (scale-down can never retire a
    runner with in-flight work), the episode schedule is identical to
    the synchronous policy run, and an `anomod audit replay` from the
    async run's header alone reproduces the canonical bytes."""
    e_sync, _ = run_power_law(shards=1, chaos=SURGE, policy="auto",
                              min_shards=1, max_shards=2,
                              cooldown_ticks=5, async_commit=False,
                              **EL_KW)
    e_async, rep = run_power_law(shards=1, chaos=SURGE, policy="auto",
                                 min_shards=1, max_shards=2,
                                 cooldown_ticks=5, async_commit=True,
                                 **EL_KW)
    events = _scaling_events(e_async)
    kinds = [ev["kind"] for ev in events]
    assert "scale_up" in kinds and "scale_down" in kinds
    assert events == _scaling_events(e_sync)
    assert e_async.flight_recorder.canonical_bytes() \
        == e_sync.flight_recorder.canonical_bytes()
    assert rep.async_ticks > 0
    # the audit-replay leg: the header run dict re-executes RESOLVED
    run = dict(e_async.flight_recorder.header["run"])
    assert run["async_commit"] is True and run["policy"] == "auto"
    run["buckets"] = tuple(run["buckets"])
    run["lane_buckets"] = tuple(run["lane_buckets"])
    replay, _ = run_power_law(**run)
    assert _scaling_events(replay) == events
    assert replay.flight_recorder.canonical_bytes() \
        == e_async.flight_recorder.canonical_bytes()


# ---------------------------------------------------------------------------
# env contract (satellite: garbage values raise, knobs covered)
# ---------------------------------------------------------------------------

def test_async_env_knobs_registered_and_validated(monkeypatch):
    from anomod.config import Config
    monkeypatch.delenv("ANOMOD_SERVE_ASYNC_COMMIT", raising=False)
    monkeypatch.delenv("ANOMOD_SERVE_NATIVE_DRAIN", raising=False)
    cfg = Config()
    assert cfg.serve_async_commit is False       # sync stays the oracle
    assert cfg.serve_native_drain == "auto"

    for tok in ("1", "on", "true", "YES"):
        monkeypatch.setenv("ANOMOD_SERVE_ASYNC_COMMIT", tok)
        assert Config().serve_async_commit is True
    for tok in ("0", "off", "false", "no", ""):
        monkeypatch.setenv("ANOMOD_SERVE_ASYNC_COMMIT", tok)
        assert Config().serve_async_commit is False
    # garbage RAISES — the knob flips the whole tick structure, so a
    # typo must fail at config construction, not serve synchronously
    for bad in ("treu", "2", "banana", "async"):
        monkeypatch.setenv("ANOMOD_SERVE_ASYNC_COMMIT", bad)
        with pytest.raises(ValueError,
                           match="ANOMOD_SERVE_ASYNC_COMMIT"):
            Config()
    monkeypatch.delenv("ANOMOD_SERVE_ASYNC_COMMIT")

    for tok, want in (("auto", "auto"), ("1", "on"), ("on", "on"),
                      ("0", "off"), ("OFF", "off")):
        monkeypatch.setenv("ANOMOD_SERVE_NATIVE_DRAIN", tok)
        assert Config().serve_native_drain == want
    for bad in ("fast", "numpy", "2", "native"):
        monkeypatch.setenv("ANOMOD_SERVE_NATIVE_DRAIN", bad)
        with pytest.raises(ValueError,
                           match="ANOMOD_SERVE_NATIVE_DRAIN"):
            Config()


def test_drain_engine_ctor_validates():
    """The AdmissionController mirror of the env contract: an explicit
    garbage ``drain_engine=`` fails loudly at construction."""
    from anomod.serve import AdmissionController, TenantSpec
    specs = [TenantSpec(tenant_id=0, name="t0", priority=0)]
    with pytest.raises(ValueError, match="drain_engine"):
        AdmissionController(specs, max_backlog=100,
                            drain_engine="banana")
    for mode in ("auto", "on", "off"):
        adm = AdmissionController(specs, max_backlog=100,
                                  drain_engine=mode)
        assert adm.drain_engine in ("heap", "numpy", "native")


def test_async_knobs_env_contract_covered():
    """Every new ISSUE-16 knob is in the validated Config contract
    (check_env_contract green — the CI-gate clause)."""
    import sys as _sys
    _sys.path.insert(0, str(Path(__file__).parent.parent / "scripts"))
    try:
        import check_env_contract as cec
    finally:
        _sys.path.pop(0)
    refs = cec.referenced_vars(Path(cec.ROOT))
    corpus = cec.covered_vars(Path(cec.ROOT))
    for knob in ("ANOMOD_SERVE_ASYNC_COMMIT",
                 "ANOMOD_SERVE_NATIVE_DRAIN"):
        assert knob in refs and knob in corpus


def test_report_carries_async_fields(sync_ref):
    """The report names the seam: the mode bit, how many ticks ran
    deferred, and the (variant) hidden-wait wall — and the variant
    list covers ONLY the wall, so the mode stays parity-checked."""
    d = sync_ref[1].to_dict()
    assert d["async_commit"] is False and d["async_ticks"] == 0
    assert "commit_defer_wall_s" in d
    assert "commit_defer_wall_s" in SHARD_VARIANT_REPORT_FIELDS
    assert "async_commit" not in SHARD_VARIANT_REPORT_FIELDS
    assert "async_ticks" not in SHARD_VARIANT_REPORT_FIELDS


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def test_cli_async_flag_conflicts():
    from anomod.cli import main
    base = ["serve", "--tenants", "2", "--duration", "1"]
    with pytest.raises(SystemExit):      # contradiction
        main(base + ["--async-commit", "--no-async-commit"])
    with pytest.raises(SystemExit):      # mesh runs synchronous
        main(base + ["--devices", "1", "--async-commit"])
