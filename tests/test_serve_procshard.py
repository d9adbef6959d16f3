"""Process-shard serving: the GIL escape (ANOMOD_SERVE_WORKER=process,
ISSUE-20).

The central pin: with the knob ON, each shard's WHOLE scoring plane —
detectors, replay states, its BucketRunner, its metrics registry —
lives in a spawn-context worker process behind the same ShardWorker
seam, driven by a picklable per-tick command protocol, and every
decision plane (tenant states, alert streams, SLO, shed, the canonical
flight journal) is BYTE-identical to the thread engine of the same
seed — and to the same run on ONE process.  The thread engine stays
the parity oracle (``ANOMOD_SERVE_WORKER=thread``, the default).

The second pin is the tick barrier itself: cross-shard registry merges
serialize as SPARSE touched-key deltas (``ANOMOD_SERVE_FOLD=sparse``)
or dense full walks, combined in fixed (shard, seq) order — scrape
output byte-identical either way, with the sparse payload bounded at
half the dense walk's bytes on the module scenario.  State digests
cross the pipe as per-tenant ``(crc, len)`` fragments folded through
``crc32_combine`` — pinned bit-equal to the sequential walk here.

Tier-1 covers the parity core, worker-crash respawn through
supervision, elastic scaling across process workers, the knob/refusal
matrix and the env contract; wall-clock scaling is a chip run's to
claim (PERF.md), never asserted here.
"""

import dataclasses
import zlib

import numpy as np
import pytest

from anomod.obs.flight import (crc32_combine, diff_journals,
                               fold_digest_parts, state_digest,
                               state_digest_parts)
from anomod.obs.registry import Registry, delta_nbytes, set_registry
from anomod.serve.engine import (SHARD_VARIANT_REPORT_FIELDS, ServeEngine,
                                 run_power_law)

#: the compact seeded scenario (the supervise-module idiom): 20 virtual
#: ticks, alerts firing mid-run, so every canonical plane is LIVE when
#: it crosses the process boundary
KW = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
          overload=2.0, duration_s=20, tick_s=1.0, seed=5,
          window_s=2.0, baseline_windows=4, fault_tenants=1,
          buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
          n_windows=16, flight_digest_every=4)

#: report fields that legitimately differ between a fault-free
#: unsupervised run and a supervised recovered one (the supervise
#: module's inventory plus the supervision config bits themselves)
RECOVERY_REPORT_FIELDS = ("supervised", "ckpt_every", "n_checkpoints",
                          "n_shard_crashes", "n_respawns",
                          "n_restored_ticks", "n_quarantined",
                          "n_migrated_tenants")

#: the policy-module inventory: executed decision counts + the mode
POLICY_REPORT_FIELDS = ("policy", "n_scale_ups", "n_scale_downs",
                        "n_rebalances", "n_policy_migrations",
                        "brownout_ticks", "n_checkpoints")


def _run(**kw):
    """One engine run under its OWN enabled registry (the bench-leg
    idiom): the barrier folds need somewhere to land, and the module's
    runs must not cross-pollinate one shared registry."""
    prev = set_registry(Registry(enabled=True))
    try:
        return run_power_law(**kw)
    finally:
        set_registry(prev)


@pytest.fixture(scope="module")
def thread_ref():
    """ONE thread-engine 2-shard pipelined reference run — the parity
    oracle every process leg in this module compares against."""
    eng, rep = _run(shards=2, pipeline=2, worker="thread",
                    fold="sparse", **KW)
    return eng, rep, eng.flight_recorder.journal()


@pytest.fixture(scope="module")
def proc_run():
    eng, rep = _run(shards=2, pipeline=2, worker="process",
                    fold="sparse", **KW)
    return eng, rep


@pytest.fixture(scope="module")
def proc_one():
    eng, rep = _run(shards=1, worker="process", fold="sparse", **KW)
    return eng, rep


@pytest.fixture(scope="module")
def proc_dense():
    eng, rep = _run(shards=2, pipeline=2, worker="process",
                    fold="dense", **KW)
    return eng, rep


def assert_proc_parity(reference, eng, rep, extra_skip=()):
    """Identical alert streams (read through the coordinator mirrors —
    a process engine's replay planes live in its children), identical
    report decision fields, equal canonical flight journals.  Tenant
    STATE bytes are pinned by the journal's state digests (digest
    cadence 4 over 20 ticks), computed where the states live."""
    ref_eng, ref_rep, ref_journal = reference
    tids = sorted(ref_eng._tenant_det)
    assert tids == sorted(eng._tenant_det)
    for tid in tids:
        assert [dataclasses.asdict(a) for a in ref_eng.alerts_for(tid)] \
            == [dataclasses.asdict(a) for a in eng.alerts_for(tid)], \
            f"tenant {tid} alert stream diverges"
    skip = set(SHARD_VARIANT_REPORT_FIELDS) | set(extra_skip)
    a = {k: v for k, v in ref_rep.to_dict().items() if k not in skip}
    b = {k: v for k, v in rep.to_dict().items() if k not in skip}
    assert a == b, sorted(k for k in a if a[k] != b[k])
    d = diff_journals(ref_journal, eng.flight_recorder.journal())
    assert d is None, d


# ---------------------------------------------------------------------------
# the parity core
# ---------------------------------------------------------------------------

def test_process_byte_parity(thread_ref, proc_run):
    """The headline pin: N shard processes are byte-identical to N
    shard threads on every decision plane — and actually ran as
    processes (the report names the resolved engine)."""
    eng, rep = proc_run
    assert rep.worker == "process" and thread_ref[1].worker == "thread"
    assert rep.fold == "sparse"
    assert rep.n_alerts > 0          # parity would be vacuous silent
    assert_proc_parity(thread_ref, eng, rep)


def test_two_vs_one_process_parity(proc_run, proc_one):
    """Decomposition honesty: 2 processes vs 1 process of the same
    seed — byte-identical decisions, so process-count changes move
    only wall-clock."""
    eng2, rep2 = proc_run
    eng1, rep1 = proc_one
    assert rep1.worker == "process"
    assert_proc_parity((eng2, rep2,
                        eng2.flight_recorder.journal()), eng1, rep1)


def test_audit_diff_thread_vs_process_journals(tmp_path, thread_ref,
                                               proc_run):
    """The forensic surface: dumped thread and process journals are
    equal under the `anomod audit diff` CLI itself (exit 0)."""
    from anomod.cli import main
    a = str(tmp_path / "thread.json")
    b = str(tmp_path / "proc.json")
    thread_ref[0].flight_recorder.dump(a)
    proc_run[0].flight_recorder.dump(b)
    assert main(["audit", "diff", a, b]) == 0


def test_flight_header_records_resolved_worker_and_fold(proc_run,
                                                        thread_ref):
    """The flight header records the RESOLVED knobs (the async-commit
    precedent), so `anomod audit replay` re-executes the run dict
    as-is on the same engine shape."""
    run = proc_run[0].flight_recorder.header["run"]
    assert run["worker"] == "process" and run["fold"] == "sparse"
    assert thread_ref[0].flight_recorder.header["run"]["worker"] \
        == "thread"


def test_process_rerun_deterministic(proc_run):
    """Same seed, same knob ⇒ same canonical journal bytes."""
    eng, _ = proc_run
    rerun, _ = _run(shards=2, pipeline=2, worker="process",
                    fold="sparse", **KW)
    assert rerun.flight_recorder.canonical_bytes() \
        == eng.flight_recorder.canonical_bytes()


# ---------------------------------------------------------------------------
# the sparse tick-barrier fold
# ---------------------------------------------------------------------------

def test_sparse_fold_payload_under_half_dense(proc_run, proc_dense):
    """The barrier-payload acceptance bound: the sparse fold ships at
    most half the dense walk's structural bytes on this scenario, and
    the two runs' canonical journals are equal (the fold discipline
    moves payload, never a scored byte)."""
    _, rep_sparse = proc_run
    eng_dense, rep_dense = proc_dense
    assert rep_dense.worker == "process" and rep_dense.fold == "dense"
    assert rep_sparse.fold_payload_bytes > 0
    assert rep_dense.fold_payload_bytes > 0
    assert rep_sparse.fold_payload_bytes \
        <= 0.5 * rep_dense.fold_payload_bytes
    d = diff_journals(proc_run[0].flight_recorder.journal(),
                      eng_dense.flight_recorder.journal())
    assert d is None, d


def test_sparse_and_dense_deltas_apply_identically():
    """The registry-level pin behind the scrape-parity contract: the
    same source registry history folded sparse and folded dense lands
    the destination registries on identical metric samples — dense
    just ships more bytes to say it."""

    def _mk_src():
        src = Registry(enabled=True)
        src.counter("c_total", shard="0").inc(3.0)
        src.counter("c_once_total").inc(2.5)       # touched tick 0 only
        src.gauge("g_frac", lane="1").set(0.25)    # ditto
        src.histogram("h_seconds").observe(0.5)
        return src

    def _fold(src, mode):
        dst, st = Registry(enabled=True), {}
        # tick 0: everything dirty
        dst.apply_delta(src.delta_snapshot(st, mode=mode), shard="0")
        # tick 1: only c_total moves — sparse must skip the rest
        src.counter("c_total", shard="0").inc(4.0)
        dst.apply_delta(src.delta_snapshot(st, mode=mode), shard="0")
        # run end: final drains the histograms
        dst.apply_delta(src.delta_snapshot(st, mode=mode, final=True),
                        shard="0")
        return dst

    def _samples(reg):
        return sorted((m.name, m.rendered, tuple(sorted(m.samples())))
                      for m in reg.metrics())

    assert _samples(_fold(_mk_src(), "sparse")) \
        == _samples(_fold(_mk_src(), "dense"))
    # and the sparse tick-1 delta is strictly smaller: the untouched
    # once-families are skipped entirely
    src_s, src_d, st_s, st_d = _mk_src(), _mk_src(), {}, {}
    src_s.delta_snapshot(st_s, mode="sparse")
    src_d.delta_snapshot(st_d, mode="dense")
    src_s.counter("c_total", shard="0").inc(1.0)
    src_d.counter("c_total", shard="0").inc(1.0)
    sparse_1 = src_s.delta_snapshot(st_s, mode="sparse")
    dense_1 = src_d.delta_snapshot(st_d, mode="dense")
    assert delta_nbytes(sparse_1) < delta_nbytes(dense_1)
    with pytest.raises(ValueError, match="dense|sparse"):
        _mk_src().delta_snapshot({}, mode="csr")


# ---------------------------------------------------------------------------
# digest fragments across the pipe
# ---------------------------------------------------------------------------

def test_crc32_combine_matches_zlib():
    """The pure-Python crc32_combine is bit-equal to crc32 over the
    concatenation — the identity the fragment fold rests on."""
    rng = np.random.default_rng(11)
    for n_a, n_b in ((0, 1), (1, 0), (7, 13), (256, 1024), (4096, 3)):
        a = rng.integers(0, 256, n_a, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, n_b, dtype=np.uint8).tobytes()
        assert crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) \
            == zlib.crc32(a + b)


def test_fold_digest_parts_matches_sequential_walk(thread_ref):
    """Per-tenant (crc, len) fragments — computed per shard, folded in
    global sorted-tenant order — land on state_digest's sequential
    walk bit-for-bit, including a non-zero running prefix."""
    replays = thread_ref[0]._tenant_replay
    assert len(replays) >= 4
    parts = state_digest_parts(replays)
    assert fold_digest_parts(parts) == state_digest(replays)
    # shard-split the fleet arbitrarily: the fold is split-invariant
    tids = sorted(replays)
    shard_a = {t: replays[t] for t in tids[::2]}
    shard_b = {t: replays[t] for t in tids[1::2]}
    mixed = state_digest_parts(shard_a) + state_digest_parts(shard_b)
    assert fold_digest_parts(mixed, prev=0xDEAD) \
        == state_digest(replays, prev=0xDEAD)


# ---------------------------------------------------------------------------
# supervision + elasticity across the process boundary
# ---------------------------------------------------------------------------

def test_worker_crash_respawns_with_no_score_gap(thread_ref):
    """A worker-process KILL mid-run, under supervision: the
    coordinator respawns a FRESH (empty) child, restores it from the
    checkpoint through the snapshot seams, re-executes the logged
    slices — and the run stays byte-identical to the fault-free
    thread run of the same seed."""
    eng, rep = _run(shards=2, pipeline=2, worker="process",
                    fold="sparse", ckpt_every=4,
                    chaos="crash@6:shard=1:phase=fold:repeat=1", **KW)
    assert rep.worker == "process"
    assert rep.n_shard_crashes >= 1
    assert rep.n_respawns >= 1
    assert rep.n_restored_ticks >= 1
    assert_proc_parity(thread_ref, eng, rep,
                       extra_skip=RECOVERY_REPORT_FIELDS)


def test_policy_scales_across_process_workers():
    """The elastic policy migrates tenants ACROSS process boundaries
    (snapshot out of one child, install into another): a full
    up→down episode under a scripted surge, byte-identical to the
    static THREAD run of the same seed+surge."""
    pkw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
               overload=0.6, duration_s=24, tick_s=1.0, seed=5,
               window_s=5.0, baseline_windows=4, fault_tenants=0,
               buckets=(64, 256), lane_buckets=(1, 2, 4),
               max_backlog=1500, n_windows=16, flight_digest_every=4)
    surge = "surge@6:factor=6:ticks=6"
    eng_s, rep_s = _run(shards=1, chaos=surge, worker="thread", **pkw)
    eng_e, rep_e = _run(shards=1, chaos=surge, worker="process",
                        policy="auto", min_shards=1, max_shards=2,
                        cooldown_ticks=3, **pkw)
    assert rep_e.worker == "process"
    assert rep_e.n_scale_ups >= 1 and rep_e.n_scale_downs >= 1
    assert rep_e.n_policy_migrations >= 1
    assert_proc_parity((eng_s, rep_s,
                        eng_s.flight_recorder.journal()),
                       eng_e, rep_e,
                       extra_skip=set(POLICY_REPORT_FIELDS)
                       | set(RECOVERY_REPORT_FIELDS))


# ---------------------------------------------------------------------------
# the knob / refusal matrix
# ---------------------------------------------------------------------------

def _mk_engine(**kw):
    from anomod.replay import ReplayConfig
    from anomod.serve import PowerLawTraffic
    traffic = PowerLawTraffic(n_tenants=2, total_rate_spans_per_s=100,
                              seed=0, n_services=4)
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    return ServeEngine(traffic.specs, traffic.services, cfg, **kw)


def test_worker_and_fold_knobs_validated():
    with pytest.raises(ValueError, match="thread|process"):
        _mk_engine(worker="greenlet")
    with pytest.raises(ValueError, match="dense|sparse"):
        _mk_engine(fold="csr")


def test_env_knobs_validated(monkeypatch):
    from anomod.config import Config, set_config
    monkeypatch.setenv("ANOMOD_SERVE_WORKER", "goroutine")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_WORKER"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_WORKER")
    monkeypatch.setenv("ANOMOD_SERVE_FOLD", "blocked")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_FOLD"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_FOLD")
    monkeypatch.setenv("ANOMOD_SERVE_WORKER_START_TIMEOUT_S", "0")
    with pytest.raises(ValueError,
                       match="ANOMOD_SERVE_WORKER_START_TIMEOUT_S"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_WORKER_START_TIMEOUT_S")
    set_config(Config())


@pytest.mark.parametrize("blocker_kw", [
    dict(async_commit=True),
    dict(tier_hot=8),
    dict(census=True),
])
def test_process_refused_with_in_process_planes(blocker_kw):
    """Planes that share coordinator memory with the score plane
    cannot cross the process boundary: an EXPLICIT worker='process'
    alongside one is a hard error (the shards-on-mesh idiom)."""
    with pytest.raises(ValueError, match="process shard workers"):
        _mk_engine(worker="process", **blocker_kw)


def test_process_refused_on_a_chip_backend(monkeypatch):
    """One process per chip: each worker child imports jax and compiles,
    and the device already belongs to the coordinator — on a non-CPU
    backend an explicit worker='process' is refused with the reason, and
    an env-sourced one degrades to threads (the matrix's idiom)."""
    import jax

    from anomod.config import Config, set_config
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="a chip belongs to one process.*"
                                         "tpu device this process holds"):
        _mk_engine(worker="process")
    monkeypatch.setenv("ANOMOD_SERVE_WORKER", "process")
    set_config(Config())
    try:
        assert _mk_engine().worker_mode == "thread"
    finally:
        monkeypatch.delenv("ANOMOD_SERVE_WORKER")
        set_config(Config())


def test_mesh_refuses_explicit_process_worker():
    from anomod.parallel import make_mesh
    with pytest.raises(ValueError, match="mesh"):
        _mk_engine(worker="process", mesh=make_mesh(2))


def test_env_sourced_process_degrades_not_raises(monkeypatch):
    """An env-sourced ANOMOD_SERVE_WORKER=process degrades to the
    thread engine under a blocking plane, so globally exported knobs
    never break existing workflows — the policy/state idiom."""
    from anomod.config import Config, set_config
    monkeypatch.setenv("ANOMOD_SERVE_WORKER", "process")
    set_config(Config())
    try:
        eng = _mk_engine(census=True)
        assert eng.worker_mode == "thread"
    finally:
        monkeypatch.delenv("ANOMOD_SERVE_WORKER")
        set_config(Config())


# ---------------------------------------------------------------------------
# the tier-1 smoke
# ---------------------------------------------------------------------------

def test_procshard_smoke_fast():
    """A minimal process-worker run spawns, serves, folds and joins —
    the cheap canary a broken spawn path fails in seconds, not at the
    module fixtures."""
    eng, rep = _run(n_tenants=2, n_services=4, capacity_spans_per_s=500,
                    overload=1.0, duration_s=4, tick_s=1.0, seed=3,
                    window_s=2.0, baseline_windows=2, fault_tenants=0,
                    buckets=(64,), lane_buckets=(1,), max_backlog=800,
                    n_windows=16, shards=1, worker="process")
    assert rep.worker == "process"
    assert rep.served_spans > 0
    # run end closed and reaped every child
    assert not (eng._workers or [])

