#!/usr/bin/env python
"""Pre-bench gate: ingest-cache warmth (replay mode) / bucket-set
compilability (serve mode).

A throughput capture taken against a cold ingest cache silently folds host
synth/parse time into the session (and, before the cache, re-measured it on
every invocation) — the recorded kernel numbers stop being comparable.
This gate is the scripts/ hook a driver runs before ``python bench.py``:

    python scripts/pre_bench_check.py            # exit 0 iff cache is warm
    python scripts/pre_bench_check.py --cold     # cold capture, on purpose
    python scripts/pre_bench_check.py --mode serve   # serve preconditions

Serve mode validates the serve bench's preconditions instead: the
``ANOMOD_SERVE_BUCKETS`` / ``ANOMOD_SERVE_MAX_BACKLOG`` env contract must
parse, and the bucket set must COMPILE (every bucket width traced through
the shared chunk step on the pinned-CPU backend — a bucket set that can't
compile would burn the capture window mid-serve).  The online-RCA
``ANOMOD_SERVE_RCA_BUCKETS`` (nodes, neighbors) grid gets the same
treatment: every bucket AOT-compiles or the gate fails on the shape
miss.  Exit 3 = serve preconditions failed.

Both modes FIRST run the env-contract gate
(``scripts/check_env_contract.py``): every ``ANOMOD_*`` env var read in
the package must be in the validated Config contract or documented —
a capture driven by an undocumented knob is not reproducible from the
record.  Exit 4 = env contract violation.

Serve mode also builds/validates the NATIVE runtime when the validated
``ANOMOD_NATIVE`` knob requests it: the .so is (re)built on first touch,
a tiny ``stage_lanes`` round-trip must reproduce the interpreter fill
byte-for-byte, and a requested-but-unusable runtime (``ANOMOD_NATIVE=1``
on a box without a toolchain) fails with the recorded build reason —
exit 5, distinct from the generic serve failure, so a driver can tell
"install g++ or unset ANOMOD_NATIVE" from "the bucket grid is broken".
When staging is in play the gate also runs the ThreadSanitizer staging
smoke (``scripts/native_sanitize_smoke.py``: the whole native layer
rebuilt ``-fsanitize=thread`` + the concurrent StagePlan-pattern fill
hammer); a detected race is exit 5 too (a racy staging runtime must
not serve), and a toolchain without sanitizer support SKIPs with its
reason recorded in the JSON line.

Serve mode also runs a <5 s tenant-state RESIDENCY parity smoke: the
same tiny seeded multi-tick run on the device pool
(``ANOMOD_SERVE_STATE=device``) and on the host seam must be
byte-equal — per-tenant alert streams, replay states, SLO quantiles
and shed.  A divergence is a generic serve failure (exit 3: the pool
broke the bit-parity contract); ``ANOMOD_SERVE_STATE=device`` forced
on a box whose pool cannot even construct/operate is its own failure
mode — exit 6, distinct, so a driver can tell "unset
ANOMOD_SERVE_STATE" from "the fold math is broken".

Serve mode also runs a <5 s flight-recorder record→replay→diff smoke
(anomod.obs.flight): the same tiny seeded run journaled twice — once at
1 shard, once at 2 — must produce byte-identical canonical journals;
``diff_journals`` bisecting a divergence fails the gate with its own
exit code (7), distinct from the generic serve failure, so a driver can
tell "the tick journal broke determinism" from "the grid is broken".

Exit codes (the ``EXIT_*`` constants below are the one definition — the
uniqueness test in tests/test_bench_contract.py collects them by prefix
and the table in docs/BENCHMARKS.md mirrors them):

- ``EXIT_READY`` (0): ready — warm cache, or --cold / caching disabled
  is explicit, or serve preconditions hold
- ``EXIT_COLD_CACHE`` (1): cold ingest cache without --cold
- ``EXIT_CACHE_DISABLED`` (2): caching disabled without --cold
- ``EXIT_SERVE_PRECONDITION`` (3): serve precondition failure (env
  knobs, bucket-grid compile, shard fan-out / state-residency parity)
- ``EXIT_ENV_CONTRACT`` (4): undocumented ``ANOMOD_*`` env read
- ``EXIT_NATIVE_UNUSABLE`` (5): ANOMOD_NATIVE requested but the native
  runtime is unusable (compiler missing / build failed)
- ``EXIT_STATE_POOL_UNUSABLE`` (6): ANOMOD_SERVE_STATE=device forced
  but the device state pool is unusable
- ``EXIT_FLIGHT_DIVERGENCE`` (7): the flight-journal record→replay→diff
  smoke found a divergent tick/plane
- ``EXIT_RECOVERY_DIVERGENCE`` (8): the crash→respawn→audit-diff smoke
  found a score gap — a recovered run's canonical journal diverged
  from the fault-free run of the same seed
- ``EXIT_LINT`` (9): the contract linter / parity-surface audit
  (``scripts/check_contracts.py``, docs/CONTRACTS.md) found a new
  unsuppressed, unbaselined violation — a capture of a tree with a
  broken determinism or parity contract is not reproducible from its
  record.  Both modes run this gate right after the env contract.
- ``EXIT_POLICY_DIVERGENCE`` (10): the elastic smoke (scale 1→2→1
  under a scripted load surge, ``anomod audit diff`` vs the static run
  of the same seed) found a score gap or failed to produce both a
  scale-up and a scale-down episode — the elastic policy either moved
  a scored byte or never scaled at all.
- ``EXIT_PERF_DIVERGENCE`` (11): the performance-observatory smoke
  (record → report → self-diff, anomod.obs.perf) failed — the
  dispatch-lifecycle recorder moved a decision byte, the timeline no
  longer reconciles with the five-leg walls, or ``anomod perf diff``
  semantics broke (a same-capture self-diff flagged something, or a
  doctored 2× slowdown went unflagged) — a capture's perf block /
  regression verdicts could not be trusted.
- ``EXIT_CENSUS_DIVERGENCE`` (12): the fleet-census smoke (record →
  report → on/off byte-parity → pool-bytes reconciliation,
  anomod.obs.census) failed — the census recorder moved a decision
  byte, recorded no census, or a state pool's array bytes stopped
  reconciling with ``(capacity + 1) × per-slot nbytes`` — a capture's
  census block (the tiering baseline) could not be trusted.
- ``EXIT_ASYNC_DIVERGENCE`` (13): the deferred-commit smoke (the same
  tiny seeded run served synchronous and with
  ``ANOMOD_SERVE_ASYNC_COMMIT`` on) diverged on states, alerts, SLO,
  shed or the canonical flight journal, or never actually deferred a
  tick — the async engine broke the byte-parity contract and an
  async capture's decision planes could not be trusted.
- ``EXIT_TIERING_DIVERGENCE`` (15): the state-tiering smoke (a small
  sub-capacity fleet with an idle tail, tiered hot→warm→cold vs the
  same seed never-evicted) found a demotion that never fired, a parity
  break (alerts/SLO/shed/final state digest), or a tenant left
  stranded in the tier at run end — do not capture fleet blocks with
  ``ANOMOD_SERVE_TIER_HOT`` set
- ``EXIT_FEED_DIVERGENCE`` (14): the live-feed loop smoke (an
  in-process ``/metrics`` endpoint scraped by ``LiveFeed``, the wire
  journal replayed through ``ReplayTransport``, live vs replay
  compared on states, alerts, SLO, shed and the canonical flight
  journal) diverged, or the live leg consumed nothing — a
  ``--from-live`` capture could not be reproduced from its wire
  journal.
- ``EXIT_PROCSHARD_DIVERGENCE`` (16): the process-worker smoke (the
  same tiny seeded run served on 2 shard threads, 2 shard processes
  and 1 shard process, sparse barrier fold) diverged on states,
  alerts, SLO, shed or the canonical flight journal, the process legs
  silently degraded to threads, or the sparse fold failed to shrink
  the barrier payload — the GIL-free engine broke the byte-parity
  contract and an ``ANOMOD_SERVE_WORKER=process`` capture's decision
  planes could not be trusted.

Always prints one JSON line describing the decision (plus the contract
gate's line).  ``--traces`` must match the bench invocation's span
count (the cache key includes it).
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: the gate's exit-code contract, accreted one failure mode per PR —
#: named in ONE place so drivers, docs/BENCHMARKS.md and the uniqueness
#: test cannot drift apart
EXIT_READY = 0
EXIT_COLD_CACHE = 1
EXIT_CACHE_DISABLED = 2
EXIT_SERVE_PRECONDITION = 3
EXIT_ENV_CONTRACT = 4
EXIT_NATIVE_UNUSABLE = 5
EXIT_STATE_POOL_UNUSABLE = 6
EXIT_FLIGHT_DIVERGENCE = 7
EXIT_RECOVERY_DIVERGENCE = 8
EXIT_LINT = 9
EXIT_POLICY_DIVERGENCE = 10
EXIT_PERF_DIVERGENCE = 11
EXIT_CENSUS_DIVERGENCE = 12
EXIT_ASYNC_DIVERGENCE = 13
EXIT_FEED_DIVERGENCE = 14
EXIT_TIERING_DIVERGENCE = 15
EXIT_PROCSHARD_DIVERGENCE = 16


def _shard_fanout_smoke() -> dict:
    """The 2-shard fan-out smoke (<5 s): a tiny seeded fused run on 2
    engine workers must produce the EXACT decision output of the same
    run on 1 shard — per-tenant alerts, replay states (bitwise), and
    every report field that is not wall-clock or shard topology.  A
    divergence here means the sharded score path broke determinism and
    a shard-scaling capture would compare different computations."""
    import dataclasses

    import numpy as np

    from anomod.serve.engine import (SHARD_VARIANT_REPORT_FIELDS,
                                     run_power_law)

    def go(n_shards):
        return run_power_law(
            n_tenants=6, n_services=4, capacity_spans_per_s=1000,
            overload=2.0, duration_s=20, tick_s=1.0, seed=5,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
            n_windows=16, shards=n_shards, pipeline=2)

    e1, r1 = go(1)
    e2, r2 = go(2)
    skip = SHARD_VARIANT_REPORT_FIELDS
    a = {k: v for k, v in r1.to_dict().items() if k not in skip}
    b = {k: v for k, v in r2.to_dict().items() if k not in skip}
    if a != b:
        diff = sorted(k for k in a if a[k] != b[k])
        raise RuntimeError(f"shard fan-out smoke: 2-shard report "
                           f"diverges from 1-shard on {diff}")
    for tid in e1._tenant_det:
        if [dataclasses.asdict(x) for x in e1.alerts_for(tid)] != \
                [dataclasses.asdict(x) for x in e2.alerts_for(tid)]:
            raise RuntimeError(f"shard fan-out smoke: tenant {tid} "
                               "alert stream diverges")
        s1 = e1._tenant_replay[tid].state
        s2 = e2._tenant_replay[tid].state
        if not (np.array_equal(np.asarray(s1.agg), np.asarray(s2.agg))
                and np.array_equal(np.asarray(s1.hist),
                                   np.asarray(s2.hist))):
            raise RuntimeError(f"shard fan-out smoke: tenant {tid} "
                               "replay state diverges")
    return {"tenants": len(e1._tenant_det),
            "served_spans": r1.served_spans}


def _state_parity_smoke() -> dict:
    """The device-vs-host residency smoke (<5 s): a tiny seeded fused
    multi-tick run with the device state pool must produce the EXACT
    decision output of the same run on the host seam — per-tenant alert
    streams, replay states (bitwise), SLO quantiles and shed fraction.
    A divergence means the pool's scatter/roll/gather broke the
    bit-parity contract and a serve capture would compare different
    computations."""
    import dataclasses

    import numpy as np

    from anomod.serve.engine import run_power_law

    def go(state):
        return run_power_law(
            n_tenants=5, n_services=4, capacity_spans_per_s=1000,
            overload=2.0, duration_s=16, tick_s=1.0, seed=9,
            window_s=2.0, baseline_windows=4, fault_tenants=1,
            buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
            n_windows=16, shards=1, pipeline=2, state=state)

    eh, rh = go("host")
    ed, rd = go("device")
    for tid in eh._tenant_det:
        if [dataclasses.asdict(a) for a in eh.alerts_for(tid)] != \
                [dataclasses.asdict(a) for a in ed.alerts_for(tid)]:
            raise RuntimeError(f"state parity smoke: tenant {tid} alert "
                               "stream diverges device vs host")
        s1 = eh._tenant_replay[tid].state
        s2 = ed._tenant_replay[tid].state
        if not (np.array_equal(np.asarray(s1.agg), np.asarray(s2.agg))
                and np.array_equal(np.asarray(s1.hist),
                                   np.asarray(s2.hist))):
            raise RuntimeError(f"state parity smoke: tenant {tid} "
                               "replay state diverges device vs host")
    if rh.latency != rd.latency or rh.shed_fraction != rd.shed_fraction:
        raise RuntimeError("state parity smoke: SLO/shed diverge "
                           "device vs host")
    return {"tenants": len(eh._tenant_det),
            "pool_engine": ed.runner.pool.engine,
            "alerts": sum(len(ed.alerts_for(t))
                          for t in ed._tenant_det)}


def _native_smoke() -> dict:
    """One stage_lanes round-trip vs the interpreter fill, byte-for-byte
    — proves the freshly-(re)built ABI before a capture trusts it."""
    import numpy as np

    from anomod.io import native
    scratch = {"sid": native.aligned_empty((4, 32), np.int32),
               "dur": native.aligned_empty((4, 32), np.float32)}
    rng = np.random.default_rng(0)
    group = [{"sid": rng.integers(0, 9, 20).astype(np.int32),
              "dur": rng.random(20).astype(np.float32)},
             {"sid": rng.integers(0, 9, 32).astype(np.int32),
              "dur": rng.random(32).astype(np.float32)}]
    fills = {"sid": 9, "dur": 0}
    if not native.stage_lanes(scratch, group, lambda k: fills[k]):
        raise RuntimeError("stage_lanes refused a well-formed slot")
    for k, buf in scratch.items():
        want = np.empty((4, 32), buf.dtype)
        for i, cols in enumerate(group):
            m = cols[k].shape[0]
            want[i, :m] = cols[k]
            want[i, m:] = fills[k]
        want[2:] = fills[k]
        if buf.tobytes() != want.tobytes():
            raise RuntimeError(f"stage_lanes byte mismatch on {k!r}")
    return {"status": "ok", "cols": len(scratch)}


def _flight_smoke():
    """The flight-recorder record→replay→diff smoke (<5 s): the same
    tiny seeded run journaled at 1 shard (record) and re-executed at 2
    shards (the forensic replay) must produce canonical journals
    ``diff_journals`` finds identical — every plane, every tick.  A
    divergence means the tick journal broke the determinism contract
    and every audit trail a capture leaves would be unusable.  Returns
    ``(info, divergence_or_None)``."""
    from anomod.obs.flight import diff_journals
    from anomod.serve.engine import run_power_law

    def go(n_shards):
        eng, _ = run_power_law(
            n_tenants=6, n_services=4, capacity_spans_per_s=1000,
            overload=2.0, duration_s=20, tick_s=1.0, seed=5,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
            n_windows=16, shards=n_shards, pipeline=2, flight=True,
            flight_digest_every=4)
        return eng.flight_recorder

    rec = go(1)
    rep = go(2)
    info = {"ticks": rec.n_recorded, "dropped": rec.n_dropped,
            "digest_every": rec.digest_every}
    return info, diff_journals(rec.journal(), rep.journal())


def _recovery_smoke():
    """The crash→respawn→audit-diff smoke (<5 s): the same tiny seeded
    run executed fault-free and again with scripted mid-tick shard
    crashes (a worker kill + a score-path exception) under supervision
    (anomod.serve.supervise) must produce canonical flight journals
    ``diff_journals`` finds identical — the no-score-gap recovery
    contract.  A divergence means recovery re-execution broke
    determinism and a chaos campaign's results could not be trusted.
    Returns ``(info, divergence_or_None)``."""
    from anomod.obs.flight import diff_journals
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=2.0, duration_s=20, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, shards=2, pipeline=2,
              flight=True, flight_digest_every=4, ckpt_every=4)
    eng_ref, _ = run_power_law(**kw)
    eng_chaos, rep = run_power_law(
        chaos="crash@6:shard=0:phase=dispatch;"
              "except@11:shard=1:phase=score", **kw)
    info = {"crashes": rep.n_shard_crashes, "respawns": rep.n_respawns,
            "restored_ticks": rep.n_restored_ticks,
            "quarantined": rep.n_quarantined,
            "checkpoints": rep.n_checkpoints}
    if rep.n_shard_crashes < 2 or rep.n_respawns < 1:
        raise RuntimeError(
            f"recovery smoke injected faults did not fire: {info}")
    return info, diff_journals(eng_ref.flight_recorder.journal(),
                               eng_chaos.flight_recorder.journal())


def _elastic_smoke():
    """The elastic-policy smoke (<5 s): the same tiny seeded
    sub-capacity run hit by a scripted load surge (the chaos ``surge``
    kind), served static and again under ``ANOMOD_SERVE_POLICY=auto``
    with a 1→2 shard envelope.  The policy leg must produce at least
    one scale-up AND one scale-down episode (a policy that never
    scales is a silent no-op — raised as a precondition failure), and
    its canonical flight journal must equal the static leg's (the
    elastic no-score-gap contract: scaling moves wall capacity, never
    a scored byte).  Returns ``(info, divergence_or_None)``."""
    from anomod.obs.flight import diff_journals
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=0.6, duration_s=24, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, flight_digest_every=4,
              chaos="surge@6:factor=6:ticks=6")
    eng_static, _ = run_power_law(shards=1, **kw)
    eng_elastic, rep = run_power_law(
        shards=1, policy="auto", min_shards=1, max_shards=2,
        cooldown_ticks=3, **kw)
    info = {"scale_ups": rep.n_scale_ups,
            "scale_downs": rep.n_scale_downs,
            "migrated_tenants": rep.n_policy_migrations,
            "peak_shards": rep.peak_shards}
    if rep.n_scale_ups < 1 or rep.n_scale_downs < 1:
        raise RuntimeError(
            f"elastic smoke produced no full scaling episode: {info}")
    return info, diff_journals(eng_static.flight_recorder.journal(),
                               eng_elastic.flight_recorder.journal())


def _async_commit_smoke():
    """The deferred-commit byte-parity smoke (<5 s): the same tiny
    seeded run served synchronous (the parity oracle) and again with
    the deferred-commit tick on (``ANOMOD_SERVE_ASYNC_COMMIT``).  The
    async leg must actually defer (``async_ticks > 0`` — a silently
    synchronous "async" run would pass parity vacuously, raised as a
    precondition failure) and must match the oracle on tenant states,
    alerts, SLO, shed and the canonical flight journal — the deferred
    barrier moves wall-clock attribution, never a scored byte.
    Returns ``(info, divergence_or_None)``."""
    from anomod.obs.flight import diff_journals
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=2.0, duration_s=20, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, shards=2, pipeline=2,
              flight=True, flight_digest_every=4, ckpt_every=4)
    eng_sync, rep_sync = run_power_law(async_commit=False, **kw)
    eng_async, rep_async = run_power_law(async_commit=True, **kw)
    info = {"async_ticks": rep_async.async_ticks,
            "commit_defer_wall_s": rep_async.commit_defer_wall_s,
            "p99_identical": rep_async.latency.get("p99_latency_s")
            == rep_sync.latency.get("p99_latency_s"),
            "shed_identical":
                rep_async.shed_fraction == rep_sync.shed_fraction}
    if rep_async.async_ticks < 1:
        raise RuntimeError(
            f"async-commit smoke never deferred a tick: {info}")
    if not (info["p99_identical"] and info["shed_identical"]):
        return info, {"tick": -1, "plane": "slo/shed"}
    return info, diff_journals(eng_sync.flight_recorder.journal(),
                               eng_async.flight_recorder.journal())


def _feed_smoke():
    """The live-feed loop smoke (<5 s): the serve tick fed from a REAL
    socket.  An in-process ``/metrics`` endpoint (anomod.obs.http)
    serves this process's own registry; a :class:`LiveFeed` scrapes it
    through the recording transport while the engine runs; the wire
    journal is then replayed through :class:`ReplayTransport` and the
    two runs must be byte-identical on tenant states, alerts, SLO,
    shed and the canonical flight journal — the ``--from-live``
    reproducibility contract.  A live leg that consumed nothing is a
    precondition failure (parity would pass vacuously).  Returns
    ``(info, divergence_or_None)``."""
    import tempfile

    import numpy as np

    from anomod.obs.flight import diff_journals
    from anomod.obs.http import ObsHttpServer
    from anomod.obs.registry import Registry, set_registry
    from anomod.serve.feed import run_live_feed

    kw = dict(n_tenants=4, n_services=4, capacity_spans_per_s=2000.0,
              duration_s=8.0, tick_s=1.0, window_s=2.0,
              baseline_windows=2, buckets=(64,), n_windows=16,
              flight=True, flight_digest_every=2)
    prev = set_registry(Registry(enabled=True))
    try:
        with tempfile.TemporaryDirectory() as tmp, \
                ObsHttpServer(port=0) as srv:
            jpath = Path(tmp) / "feed_wire.json"
            eng_live, rep_live, feed = run_live_feed(
                scrape_url=f"{srv.url}/metrics", journal=jpath, **kw)
            srv.stop()
            eng_rep, rep_rep, _ = run_live_feed(
                replay=jpath,
                **{k: v for k, v in kw.items()
                   if k not in ("n_tenants", "n_services")})
    finally:
        set_registry(prev)
    info = {"polls": feed.n_polls, "samples": feed.n_samples,
            "spans": feed.n_spans, "gaps": feed.n_gaps,
            "served_spans": rep_live.served_spans,
            "p99_identical": rep_rep.latency.get("p99_latency_s")
            == rep_live.latency.get("p99_latency_s"),
            "shed_identical":
                rep_rep.shed_fraction == rep_live.shed_fraction}
    if feed.n_polls < 1 or feed.n_samples < 1 \
            or rep_live.served_spans < 1:
        raise RuntimeError(
            f"live-feed smoke consumed nothing: {info}")
    tids = sorted(set(eng_live._tenant_replay)
                  | set(eng_rep._tenant_replay))
    states_same = all(
        t in eng_live._tenant_replay and t in eng_rep._tenant_replay
        and np.array_equal(
            np.asarray(eng_live._tenant_replay[t].state.agg),
            np.asarray(eng_rep._tenant_replay[t].state.agg))
        and np.array_equal(
            np.asarray(eng_live._tenant_replay[t].state.hist),
            np.asarray(eng_rep._tenant_replay[t].state.hist))
        for t in tids)
    alerts_same = all(eng_live.alerts_for(t) == eng_rep.alerts_for(t)
                      for t in sorted(set(eng_live._tenant_det)
                                      | set(eng_rep._tenant_det)))
    if not (states_same and alerts_same
            and info["p99_identical"] and info["shed_identical"]):
        return info, {"tick": -1, "plane": "states/alerts/slo/shed"}
    return info, diff_journals(eng_live.flight_recorder.journal(),
                               eng_rep.flight_recorder.journal())


def _procshard_smoke():
    """The process-worker byte-parity smoke: the same tiny seeded run
    served on 2 shard THREADS (the parity oracle), 2 shard PROCESSES
    and 1 shard process, sparse barrier fold throughout.  The process
    legs must actually run process workers (``ServeReport.worker`` —
    an env-degraded thread run would pass parity vacuously), and all
    three legs must agree on states, alerts, SLO, shed and the
    canonical flight journal — the GIL escape moves wall-clock, never
    a scored byte.  The sparse fold's payload bytes ride the info
    line; the sparse-vs-dense payload bound and real worker RESPAWN
    through a process crash are pinned by
    tests/test_serve_procshard.py, not re-run here.  Returns
    ``(info, divergence_or_None)``."""
    from anomod.obs.flight import diff_journals
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=2.0, duration_s=12, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, pipeline=2,
              flight=True, flight_digest_every=4)
    eng_thr, rep_thr = run_power_law(shards=2, worker="thread",
                                     fold="sparse", **kw)
    eng_prc, rep_prc = run_power_law(shards=2, worker="process",
                                     fold="sparse", **kw)
    eng_one, rep_one = run_power_law(shards=1, worker="process",
                                     fold="sparse", **kw)
    info = {"worker_thread_leg": rep_thr.worker,
            "worker_process_leg": rep_prc.worker,
            "fold": rep_prc.fold,
            "fold_payload_bytes_thread": rep_thr.fold_payload_bytes,
            "fold_payload_bytes_process": rep_prc.fold_payload_bytes,
            "p99_identical": rep_prc.latency.get("p99_latency_s")
            == rep_thr.latency.get("p99_latency_s"),
            "shed_identical":
                rep_prc.shed_fraction == rep_thr.shed_fraction}
    if rep_prc.worker != "process" or rep_one.worker != "process":
        raise RuntimeError(
            "process legs silently degraded to the thread engine: "
            f"{info}")
    alerts_same = all(
        eng_thr.alerts_for(t) == eng_prc.alerts_for(t)
        == eng_one.alerts_for(t)
        for t in sorted(set(eng_thr._tenant_det)
                        | set(eng_prc._tenant_det)
                        | set(eng_one._tenant_det)))
    if not (alerts_same and info["p99_identical"]
            and info["shed_identical"]):
        return info, {"tick": -1, "plane": "alerts/slo/shed"}
    for pair, (a, b) in (("thread_vs_process", (eng_thr, eng_prc)),
                         ("2_vs_1_process", (eng_prc, eng_one))):
        div = diff_journals(a.flight_recorder.journal(),
                            b.flight_recorder.journal())
        if div is not None:
            div["pair"] = pair
            return info, div
    return info, None


def _perf_smoke():
    """The performance-observatory smoke (<5 s): record → report →
    self-diff.  RECORD: a tiny seeded run with the dispatch-lifecycle
    timeline ON must record events and leave every decision
    byte-identical to the same run with it OFF (alert streams, SLO
    quantiles, shed, canonical flight journal — the read-side
    contract).  REPORT: the event-timeline durations must reconcile
    with the five-leg ServeReport walls within tolerance (the events
    reuse the wall-leg clock reads, so drift means a hook moved).
    SELF-DIFF: ``diff_captures`` of a capture-shaped doc against
    itself must be clean, and against a doctored 2× wall slowdown must
    flag a regression — the noise-aware verdict machinery proves both
    directions before a driver trusts it.  Returns
    ``(info, problem_or_None)``."""
    import copy
    import dataclasses
    import gc

    from anomod.obs.perf import diff_captures
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=2.0, duration_s=16, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, shards=1, pipeline=2)
    eng_off, rep_off = run_power_law(**kw)
    # The doctored-2x check below proves the VERDICT MACHINERY, and a
    # gen-2 stop-the-world GC pause (~0.25 s against ~2 ms ticks, landing
    # wherever the gate's prior smokes left the allocator thresholds) is
    # the one wall outlier that can blind a 16-sample mean-ratio
    # bootstrap — collect up front and hold GC off for the measured run
    # so raw_wall_s prices the serve tick, not the gate's garbage.
    gc.collect()
    gc.disable()
    try:
        eng_on, rep_on = run_power_law(perf=True, **kw)
    finally:
        gc.enable()
    info = {"events": rep_on.perf_events_recorded,
            "overlap_headroom_s": rep_on.overlap_headroom_s,
            "fold_wait_s": rep_on.fold_wait_s}

    def problem(what, detail):
        return info, {"what": what, "detail": detail}

    if rep_on.perf_events_recorded < 1:
        return problem("no-events", "the perf run recorded no dispatch "
                       "lifecycle events")
    for tid in eng_off._tenant_det:
        if [dataclasses.asdict(a) for a in eng_off.alerts_for(tid)] != \
                [dataclasses.asdict(a) for a in eng_on.alerts_for(tid)]:
            return problem("decision-divergence",
                           f"tenant {tid} alert stream diverges with "
                           "perf recording on")
    if rep_off.latency != rep_on.latency \
            or rep_off.shed_fraction != rep_on.shed_fraction:
        return problem("decision-divergence",
                       "SLO/shed diverge with perf recording on")
    if eng_off.flight_recorder is not None \
            and eng_on.flight_recorder is not None \
            and eng_off.flight_recorder.canonical_bytes() \
            != eng_on.flight_recorder.canonical_bytes():
        return problem("decision-divergence",
                       "canonical flight journal diverges with perf "
                       "recording on")
    evs = eng_on.perf_events
    disp = sum(e["submitted"] - e["submitted_t0"] for e in evs)
    fold = sum(e["folded"] - e["retire_t0"] for e in evs)
    stage = sum(e["staged"] - e["staged_t0"] for e in evs)
    for name, got, wall in (("dispatch", disp, rep_on.dispatch_wall_s),
                            ("fold", fold, rep_on.fold_wall_s)):
        if abs(got - wall) > 1e-3 + 0.02 * wall:
            return problem("reconciliation",
                           f"timeline {name} {got:.6f}s vs report "
                           f"wall {wall:.6f}s")
    if stage > rep_on.stage_wall_s + 1e-3:
        return problem("reconciliation",
                       f"timeline stage {stage:.6f}s exceeds report "
                       f"wall {rep_on.stage_wall_s:.6f}s")
    cap = {"metric": "perf_smoke",
           "shed_fraction": rep_on.shed_fraction,
           "p99_admission_to_scored_latency_s":
               rep_on.latency.get("p99_latency_s"),
           "perf": {"raw_wall_s": [round(t, 6)
                                   for t in eng_on.tick_walls]}}
    if diff_captures(cap, copy.deepcopy(cap))["status"] != "ok":
        return problem("self-diff", "a capture self-diff was not clean")
    doctored = copy.deepcopy(cap)
    doctored["perf"]["raw_wall_s"] = [
        2.0 * t for t in doctored["perf"]["raw_wall_s"]]
    if not diff_captures(cap, doctored)["regressions"]:
        return problem("self-diff",
                       "a doctored 2x wall slowdown went unflagged")
    return info, None


def _census_smoke():
    """The fleet-census smoke (<5 s): record → report → on/off
    byte-parity → pool-bytes reconciliation (anomod.obs.census).  A
    tiny seeded run with the census ON must take censuses, reconcile
    every state pool's bytes exactly with ``(capacity + 1) × per-slot
    nbytes``, and leave every decision byte-identical to the same run
    with it OFF (alert streams, SLO quantiles, shed, the canonical
    flight journal — the read-side contract).  A failure means the
    census block a capture commits (the million-tenant tiering
    baseline) could not be trusted.  Returns
    ``(info, problem_or_None)``."""
    import dataclasses

    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=2.0, duration_s=16, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, shards=1, pipeline=2)
    eng_off, rep_off = run_power_law(**kw)
    eng_on, rep_on = run_power_law(census=True, census_every=4, **kw)
    resident = rep_on.census_resident_bytes
    info = {"census_ticks": rep_on.census_ticks,
            "resident_bytes": resident.get("total"),
            "pool_reconciled": resident.get("pool_reconciled"),
            "hot_tenants": (rep_on.census_hot_set.get("hot_by_decay")
                            or {}).get("4")}

    def problem(what, detail):
        return info, {"what": what, "detail": detail}

    if rep_on.census_ticks < 1 or not resident.get("total"):
        return problem("no-census", "the census run recorded no "
                       "resident-bytes census")
    if resident.get("pool_reconciled") is not True:
        return problem("pool-reconciliation",
                       "state-pool bytes do not reconcile with "
                       "(capacity + 1) x per-slot nbytes")
    for tid in eng_off._tenant_det:
        if [dataclasses.asdict(a) for a in eng_off.alerts_for(tid)] != \
                [dataclasses.asdict(a) for a in eng_on.alerts_for(tid)]:
            return problem("decision-divergence",
                           f"tenant {tid} alert stream diverges with "
                           "the census on")
    if rep_off.latency != rep_on.latency \
            or rep_off.shed_fraction != rep_on.shed_fraction:
        return problem("decision-divergence",
                       "SLO/shed diverge with the census on")
    if eng_off.flight_recorder is not None \
            and eng_on.flight_recorder is not None \
            and eng_off.flight_recorder.canonical_bytes() \
            != eng_on.flight_recorder.canonical_bytes():
        return problem("decision-divergence",
                       "canonical flight journal diverges with the "
                       "census on")
    return info, None


def _tiering_smoke():
    """The state-tiering smoke (<5 s): a small SUB-capacity fleet whose
    power-law tail goes idle (so the decay plane actually demotes),
    run tiered (device hot pool → host warm tier → content-addressed
    disk cold tier) and never-evicted on the same seed.  The tiered
    run must demote AND spill AND promote at least once, and leave
    every decision byte-identical: alert streams, SLO quantiles, shed,
    the final tenant-state digest — with the tier EMPTY at run end
    (the run-end promote-all settlement).  A failure means a fleet
    capture under ``ANOMOD_SERVE_TIER_HOT`` could not be trusted.
    Returns ``(info, problem_or_None)``."""
    import dataclasses
    import tempfile

    from anomod.obs.flight import state_digest
    from anomod.serve.engine import run_power_law

    kw = dict(n_tenants=24, n_services=4, capacity_spans_per_s=400,
              overload=0.5, duration_s=14, tick_s=1.0, seed=7,
              window_s=5.0, baseline_windows=2, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16, shards=1, pipeline=2)
    eng_off, rep_off = run_power_law(**kw)
    with tempfile.TemporaryDirectory() as cold_dir:
        eng_on, rep_on = run_power_law(
            tier_hot=6, tier_demote_after=2, tier_warm_bytes=4096,
            tier_cold_dir=cold_dir, tier_prefetch=2, **kw)
        info = {"demotions_warm": rep_on.n_tier_demotions_warm,
                "demotions_cold": rep_on.n_tier_demotions_cold,
                "promotions": rep_on.n_tier_promotions,
                "misses": rep_on.n_tier_misses,
                "prefetch_hidden": rep_on.tier_prefetch_hidden}

        def problem(what, detail):
            return info, {"what": what, "detail": detail}

        if not (rep_on.n_tier_demotions_warm
                and rep_on.n_tier_demotions_cold
                and rep_on.n_tier_promotions):
            return problem("no-tiering", "the tiered run never "
                           "demoted/spilled/promoted — the smoke "
                           "exercised nothing")
        if len(eng_on._tier):
            return problem("stranded-tenants",
                           f"{len(eng_on._tier)} tenants left in the "
                           "tier at run end (promote-all settlement "
                           "broke)")
        for tid in eng_off._tenant_det:
            if [dataclasses.asdict(a) for a in eng_off.alerts_for(tid)] \
                    != [dataclasses.asdict(a)
                        for a in eng_on.alerts_for(tid)]:
                return problem("decision-divergence",
                               f"tenant {tid} alert stream diverges "
                               "under tiering")
        if rep_off.latency != rep_on.latency \
                or rep_off.shed_fraction != rep_on.shed_fraction \
                or rep_off.served_spans != rep_on.served_spans:
            return problem("decision-divergence",
                           "SLO/shed/served diverge under tiering")
        if state_digest(eng_off._tenant_replay) \
                != state_digest(eng_on._tenant_replay):
            return problem("decision-divergence",
                           "final tenant-state digest diverges under "
                           "tiering")
    return info, None


def check_serve() -> int:
    """Serve-bench preconditions: env contract parses, bucket set
    compiles, the shard fan-out reproduces the 1-shard output, and the
    native runtime is healthy when ANOMOD_NATIVE requests it.  Runs on
    the pinned-CPU backend: it checks the env contract and decision
    parity, NOT whether the programs compile for the chip (that is
    tests/test_pallas_lowering.py here and chip_smoke.py / tpu_tests/
    on the device)."""
    out = {"check": "pre_bench_serve", "mode": "serve"}
    try:
        from anomod.utils.platform import enable_compile_cache, pin_cpu
        pin_cpu(1)
        from anomod.config import Config
        cfg = Config()                    # validates the serve env knobs
        out["buckets"] = list(cfg.serve_buckets)
        out["max_backlog"] = cfg.serve_max_backlog
        out["shards"] = cfg.serve_shards
        out["pipeline"] = cfg.serve_pipeline
        out["jit_cache"] = enable_compile_cache()
        # native runtime: status() triggers the build when the .so is
        # stale/missing; a requested-but-unusable runtime is its OWN
        # failure mode (exit 5) — "install a toolchain or unset
        # ANOMOD_NATIVE", not a bucket-grid problem
        from anomod.io import native
        out["native"] = native.status()
        if cfg.native == "on" and not native.available():
            out["status"] = "native-unusable"
            print(json.dumps(out))
            print("pre_bench_check: ANOMOD_NATIVE=on but the native "
                  f"runtime is unusable: {native.build_error()} — "
                  "install g++ and `make -C native smoke`, or unset "
                  "ANOMOD_NATIVE to serve the pure-Python path",
                  file=sys.stderr)
            return EXIT_NATIVE_UNUSABLE
        if out["native"]["staging"]:
            out["native"]["smoke"] = _native_smoke()
            # TSan leg: rebuild the staging layer -fsanitize=thread and
            # run the concurrent-fill hammer (native/sanitize_hammer.
            # cpp).  A detected race means the GIL-free staging runtime
            # must not serve (same exit as unusable); a box whose
            # toolchain can't build sanitized binaries SKIPs with the
            # recorded reason — never silently.
            import native_sanitize_smoke as nss
            tsan = nss.run("tsan", workers=4, iters=20)
            out["native"]["tsan"] = tsan
            if tsan["status"] == "fail":
                out["status"] = "native-sanitize-failed"
                print(json.dumps(out))
                print("pre_bench_check: the native staging sanitize "
                      f"smoke failed — {tsan.get('reason')} — run "
                      "`make -C native tsan` for the full report; do "
                      "not serve this runtime", file=sys.stderr)
                return EXIT_NATIVE_UNUSABLE
        from anomod.serve.batcher import BucketRunner
        from anomod.serve.engine import serve_plane_cfg
        # tenant-state residency: a FORCED device pool that cannot even
        # construct/operate on this box is its own failure mode (exit
        # 6 — "unset ANOMOD_SERVE_STATE", not "the grid is broken");
        # auto silently serves whatever engine the backend supports
        out["serve_state"] = cfg.serve_state
        if cfg.serve_state == "device":
            try:
                from anomod.replay import TenantStatePool
                probe = TenantStatePool(serve_plane_cfg(), capacity=1)
                slot = probe.acquire()
                probe.put(slot, probe.zero_state())
                probe.gather(slot)
            except Exception as e:
                out["status"] = "serve-state-unusable"
                print(json.dumps(out))
                print("pre_bench_check: ANOMOD_SERVE_STATE=device but "
                      f"the device state pool is unusable: "
                      f"{type(e).__name__}: {e} — unset "
                      "ANOMOD_SERVE_STATE (auto picks the backend's "
                      "engine) or serve the host seam",
                      file=sys.stderr)
                return EXIT_STATE_POOL_UNUSABLE
        # the serve bench's plane shape (ONE definition with bench.py's
        # serve path): compile every bucket width once so the capture's
        # compile_s is warm-path bookkeeping, not a mid-capture stall.
        # The bench's shard legs each compile this same grid per shard
        # runner — they read it back from the
        # persistent cache this warm just populated.
        runner = BucketRunner(serve_plane_cfg(), cfg.serve_buckets,
                              lane_buckets=cfg.serve_lane_buckets)
        compile_s = runner.warm()
        out.update(status="ready", widths=list(runner.widths),
                   compile_s=round(compile_s, 3))
        if cfg.serve_fuse:
            # the fused path additionally needs the full
            # (width x lane-bucket) grid compiled — a shape miss here
            # would stall (or crash) the capture mid-serve
            out["lane_buckets"] = list(runner.lane_buckets)
            lane_compile_s = runner.warm_lanes()
            expected = {(w, l) for w in runner.widths
                        for l in runner.lane_buckets}
            missing = sorted(expected - runner.lane_shapes)
            if missing:
                raise RuntimeError(
                    f"fused lane grid shape miss: {missing} did not "
                    "compile")
            out.update(lane_shapes=len(runner.lane_shapes),
                       lane_compile_s=round(lane_compile_s, 3))
            # determinism gate for the bench's shard-scaling legs
            out["shard_smoke"] = _shard_fanout_smoke()
        # determinism gate for the bench's serve_state legs: device-vs-
        # host residency byte-parity over a multi-tick seeded run
        out["state_smoke"] = _state_parity_smoke()
        # the online-RCA bucket grid (the bench's --rca legs): every
        # (nodes, neighbors) bucket must AOT-compile — a shape miss here
        # would stall the capture's alert→culprit path mid-serve
        from anomod.serve.rca import RcaRunner
        rca_runner = RcaRunner(cfg.serve_rca_buckets)
        # warm() compiles every bucket or raises — a shape that cannot
        # compile fails the gate here, never mid-capture
        rca_compile_s = rca_runner.warm()
        out.update(rca_buckets=[list(b) for b in rca_runner.buckets],
                   rca_compile_s=round(rca_compile_s, 3))
        # the flight-recorder record→replay→diff smoke: a capture whose
        # tick journal cannot replay clean leaves no usable audit trail
        # — its own exit code, distinct from the generic serve failure
        flight_info, divergence = _flight_smoke()
        out["flight_smoke"] = flight_info
        if divergence is not None:
            out["status"] = "flight-divergence"
            out["divergence"] = divergence
            print(json.dumps(out))
            print(f"pre_bench_check: flight-journal smoke diverged at "
                  f"tick {divergence['tick']} in the "
                  f"{divergence['plane']} plane — the tick journal broke "
                  "the determinism contract and a capture's audit trail "
                  "would be unusable", file=sys.stderr)
            return EXIT_FLIGHT_DIVERGENCE
        # the crash→respawn→audit-diff smoke: supervised recovery must
        # leave NO score gap (canonical journal equal to fault-free) —
        # its own exit code, distinct from a replay-path divergence
        recovery_info, recovery_div = _recovery_smoke()
        out["recovery_smoke"] = recovery_info
        if recovery_div is not None:
            out["status"] = "recovery-divergence"
            out["divergence"] = recovery_div
            print(json.dumps(out))
            print(f"pre_bench_check: recovery smoke diverged at tick "
                  f"{recovery_div['tick']} in the "
                  f"{recovery_div['plane']} plane — a recovered run "
                  "left a score gap vs the fault-free run of the same "
                  "seed", file=sys.stderr)
            return EXIT_RECOVERY_DIVERGENCE
        # the elastic smoke: scale 1→2→1 under a scripted surge must
        # leave the canonical journal equal to the static run — its own
        # exit code, distinct from a recovery or replay divergence
        elastic_info, elastic_div = _elastic_smoke()
        out["elastic_smoke"] = elastic_info
        if elastic_div is not None:
            out["status"] = "policy-divergence"
            out["divergence"] = elastic_div
            print(json.dumps(out))
            print(f"pre_bench_check: elastic smoke diverged at tick "
                  f"{elastic_div['tick']} in the "
                  f"{elastic_div['plane']} plane — a policy-scaled run "
                  "left a score gap vs the static run of the same "
                  "seed", file=sys.stderr)
            return EXIT_POLICY_DIVERGENCE
        # the performance-observatory smoke: record → report →
        # self-diff — a perf-block capture or an `anomod perf diff`
        # verdict from a broken observatory would be worse than none
        perf_info, perf_problem = _perf_smoke()
        out["perf_smoke"] = perf_info
        if perf_problem is not None:
            out["status"] = "perf-divergence"
            out["problem"] = perf_problem
            print(json.dumps(out))
            print(f"pre_bench_check: perf-observatory smoke failed "
                  f"({perf_problem['what']}): {perf_problem['detail']}"
                  " — the dispatch-lifecycle recorder or the "
                  "noise-aware diff broke its contract; do not trust "
                  "perf blocks or regression verdicts",
                  file=sys.stderr)
            return EXIT_PERF_DIVERGENCE
        # the fleet-census smoke: record → report → on/off byte-parity
        # → pool-bytes reconciliation — a census block (the tiering
        # baseline curve) from a broken census would anchor the
        # tiering refactor against fiction
        census_info, census_problem = _census_smoke()
        out["census_smoke"] = census_info
        if census_problem is not None:
            out["status"] = "census-divergence"
            out["problem"] = census_problem
            print(json.dumps(out))
            print(f"pre_bench_check: fleet-census smoke failed "
                  f"({census_problem['what']}): "
                  f"{census_problem['detail']} — the census recorder "
                  "broke its read-side or reconciliation contract; do "
                  "not trust census blocks or `anomod census diff` "
                  "verdicts", file=sys.stderr)
            return EXIT_CENSUS_DIVERGENCE
        # the state-tiering smoke: demote → spill → re-admit must be a
        # pure residency move — byte parity with the never-evicted run
        # on every decision plane, its own exit code so a driver can
        # tell "tiering moved a scored byte" from a census-recorder or
        # replay-path break
        tier_info, tier_problem = _tiering_smoke()
        out["tiering_smoke"] = tier_info
        if tier_problem is not None:
            out["status"] = "tiering-divergence"
            out["problem"] = tier_problem
            print(json.dumps(out))
            print(f"pre_bench_check: state-tiering smoke failed "
                  f"({tier_problem['what']}): {tier_problem['detail']}"
                  " — demotion/promotion through the snapshot seams "
                  "broke byte parity; do not capture with "
                  "ANOMOD_SERVE_TIER_HOT set", file=sys.stderr)
            return EXIT_TIERING_DIVERGENCE
        # the deferred-commit smoke: the async engine must be a pure
        # wall-clock move — byte parity with the synchronous oracle on
        # every decision plane, its own exit code so a driver can tell
        # "async broke parity" from every other divergence
        async_info, async_div = _async_commit_smoke()
        out["async_commit_smoke"] = async_info
        if async_div is not None:
            out["status"] = "async-divergence"
            out["divergence"] = async_div
            print(json.dumps(out))
            print(f"pre_bench_check: deferred-commit smoke diverged at "
                  f"tick {async_div['tick']} in the "
                  f"{async_div['plane']} plane — the async tick moved "
                  "a scored byte; do not capture with "
                  "ANOMOD_SERVE_ASYNC_COMMIT on", file=sys.stderr)
            return EXIT_ASYNC_DIVERGENCE
        # the live-feed loop smoke: endpoint → LiveFeed → wire-journal
        # replay must be a closed deterministic loop — its own exit
        # code so a driver can tell "the live adapter broke replay"
        # from every other divergence
        feed_info, feed_div = _feed_smoke()
        out["feed_smoke"] = feed_info
        if feed_div is not None:
            out["status"] = "feed-divergence"
            out["divergence"] = feed_div
            print(json.dumps(out))
            print(f"pre_bench_check: live-feed smoke diverged at tick "
                  f"{feed_div['tick']} in the {feed_div['plane']} "
                  "plane — a live run and its wire-journal replay "
                  "disagree; do not trust --from-live captures",
                  file=sys.stderr)
            return EXIT_FEED_DIVERGENCE
        # the process-worker smoke: the GIL-free engine must be a pure
        # wall-clock move — byte parity with the thread oracle and the
        # 1-process run on every decision plane, its own exit code so
        # a driver can tell "the process seam broke parity" from every
        # other divergence
        proc_info, proc_div = _procshard_smoke()
        out["procshard_smoke"] = proc_info
        if proc_div is not None:
            out["status"] = "procshard-divergence"
            out["divergence"] = proc_div
            print(json.dumps(out))
            print(f"pre_bench_check: process-worker smoke diverged at "
                  f"tick {proc_div['tick']} in the "
                  f"{proc_div['plane']} plane "
                  f"({proc_div.get('pair', 'decision planes')}) — the "
                  "process seam moved a scored byte; do not capture "
                  "with ANOMOD_SERVE_WORKER=process", file=sys.stderr)
            return EXIT_PROCSHARD_DIVERGENCE
        print(json.dumps(out))
        return EXIT_READY
    except Exception as e:
        out.update(status="serve-precondition-failed",
                   error=f"{type(e).__name__}: {e}")
        print(json.dumps(out))
        print(f"pre_bench_check: serve preconditions failed: {e}",
              file=sys.stderr)
        return EXIT_SERVE_PRECONDITION


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["replay", "serve"], default="replay",
                    help="replay: ingest-cache warmth gate (default); "
                         "serve: serve-bench precondition gate")
    ap.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    ap.add_argument("--traces", type=int, default=2_000,
                    help="bench.py span corpus size (default matches "
                         "bench.py's argv default)")
    ap.add_argument("--cold", action="store_true",
                    help="allow the capture anyway; the bench line still "
                         "records cache_hit=false for honesty")
    args = ap.parse_args(argv)

    # env-contract gate first (quiet on success: the drivers parse this
    # script's stdout as ONE JSON line)
    import check_env_contract as cec
    root = Path(cec.ROOT)
    corpus = cec.covered_vars(root)
    missing = {name: sorted(files)
               for name, files in sorted(cec.referenced_vars(root).items())
               if name not in corpus}
    if missing:
        print(json.dumps({"check": "pre_bench_env_contract",
                          "status": "uncovered-env-vars",
                          "missing": missing}))
        print("pre_bench_check: env contract violated — run "
              "scripts/check_env_contract.py and fix the listed ANOMOD_* "
              "vars (Config or docs) before capturing", file=sys.stderr)
        return EXIT_ENV_CONTRACT

    # contract lint + parity-surface audit (static AST — milliseconds,
    # never touches the backend): a capture of a tree violating a
    # determinism/seam/parity contract is not reproducible from its
    # record, so both modes gate on it
    import check_contracts
    lint_doc = check_contracts.run()
    if lint_doc["status"] != "ok":
        print(json.dumps({"check": "pre_bench_contracts", **lint_doc}))
        print("pre_bench_check: contract lint failed — run `anomod "
              "lint`, then fix each finding in place, add a reasoned "
              "inline suppression, or baseline it deliberately "
              "(docs/CONTRACTS.md)", file=sys.stderr)
        return EXIT_LINT

    if args.mode == "serve":
        return check_serve()

    from anomod.io import cache
    from anomod.io.dataset import bench_cache_status

    root = cache.cache_root()
    out = {"check": "pre_bench_ingest", "testbed": args.testbed,
           "traces": args.traces,
           "cache_dir": str(root) if root else None,
           "cold_ok": bool(args.cold)}
    if root is None:
        out["status"] = "caching-disabled"
        print(json.dumps(out))
        if args.cold:
            return EXIT_READY
        print("pre_bench_check: ANOMOD_CACHE_DIR is disabled — captures "
              "would re-synthesize the corpus every run; pass --cold to "
              "record one anyway", file=sys.stderr)
        return EXIT_CACHE_DISABLED
    present, total = bench_cache_status(args.testbed, args.traces)
    out.update(entries_present=present, entries_total=total,
               status="warm" if present == total else "cold")
    print(json.dumps(out))
    if present == total or args.cold:
        return EXIT_READY
    print(f"pre_bench_check: ingest cache at {root} is cold for the "
          f"{args.testbed}/{args.traces}-trace bench corpus — run "
          f"`anomod ingest --warm-cache --bench-traces {args.traces}` "
          "first, or pass --cold to capture an ingest-bound number on "
          "purpose", file=sys.stderr)
    return EXIT_COLD_CACHE


if __name__ == "__main__":
    sys.exit(main())
