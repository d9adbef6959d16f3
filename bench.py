#!/usr/bin/env python
"""Headline benchmark: TT-corpus span replay throughput on one chip.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "spans/sec/chip", "vs_baseline": N}

Baseline (BASELINE.json north star): 1,000,000 spans/sec/chip on TT_data
replay.  The corpus is the full 13-experiment TT tree loaded via the typed
loaders (LFS stubs fall back to the seeded synthetic generator, which is the
shipped checkout's situation), staged to HBM once and replayed with the
jitted windowed-aggregation kernel; ``replicate`` loops the corpus on device
to reach steady state (~30M spans counted per dispatch on TPU).

Corpus prep reads through the content-addressed ingest cache
(anomod.io.cache; ``ANOMOD_CACHE_DIR``), so repeat captures measure the
kernel instead of re-synthesizing the corpus.  The JSON line reports the
split: ``prep_s`` (what this run paid), ``parse_s`` (the recorded cold
generate+concat wall), ``cache_hit``, and ``tt_ingest_throughput``
(experiments/sec cold vs warm) — see docs/BENCHMARKS.md.  Warm the cache
before driver captures with ``anomod ingest --warm-cache`` or gate on
``scripts/pre_bench_check.py``.

The bench runs on the backend JAX gives it and every line carries
``platform``, ``device_kind`` and ``n_devices``.  With no TPU it exits
non-zero — unless ``JAX_PLATFORMS=cpu`` was set explicitly, the contract
tests' mode, which exercises the protocol and the line's schema (counts,
parity bits) and whose rates are labelled ``spans/sec/cpu-host``, never a
chip unit.  A Pallas kernel asked for off-TPU is an error, not a
downgrade.  Any failure prints the JSON line with an ``error`` field, the
traceback on stderr, and exits 1.

Serve mode (``python bench.py --mode serve`` or ``ANOMOD_BENCH_MODE=serve``):
instead of the batch replay, drives the multi-tenant serving plane
(anomod.serve) with a seeded power-law fleet offering 2x the engine's
capacity and emits ONE JSON line with sustained spans/sec through
admission+batching+scoring, the p99 admission->scored latency, and the
shed fraction under that overload at the configured backlog budget —
plus a ``fused_dispatch`` block comparing the tenant-fused (lane-stacked)
path against one-dispatch-per-micro-batch on the same seed.
Gate serve captures on ``scripts/pre_bench_check.py --mode serve`` (bucket
set AND the (width x lane-bucket) fused grid must validate + compile).  Knobs: ``ANOMOD_SERVE_BENCH_CAPACITY``
(spans/sec, default 25000), ``ANOMOD_SERVE_BENCH_DURATION`` (virtual
seconds, default 60), ``ANOMOD_SERVE_BENCH_TENANTS`` (default 200).

Telemetry (anomod.obs, docs/OBSERVABILITY.md): both modes inline an
``obs_snapshot`` of the process registry in the JSON line; serve mode
additionally runs the same seed twice (telemetry on, then off — the off
leg inherits the process warmup, so the fraction is an upper bound) to
report the enabled-telemetry overhead (bar: <= 5%) and exports the
enabled leg's scrape journal as a TT-CSV self-scrape capture next to the
provenance record, scored through the framework's own detector stack.
"""

import json
import os
import sys
import time


def serve_run_kw(capacity: float = 25_000, duration: float = 60,
                 tenants: int = 200) -> dict:
    """The serve cell's ``run_power_law`` configuration — ONE definition,
    shared by :func:`serve_main` and ``chip_smoke.py``."""
    return dict(
        n_tenants=int(tenants), n_services=12,
        capacity_spans_per_s=float(capacity), overload=2.0,
        duration_s=float(duration), tick_s=0.5, seed=7,
        window_s=5.0, baseline_windows=4, fault_tenants=2,
        # the fixed shed budget: 8 seconds of capacity worth of
        # backlog — scale-invariant, so a down-sized contract run
        # sheds in the same regime as the headline capture
        max_backlog=int(8 * float(capacity)))


def engines_identical(eng_a, eng_b):
    """(alerts_same, states_same) over the union of the two engines'
    tenants — the one definition every parity bit reads (the serve
    capture's legs and ``chip_smoke.py``'s on-chip report alike)."""
    import numpy as np
    tids = sorted(set(eng_a._tenant_det) | set(eng_b._tenant_det))
    alerts = all(eng_a.alerts_for(t) == eng_b.alerts_for(t) for t in tids)
    states = all(
        t in eng_a._tenant_replay and t in eng_b._tenant_replay
        and np.array_equal(np.asarray(eng_a._tenant_replay[t].state.agg),
                           np.asarray(eng_b._tenant_replay[t].state.agg))
        and np.array_equal(np.asarray(eng_a._tenant_replay[t].state.hist),
                           np.asarray(eng_b._tenant_replay[t].state.hist))
        for t in tids)
    return alerts, states


def _device_fields() -> dict:
    """The backend this process measures on, as the three fields every
    result line carries.  No TPU is an error unless the caller asked for
    the CPU explicitly with ``JAX_PLATFORMS=cpu`` (the contract tests'
    mode): a capture must never run on a device nobody chose."""
    import jax
    devs = jax.devices()
    fields = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind,
              "n_devices": len(devs)}
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if fields["platform"] != "tpu" and not asked_cpu:
        raise SystemExit(
            f"bench.py: no TPU — JAX found {fields}.  Run it through the "
            "chip tool, or set JAX_PLATFORMS=cpu explicitly for a "
            "schema-only CPU run")
    return fields


def _write_capture(out: dict):
    """Write the bench_runs/ provenance record of a finished line (device
    string + versions + git SHA) and note it in the line; returns the
    record's path, or None when the filesystem refused."""
    from anomod.provenance import capture_record, write_capture
    path = write_capture(capture_record(
        out["metric"], out["value"], out["unit"],
        **{k: v for k, v in out.items()
           if k not in ("metric", "value", "unit")}))
    if path:
        out["capture_file"] = os.path.relpath(
            path, os.path.dirname(os.path.abspath(__file__)))
    return path


def _fail(out: dict, exc: Exception) -> int:
    """Print the JSON line with the error (the driver's contract) and the
    traceback on stderr; exit code 1."""
    import traceback
    traceback.print_exc()
    out["error"] = f"{type(exc).__name__}: {exc}"
    print(json.dumps(out))
    return 1


def _bench_mode(argv) -> str:
    """"replay" (default) or "serve"; --mode beats ANOMOD_BENCH_MODE."""
    if "--mode" in argv:
        i = argv.index("--mode")
        if i + 1 >= len(argv):
            raise SystemExit("bench.py: --mode needs a value "
                             "(replay|serve)")
        mode = argv[i + 1].strip().lower()
    else:
        mode = os.environ.get("ANOMOD_BENCH_MODE", "replay").strip().lower()
    if mode not in ("replay", "serve"):
        raise SystemExit(f"bench.py: unknown mode {mode!r} (replay|serve)")
    return mode


def serve_main() -> int:
    """The serve-mode capture: sustained spans/sec + p99 latency + shed
    fraction under a seeded 2x overload (fixed backlog budget).

    The run executes THREE times on the same seed: first with the
    self-scraping registry (anomod.obs) + default tracer on (the
    headline numbers, fused dispatch per the config default), then with
    telemetry forced off — the ``telemetry`` block reports both
    sustained rates and the enabled-telemetry overhead fraction
    (acceptance bar: <= 5%; the off leg runs second so it inherits the
    one-time process warmup and the fraction is an upper bound) — and
    then with the tenant-FUSED dispatch forced off (telemetry on,
    its own registry): the ``fused_dispatch`` block reports fused vs
    unfused sustained spans/sec, p99 and shed fraction on the same seed
    (the unfused leg runs after both headline legs so the speedup is
    never flattered by warmup order).  A PYTHON-STAGING leg (same seed,
    ``native=False``) then isolates the C++ GIL-free lane packing: the
    ``staging`` block decomposes the serve wall into stage / dispatch /
    fold / score / other for both legs — the serving-overhead gap
    attributed with numbers — plus the byte-parity bits (native staging
    is pinned byte-identical, so every decision metric must match
    exactly).  A HOST-SEAM state leg (same seed,
    ``ANOMOD_SERVE_STATE=host``) isolates the device-resident tenant
    pool the same way: the ``serve_state`` block carries both legs'
    five-way decompositions, the fold+score+other share the residency
    change attacks, and the pool's byte-parity bits.  A FLIGHT-OFF leg
    (same seed, ``flight=False``) prices the black-box tick journal
    (anomod.obs.flight): the ``flight`` block reports the recorder's
    overhead fraction (bar: <= 5%), its drop counters (zero = the ring
    never evicted) and the read-side byte-parity bits.  A CHAOS leg
    (scripted mid-run shard crashes, same seed) fills the ``recovery``
    block: checkpoint-cadence overhead measured in-run on the headline
    (ckpt_wall_s / serve_wall_s, bar: <= 5%), crash/restored-tick
    counts, and the no-score-gap parity bits (the chaos leg's
    states/alerts/p99/shed and canonical flight journal must equal the
    fault-free headline's).
    An ELASTICITY pair (sub-capacity load + a scripted ``surge`` chaos
    window, served static and again under ``ANOMOD_SERVE_POLICY=auto``)
    fills the ``elasticity`` block: scale-up/down episode counts, the
    migration volume, and the elastic determinism parity bits (the
    policy run's states/alerts/p99/shed and canonical flight journal
    must equal the static leg's).
    A PROCESS-WORKER quartet (ISSUE-20: 2-shard thread oracle, 2-shard
    and 1-shard process engines, and a dense-fold process reference,
    same seed) fills the ``proc_shard`` block: thread-vs-process and
    N-vs-1-process parity bits, the sparse barrier fold's payload
    bytes against the dense walk, and the per-leg raw_wall_s samples —
    throughput scaling quoted only when the box has >= 4 cores
    (``scaling_quotable``).
    After the shard-scaling legs,
    two ONLINE-RCA legs (1-shard and 2-shard, ``rca=True``, same seed)
    fill the ``rca`` block: top-k hit-rate (k=1,3,5) against the
    injected-fault ground truth, alert→culprit latency quantiles, and
    the determinism pins (RCA-on leaves alerts/states/p99/shed
    byte-identical; 2-shard verdicts equal 1-shard).  The enabled
    run's scrape journal is exported as a
    TT-CSV self-scrape capture next to the provenance record and scored
    through the framework's own detector stack (``self_scrape``
    block)."""
    from anomod.utils.platform import env_number
    out = {
        "metric": "serve_sustained_throughput",
        "value": 0.0,
        "unit": "spans/sec",
        "mode": "serve",
    }
    out.update(_device_fields())
    on_cpu = out["platform"] == "cpu"
    try:
        import jax

        from anomod.obs.registry import Registry, set_registry
        from anomod.serve.engine import run_power_law
        from anomod.utils.platform import enable_compile_cache
        jit_cache_dir = enable_compile_cache()
        run_kw = serve_run_kw(
            capacity=env_number("ANOMOD_SERVE_BENCH_CAPACITY", 25_000),
            duration=env_number("ANOMOD_SERVE_BENCH_DURATION", 60),
            tenants=env_number("ANOMOD_SERVE_BENCH_TENANTS", 200))
        # telemetry-on leg FIRST (the headline numbers), telemetry-off
        # reference leg second: the second leg inherits every one-time
        # process warmup (allocator growth, first-touch code paths), so
        # the reported overhead fraction is an upper bound on what
        # telemetry actually costs — never flattered by run order
        # the headline leg pins shards=1: comparable with every prior
        # capture, and it doubles as leg 1 of the shard-scaling table
        reg = Registry(enabled=True)
        prev_reg = set_registry(reg)
        eng_head, rep = run_power_law(shards=1, **run_kw)
        set_registry(Registry(enabled=False))
        try:
            _, rep_off = run_power_law(shards=1, **run_kw)
            # the unfused reference leg: same seed, fused dispatch
            # forced OFF, telemetry on (matching the headline leg) but
            # in its OWN registry so the headline journal/snapshot stays
            # the headline run's.  Runs after both headline legs (only
            # the shard-scaling legs follow), so it inherits the
            # process warmup and the reported fused speedup is not
            # flattered by run order.
            set_registry(Registry(enabled=True))
            _, rep_unfused = run_power_law(fuse=False, shards=1, **run_kw)
            # the python-staging reference leg: same seed, the C++
            # GIL-free lane packing forced OFF (interpreter fill), own
            # registry, run after the headline legs so the native
            # speedup is never flattered by warmup order.  Output is
            # byte-identical by construction — the leg isolates the
            # STAGE wall, and its parity bits are recorded in the
            # capture itself.
            set_registry(Registry(enabled=True))
            eng_pystage, rep_pystage = run_power_law(
                native=False, shards=1, **run_kw)
            # the host-seam state reference leg: same seed, tenant
            # states kept as per-tenant numpy pytrees (the pre-pool
            # seam, ANOMOD_SERVE_STATE=host) with the per-lane fold
            # adds and per-tenant sequential window scoring — the
            # device-pool headline is pinned byte-identical, and this
            # leg's five-way wall decomposition is what the residency
            # change is measured against
            set_registry(Registry(enabled=True))
            eng_hostst, rep_hostst = run_power_law(
                state="host", shards=1, **run_kw)
            # the flight-recorder-off reference leg: same seed, the
            # black-box tick journal (anomod.obs.flight) forced OFF,
            # telemetry on, own registry, run after the headline legs
            # so the recorder's measured overhead is an upper bound.
            # The recorder is a pure read-side consumer, so every
            # decision metric must match the headline byte-for-byte —
            # the `flight` block records the parity bits with the
            # overhead (bar: <= 5%, the telemetry discipline)
            set_registry(Registry(enabled=True))
            eng_floff, rep_floff = run_power_law(
                flight=False, shards=1, **run_kw)
            # the CHAOS leg: same seed, scripted mid-run shard faults
            # (two worker kills, a score-path exception) under
            # supervision — the capture's own proof that recovery
            # leaves NO score gap: states/alerts/SLO/shed and the
            # canonical flight journal must equal the headline's.
            # Checkpoint overhead is measured DIRECTLY on the headline
            # (ckpt_wall_s / serve_wall_s — snapshot wall is accounted
            # inside the tick, so the fraction needs no A/B leg and is
            # immune to this box's run-to-run noise); real worker
            # respawn is exercised by the 2-shard pre-bench smoke.
            n_ticks = int(round(run_kw["duration_s"] / run_kw["tick_s"]))
            chaos_script = (
                f"crash@{n_ticks // 3}:shard=0:phase=dispatch;"
                f"except@{n_ticks // 2}:shard=0:phase=score;"
                f"crash@{(2 * n_ticks) // 3}:shard=0:phase=stage")
            set_registry(Registry(enabled=True))
            eng_chaos, rep_chaos = run_power_law(
                chaos=chaos_script, shards=1, **run_kw)
            # the shard-scaling legs (2 and 4 engine workers, same
            # seed), then a FRESH 1-shard reference leg LAST: the
            # reference inherits the most process warmup of the whole
            # capture, so speedup_vs_1_shard can only understate shard
            # scaling, never report warmup as speedup (the same
            # run-order discipline as the unfused leg above).  Each leg
            # gets its own registry; the per-shard compile grids hit the
            # persistent compilation cache.
            shard_reps = {}
            for n_shards in (2, 4, 1):
                set_registry(Registry(enabled=True))
                _, shard_reps[n_shards] = run_power_law(
                    shards=n_shards, **run_kw)
            # online-RCA legs (same seed, run LAST so the headline legs
            # never inherit their warmup): shards=1 with RCA on for the
            # alert→culprit product numbers, then a 2-shard RCA leg
            # whose verdict stream must be byte-identical — the capture
            # records the determinism checks it ran, not just numbers
            set_registry(Registry(enabled=True))
            eng_rca, rep_rca = run_power_law(shards=1, rca=True, **run_kw)
            set_registry(Registry(enabled=True))
            eng_rca2, _ = run_power_law(shards=2, rca=True, **run_kw)
            # the PERF leg: same seed, the dispatch-lifecycle timeline
            # (anomod.obs.perf) forced ON — the `perf` block carries
            # the overlap-headroom bound (the go/no-go instrument for
            # the fold-wait-overlap attack), the measured fold WAIT,
            # the on/off overhead fraction (bar: <= 5%, the telemetry/
            # flight discipline; the on leg runs after the headline so
            # the ratio inherits warmup like every A/B pair here), the
            # read-side parity bits, and the headline leg's per-tick
            # raw_wall_s samples `anomod perf diff` bootstraps over
            set_registry(Registry(enabled=True))
            eng_perf, rep_perf = run_power_law(perf=True, shards=1,
                                               **run_kw)
            # the ASYNC-COMMIT leg (ISSUE-16): same seed, the deferred-
            # commit tick forced ON with the perf recorder — tick N's
            # fold dispatch is issued un-waited, the coordinator runs
            # tick N+1's admission/drain/shed/SLO under the in-flight
            # XLA work, and the commit barrier lands just before the
            # results are first read.  Runs right after the perf leg
            # (its matched synchronous A side) so the hidden-wait
            # numbers inherit identical warmup; the parity bits are
            # the capture's own proof that the overlap moved only
            # wall-clock, never a scored byte.
            set_registry(Registry(enabled=True))
            eng_async, rep_async = run_power_law(
                async_commit=True, perf=True, shards=1, **run_kw)
            # the PROCESS-WORKER legs (ISSUE-20): the same seed served
            # four ways — 2 shard THREADS (the byte-parity oracle,
            # sparse fold), 2 shard PROCESSES (the GIL-free engine,
            # sparse fold), 1 shard process (the N-vs-1 process parity
            # side), and 2 shard processes under the DENSE barrier fold
            # (the sparse payload's reference walk).  The thread leg
            # runs FIRST so the process legs inherit its warmup and the
            # thread/process wall comparison is never flattered by run
            # order; every decision plane and the canonical flight
            # journal must be byte-identical across all four.  CPU
            # backend only: a chip belongs to one process, and each
            # worker child imports jax and compiles — it could never get
            # the device this process already holds (the engine refuses
            # the mode there; see the ``not_run`` block below).
            if on_cpu:
                set_registry(Registry(enabled=True))
                eng_pwt, rep_pwt = run_power_law(
                    shards=2, worker="thread", fold="sparse", **run_kw)
                set_registry(Registry(enabled=True))
                eng_pwp, rep_pwp = run_power_law(
                    shards=2, worker="process", fold="sparse", **run_kw)
                set_registry(Registry(enabled=True))
                eng_pw1, rep_pw1 = run_power_law(
                    shards=1, worker="process", fold="sparse", **run_kw)
                set_registry(Registry(enabled=True))
                eng_pwd, rep_pwd = run_power_law(
                    shards=2, worker="process", fold="dense", **run_kw)
            # the ELASTICITY legs: a sub-capacity fleet hit by a
            # scripted load surge (the chaos 'surge' kind), served
            # twice on the same seed — once static, once under the
            # signal-fed elastic policy (scale 1→2 into the surge, back
            # down after it).  The capture's own proof of the elastic
            # determinism contract: the policy run must produce ≥1
            # scale-up and ≥1 scale-down episode AND leave every
            # decision plane byte-identical to the static run — the
            # autoscaler moves wall-clock capacity around, never a
            # scored byte.
            elastic_kw = dict(run_kw)
            elastic_kw["overload"] = 0.6
            # an eighth-of-the-run surge: long enough to sustain the
            # scale-up hysteresis, short enough that the brownout
            # ladder never reaches level 2 (digest coarsening) — the
            # parity bit below compares canonical journals, and a
            # deliberately coarsened digest cadence would read as fold
            # divergence (the ladder has its own pinned test)
            surge_script = (f"surge@{n_ticks // 4}:factor=4:"
                            f"ticks={max(1, n_ticks // 8)}")
            set_registry(Registry(enabled=True))
            eng_els, rep_els = run_power_law(
                shards=1, chaos=surge_script, **elastic_kw)
            set_registry(Registry(enabled=True))
            eng_el, rep_el = run_power_law(
                shards=1, chaos=surge_script, policy="auto",
                min_shards=1, max_shards=2, cooldown_ticks=5,
                **elastic_kw)
            # the CENSUS leg (ISSUE-15): same seed, the fleet census
            # observatory (anomod.obs.census) forced ON — deterministic
            # resident-bytes per plane, the hot-set/Zipf census, the
            # read-side parity bits, and the on/off overhead fraction
            # (≤5% bar, the telemetry discipline)
            set_registry(Registry(enabled=True))
            eng_cen, rep_cen = run_power_law(census=True, shards=1,
                                             **run_kw)
            # the registered-fleet sweep: per-tick wall and resident-
            # bytes slopes vs the REGISTERED count at fixed ~1e3-hot
            # traffic — the committed O(registered) baseline curve the
            # million-tenant tiering refactor must flatten (`anomod
            # census diff` judges the before/after).  Own registry so
            # the probe engines' gauges stay out of the headline
            # journal.
            set_registry(Registry(enabled=True))
            from anomod.obs.census import fleet_probe
            census_sweep = fleet_probe()
            # the TIERING legs (ISSUE-19): (a) the registered-fleet
            # sweep re-run with the tenant-state tiering plane ON —
            # the same ~1e3-hot traffic against up to 1e6 REGISTERED
            # tenants, the O(hot-set) curve the committed PR-15
            # O(registered) baseline must collapse to (`anomod census
            # diff OLD NEW` judges the pair); (b) a sub-capacity
            # tiered-vs-never-evicted parity pair on the same seed —
            # sub-capacity because the power-law tail must idle whole
            # ticks for the decay plane to demote at all (an
            # overloaded fleet keeps every tenant backlogged and the
            # anti-thrash exclusion never fires); the tiny warm budget
            # pushes most demotions through the content-addressed
            # disk cold tier, so the counters below evidence all four
            # event legs (warm demote, cold spill, promote, miss) and
            # the prefetch lane.  Own registries throughout.
            import tempfile as _tempfile
            set_registry(Registry(enabled=True))
            # one extra 10x-the-max top point past the untiered sweep:
            # on the default 1e3/1e4/1e5 sweep that is the committed
            # capture's 1e6-registered / 1e3-hot mode; a down-sized
            # ANOMOD_CENSUS_SWEEP (the bench contract test) scales the
            # same shape without the minute-class top row
            _tier_sizes = [*census_sweep["sizes"],
                           10 * max(census_sweep["sizes"])]
            tiered_sweep = fleet_probe(
                sizes=_tier_sizes,
                tier_hot=1_000, tier_demote_after=2)
            tier_kw = dict(
                n_tenants=48, n_services=8,
                capacity_spans_per_s=800.0, overload=0.5,
                duration_s=24.0, tick_s=1.0, seed=7, window_s=5.0,
                baseline_windows=2, fault_tenants=2,
                buckets=(64, 256), lane_buckets=(1, 2, 4),
                max_backlog=6400, n_windows=16)
            set_registry(Registry(enabled=True))
            eng_toff, rep_toff = run_power_law(shards=1, **tier_kw)
            with _tempfile.TemporaryDirectory() as _tier_cold:
                set_registry(Registry(enabled=True))
                eng_ton, rep_ton = run_power_law(
                    shards=1, tier_hot=12, tier_demote_after=2,
                    tier_warm_bytes=4096, tier_cold_dir=_tier_cold,
                    tier_prefetch=2, **tier_kw)
                _tier_left = len(eng_ton._tier)
                _tier_joins = eng_ton._tier.prefetch_joins
            # the same-config rerun: a deferred cold fold legitimately
            # moves spans one tick later, so the tiered journal is NOT
            # tick-for-tick equal to the never-evicted twin's — the
            # journal determinism pin is instead that the SAME tiered
            # config replays byte-identically (what `anomod audit
            # replay` relies on)
            with _tempfile.TemporaryDirectory() as _tier_cold2:
                set_registry(Registry(enabled=True))
                eng_ton2, rep_ton2 = run_power_law(
                    shards=1, tier_hot=12, tier_demote_after=2,
                    tier_warm_bytes=4096, tier_cold_dir=_tier_cold2,
                    tier_prefetch=2, **tier_kw)
            # the LIVE-FEED leg (ISSUE-18): the closed telemetry loop —
            # an embedded /metrics endpoint serving THIS process's
            # registry, scraped by LiveFeed into the serve tick,
            # wire-journaled, then replayed through ReplayTransport.
            # Live-vs-replay byte parity is the --from-live
            # reproducibility pin.  Own registry so the loop scrapes a
            # stable, self-generated fleet.
            from anomod.obs.http import ObsHttpServer
            from anomod.serve.feed import run_live_feed
            _feed_reg = Registry(enabled=True)
            set_registry(_feed_reg)
            _feed_kw = dict(capacity_spans_per_s=2000.0,
                            duration_s=10.0, tick_s=1.0, window_s=2.0,
                            baseline_windows=2, buckets=(64,),
                            n_windows=16, flight=True,
                            flight_digest_every=2)
            with _tempfile.TemporaryDirectory() as _ftmp, \
                    ObsHttpServer(port=0) as _fsrv:
                _fjournal = os.path.join(_ftmp, "feed_wire.json")
                eng_lf, rep_lf, feed_lf = run_live_feed(
                    scrape_url=f"{_fsrv.url}/metrics", n_tenants=4,
                    n_services=4, journal=_fjournal, **_feed_kw)
                _feed_journal_entries = len(feed_lf.journal_entries())
                _fsrv.stop()
                eng_lfr, rep_lfr, _ = run_live_feed(
                    replay=_fjournal, **_feed_kw)
        finally:
            set_registry(prev_reg)
        set_registry(reg)
        d = rep.to_dict()
        out.update({
            "value": rep.sustained_spans_per_sec,
            "p99_admission_to_scored_latency_s":
                rep.latency.get("p99_latency_s"),
            "p50_admission_to_scored_latency_s":
                rep.latency.get("p50_latency_s"),
            "shed_fraction": rep.shed_fraction,
            "offered_spans": rep.offered_spans,
            "served_spans": rep.served_spans,
            "overload": 2.0,
            "capacity_spans_per_s": rep.capacity_spans_per_s,
            "max_backlog": rep.max_backlog,
            "n_tenants": rep.n_tenants,
            "duration_virtual_s": rep.duration_s,
            "serve_wall_s": rep.serve_wall_s,
            "compile_s": rep.compile_s,
            "buckets": d["buckets"],
            "dispatches_by_width": d["dispatches_by_width"],
            "fault_detection": rep.fault_detection,
            "n_alerts": rep.n_alerts,
            "device": str(jax.devices()[0]),
        })
        # fused vs unfused on the same seed (both telemetry-on): the
        # tenant-fused lane-stacked dispatch against one dispatch per
        # tenant micro-batch
        out["fused_dispatch"] = {
            "fused": rep.fused,
            "spans_per_sec_fused": rep.sustained_spans_per_sec,
            "spans_per_sec_unfused": rep_unfused.sustained_spans_per_sec,
            "speedup": round(rep.sustained_spans_per_sec
                             / max(rep_unfused.sustained_spans_per_sec,
                                   1e-9), 2),
            "p99_latency_s_unfused":
                rep_unfused.latency.get("p99_latency_s"),
            "shed_fraction_unfused": rep_unfused.shed_fraction,
            "fused_dispatches": rep.fused_dispatches,
            "lane_buckets": list(rep.lane_buckets),
            "lanes_by_bucket": {str(k): v for k, v
                                in rep.lanes_by_bucket.items()},
            "lane_pad_waste": rep.lane_pad_waste,
            "lane_compile_s": rep.lane_compile_s,
        }
        # the serve-tick wall DECOMPOSITION (the serving-overhead gap,
        # attributed with numbers): host packing (stage) vs executable
        # issue (dispatch) vs output materialization + state folds
        # (fold), native vs interpreter staging legs on the same seed —
        # `other` is what the serve wall spends in admission/detector/
        # bookkeeping Python, the remaining interpreter tax
        from anomod.io import native as _native
        _nat_status = _native.status()

        def _decomp(r):
            walls = {"stage": r.stage_wall_s, "dispatch": r.dispatch_wall_s,
                     "fold": r.fold_wall_s, "score": r.score_wall_s}
            walls["other"] = round(
                max(0.0, r.serve_wall_s - sum(walls.values())), 4)
            walls["serve"] = r.serve_wall_s
            return walls

        def _fso_share(r):
            """fold+score+other share of the serve wall — the serving-
            overhead gap's remaining interpreter/fold tax (the ISSUE-8
            acceptance number)."""
            w = _decomp(r)
            return round((w["fold"] + w["score"] + w["other"])
                         / max(w["serve"], 1e-9), 4)

        _stage_alerts_same, _stage_states_same = engines_identical(
            eng_head, eng_pystage)
        out["staging"] = {
            "native_mode": _nat_status["mode"],
            "native_available": _nat_status["available"],
            "build_error": _nat_status["build_error"],
            "native_staging_headline": rep.native_staging,
            "native_staged_dispatches": rep.native_staged_dispatches,
            "wall_s_native": _decomp(rep),
            "wall_s_python": _decomp(rep_pystage),
            "spans_per_sec_native": rep.sustained_spans_per_sec,
            "spans_per_sec_python": rep_pystage.sustained_spans_per_sec,
            "stage_wall_speedup": round(
                rep_pystage.stage_wall_s / max(rep.stage_wall_s, 1e-9), 2),
            "parity": {
                "alerts_identical": _stage_alerts_same,
                "states_identical": _stage_states_same,
                "p99_identical": rep_pystage.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_pystage.shed_fraction == rep.shed_fraction,
            },
        }
        # tenant-state residency (ISSUE-8): the device-pool headline vs
        # the host-seam reference on the same seed — five-leg wall
        # decomposition, the fold+score+other share the residency
        # change attacks, and the byte-parity bits the pool is pinned
        # to (states, alerts, p99, shed — the pool performs the exact
        # same f32 adds, so every bit must match)
        _st_alerts_same, _st_states_same = engines_identical(
            eng_head, eng_hostst)
        out["serve_state"] = {
            "headline": rep.serve_state,
            "pool_engine": (eng_head.runner.pool.engine
                            if eng_head.runner.pool is not None else None),
            "wall_s_device": _decomp(rep),
            "wall_s_host_seam": _decomp(rep_hostst),
            "fold_score_other_share_device": _fso_share(rep),
            "fold_score_other_share_host_seam": _fso_share(rep_hostst),
            "spans_per_sec_device": rep.sustained_spans_per_sec,
            "spans_per_sec_host_seam": rep_hostst.sustained_spans_per_sec,
            "parity": {
                "alerts_identical": _st_alerts_same,
                "states_identical": _st_states_same,
                "p99_identical": rep_hostst.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_hostst.shed_fraction == rep.shed_fraction,
            },
        }
        # flight recorder (ISSUE-9): the always-on tick journal's
        # measured overhead on the same seed, its drop counters (zero =
        # no silent loss — the ring never evicted), and the byte-parity
        # bits a read-side recorder must hold against the no-recorder
        # leg
        _fl_alerts_same, _fl_states_same = engines_identical(
            eng_head, eng_floff)
        out["flight"] = {
            "enabled_headline": rep.flight_enabled,
            "recorded_ticks": rep.flight_recorded_ticks,
            "dropped_ticks": rep.flight_dropped_ticks,
            "digest_every": (eng_head.flight_recorder.digest_every
                             if eng_head.flight_recorder is not None
                             else None),
            "spans_per_sec_on": rep.sustained_spans_per_sec,
            "spans_per_sec_off": rep_floff.sustained_spans_per_sec,
            "overhead_fraction": round(max(
                0.0, 1.0 - rep.sustained_spans_per_sec
                / max(rep_floff.sustained_spans_per_sec, 1e-9)), 4),
            "parity": {
                "alerts_identical": _fl_alerts_same,
                "states_identical": _fl_states_same,
                "p99_identical": rep_floff.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_floff.shed_fraction == rep.shed_fraction,
            },
        }
        # chaos-hardened recovery (ISSUE-10): the checkpoint cadence
        # priced IN-RUN on the headline (ckpt_wall / serve_wall — no
        # A/B leg, see the block comment above the chaos leg), and the
        # chaos leg's in-capture proof that scripted mid-tick crashes
        # leave NO score gap — states/alerts/p99/shed byte-identical
        # to the fault-free headline and the canonical flight journals
        # equal under `anomod audit diff` semantics
        from anomod.obs.flight import diff_journals as _diff_journals
        _rc_alerts_same, _rc_states_same = engines_identical(
            eng_head, eng_chaos)
        # the parity bit must be None (unknown), never vacuously true,
        # when no journals exist to compare (ANOMOD_FLIGHT=0 runs)
        _rc_journal_ok = None
        if eng_head.flight_recorder is not None \
                and eng_chaos.flight_recorder is not None:
            _rc_journal_ok = _diff_journals(
                eng_head.flight_recorder.journal(),
                eng_chaos.flight_recorder.journal()) is None
        out["recovery"] = {
            "supervised_headline": rep.supervised,
            "ckpt_every": rep.ckpt_every,
            "n_checkpoints": rep.n_checkpoints,
            "ckpt_wall_s": rep.ckpt_wall_s,
            # snapshot wall as a fraction of the headline serve wall —
            # the checkpoint-cadence overhead, measured in-run (the
            # snapshot is inside the tick wall, so this is exact; an
            # A/B leg would only add this box's ±35% noise on top)
            "ckpt_overhead_fraction": round(
                rep.ckpt_wall_s / max(rep.serve_wall_s, 1e-9), 4),
            "chaos_script": chaos_script,
            "n_shard_crashes": rep_chaos.n_shard_crashes,
            "n_respawns": rep_chaos.n_respawns,
            "n_restored_ticks": rep_chaos.n_restored_ticks,
            "n_quarantined": rep_chaos.n_quarantined,
            "n_migrated_tenants": rep_chaos.n_migrated_tenants,
            # mean ticks re-executed per recovery incident — how deep
            # into the checkpoint window the crashes landed (recovery
            # completes within the failing tick, so virtual-time MTTR
            # is bounded by one tick; this is the re-execution depth)
            "mttr_ticks": round(rep_chaos.n_restored_ticks
                                / max(rep_chaos.n_shard_crashes, 1), 2),
            "recovery_wall_s": rep_chaos.recovery_wall_s,
            "parity": {
                "alerts_identical": _rc_alerts_same,
                "states_identical": _rc_states_same,
                "p99_identical": rep_chaos.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_chaos.shed_fraction == rep.shed_fraction,
                "journal_canonical_identical": _rc_journal_ok,
            },
        }
        # shard scaling on the same seed (1 / 2 / 4 engine workers; the
        # 1-shard row is the dedicated warm REFERENCE leg, run last).
        # Decision parity across legs is pinned by tests; the table
        # reports the wall-clock effect alone.  p99/shed are identical
        # across legs by construction (admission is shard-count-
        # invariant) — reported per leg anyway so the capture shows it.
        ref_sps = shard_reps[1].sustained_spans_per_sec
        out["shard_scaling"] = {
            str(n): {
                "spans_per_sec": r.sustained_spans_per_sec,
                "serve_wall_s": r.serve_wall_s,
                "speedup_vs_1_shard": round(
                    r.sustained_spans_per_sec / max(ref_sps, 1e-9), 3),
                "p99_latency_s": r.latency.get("p99_latency_s"),
                "shed_fraction": r.shed_fraction,
                "pipeline": r.pipeline,
                "shard_imbalance": r.shard_imbalance,
                "compile_s": round(r.compile_s + r.lane_compile_s, 4),
            } for n, r in sorted(shard_reps.items())}
        # one (width x lane-bucket) grid wall per runner, per leg: with
        # the persistent compilation cache placed, every grid after the
        # first is cache reads
        out["jit_cache"] = {
            "dir": jit_cache_dir,
            "grid_compile_s_per_runner": [
                round((r.compile_s + r.lane_compile_s) / n, 3)
                for n, r in shard_reps.items()],
        }
        # online RCA on the same seed: top-k hit-rate against the
        # traffic script's injected-fault ground truth, alert→culprit
        # latency (RCA runs in the same wall tick its alert fires, so
        # the per-run wall IS the alert→culprit wall), and the
        # determinism pins — RCA-on must leave every detector decision
        # byte-identical to the RCA-off headline leg, and the 2-shard
        # verdict stream must equal the 1-shard one
        alerts_same, states_same = engines_identical(eng_head, eng_rca)
        n_fault = (rep_rca.fault_detection or {}).get("n_fault_tenants", 0)
        out["rca"] = {
            "enabled": True,
            "n_rca_runs": rep_rca.n_rca_runs,
            "topk_hits": {str(k): v for k, v
                          in sorted(rep_rca.rca_topk_hits.items())},
            "topk_hit_rate": {
                str(k): (round(v / n_fault, 4) if n_fault else None)
                for k, v in sorted(rep_rca.rca_topk_hits.items())},
            # conditional on the detector having fired for the fault
            # tenant at all — separates RCA ranking quality from the
            # detection recall ceiling it inherits (a fault tenant whose
            # spans mostly shed may never alert; that miss belongs to
            # the detection/shedding story, not to culprit ranking)
            "topk_hit_rate_given_detected": {
                str(k): (round(v / rep_rca.rca_eligible, 4)
                         if rep_rca.rca_eligible else None)
                for k, v in sorted(rep_rca.rca_topk_hits.items())},
            "eligible_fault_tenants": rep_rca.rca_eligible,
            "n_fault_tenants": n_fault,
            "alert_to_culprit_latency_s": rep_rca.rca_latency,
            "queue_delay_virtual_s": rep_rca.rca_alert_to_culprit_s,
            "rca_wall_s": rep_rca.rca_wall_s,
            "spans_per_sec_rca_on": rep_rca.sustained_spans_per_sec,
            "rca_overhead_fraction": round(max(
                0.0, 1.0 - rep_rca.sustained_spans_per_sec
                / max(rep.sustained_spans_per_sec, 1e-9)), 4),
            "parity": {
                "alerts_identical_to_rca_off": alerts_same,
                "states_identical_to_rca_off": states_same,
                "p99_identical_to_rca_off":
                    rep_rca.latency.get("p99_latency_s")
                    == rep.latency.get("p99_latency_s"),
                "shed_identical_to_rca_off":
                    rep_rca.shed_fraction == rep.shed_fraction,
                "verdicts_identical_1_vs_2_shards":
                    [v.to_dict() for v in eng_rca.rca_verdicts]
                    == [v.to_dict() for v in eng_rca2.rca_verdicts],
            },
        }
        # the performance observatory (ISSUE-14): the dispatch-lifecycle
        # timeline's overlap-bubble analysis on the same seed — the
        # overlap-headroom bound is the go/no-go instrument for ROADMAP
        # attack (1) (overlap the fold wait behind next-round staging),
        # the overhead fraction prices the recorder (≤5% bar), the
        # parity bits pin the read-side contract, and the raw_wall_s
        # per-tick samples are what `anomod perf diff` bootstraps over
        # instead of hedging wall ratios in prose
        from anomod.config import get_config as _get_config
        _pf_alerts_same, _pf_states_same = engines_identical(
            eng_head, eng_perf)
        out["perf"] = {
            "enabled_headline": rep.perf_enabled,
            "events_recorded": rep_perf.perf_events_recorded,
            "events_dropped": eng_perf.perf_events_dropped,
            "overlap_headroom_s": rep_perf.overlap_headroom_s,
            "fold_wait_s": rep_perf.fold_wait_s,
            "fold_wall_s": rep_perf.fold_wall_s,
            "bubble_fractions": rep_perf.bubble_fractions,
            # the headline leg's per-tick serve walls: the matched-leg
            # sample list noise-aware capture diffing pairs by path
            "raw_wall_s": [round(t, 6) for t in eng_head.tick_walls],
            "perf_leg": {"raw_wall_s": [round(t, 6)
                                        for t in eng_perf.tick_walls]},
            "noise_floor": _get_config().perf_noise_floor,
            "spans_per_sec_on": rep_perf.sustained_spans_per_sec,
            "spans_per_sec_off": rep.sustained_spans_per_sec,
            "overhead_fraction": round(max(
                0.0, 1.0 - rep_perf.sustained_spans_per_sec
                / max(rep.sustained_spans_per_sec, 1e-9)), 4),
            "parity": {
                "alerts_identical": _pf_alerts_same,
                "states_identical": _pf_states_same,
                "p99_identical": rep_perf.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_perf.shed_fraction == rep.shed_fraction,
            },
        }
        # the deferred-commit serve tick (ISSUE-16): the async leg vs
        # its matched synchronous perf leg — the committed fold WAIT
        # collapsing out of the serve wall (the `commit_defer` perf leg
        # carries where it went), with states/alerts/p99/shed and the
        # canonical flight journal pinned byte-identical.  The per-tick
        # raw_wall_s sample list is what `anomod perf diff` bootstraps
        # over to judge the overlap noise-aware.
        _as_alerts_same, _as_states_same = engines_identical(
            eng_perf, eng_async)
        _as_journal_ok = None
        if eng_perf.flight_recorder is not None \
                and eng_async.flight_recorder is not None:
            _as_journal_ok = _diff_journals(
                eng_perf.flight_recorder.journal(),
                eng_async.flight_recorder.journal()) is None
        out["async_commit"] = {
            "enabled_headline": rep.async_commit,
            "async_ticks": rep_async.async_ticks,
            "commit_defer_wall_s": rep_async.commit_defer_wall_s,
            "fold_wait_s_sync": rep_perf.fold_wait_s,
            "fold_wait_s_async": rep_async.fold_wait_s,
            "fold_wait_hidden_fraction": round(max(
                0.0, 1.0 - rep_async.fold_wait_s
                / max(rep_perf.fold_wait_s, 1e-9)), 4),
            "serve_wall_s_sync": rep_perf.serve_wall_s,
            "serve_wall_s_async": rep_async.serve_wall_s,
            "spans_per_sec_sync": rep_perf.sustained_spans_per_sec,
            "spans_per_sec_async": rep_async.sustained_spans_per_sec,
            "speedup": round(rep_async.sustained_spans_per_sec
                             / max(rep_perf.sustained_spans_per_sec,
                                   1e-9), 2),
            "async_leg": {"raw_wall_s": [round(t, 6) for t
                                         in eng_async.tick_walls]},
            "parity": {
                "alerts_identical": _as_alerts_same,
                "states_identical": _as_states_same,
                "p99_identical": rep_async.latency.get("p99_latency_s")
                == rep_perf.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_async.shed_fraction == rep_perf.shed_fraction,
                "journal_canonical_identical": _as_journal_ok,
            },
        }
        # process-shard serving (ISSUE-20): the GIL-free worker engine
        # vs its matched 2-shard thread leg, the sparse barrier fold's
        # payload bytes vs the dense walk, and the determinism parity
        # bits — alerts compared tenant-by-tenant over the coordinator
        # mirrors, states pinned through the canonical flight journal's
        # state digests (a process engine's replay planes live in its
        # children; the journal digest IS the whole-fleet state bit).
        # Throughput scaling is quoted ONLY on a >= 4-core box: on two
        # cores the coordinator and two workers contend for the same
        # silicon and a speedup number would be noise, not signal —
        # `scaling_quotable` records which side this capture is on.
        _n_cores = os.cpu_count() or 1

        def _alerts_identical(eng_a, eng_b):
            tids = sorted(set(eng_a._tenant_det)
                          | set(eng_b._tenant_det))
            return all(eng_a.alerts_for(t) == eng_b.alerts_for(t)
                       for t in tids)

        def _pw_journal_bit(eng_a, eng_b):
            if eng_a.flight_recorder is None \
                    or eng_b.flight_recorder is None:
                return None
            return _diff_journals(
                eng_a.flight_recorder.journal(),
                eng_b.flight_recorder.journal()) is None

        if not on_cpu:
            out["proc_shard"] = {"not_run": (
                f"a {out['platform']} chip belongs to one process: each "
                "worker child imports jax and compiles, and cannot get "
                "the device this process holds (ServeEngine refuses "
                "worker='process' on a non-CPU backend)")}
        else:
            out["proc_shard"] = {
                "worker_headline": rep.worker,
                "fold_headline": rep.fold,
                "n_cores": _n_cores,
                "scaling_quotable": _n_cores >= 4,
                "spans_per_sec_thread_2shard":
                    rep_pwt.sustained_spans_per_sec,
                "spans_per_sec_process_2shard":
                    rep_pwp.sustained_spans_per_sec,
                "spans_per_sec_process_1shard":
                    rep_pw1.sustained_spans_per_sec,
                "speedup_process_vs_thread": (round(
                    rep_pwp.sustained_spans_per_sec
                    / max(rep_pwt.sustained_spans_per_sec, 1e-9), 2)
                    if _n_cores >= 4 else None),
                "wall_s_thread": _decomp(rep_pwt),
                "wall_s_process": _decomp(rep_pwp),
                "fold_payload_bytes_sparse": rep_pwp.fold_payload_bytes,
                "fold_payload_bytes_dense": rep_pwd.fold_payload_bytes,
                "fold_payload_ratio": round(
                    rep_pwp.fold_payload_bytes
                    / max(rep_pwd.fold_payload_bytes, 1), 4),
                "thread_leg": {"raw_wall_s": [round(t, 6) for t
                                              in eng_pwt.tick_walls]},
                "process_leg": {"raw_wall_s": [round(t, 6) for t
                                               in eng_pwp.tick_walls]},
                "parity": {
                    "alerts_identical_thread_vs_process":
                        _alerts_identical(eng_pwt, eng_pwp),
                    "alerts_identical_2_vs_1_process":
                        _alerts_identical(eng_pwp, eng_pw1),
                    "p99_identical": rep_pwp.latency.get("p99_latency_s")
                    == rep_pwt.latency.get("p99_latency_s"),
                    "shed_identical":
                        rep_pwp.shed_fraction == rep_pwt.shed_fraction,
                    "served_identical":
                        rep_pwp.served_spans == rep_pwt.served_spans,
                    "journal_canonical_identical_thread_vs_process":
                        _pw_journal_bit(eng_pwt, eng_pwp),
                    "journal_canonical_identical_2_vs_1_process":
                        _pw_journal_bit(eng_pwp, eng_pw1),
                    "journal_canonical_identical_sparse_vs_dense":
                        _pw_journal_bit(eng_pwp, eng_pwd),
                },
            }
        # elastic serving (ISSUE-13): the policy leg's scaling episodes
        # under the scripted surge, the migration volume, the shard
        # imbalance the run ended on, and the determinism parity bits —
        # states/alerts/p99/shed byte-identical to the static leg of
        # the same seed+surge, canonical flight journals equal under
        # `anomod audit diff` semantics
        _el_alerts_same, _el_states_same = engines_identical(
            eng_els, eng_el)
        _el_journal_ok = None
        if eng_els.flight_recorder is not None \
                and eng_el.flight_recorder is not None:
            _el_journal_ok = _diff_journals(
                eng_els.flight_recorder.journal(),
                eng_el.flight_recorder.journal()) is None
        _el_events = [ev for t in (eng_el.flight_recorder.records()
                                   if eng_el.flight_recorder is not None
                                   else [])
                      for ev in t.get("scaling", ())]
        out["elasticity"] = {
            "policy": rep_el.policy,
            "chaos_script": surge_script,
            "min_shards": 1, "max_shards": 2, "cooldown_ticks": 5,
            "n_scale_ups": rep_el.n_scale_ups,
            "n_scale_downs": rep_el.n_scale_downs,
            "n_rebalances": rep_el.n_rebalances,
            "n_policy_migrations": rep_el.n_policy_migrations,
            "migrated_spans": eng_el.policy_migrated_spans,
            "brownout_ticks": rep_el.brownout_ticks,
            "peak_shards": rep_el.peak_shards,
            "final_shards": rep_el.shards,
            "policy_wall_s": rep_el.policy_wall_s,
            "shard_imbalance_static": rep_els.shard_imbalance,
            "shard_imbalance_elastic": rep_el.shard_imbalance,
            "episodes": [{"kind": ev.get("kind"),
                          "tick": ev.get("tick"),
                          "tenants": ev.get("tenants", 0)}
                         for ev in _el_events],
            "spans_per_sec_static": rep_els.sustained_spans_per_sec,
            "spans_per_sec_elastic": rep_el.sustained_spans_per_sec,
            "parity": {
                "alerts_identical": _el_alerts_same,
                "states_identical": _el_states_same,
                "p99_identical": rep_el.latency.get("p99_latency_s")
                == rep_els.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_el.shed_fraction == rep_els.shed_fraction,
                "journal_canonical_identical": _el_journal_ok,
            },
        }
        # fleet census (ISSUE-15): the deterministic resident-bytes and
        # hot-set/Zipf census on the same seed, the registered-fleet
        # sweep's fitted O(registered) wall and bytes slopes (the
        # tiering baseline), one INFORMATIONAL /proc RSS sample beside
        # the deterministic total (cross-check only — never a pin,
        # never compared), and the read-side parity bits
        from anomod.obs.census import process_resident_bytes
        _cn_alerts_same, _cn_states_same = engines_identical(
            eng_head, eng_cen)
        _cn_journal_ok = None
        if eng_head.flight_recorder is not None \
                and eng_cen.flight_recorder is not None:
            _cn_journal_ok = _diff_journals(
                eng_head.flight_recorder.journal(),
                eng_cen.flight_recorder.journal()) is None
        out["census"] = {
            "enabled_headline": rep.census_enabled,
            "census_ticks": rep_cen.census_ticks,
            "census_every": eng_cen.census_every,
            "resident_bytes": rep_cen.census_resident_bytes,
            "hot_set": rep_cen.census_hot_set,
            # ONE informational RSS sample: the order-of-magnitude
            # cross-check on the deterministic total above — never a
            # pin (allocator/runtime memory moves run to run)
            "process_resident_memory_bytes": process_resident_bytes(),
            "sweep": census_sweep,
            # census overhead measured IN-RUN (census_wall / serve_wall
            # — the ckpt_wall idiom: the drain is timed inside the
            # tick, so the fraction is exact and immune to this box's
            # ±35% A/B leg noise; acceptance bar: <= 5%).  The A/B
            # spans/sec pair below is recorded informationally.
            "census_wall_s": rep_cen.census_wall_s,
            "census_overhead_in_run": round(
                rep_cen.census_wall_s
                / max(rep_cen.serve_wall_s, 1e-9), 4),
            "spans_per_sec_on": rep_cen.sustained_spans_per_sec,
            "spans_per_sec_off": rep.sustained_spans_per_sec,
            "overhead_fraction": round(max(
                0.0, 1.0 - rep_cen.sustained_spans_per_sec
                / max(rep.sustained_spans_per_sec, 1e-9)), 4),
            "parity": {
                "alerts_identical": _cn_alerts_same,
                "states_identical": _cn_states_same,
                "p99_identical": rep_cen.latency.get("p99_latency_s")
                == rep.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_cen.shed_fraction == rep.shed_fraction,
                "journal_canonical_identical": _cn_journal_ok,
            },
        }
        # state tiering (ISSUE-19): the tiered registered-fleet sweep
        # (device hot pool → host warm tier → content-addressed disk
        # cold tier) beside the untiered census baseline above, the
        # demote/spill/promote/miss counters and prefetch-hidden
        # fraction from the sub-capacity parity pair, and the parity
        # bits — the capture's own proof that tiering moved only
        # resident bytes and wall-clock, never a scored byte.  The
        # journal bit compares the tiered run against its SAME-config
        # rerun (deferred cold folds move tick placement vs the
        # never-evicted twin, deterministically — that determinism IS
        # the audit-replay pin).
        _tr_alerts_same, _tr_states_same = engines_identical(
            eng_toff, eng_ton)
        _tr_journal_ok = None
        if eng_ton.flight_recorder is not None \
                and eng_ton2.flight_recorder is not None:
            _tr_journal_ok = _diff_journals(
                eng_ton.flight_recorder.journal(),
                eng_ton2.flight_recorder.journal()) is None
        out["tiering"] = {
            "tier_hot": rep_ton.tier_hot,
            "sweep": tiered_sweep,
            # the committed-baseline collapse, restated locally: the
            # tiered sweep's deterministic bytes slope vs THIS
            # capture's untiered sweep (the cross-capture judgement —
            # 384 B/registered on the PR-15 curve — is `anomod census
            # diff OLD NEW`'s job)
            "bytes_slope_per_registered":
                tiered_sweep["bytes_slope_per_registered"],
            "wall_slope_s_per_registered":
                tiered_sweep["wall_slope_s_per_registered"],
            "baseline_bytes_slope_per_registered":
                census_sweep["bytes_slope_per_registered"],
            "counters": {
                "demotions_warm": rep_ton.n_tier_demotions_warm,
                "demotions_cold": rep_ton.n_tier_demotions_cold,
                "promotions": rep_ton.n_tier_promotions,
                "tier_misses": rep_ton.n_tier_misses,
            },
            "prefetch_hidden": rep_ton.tier_prefetch_hidden,
            "prefetch_joins": _tier_joins,
            "prefetch_hidden_fraction": round(
                rep_ton.tier_prefetch_hidden / max(_tier_joins, 1), 4),
            "tier_wall_s": rep_ton.tier_wall_s,
            "tier_empty_at_end": _tier_left == 0,
            "parity": {
                "alerts_identical": _tr_alerts_same,
                "states_identical": _tr_states_same,
                "p99_identical": rep_ton.latency.get("p99_latency_s")
                == rep_toff.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_ton.shed_fraction == rep_toff.shed_fraction,
                "served_identical":
                    rep_ton.served_spans == rep_toff.served_spans,
                "journal_rerun_identical": _tr_journal_ok,
            },
        }
        # live-feed loop (ISSUE-18): closed-loop self-scrape throughput,
        # the feed-lag histogram, and the live-vs-replay parity bits —
        # all five true is the --from-live reproducibility pin the
        # committed capture carries
        _lf_alerts_same, _lf_states_same = engines_identical(
            eng_lf, eng_lfr)
        _lf_journal_ok = None
        if eng_lf.flight_recorder is not None \
                and eng_lfr.flight_recorder is not None:
            _lf_journal_ok = _diff_journals(
                eng_lf.flight_recorder.journal(),
                eng_lfr.flight_recorder.journal()) is None
        _lf_lag = next((m for m in _feed_reg.metrics()
                        if m.name == "anomod_feed_lag_s"), None)
        out["live_feed"] = {
            "spans_per_s": rep_lf.sustained_spans_per_sec,
            "served_spans": rep_lf.served_spans,
            "n_polls": feed_lf.n_polls,
            "n_samples": feed_lf.n_samples,
            "gaps": feed_lf.n_gaps,
            "feed_lag": {
                "p50": None if _lf_lag is None else _lf_lag.quantile(0.5),
                "p99": None if _lf_lag is None else _lf_lag.quantile(0.99),
            },
            "journal_entries": _feed_journal_entries,
            "parity": {
                "alerts_identical": _lf_alerts_same,
                "states_identical": _lf_states_same,
                "p99_identical": rep_lfr.latency.get("p99_latency_s")
                == rep_lf.latency.get("p99_latency_s"),
                "shed_identical":
                    rep_lfr.shed_fraction == rep_lf.shed_fraction,
                "journal_canonical_identical": _lf_journal_ok,
            },
        }
        # enabled-vs-off telemetry overhead on the same seed (acceptance
        # bar: <= 5% sustained spans/sec); both rates are steady-state
        # serving walls with compile excluded by warm()
        off_sps = rep_off.sustained_spans_per_sec
        on_sps = rep.sustained_spans_per_sec
        out["telemetry"] = {
            "spans_per_sec_off": off_sps,
            "spans_per_sec_on": on_sps,
            "overhead_fraction": round(max(0.0, 1.0 - on_sps
                                           / max(off_sps, 1e-9)), 4),
            "journal_samples": reg.n_samples,
        }
        out["obs_snapshot"] = reg.snapshot()
        path = _write_capture(out)
        if path:
            # the self-scrape capture: the enabled leg's telemetry
            # timeline in the framework's own TT-CSV shape, scored
            # through its own detector stack
            from anomod.obs.export import export_tt_csv
            from anomod.obs.selfscrape import score_self_scrape
            csv_path = path[:-len(".json")] + "_selfscrape.csv"
            n_csv = export_tt_csv(reg, csv_path)
            score = score_self_scrape(csv_path, window_s=5.0,
                                      baseline_windows=4)
            out["self_scrape"] = {
                "capture_file": out["capture_file"][:-len(".json")]
                + "_selfscrape.csv",
                "samples": n_csv,
                "n_alerts": score["n_alerts"],
                "alerted_subsystems": score["alerted_subsystems"],
            }
        print(json.dumps(out))
        return 0
    except Exception as e:
        return _fail(out, e)


def main() -> int:
    argv = list(sys.argv[1:])
    mode = _bench_mode(argv)
    if "--mode" in argv:
        i = argv.index("--mode")
        del argv[i:i + 2]
    if mode == "serve":
        # serve mode is env-knob driven; stray argv must error, not
        # silently record a capture at the default configuration
        if argv:
            raise SystemExit(f"bench.py --mode serve takes no positional "
                             f"arguments (use ANOMOD_SERVE_BENCH_* env "
                             f"knobs), got {argv!r}")
        return serve_main()
    # replay mode keeps the historical positional contract: one optional
    # n_traces integer; anything else must error, not silently fall back
    # to the 2000-trace default (the capture would record a throughput
    # number for the wrong corpus size)
    n_traces = 2_000
    if argv:
        if len(argv) > 1 or not argv[0].isdigit():
            raise SystemExit(f"bench.py: expected a single positive "
                             f"n_traces integer, got {argv!r}")
        n_traces = int(argv[0])
    out = {
        "metric": "tt_replay_throughput",
        "value": 0.0,
        "unit": "spans/sec/chip",
        "vs_baseline": 0.0,
    }
    baseline = 1_000_000.0
    out.update(_device_fields())
    on_tpu = out["platform"] == "tpu"
    if not on_tpu:
        out["unit"] = "spans/sec/cpu-host"   # never a chip unit off-chip

    # Engine per backend (the BASELINE.json backend switch): the
    # sorted-window pallas kernel on TPU; the explicit-CPU mode runs the
    # numpy scatter-add engine — the right shape for a host core (one-hot
    # matmuls are wasted work there).  Mosaic only executes on a TPU, so
    # asking for a Pallas kernel anywhere else is an error: the interpret
    # path would never finish at bench sizes, and a silent downgrade
    # would file another engine's number under the requested name.
    kernel = os.environ.get("ANOMOD_BENCH_KERNEL", "").strip().lower() \
        or ("pallas-sorted" if on_tpu else "numpy")
    if kernel in ("pallas", "pallas-sorted") and not on_tpu:
        raise SystemExit(f"bench.py: ANOMOD_BENCH_KERNEL={kernel} needs a "
                         f"TPU backend (Mosaic); JAX found "
                         f"{out['platform']}")
    # Device-side replication loops the staged corpus inside ONE dispatch
    # so the wall measures steady-state kernel rate, not the fixed
    # per-dispatch overhead: 4096 for the sorted kernel (~1.3 s/dispatch
    # in the 2026-07-31 builder capture), 64 for the slower device
    # kernels; the host engine sizes for one core.
    # ANOMOD_BENCH_REPLICATE overrides the per-kernel default.
    replicate = {"pallas-sorted": 4096, "numpy": 2}.get(
        kernel, 64 if on_tpu else 2)
    rep_env = os.environ.get("ANOMOD_BENCH_REPLICATE", "").strip()
    if rep_env:
        if not (rep_env.isdigit() and int(rep_env) > 0):
            raise SystemExit(f"bench.py: ANOMOD_BENCH_REPLICATE must be a "
                             f"positive integer, got {rep_env!r}")
        replicate = int(rep_env)

    try:
        import jax

        from anomod.io import cache as ingest_cache
        from anomod.io.dataset import bench_cache_status, load_bench_corpus
        from anomod.replay import ReplayConfig, measure_throughput
        from anomod.utils.platform import enable_compile_cache
        out["jit_cache_dir"] = enable_compile_cache()

        # Corpus prep through the content-addressed ingest cache: repeat
        # captures measure the kernel, not host synth.  ``parse_s`` keeps
        # the honest cold generate+concat wall (recorded at first publish),
        # ``prep_s`` is what THIS run actually paid.
        t0 = time.perf_counter()
        batch, ingest = load_bench_corpus("TT", n_traces)
        prep_s = time.perf_counter() - t0
        # The ingest throughput metric needs both regimes: the recorded
        # cold wall and a measured warm read.  The presence probe guards
        # the second load: if the first run's publish failed (read-only
        # cache dir, ENOSPC) a "warm" load would silently re-synthesize
        # the whole corpus a second time for a metric that then gets
        # discarded anyway.
        ingest_tp = None
        if ingest_cache.cache_root() is not None \
                and bench_cache_status("TT", n_traces)[0] == 1:
            _, warm = load_bench_corpus("TT", n_traces)
            if warm["cache_hit"] and warm["load_s"] > 0 \
                    and ingest["parse_s"] > 0:
                n_exp = ingest["n_experiments"]
                ingest_tp = {
                    "unit": "experiments/sec",
                    "cold": round(n_exp / ingest["parse_s"], 2),
                    "warm": round(n_exp / warm["load_s"], 2),
                    "speedup": round(ingest["parse_s"] / warm["load_s"], 2),
                }

        repeats = 3
        cfg = ReplayConfig(n_services=batch.n_services)
        # f32 exactness clamp: device kernels accumulate per-segment counts
        # in f32 across the replicate loop, losing integer exactness past
        # 2^24 per (service, window) segment — a replicate that pushes the
        # hottest segment over that trips measure_throughput's count assert
        # and fails the capture.  Clamp from the ACTUAL staged corpus
        # (applies to the env override too; the numpy engine sums per-pass
        # in f64, so it is exempt).
        if kernel != "numpy" and replicate > 1:
            import numpy as _np

            from anomod.replay import segment_ids
            hottest = int(_np.bincount(segment_ids(batch, cfg),
                                       minlength=cfg.sw).max())
            cap = max(1, (1 << 24) // max(1, hottest))
            if replicate > cap:
                out["replicate_note"] = (
                    f"replicate clamped {replicate}->{cap}: hottest "
                    f"segment holds {hottest} spans and f32 counts are "
                    f"exact only to 2^24")
                replicate = cap
        # ANOMOD_PROFILE_DIR=<dir> wraps the measured dispatches in a
        # jax.profiler device trace (TensorBoard/Perfetto) for kernel-level
        # inspection of the replay hot loop on real hardware
        from anomod.utils.tracing import profile_to
        with profile_to(os.environ.get("ANOMOD_PROFILE_DIR")):
            result = measure_throughput(batch, cfg, repeats=repeats,
                                        replicate=replicate, kernel=kernel)

        out.update({
            "value": round(result.spans_per_sec, 1),
            "vs_baseline": round(result.spans_per_sec / baseline, 3),
            "n_spans": result.n_spans,
            "wall_s": round(result.wall_s, 4),
            "raw_wall_s": [round(t, 4) for t in result.raw_wall_s],
            "compile_s": round(result.compile_s, 2),
            "prep_s": round(prep_s, 4),
            "parse_s": round(ingest["parse_s"], 4),
            "cache_hit": bool(ingest["cache_hit"]),
            "kernel": result.kernel,
            "replicate_used": replicate,
            "device": str(jax.devices()[0]),
        })
        if ingest_tp is not None:
            out["tt_ingest_throughput"] = ingest_tp
        # the run's own telemetry (anomod.obs): cache traffic + replay
        # compile/dispatch book, inline so every capture line carries its
        # metrics snapshot (the serve mode additionally exports the full
        # self-scrape time series)
        from anomod.obs import get_registry
        out["obs_snapshot"] = get_registry().snapshot()
        _write_capture(out)
        print(json.dumps(out))
        return 0
    except Exception as e:
        return _fail(out, e)


if __name__ == "__main__":
    sys.exit(main())
