"""Multi-tenant serving plane: replay parity, weighted-fair admission,
priority-ordered shedding, SLO accounting, env contract.

The two acceptance-critical pins:

- PARITY: the batched+bucketed serving plane emits the EXACT (bit-
  identical, CPU, seeded) alert stream of a per-tenant sequential
  StreamReplay/OnlineDetector on the same spans — padding rows target
  the dead lane, real rows keep their sequential positions, so the f32
  state and every alert float match to the bit.
- OVERLOAD: under a seeded 2x overload, shedding is priority-ordered
  (gold < silver < bronze shed fractions) and the whole report is
  deterministic (wall-clock fields aside).
"""

import dataclasses

import numpy as np
import pytest

from anomod import labels, synth
from anomod.replay import ReplayConfig
from anomod.schemas import take_spans
from anomod.serve import (AdmissionController, BucketedStreamReplay,
                          BucketRunner, PowerLawTraffic, ScriptedTraffic,
                          ServeEngine, TenantSpec, split_plan)
from anomod.serve.engine import run_power_law
from anomod.serve.batcher import validate_buckets
from anomod.serve.traffic import TenantFault
from anomod.stream import OnlineDetector, StreamReplay


# ---------------------------------------------------------------------------
# batcher: split plan + bucket contract + state parity
# ---------------------------------------------------------------------------

def test_split_plan_full_chunks_then_bucketed_tail():
    assert split_plan(0, 4096, (256, 1024)) == []
    assert split_plan(100, 4096, (256, 1024)) == [(0, 100, 256)]
    assert split_plan(256, 4096, (256, 1024)) == [(0, 256, 256)]
    assert split_plan(300, 4096, (256, 1024)) == [(0, 300, 1024)]
    # tail wider than every bucket pads to the full chunk width
    assert split_plan(2000, 4096, (256, 1024)) == [(0, 2000, 4096)]
    assert split_plan(5000, 4096, (256, 1024)) == [
        (0, 4096, 4096), (4096, 5000, 1024)]
    # buckets wider than chunk_size never stage (parity would break)
    assert split_plan(100, 512, (256, 1024)) == [(0, 100, 256)]
    assert split_plan(400, 512, (256, 1024)) == [(0, 400, 512)]


def test_validate_buckets_contract():
    assert validate_buckets((256, 1024)) == (256, 1024)
    assert validate_buckets(["8", "16"]) == (8, 16)
    with pytest.raises(ValueError):
        validate_buckets(())
    with pytest.raises(ValueError):
        validate_buckets((1024, 256))          # not ascending
    with pytest.raises(ValueError):
        validate_buckets((256, 256))           # not strictly ascending
    with pytest.raises(ValueError):
        validate_buckets((0, 256))
    with pytest.raises(ValueError):
        validate_buckets(("x",))


def test_bucketed_replay_state_bit_identical_to_stream_replay():
    """Same pushes through the bucketed runner and the sequential
    StreamReplay give bit-identical f32 state (the parity mechanism)."""
    batch = synth.generate_spans(labels.label_for("Lv_P_CPU_preserve"),
                                 n_traces=60)
    cfg = ReplayConfig(n_services=batch.n_services, chunk_size=2048)
    order = np.argsort(batch.start_us, kind="stable")
    batch = take_spans(batch, order)
    t0 = int(batch.start_us.min())

    seq = StreamReplay(cfg, t0)
    bucketed = BucketedStreamReplay(cfg, t0, BucketRunner(cfg, (256, 1024)))
    cuts = [0, 137, 700, 2500, batch.n_spans]
    for lo, hi in zip(cuts, cuts[1:]):
        mb = take_spans(batch, slice(lo, hi))
        assert seq.push(mb) == bucketed.push(mb)   # same window binning
    assert seq.window_offset == bucketed.window_offset
    np.testing.assert_array_equal(np.asarray(seq.state.agg),
                                  np.asarray(bucketed.state.agg))
    np.testing.assert_array_equal(np.asarray(seq.state.hist),
                                  np.asarray(bucketed.state.hist))


# ---------------------------------------------------------------------------
# admission: WFQ order, backpressure, priority eviction
# ---------------------------------------------------------------------------

def _spans(n):
    from anomod.schemas import SpanBatch
    return SpanBatch(
        trace=np.zeros(n, np.int32), parent=np.full(n, -1, np.int32),
        service=np.zeros(n, np.int32), endpoint=np.zeros(n, np.int32),
        start_us=np.arange(n, dtype=np.int64),
        duration_us=np.ones(n, np.int64),
        is_error=np.zeros(n, np.bool_), status=np.full(n, 200, np.int16),
        kind=np.zeros(n, np.int8), services=("s",), endpoints=("e",),
        trace_ids=("t",)).validate()


def test_wfq_serves_by_weight_and_keeps_tenant_fifo():
    specs = [TenantSpec(0, "gold", priority=0),     # weight 4
             TenantSpec(1, "bronze", priority=2)]   # weight 1
    adm = AdmissionController(specs, max_backlog=10_000,
                              max_tenant_backlog=10_000)
    for i in range(4):
        assert adm.offer(0, _spans(100), now_s=0.0)
        assert adm.offer(1, _spans(100), now_s=0.0)
    served = adm.drain(500)
    got = [(qb.tenant_id, qb.seq) for qb in served]
    # weight 4 vs 1: gold finishes tags at 25/wf spacing vs 100 -> gold's
    # first four batches drain before bronze's second
    assert [t for t, _ in got[:4]].count(0) >= 3
    # per-tenant FIFO: seqs strictly increase within each tenant
    for tid in (0, 1):
        seqs = [s for t, s in got if t == tid]
        assert seqs == sorted(seqs)


def test_per_tenant_backlog_bounds_runaway_feed():
    specs = [TenantSpec(0, "noisy", priority=0),
             TenantSpec(1, "quiet", priority=2)]
    adm = AdmissionController(specs, max_backlog=10_000,
                              max_tenant_backlog=250)
    assert adm.offer(0, _spans(200), now_s=0.0)
    assert not adm.offer(0, _spans(200), now_s=0.0)   # own overflow shed
    assert adm.offer(1, _spans(200), now_s=0.0)       # nobody else pays
    assert adm.counters[0].shed_spans == 200
    assert adm.counters[1].shed_spans == 0


def test_global_overflow_evicts_strictly_lower_priority_only():
    specs = [TenantSpec(0, "gold", priority=0),
             TenantSpec(1, "bronze", priority=2)]
    adm = AdmissionController(specs, max_backlog=500,
                              max_tenant_backlog=500)
    assert adm.offer(1, _spans(400), now_s=0.0)
    # gold arrival displaces queued bronze work
    assert adm.offer(0, _spans(400), now_s=1.0)
    assert adm.counters[1].shed_spans == 400
    assert adm.backlog_spans == 400
    # bronze arrival cannot displace queued gold work: it is shed itself
    assert not adm.offer(1, _spans(400), now_s=2.0)
    assert adm.counters[0].shed_spans == 0


def test_oversized_batch_admits_against_empty_queue():
    """A batch wider than a backlog bound must still admit when nothing
    is queued (the admission mirror of drain()'s one-batch overdraw) —
    otherwise it would be starved forever at any load (review finding)."""
    specs = [TenantSpec(0, "t", priority=1)]
    adm = AdmissionController(specs, max_backlog=100,
                              max_tenant_backlog=100)
    assert adm.offer(0, _spans(500), now_s=0.0)       # idle: overdraw
    assert not adm.offer(0, _spans(10), now_s=0.0)    # now bounded
    assert adm.drain(1_000_000)
    assert adm.offer(0, _spans(500), now_s=1.0)       # drained: again ok
    # a gold mega-batch may still displace an all-bronze backlog wholesale
    specs = [TenantSpec(0, "gold", priority=0),
             TenantSpec(1, "bronze", priority=2)]
    adm = AdmissionController(specs, max_backlog=100,
                              max_tenant_backlog=100)
    assert adm.offer(1, _spans(80), now_s=0.0)
    assert adm.offer(0, _spans(500), now_s=1.0)
    assert adm.counters[1].shed_spans == 80


def test_eviction_is_transactional_when_infeasible():
    """An arrival that cannot fit even after evicting ALL lower-priority
    work must be shed alone — evicting victims it still can't use would
    lose both (review finding)."""
    specs = [TenantSpec(0, "gold", priority=0),
             TenantSpec(1, "bronze", priority=2)]
    adm = AdmissionController(specs, max_backlog=500,
                              max_tenant_backlog=500)
    assert adm.offer(1, _spans(400), now_s=0.0)
    assert adm.offer(0, _spans(100), now_s=0.0)       # backlog full: 500
    # gold 450 needs 450 headroom; only 400 bronze is evictable -> the
    # arrival sheds and the queued work survives untouched
    assert not adm.offer(0, _spans(450), now_s=1.0)
    assert adm.backlog_spans == 500
    assert adm.counters[1].shed_spans == 0
    assert adm.counters[0].shed_spans == 450


def test_evict_heap_stays_bounded_on_long_healthy_run():
    """Drained batches must not accumulate forever in the eviction heap
    on a never-overloaded controller (review finding)."""
    specs = [TenantSpec(0, "t", priority=1)]
    adm = AdmissionController(specs, max_backlog=10_000,
                              max_tenant_backlog=10_000)
    for _ in range(2000):
        adm.offer(0, _spans(10), now_s=0.0)
        adm.drain(1_000_000)
    assert adm.backlog_spans == 0
    assert len(adm._evict_heap) < 200


class _WalkCounted(dict):
    """A dict that counts the walks over all of its values."""

    walks = 0

    def values(self):
        self.walks += 1
        return super().values()


def test_depth_gauges_are_set_at_the_scrape_and_no_offer_walks_the_tenants():
    """PR 38: the deepest-queue gauge is a walk over every tenant seen;
    it was taken on every offer and drain (73% of a 34,500-tenant tick)
    and is taken where it is read now, once a tick under
    ``serve.scrape``."""
    from anomod import obs
    backlog = obs.gauge("anomod_serve_backlog_spans")
    deepest = obs.gauge("anomod_serve_max_tenant_backlog_spans")
    specs = [TenantSpec(t, f"t{t}", priority=1) for t in range(50)]
    for engine in ("off", "auto"):              # the heap and the columns
        adm = AdmissionController(specs, max_backlog=10 ** 6,
                                  max_tenant_backlog=10 ** 6,
                                  drain_engine=engine)
        adm._tenant_backlog = seen = _WalkCounted()
        for t in range(50):
            assert adm.offer(t, _spans(10 + t), now_s=0.0)
        assert adm.offer(7, _spans(100), now_s=0.0)
        assert adm.drain(200) and adm.backlog_spans > 0
        assert seen.walks == 0
        adm.observe_depths()
        assert seen.walks == 1
        assert backlog.value == adm.backlog_spans
        assert deepest.value == max(adm.tenant_backlog(t)
                                    for t in range(50)) > 0
    # a tick ends with the scrape: the gauges read the queue as it
    # stands after the tick's drain (what the drain's own set read)
    eng, _ = run_power_law(n_tenants=30, n_services=5, duration_s=8.0,
                           capacity_spans_per_s=150.0, overload=3.0,
                           seed=2)
    adm = eng.admission
    assert adm.backlog_spans > 0                # overloaded: work queued
    assert backlog.value == adm.backlog_spans
    assert deepest.value == max(adm._tenant_backlog.values())


def test_drain_overdraws_at_most_one_batch():
    specs = [TenantSpec(0, "t", priority=1)]
    adm = AdmissionController(specs, max_backlog=10_000,
                              max_tenant_backlog=10_000)
    adm.offer(0, _spans(300), now_s=0.0)
    adm.offer(0, _spans(300), now_s=0.0)
    served = adm.drain(100)          # budget smaller than one batch
    assert len(served) == 1          # overdraw by one, never deadlock
    assert adm.backlog_spans == 300


# ---------------------------------------------------------------------------
# traffic: determinism, power-law shape, batch cap
# ---------------------------------------------------------------------------

def test_powerlaw_traffic_deterministic_and_capped():
    def collect(seed):
        tr = PowerLawTraffic(n_tenants=8, total_rate_spans_per_s=2000,
                             seed=seed, n_services=4, batch_cap=128)
        out = []
        for k in range(5):
            out.append([(t, b.n_spans, b.start_us.tolist())
                        for t, b in tr.arrivals(k * 1.0, (k + 1) * 1.0)])
        return out
    a, b_, c = collect(1), collect(1), collect(2)
    assert a == b_                       # seeded determinism
    assert a != c                        # seed actually matters
    assert all(n <= 128 for tick in a for _, n, _ in tick)
    # power law: the head tenant offers more than the tail tenant
    tr = PowerLawTraffic(n_tenants=8, total_rate_spans_per_s=2000,
                         alpha=1.2, seed=0)
    assert tr.specs[0].rate_spans_per_s > 3 * tr.specs[7].rate_spans_per_s


def test_scripted_traffic_slices_by_virtual_time():
    b = synth.generate_spans(labels.label_for("Normal_case"), n_traces=30)
    t0 = int(b.start_us.min())
    tr = ScriptedTraffic({0: b}, [TenantSpec(0, "t")], t0)
    total = 0
    t, end = 0.0, tr.end_s() + 60.0
    while t < end:
        for tid, mb in tr.arrivals(t, t + 60.0):
            assert tid == 0
            assert (mb.start_us >= t0 + t * 1e6).all()
            assert (mb.start_us < t0 + (t + 60.0) * 1e6).all()
            total += mb.n_spans
        t += 60.0
    assert total == b.n_spans


# ---------------------------------------------------------------------------
# the acceptance pins
# ---------------------------------------------------------------------------

def test_serving_plane_alert_stream_bit_identical_to_sequential():
    """THE parity criterion: multi-tenant batched+bucketed serving emits
    the exact alert stream of per-tenant sequential StreamReplay/
    OnlineDetector on the same spans (CPU, seeded)."""
    streams = {
        0: synth.generate_spans(labels.label_for("Lv_P_CPU_preserve"),
                                n_traces=120),
        1: synth.generate_spans(
            labels.label_for("Lv_C_travel_detail_failure"), n_traces=120),
    }
    services = streams[0].services
    t0 = min(int(b.start_us.min()) for b in streams.values())
    cfg = ReplayConfig(n_services=len(services), chunk_size=4096)
    specs = [TenantSpec(tenant_id=i, name=f"t{i}", priority=i % 3)
             for i in streams]
    traffic = ScriptedTraffic(streams, specs, t0)
    duration = traffic.end_s() + 60.0

    eng = ServeEngine(specs, services, cfg, t0_us=t0,
                      capacity_spans_per_s=10_000_000, tick_s=60.0,
                      buckets=(256, 1024), max_backlog=10_000_000,
                      max_tenant_backlog=10_000_000, baseline_windows=8)
    rep = eng.run(traffic, duration_s=duration)
    assert rep.shed_spans == 0                      # ample capacity
    assert rep.n_alerts > 0                         # faults actually alert

    for tid in streams:
        solo = OnlineDetector(services, cfg, t0,
                              replay=StreamReplay(cfg, t0),
                              baseline_windows=8)
        t = 0.0
        while t < duration:
            for tid2, mb in traffic.arrivals(t, t + 60.0):
                if tid2 == tid:
                    solo.push(mb)
            t += 60.0
        solo.finish()
        assert [dataclasses.asdict(a) for a in eng.alerts_for(tid)] \
            == [dataclasses.asdict(a) for a in solo.alerts]


def test_multimodal_serving_parity_with_sequential_detector():
    """Log/metric/api micro-batches ride the serving plane too
    (MultimodalDetector per tenant): the alert stream stays bit-identical
    to a sequential multimodal baseline fed the same one-clock slices."""
    from anomod.stream import MultimodalDetector
    label = labels.label_for("Svc_Kill_UserTimeline")
    exp = synth.generate_experiment(label, n_traces=100, seed=0)
    services = exp.spans.services
    t0 = int(exp.spans.start_us.min())
    cfg = ReplayConfig(n_services=len(services), chunk_size=4096)
    specs = [TenantSpec(tenant_id=0, name="t0")]
    traffic = ScriptedTraffic({0: exp.spans}, specs, t0,
                              experiments={0: exp})
    duration = traffic.end_s() + 60.0

    eng = ServeEngine(specs, services, cfg, t0_us=t0,
                      capacity_spans_per_s=10_000_000, tick_s=60.0,
                      buckets=(256, 1024), max_backlog=10_000_000,
                      max_tenant_backlog=10_000_000, baseline_windows=8,
                      multimodal=True, testbed=label.testbed)
    rep = eng.run(traffic, duration_s=duration)
    assert rep.modality_events["logs"] > 0
    assert rep.modality_events["metrics"] > 0
    assert rep.modality_events["api"] > 0

    solo = MultimodalDetector(services, cfg, t0, testbed=label.testbed,
                              replay=StreamReplay(cfg, t0),
                              baseline_windows=8)
    t = 0.0
    while t < duration:
        for _, kind, mb in traffic.modality_arrivals(t, t + 60.0):
            getattr(solo, f"push_{kind}")(mb)
        for _, mb in traffic.arrivals(t, t + 60.0):
            solo.push(mb)
        t += 60.0
    solo.finish()
    assert solo.alerts                           # the kill fault alerts
    assert [dataclasses.asdict(a) for a in eng.alerts_for(0)] \
        == [dataclasses.asdict(a) for a in solo.alerts]


def _overload_report(seed, score=False):
    traffic = PowerLawTraffic(
        n_tenants=12, total_rate_spans_per_s=2000, alpha=0.0, seed=seed,
        n_services=4, batch_cap=128)
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=1024)
    eng = ServeEngine(traffic.specs, traffic.services, cfg,
                      capacity_spans_per_s=1000, tick_s=1.0,
                      buckets=(128, 512), max_backlog=1500,
                      max_tenant_backlog=1500, score=score,
                      baseline_windows=4)
    return eng.run(traffic, duration_s=40.0)


def test_overload_shedding_is_priority_ordered_and_deterministic():
    """Seeded 2x overload: shed fractions order strictly by priority
    class, and the whole report reproduces bit-for-bit (wall-clock
    fields aside)."""
    rep = _overload_report(5)
    assert rep.offered_spans > 1.8 * rep.served_spans   # real overload
    assert 0.3 < rep.shed_fraction < 0.7
    pp = rep.per_priority
    assert pp[0]["shed_fraction"] < pp[1]["shed_fraction"] \
        < pp[2]["shed_fraction"]
    # gold's weighted share exceeds its equal-rate demand -> barely shed
    assert pp[0]["shed_fraction"] < 0.1
    # backpressure: the backlog never exceeded its bound
    assert rep.peak_backlog_spans <= rep.max_backlog
    # queueing under overload is visible in the latency sketch
    assert rep.latency["p99_latency_s"] > 0

    wall = ("serve_wall_s", "sustained_spans_per_sec", "compile_s",
            "lane_compile_s", "stage_wall_s", "dispatch_wall_s",
            "fold_wall_s", "score_wall_s", "ckpt_wall_s",
            "recovery_wall_s")
    a = {k: v for k, v in _overload_report(5).to_dict().items()
         if k not in wall}
    b = {k: v for k, v in _overload_report(5).to_dict().items()
         if k not in wall}
    assert a == b


def test_engine_smoke_scores_and_detects_fault_under_load():
    """Tier-1 smoke (<5s): a small scored run serves, sheds, tracks SLOs
    and detects a scripted tenant fault."""
    traffic = PowerLawTraffic(
        n_tenants=6, total_rate_spans_per_s=1200, alpha=0.0, seed=3,
        n_services=4, batch_cap=256,
        faults={1: TenantFault("latency", service=1, onset_s=30.0,
                               factor=12.0)})
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=1024)
    eng = ServeEngine(traffic.specs, traffic.services, cfg,
                      capacity_spans_per_s=900, tick_s=1.0,
                      buckets=(256,), max_backlog=2000, baseline_windows=4)
    rep = eng.run(traffic, duration_s=60.0)
    assert rep.served_spans > 0 and rep.shed_spans > 0
    assert rep.fault_detection == {
        "n_fault_tenants": 1, "n_detected": 1,
        "median_alert_latency_windows":
            rep.fault_detection["median_alert_latency_windows"]}
    assert rep.fault_detection["median_alert_latency_windows"] is not None
    assert rep.fault_detection["median_alert_latency_windows"] <= 4
    assert rep.sustained_spans_per_sec > 0
    d = rep.to_dict()
    import json
    json.dumps(d)                                  # report is JSON-able
    assert d["dispatches_by_width"] and \
        set(d["dispatches_by_width"]) <= {"256", "1024"}


def test_mesh_serve_matches_bucketed_alert_set():
    """With ``mesh=`` every tenant's plane is the pod-sharded
    ShardedStreamReplay, reused unchanged.  psum merge reorders the f32
    moment additions, so the pin is alert (window, service) identity,
    not bit equality (same contract as the existing sharded-stream
    parity tests)."""
    from anomod.parallel import make_mesh
    traffic = PowerLawTraffic(
        n_tenants=2, total_rate_spans_per_s=600, alpha=0.0, seed=2,
        n_services=4, batch_cap=256,
        faults={0: TenantFault("latency", service=1, onset_s=30.0,
                               factor=12.0)})
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    kw = dict(capacity_spans_per_s=10_000, tick_s=1.0, buckets=(256,),
              max_backlog=100_000, max_tenant_backlog=100_000,
              baseline_windows=4)
    eng_mesh = ServeEngine(traffic.specs, traffic.services, cfg,
                           mesh=make_mesh(2), **kw)
    eng_mesh.run(traffic, duration_s=50.0)
    traffic2 = PowerLawTraffic(
        n_tenants=2, total_rate_spans_per_s=600, alpha=0.0, seed=2,
        n_services=4, batch_cap=256,
        faults={0: TenantFault("latency", service=1, onset_s=30.0,
                               factor=12.0)})
    eng_bkt = ServeEngine(traffic2.specs, traffic2.services, cfg, **kw)
    eng_bkt.run(traffic2, duration_s=50.0)
    for tid in (0, 1):
        assert [(a.window, a.service) for a in eng_mesh.alerts_for(tid)] \
            == [(a.window, a.service) for a in eng_bkt.alerts_for(tid)]
    assert eng_mesh.alerts_for(0)          # the fault actually alerted


def test_tracer_records_serving_phases():
    """The fused tick wraps its one dispatch phase in serve.score_fused;
    the unfused escape hatch keeps the historical per-batch serve.score
    span."""
    from anomod.utils.tracing import Tracer

    def phases(fuse):
        tracer = Tracer("anomod-serve")
        traffic = PowerLawTraffic(n_tenants=3, total_rate_spans_per_s=300,
                                  seed=0, n_services=4)
        cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                           chunk_size=512)
        eng = ServeEngine(traffic.specs, traffic.services, cfg,
                          capacity_spans_per_s=500, tick_s=1.0,
                          buckets=(256,), score=False, tracer=tracer,
                          fuse=fuse)
        eng.run(traffic, duration_s=10.0)
        return {s["operationName"]
                for s in tracer.to_jaeger()["data"][0]["spans"]}

    fused = phases(True)
    assert {"serve.run", "serve.admit", "serve.drain",
            "serve.score_fused"} <= fused
    assert "serve.score" not in fused
    unfused = phases(False)
    assert {"serve.run", "serve.admit", "serve.drain",
            "serve.score"} <= unfused
    assert "serve.score_fused" not in unfused


# ---------------------------------------------------------------------------
# env contract
# ---------------------------------------------------------------------------

def test_serve_env_knobs_registered_and_validated(monkeypatch):
    from anomod.config import Config
    monkeypatch.setenv("ANOMOD_SERVE_BUCKETS", "128, 512,2048")
    monkeypatch.setenv("ANOMOD_SERVE_MAX_BACKLOG", "5000")
    cfg = Config()
    assert cfg.serve_buckets == (128, 512, 2048)
    assert cfg.serve_max_backlog == 5000

    monkeypatch.setenv("ANOMOD_SERVE_BUCKETS", "512,128")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_BUCKETS"):
        Config()
    monkeypatch.setenv("ANOMOD_SERVE_BUCKETS", "banana")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_BUCKETS"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_BUCKETS")
    monkeypatch.setenv("ANOMOD_SERVE_MAX_BACKLOG", "0")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_MAX_BACKLOG"):
        Config()
    monkeypatch.setenv("ANOMOD_SERVE_MAX_BACKLOG", "many")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_MAX_BACKLOG"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_MAX_BACKLOG")
    from anomod.serve.batcher import DEFAULT_BUCKETS
    assert Config().serve_buckets == DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# tenant-fused scoring: lane-stacked dispatch + coalescing (the PR-4 pins)
# ---------------------------------------------------------------------------

def _rand_spans(n, n_services, seed, t_lo_s=0.0, t_hi_s=60.0):
    from anomod.schemas import SpanBatch
    rng = np.random.default_rng(seed)
    err = rng.random(n) < 0.05
    return SpanBatch(
        trace=rng.integers(0, 16, n).astype(np.int32),
        parent=np.full(n, -1, np.int32),
        service=rng.integers(0, n_services, n).astype(np.int32),
        endpoint=np.zeros(n, np.int32),
        start_us=np.sort(rng.integers(int(t_lo_s * 1e6), int(t_hi_s * 1e6),
                                      n)).astype(np.int64),
        duration_us=rng.integers(1, 1_000_000, n).astype(np.int64),
        is_error=err.astype(np.bool_),
        status=np.where(err, 500, 200).astype(np.int16),
        kind=np.zeros(n, np.int8),
        services=tuple(f"s{i}" for i in range(n_services)),
        endpoints=("e",),
        trace_ids=tuple(f"t{i:02d}" for i in range(16))).validate()


def test_run_lanes_bit_identical_to_single_dispatch():
    """The fused mechanism itself: lane-stacked dispatches (including a
    dead-padded group) produce per-lane states bit-identical to
    dispatching each lane's chunk alone."""
    from anomod.replay import N_FEATS, ReplayState
    cfg = ReplayConfig(n_services=6, n_windows=8, window_us=5_000_000,
                       chunk_size=512)
    runner = BucketRunner(cfg, (128, 512), lane_buckets=(1, 2, 4))
    runner.warm()
    rng = np.random.default_rng(0)

    def rand_state():
        return ReplayState(
            agg=rng.lognormal(3, 2, (cfg.sw, N_FEATS)).astype(np.float32),
            hist=rng.lognormal(1, 1,
                               (cfg.sw, cfg.n_hist_buckets)).astype(
                                   np.float32))

    # five lanes of width-128 chunks: lane_plan -> a full 4-bucket group
    # plus a dead-padded 1-bucket group
    work = []
    for i in range(5):
        plan = runner.stage_plan(_rand_spans(100 + i, 6, seed=i), 0)
        assert [w for w, _ in plan] == [128]
        work.append((rand_state(), plan[0][1]))
    seq = [runner.dispatch(st, cols, 128) for st, cols in work]
    fused = runner.run_lanes(128, list(work))
    for a, b in zip(seq, fused):
        np.testing.assert_array_equal(np.asarray(a.agg), np.asarray(b.agg))
        np.testing.assert_array_equal(np.asarray(a.hist),
                                      np.asarray(b.hist))
    assert runner.fused_dispatches == 2
    assert runner.lanes_by_bucket == {4: 1, 1: 1}
    assert runner.staged_lanes == 5 and runner.live_lanes == 5
    assert runner.lane_pad_waste == 0.0


def test_scatter_step_bit_identical_to_matmul_step():
    """The CPU engine swap the fused path leans on: the segment-sum
    (scatter) formulation of the chunk step produces the BIT-identical
    f32 state of the one-hot matmul formulation, single-lane and
    lane-stacked (delta + host add) alike."""
    import jax

    from anomod.replay import (N_FEATS, ReplayState, make_chunk_step,
                               make_lane_delta, stage_columns)
    cfg = ReplayConfig(n_services=6, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    mat = jax.jit(lambda st, ch: make_chunk_step(
        cfg, engine="matmul")(st, ch)[0])
    sca = jax.jit(lambda st, ch: make_chunk_step(
        cfg, engine="scatter")(st, ch)[0])
    lane = jax.jit(make_lane_delta(cfg, engine="scatter"))
    rng = np.random.default_rng(3)
    states, chunks = [], []
    for i in range(4):
        st = ReplayState(
            agg=rng.lognormal(3, 2, (cfg.sw, N_FEATS)).astype(np.float32),
            hist=rng.lognormal(
                1, 1, (cfg.sw, cfg.n_hist_buckets)).astype(np.float32))
        staged, _ = stage_columns(_rand_spans(100 + 30 * i, 6, seed=10 + i),
                                  cfg, t0_us=0)
        ch = {k: v[0] for k, v in staged.items()}
        states.append(st)
        chunks.append(ch)
        a, b = mat(st, ch), sca(st, ch)
        np.testing.assert_array_equal(np.asarray(a.agg), np.asarray(b.agg))
        np.testing.assert_array_equal(np.asarray(a.hist),
                                      np.asarray(b.hist))
    dagg, dhist = lane({k: np.stack([c[k] for c in chunks])
                        for k in chunks[0]})
    dagg, dhist = np.asarray(dagg), np.asarray(dhist)
    for i, (st, ch) in enumerate(zip(states, chunks)):
        want = mat(st, ch)
        np.testing.assert_array_equal(np.asarray(want.agg),
                                      st.agg + dagg[i])
        np.testing.assert_array_equal(np.asarray(want.hist),
                                      st.hist + dhist[i])


@pytest.mark.parametrize(
    "seed", [0, pytest.param(1, marks=pytest.mark.slow)])
def test_fused_scoring_bit_identical_to_sequential_with_coalescing(seed):
    """THE fused parity pin: a fused engine run under overload — with
    same-tenant micro-batches genuinely coalescing per tick — emits
    per-tenant states AND alert streams bit-identical to a sequential
    per-tenant StreamReplay/OnlineDetector fed the same per-tick
    coalesced batches (CPU).  SLO parity is pinned separately against
    the unfused engine (identical admission ⇒ identical latencies)."""
    from anomod.schemas import concat_span_batches

    def traffic():
        return PowerLawTraffic(
            n_tenants=6, total_rate_spans_per_s=1800, alpha=0.6, seed=seed,
            n_services=4, batch_cap=64,
            faults={0: TenantFault("latency", service=1, onset_s=30.0,
                                   factor=12.0)})
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=1024)
    tr = traffic()
    eng = ServeEngine(tr.specs, tr.services, cfg,
                      capacity_spans_per_s=1200, tick_s=1.0,
                      buckets=(128, 512), lane_buckets=(1, 2, 4, 8),
                      max_backlog=2400, baseline_windows=4, fuse=True)
    eng.runner.warm()
    eng.runner.warm_lanes()
    served_log = []
    for k in range(50):
        served_log.append(eng.tick(tr.arrivals(k * 1.0, (k + 1) * 1.0)))
    for det in eng._tenant_det.values():
        det.finish()
    # the regrouping must actually be exercised: some tick coalesced >= 2
    # micro-batches of one tenant, and some fused dispatch ran > 1 lane
    assert any(
        int(np.bincount([qb.tenant_id for qb in served]).max()) >= 2
        for served in served_log if served)
    assert any(b > 1 for b in eng.runner.lanes_by_bucket)
    assert eng.report(traffic=tr).n_alerts > 0      # the fault alerted

    for tid in sorted({qb.tenant_id for served in served_log
                       for qb in served}):
        solo = OnlineDetector(tr.services, cfg, 0,
                              replay=StreamReplay(cfg, 0),
                              baseline_windows=4)
        for served in served_log:
            mine = [qb.spans for qb in served if qb.tenant_id == tid]
            if mine:
                solo.push(mine[0] if len(mine) == 1
                          else concat_span_batches(mine))
        solo.finish()
        assert [dataclasses.asdict(a) for a in eng.alerts_for(tid)] \
            == [dataclasses.asdict(a) for a in solo.alerts]
        rep = eng._tenant_replay[tid]
        assert rep.window_offset == solo.replay.window_offset
        assert rep.n_spans == solo.replay.n_spans
        np.testing.assert_array_equal(np.asarray(rep.state.agg),
                                      np.asarray(solo.replay.state.agg))
        np.testing.assert_array_equal(np.asarray(rep.state.hist),
                                      np.asarray(solo.replay.state.hist))


def test_fused_and_unfused_slo_and_admission_identical():
    """Fusion must not move a single admission/shed/SLO number: the
    drained batches and their latency samples are identical, so the
    report's counters and latency quantiles match exactly."""
    def go(fuse):
        _, rep = run_power_law(
            n_tenants=8, n_services=4, capacity_spans_per_s=1000,
            overload=2.0, duration_s=30, tick_s=1.0, seed=4,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(128, 512), max_backlog=1500, fuse=fuse)
        return rep
    a, b = go(True), go(False)
    assert a.fused and not b.fused
    for f in ("offered_spans", "admitted_spans", "served_spans",
              "shed_spans", "served_batches", "peak_backlog_spans",
              "latency", "per_priority", "dispatches_by_width"):
        assert getattr(a, f) == getattr(b, f), f


def test_fused_compile_count_pin():
    """Exactly ONE compile per (width, lane-bucket) shape over a long
    fused run: the warm grid covers everything the tick loop can
    dispatch, and nothing recompiles mid-serve (via the jit compile
    counters the observability plane already keeps)."""
    from anomod.obs.registry import Registry, set_registry
    reg = Registry(enabled=True)
    prev = set_registry(reg)
    try:
        eng, rep = run_power_law(
            n_tenants=10, n_services=4, capacity_spans_per_s=1500,
            overload=1.5, duration_s=60, tick_s=0.5, seed=6,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(128, 512), lane_buckets=(1, 2, 4), fuse=True,
            n_windows=16)
        grid = {(w, l) for w in eng.runner.widths
                for l in eng.runner.lane_buckets}
        assert eng.runner.lane_shapes == grid
        assert reg.counter(
            "anomod_serve_fused_compile_total").value == len(grid)
        assert rep.fused_dispatches > 0
        # fused-path telemetry rides along: lanes histogram + pad gauges
        assert reg.counter(
            "anomod_serve_fused_dispatches_total").value \
            == rep.fused_dispatches
        assert reg.histogram("anomod_serve_fused_lanes").count \
            == rep.fused_dispatches
        assert 0.0 <= reg.gauge(
            "anomod_serve_lane_pad_waste_fraction").value < 1.0
    finally:
        set_registry(prev)


def test_fused_engine_smoke():
    """Tier-1 fused smoke (<5s): a small fused run serves, sheds, fuses
    dispatches and still detects the scripted fault."""
    traffic = PowerLawTraffic(
        n_tenants=6, total_rate_spans_per_s=1200, alpha=0.0, seed=3,
        n_services=4, batch_cap=128,
        faults={1: TenantFault("latency", service=1, onset_s=30.0,
                               factor=12.0)})
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=1024)
    eng = ServeEngine(traffic.specs, traffic.services, cfg,
                      capacity_spans_per_s=900, tick_s=1.0,
                      buckets=(256,), lane_buckets=(1, 2, 4, 8),
                      max_backlog=2000, baseline_windows=4, fuse=True)
    rep = eng.run(traffic, duration_s=60.0)
    assert rep.fused is True
    assert rep.served_spans > 0 and rep.shed_spans > 0
    assert rep.fused_dispatches > 0
    assert rep.lanes_by_bucket and 0.0 <= rep.lane_pad_waste < 1.0
    assert rep.fault_detection["n_detected"] == 1
    d = rep.to_dict()
    import json
    json.dumps(d)
    assert d["lane_buckets"] == [1, 2, 4, 8]
    assert set(d["lanes_by_bucket"]) <= {"1", "2", "4", "8"}


def test_credit_clamp_bounds_float_drift():
    """The per-tick credit float is clamped to its physical envelope
    (one tick's budget of carry either way, plus at most one batch's
    overdraw), so accumulated sub-span rounding on a fractional tick
    budget can never drift into phantom capacity or phantom debt."""
    traffic = PowerLawTraffic(n_tenants=2, total_rate_spans_per_s=100,
                              seed=0, n_services=4)
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    eng = ServeEngine(traffic.specs, traffic.services, cfg,
                      capacity_spans_per_s=333.3, tick_s=0.3,
                      buckets=(256,), score=False)
    budget = 333.3 * 0.3
    # phantom capacity: a corrupted/drifted positive credit is pulled
    # back to at most one tick's budget
    eng._credit = 1e9
    eng.tick([])
    assert eng._credit <= budget + 1e-9
    # phantom debt: a drifted negative credit floors at one budget
    eng._credit = -1e9
    eng.tick([])
    assert eng._credit >= -budget - 1e-9
    # steady state with a non-representable tick budget stays bounded
    # and dust-free forever
    for k in range(300):
        eng.tick(traffic.arrivals(k * 0.3, (k + 1) * 0.3))
        assert -max(budget, 512) - 1e-9 <= eng._credit <= budget + 1e-9
        assert eng._credit == 0.0 or abs(eng._credit) >= 1e-9


def test_credit_clamp_does_not_forgive_multi_budget_overdraw():
    """A batch wider than several tick budgets legitimately overdraws;
    its debt is paid down across idle ticks and the clamp must NOT
    forgive it mid-repayment (the floor remembers the widest served
    batch, review finding)."""
    specs = [TenantSpec(0, "t", priority=1)]
    cfg = ReplayConfig(n_services=1, n_windows=8, window_us=5_000_000,
                       chunk_size=512)
    eng = ServeEngine(specs, ("s",), cfg, capacity_spans_per_s=100.0,
                      tick_s=1.0, buckets=(512,), score=False,
                      max_backlog=1000, max_tenant_backlog=1000)
    served = eng.tick([(0, _spans(350))])      # overdraw: 100 - 350
    assert [qb.n_spans for qb in served] == [350]
    assert eng._credit == pytest.approx(-250.0)
    eng.tick([])                               # repaying: -250 + 100
    assert eng._credit == pytest.approx(-150.0)   # NOT clamped to -100
    eng.tick([])
    assert eng._credit == pytest.approx(-50.0)
    eng.tick([])                               # debt paid; positive again
    assert eng._credit == pytest.approx(50.0)


def test_lane_env_knobs_registered_and_validated(monkeypatch):
    from anomod.config import Config
    monkeypatch.setenv("ANOMOD_SERVE_LANE_BUCKETS", "1, 4,16")
    monkeypatch.setenv("ANOMOD_SERVE_FUSE", "0")
    cfg = Config()
    assert cfg.serve_lane_buckets == (1, 4, 16)
    assert cfg.serve_fuse is False
    monkeypatch.setenv("ANOMOD_SERVE_FUSE", "1")
    assert Config().serve_fuse is True

    monkeypatch.setenv("ANOMOD_SERVE_LANE_BUCKETS", "16,4")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_LANE_BUCKETS"):
        Config()
    monkeypatch.setenv("ANOMOD_SERVE_LANE_BUCKETS", "0,4")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_LANE_BUCKETS"):
        Config()
    monkeypatch.setenv("ANOMOD_SERVE_LANE_BUCKETS", "x")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_LANE_BUCKETS"):
        Config()
    monkeypatch.delenv("ANOMOD_SERVE_LANE_BUCKETS")
    from anomod.config import DEFAULT_SERVE_LANE_BUCKETS
    assert Config().serve_lane_buckets == DEFAULT_SERVE_LANE_BUCKETS
    # the env-contract gate sees both knobs as Config-covered
    import sys as _sys
    from pathlib import Path as _Path
    _sys.path.insert(0, str(_Path(__file__).parent.parent / "scripts"))
    try:
        import check_env_contract as cec
        refs = cec.referenced_vars(_Path(cec.ROOT))
        corpus = cec.covered_vars(_Path(cec.ROOT))
        for knob in ("ANOMOD_SERVE_LANE_BUCKETS", "ANOMOD_SERVE_FUSE"):
            assert knob in refs and knob in corpus
    finally:
        _sys.path.pop(0)


# ---------------------------------------------------------------------------
# tenant-sharded scale-out + pipelined dispatch (the PR-5 pins)
# ---------------------------------------------------------------------------

def _report_decision_fields(rep):
    """Everything in the report that must be shard-count/pipeline-depth
    invariant (the exclusion list is engine.py's ONE definition)."""
    from anomod.serve.engine import SHARD_VARIANT_REPORT_FIELDS
    return {k: v for k, v in rep.to_dict().items()
            if k not in SHARD_VARIANT_REPORT_FIELDS}


def test_shard_plan_deterministic_balanced_and_covering():
    from anomod.serve.shard import plan_shards, rendezvous_shard
    tr = PowerLawTraffic(n_tenants=200, total_rate_spans_per_s=50_000,
                         alpha=1.2, seed=0, n_services=12)
    for n in (2, 4, 8):
        plan = plan_shards(tr.specs, n)
        assert set(plan) == {s.tenant_id for s in tr.specs}   # covering
        assert set(plan.values()) <= set(range(n))
        assert plan == plan_shards(tr.specs, n)               # stable
        # the load-balance pass spreads the Zipf head: offered-rate
        # share per shard within 15% of perfect — except that a single
        # tenant is indivisible, so the unavoidable floor is the head
        # tenant's own rate (at 8 shards the ~26% head exceeds the
        # 12.5% perfect share; the optimum parks it alone)
        loads = [0.0] * n
        for s in tr.specs:
            loads[plan[s.tenant_id]] += s.rate_spans_per_s
        head = max(s.rate_spans_per_s for s in tr.specs)
        assert max(loads) <= max(1.15 * sum(loads) / n, head * 1.001)
        # ...and an irreducible head shard must not stop the REST of
        # the fleet from leveling
        rest = sorted(loads)[:-1]
        if rest:
            assert max(rest) <= \
                1.15 * max(sum(rest) / len(rest), head)
    assert plan_shards(tr.specs, 1) == {s.tenant_id: 0 for s in tr.specs}
    # rendezvous base is pure and process-stable
    assert rendezvous_shard(17, 4) == rendezvous_shard(17, 4)
    with pytest.raises(ValueError):
        plan_shards(tr.specs, 0)


def test_served_rate_model_under_overload():
    """The balance weights under overload follow the WFQ share model:
    demand-limited tenants keep their offer, the rest split by weight;
    the total matches capacity."""
    from anomod.serve.shard import served_rate_model
    specs = [TenantSpec(0, "gold", priority=0, rate_spans_per_s=100.0),
             TenantSpec(1, "bronze", priority=2, rate_spans_per_s=1000.0),
             TenantSpec(2, "silver", priority=1, rate_spans_per_s=10.0)]
    served = served_rate_model(specs, capacity_spans_per_s=500.0)
    assert sum(served.values()) == pytest.approx(500.0, rel=1e-3)
    # gold and silver offer less than their weighted fair share: both
    # are demand-limited and keep their whole offer; bronze (the only
    # backlogged tenant) gets exactly the remainder
    assert served[0] == pytest.approx(100.0)
    assert served[2] == pytest.approx(10.0)
    assert served[1] == pytest.approx(390.0, rel=1e-3)
    # two backlogged tenants split the remainder by weight (4:1)
    specs2 = [TenantSpec(0, "g", priority=0, rate_spans_per_s=1000.0),
              TenantSpec(1, "b", priority=2, rate_spans_per_s=1000.0)]
    served2 = served_rate_model(specs2, capacity_spans_per_s=500.0)
    assert served2[0] / served2[1] == pytest.approx(4.0, rel=1e-2)
    # ample capacity: the offered rates verbatim
    ample = served_rate_model(specs, capacity_spans_per_s=5000.0)
    assert ample == {0: 100.0, 1: 1000.0, 2: 10.0}


@pytest.mark.parametrize("seed", [3, 11])
def test_sharded_engine_identical_to_single_shard(seed):
    """THE scale-out parity pin: an N-shard engine (worker threads,
    pipelined dispatch) emits per-tenant states, alert streams, SLO
    quantiles and admission/shed decisions IDENTICAL to the 1-shard
    synchronous engine on the same seed — with coalescing and
    pipelining genuinely exercised."""
    def go(shards, pipeline):
        return run_power_law(
            n_tenants=10, n_services=4, capacity_spans_per_s=1500,
            overload=2.0, duration_s=40, tick_s=0.5, seed=seed,
            window_s=5.0, baseline_windows=4, fault_tenants=1,
            buckets=(64, 128, 512), lane_buckets=(1, 2, 4),
            max_backlog=3000, n_windows=16, shards=shards,
            pipeline=pipeline)

    e1, r1 = go(1, 1)                     # the synchronous baseline
    base = _report_decision_fields(r1)
    assert r1.shed_spans > 0              # overload regime is real
    for shards, pipeline in ((1, 2), (2, 2), (4, 3)):
        en, rn = go(shards, pipeline)
        assert _report_decision_fields(rn) == base, \
            f"report diverged at shards={shards}"
        assert rn.shards == shards and rn.pipeline == pipeline
        for tid in e1._tenant_det:
            assert [dataclasses.asdict(a) for a in e1.alerts_for(tid)] \
                == [dataclasses.asdict(a) for a in en.alerts_for(tid)]
            s1 = e1._tenant_replay[tid].state
            s2 = en._tenant_replay[tid].state
            np.testing.assert_array_equal(np.asarray(s1.agg),
                                          np.asarray(s2.agg))
            np.testing.assert_array_equal(np.asarray(s1.hist),
                                          np.asarray(s2.hist))
        if shards > 1:
            # occupancy fields: every shard got tenants, spans add up
            assert sum(rn.shard_tenants.values()) == 10
            assert sum(rn.shard_spans.values()) == rn.served_spans
            assert rn.shard_imbalance >= 1.0
    # pipelining was actually exercised: a depth-2 run kept dispatches
    # in flight (the runner drained them at tick end)
    en, rn = go(2, 2)
    assert all(r.pipeline == 2 for r in en._runners)
    assert rn.fused_dispatches > 0


def test_submit_lanes_pipelined_bit_identical_to_run_lanes():
    """The pipelined submit/drain path (deferred readback, per-slot
    scratch) folds the exact bits of the synchronous run_lanes path, at
    several depths, including multi-round (multi-chunk) tenants whose
    deltas are in flight simultaneously."""
    cfg = ReplayConfig(n_services=6, n_windows=8, window_us=5_000_000,
                       chunk_size=512)

    def fresh_replays(runner, n):
        out = []
        for i in range(n):
            r = BucketedStreamReplay(cfg, 0, runner)
            out.append(r)
        return out

    batches = [_rand_spans(80 + 97 * i, 6, seed=100 + i) for i in range(5)]
    # synchronous reference
    ref_runner = BucketRunner(cfg, (128, 512), lane_buckets=(1, 2, 4))
    ref_runner.warm()
    refs = fresh_replays(ref_runner, 5)
    for r, b in zip(refs, batches):
        r.push(b)
    for depth in (2, 3):
        runner = BucketRunner(cfg, (128, 512), lane_buckets=(1, 2, 4),
                              pipeline=depth)
        runner.warm()
        runner.warm_lanes()
        replays = fresh_replays(runner, 5)
        plans = [r.plan_push(b) for r, b in zip(replays, batches)]
        rnd = 0
        while True:
            groups = {}
            for i, (_, plan) in enumerate(plans):
                if rnd < len(plan):
                    groups.setdefault(plan[rnd][0], []).append(i)
            if not groups:
                break
            for width in sorted(groups):
                runner.submit_lanes(width,
                                    [(replays[i], plans[i][1][rnd][1])
                                     for i in groups[width]])
            rnd += 1
        assert runner.inflight_dispatches <= depth - 1
        runner.drain_lanes()
        assert runner.inflight_dispatches == 0
        for ref, got in zip(refs, replays):
            np.testing.assert_array_equal(np.asarray(ref.state.agg),
                                          np.asarray(got.state.agg))
            np.testing.assert_array_equal(np.asarray(ref.state.hist),
                                          np.asarray(got.state.hist))


def test_abort_lanes_discards_inflight_without_folding():
    """Failed-tick cleanup: aborting in-flight dispatches materializes
    them (scratch stays safe to refill) but folds NOTHING — the paired
    replays keep their pre-submit states, and a later drain/run_lanes
    cannot absorb the aborted work."""
    cfg = ReplayConfig(n_services=4, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2),
                          pipeline=3)
    runner.warm()
    runner.warm_lanes()
    replays = [BucketedStreamReplay(cfg, 0, runner) for _ in range(2)]
    plans = [r.plan_push(_rand_spans(60 + i, 4, seed=40 + i))
             for i, r in enumerate(replays)]
    before = [np.asarray(r.state.agg).copy() for r in replays]
    runner.submit_lanes(64, [(r, p[1][0][1])
                             for r, p in zip(replays, plans)])
    assert runner.inflight_dispatches == 1
    runner.abort_lanes()
    assert runner.inflight_dispatches == 0
    for r, b in zip(replays, before):
        np.testing.assert_array_equal(np.asarray(r.state.agg), b)
    # the runner keeps serving after an abort: a fresh push folds
    replays[0].push(_rand_spans(50, 4, seed=99))
    assert replays[0].n_spans > 0


def test_per_shard_compile_count_pin():
    """Exactly one compile per (width, lane-bucket) per SHARD: each
    shard runner owns its executables and compiles its grid once; the
    per-shard registries fold the compile counters into the process
    registry, so the fleet total is shards x grid."""
    from anomod.obs.registry import Registry, set_registry
    reg = Registry(enabled=True)
    prev = set_registry(reg)
    try:
        eng, rep = run_power_law(
            n_tenants=10, n_services=4, capacity_spans_per_s=1500,
            overload=1.5, duration_s=40, tick_s=0.5, seed=6,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(128, 512), lane_buckets=(1, 2, 4), fuse=True,
            n_windows=16, shards=2, pipeline=2)
        grid = {(w, l) for w in eng.runner.widths
                for l in eng.runner.lane_buckets}
        for r in eng._runners:
            assert r.lane_shapes == grid          # full grid, per shard
        assert reg.counter(
            "anomod_serve_fused_compile_total").value == 2 * len(grid)
        assert rep.fused_dispatches > 0
        # shard-labeled gauge twins landed in the process registry
        assert reg.gauge("anomod_serve_lane_pad_waste_fraction",
                         shard="0").value >= 0.0
        # run-end histogram fold (merge_digest seam): lane counts from
        # both shards are in the process histogram
        assert reg.histogram("anomod_serve_fused_lanes").count == \
            rep.fused_dispatches
    finally:
        set_registry(prev)


def test_sharded_unfused_and_scoreless_paths():
    """The escape hatches compose: shards>1 with fuse=0 (per-batch
    pushes on the worker) and score=False (replay-plane only) both
    reproduce the 1-shard output."""
    def go(shards, fuse, score):
        return run_power_law(
            n_tenants=6, n_services=4, capacity_spans_per_s=1000,
            overload=1.5, duration_s=20, tick_s=1.0, seed=2,
            window_s=5.0, baseline_windows=4, fault_tenants=0,
            buckets=(128, 512), max_backlog=2000, n_windows=16,
            shards=shards, fuse=fuse, score=score)
    for fuse, score in ((False, True), (True, False)):
        e1, r1 = go(1, fuse, score)
        e2, r2 = go(2, fuse, score)
        assert _report_decision_fields(r1) == _report_decision_fields(r2)
        for tid, rep1 in e1._tenant_replay.items():
            rep2 = e2._tenant_replay[tid]
            np.testing.assert_array_equal(np.asarray(rep1.state.agg),
                                          np.asarray(rep2.state.agg))


def test_mesh_refuses_shards():
    from anomod.parallel import make_mesh
    traffic = PowerLawTraffic(n_tenants=2, total_rate_spans_per_s=100,
                              seed=0, n_services=4)
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    with pytest.raises(ValueError, match="mesh"):
        ServeEngine(traffic.specs, traffic.services, cfg,
                    mesh=make_mesh(2), shards=2)


def test_shard_worker_propagates_errors():
    from anomod.serve.shard import ShardWorker
    w = ShardWorker(0)
    try:
        def boom():
            raise RuntimeError("shard exploded")
        w.submit(boom)
        with pytest.raises(RuntimeError, match="shard exploded"):
            w.join()
        # the worker survives and keeps serving
        hit = []
        w.submit(lambda: hit.append(1))
        w.join()
        assert hit == [1]
    finally:
        w.close()
    assert not w.alive


def test_shard_env_knobs_registered_and_validated(monkeypatch):
    from anomod.config import Config
    monkeypatch.setenv("ANOMOD_SERVE_SHARDS", "4")
    monkeypatch.setenv("ANOMOD_SERVE_PIPELINE", "3")
    cfg = Config()
    assert cfg.serve_shards == 4
    assert cfg.serve_pipeline == 3

    for var, bad in (("ANOMOD_SERVE_SHARDS", "0"),
                     ("ANOMOD_SERVE_SHARDS", "many"),
                     ("ANOMOD_SERVE_SHARDS", "999"),
                     ("ANOMOD_SERVE_PIPELINE", "0"),
                     ("ANOMOD_SERVE_PIPELINE", "deep")):
        monkeypatch.setenv(var, bad)
        with pytest.raises(ValueError, match=var):
            Config()
        monkeypatch.delenv(var)
    cfg = Config()
    assert cfg.serve_shards == 1          # default: the escape hatch
    assert cfg.serve_pipeline == 2
    # the env-contract gate sees both knobs as Config-covered
    import sys as _sys
    from pathlib import Path as _Path
    _sys.path.insert(0, str(_Path(__file__).parent.parent / "scripts"))
    try:
        import check_env_contract as cec
        refs = cec.referenced_vars(_Path(cec.ROOT))
        corpus = cec.covered_vars(_Path(cec.ROOT))
        for knob in ("ANOMOD_SERVE_SHARDS", "ANOMOD_SERVE_PIPELINE"):
            assert knob in refs and knob in corpus
    finally:
        _sys.path.pop(0)


# ---------------------------------------------------------------------------
# the state seams sharding leans on (get_state/set_state, raw staging)
# ---------------------------------------------------------------------------

def test_state_seam_roundtrip_under_interleaved_shard_order():
    """StreamReplay.get_state/set_state round-trips: externally folding
    each tenant's staged chunks through the seam — in ANY cross-tenant
    interleaving — reproduces push() bit-exactly per tenant (per-tenant
    chunk order is the only ordering that matters)."""
    cfg = ReplayConfig(n_services=4, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2))
    runner.warm()
    batches = {t: _rand_spans(300 + 50 * t, 4, seed=t) for t in range(3)}

    ref = {}
    for t, b in batches.items():
        r = BucketedStreamReplay(cfg, 0, runner)
        r.push(b)
        ref[t] = r.state

    # two different shard-style interleavings of the same per-tenant
    # chunk streams (round-robin and reversed-tenant order)
    for order in ("round_robin", "reversed"):
        replays = {t: BucketedStreamReplay(cfg, 0, runner)
                   for t in batches}
        plans = {t: replays[t].plan_push(b)[1]
                 for t, b in batches.items()}
        queue = []
        max_rounds = max(len(p) for p in plans.values())
        tenant_order = sorted(batches) if order == "round_robin" \
            else sorted(batches, reverse=True)
        for rnd in range(max_rounds):
            for t in tenant_order:
                if rnd < len(plans[t]):
                    queue.append((t, plans[t][rnd]))
        for t, (width, cols) in queue:
            st = replays[t].get_state()
            replays[t].set_state(runner.dispatch(st, cols, width))
        for t in batches:
            np.testing.assert_array_equal(np.asarray(ref[t].agg),
                                          np.asarray(replays[t].state.agg))
            np.testing.assert_array_equal(
                np.asarray(ref[t].hist), np.asarray(replays[t].state.hist))


def test_stage_columns_raw_roundtrip_matches_padded_staging():
    """stage_columns_raw + the scratch-fill pad (dead-chunk fill values)
    reproduces stage_columns' padded chunks byte-for-byte — the staging
    seam the shard runners' pinned scratch relies on."""
    from anomod.replay import dead_chunk, stage_columns, stage_columns_raw
    cfg = ReplayConfig(n_services=4, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    batch = _rand_spans(500, 4, seed=9)
    padded, n = stage_columns(batch, cfg, t0_us=0)
    raw = stage_columns_raw(batch, cfg, t0_us=0)
    assert n == batch.n_spans
    dead = dead_chunk(cfg, cfg.chunk_size, xp=np)
    for k, v in raw.items():
        flat = padded[k].reshape(-1)
        np.testing.assert_array_equal(flat[:n], v)        # live rows
        fill = cfg.sw if k == "sid" else 0
        assert (flat[n:] == fill).all()                   # pad rows
        assert (np.asarray(dead[k]) == fill).all()        # one fill def
        assert flat.dtype == v.dtype


def test_serve_cli_emits_report(capsys):
    from anomod.cli import main
    rc = main(["serve", "--tenants", "4", "--services", "4",
               "--duration", "20", "--capacity", "400",
               "--overload", "2.0", "--buckets", "128,512",
               "--max-backlog", "800", "--fault-tenants", "0",
               "--no-score", "--seed", "1"])
    assert rc == 0
    import json
    out = json.loads(capsys.readouterr().out)
    assert out["n_tenants"] == 4
    assert out["offered_spans"] > 0
    assert out["buckets"] == [128, 512]
    assert 0.0 <= out["shed_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# GIL-free native staging + the serve-tick wall decomposition (ISSUE-7)
# ---------------------------------------------------------------------------

def _small_serve_kw(seed=5):
    return dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
                overload=2.0, duration_s=20, tick_s=1.0, seed=seed,
                window_s=5.0, baseline_windows=4, fault_tenants=1,
                buckets=(64, 256), lane_buckets=(1, 2, 4),
                max_backlog=1500, n_windows=16)


def _engine_fingerprint(eng):
    return {
        tid: ([dataclasses.asdict(a) for a in eng.alerts_for(tid)],
              np.asarray(eng._tenant_replay[tid].state.agg).tobytes(),
              np.asarray(eng._tenant_replay[tid].state.hist).tobytes())
        for tid in sorted(set(eng._tenant_det) | set(eng._tenant_replay))}


from anomod.io import native as _native_io


@pytest.mark.skipif(not _native_io.available(),
                    reason="native lib not built")
def test_native_staging_engine_byte_identical_to_python():
    """THE native-staging parity pin, end to end: a seeded overloaded
    fused run with the C++ GIL-free scratch packing emits per-tenant
    alerts and replay states byte-identical to the interpreter fill on
    the same seed — and the report says which path staged."""
    from anomod.serve.engine import run_power_law
    e_nat, r_nat = run_power_law(native=True, **_small_serve_kw())
    e_py, r_py = run_power_law(native=False, **_small_serve_kw())
    assert r_nat.native_staging is True and r_py.native_staging is False
    assert r_nat.native_staged_dispatches > 0
    assert r_py.native_staged_dispatches == 0
    assert _engine_fingerprint(e_nat) == _engine_fingerprint(e_py)
    # admission/SLO are staging-invariant by construction
    assert r_nat.shed_fraction == r_py.shed_fraction
    assert r_nat.latency == r_py.latency


def test_scratch_ring_refill_hazard_regression_depths_1_to_3():
    """The refill-under-dispatch hazard regression at every supported
    small pipeline depth: depths 1 (synchronous), 2 (double-buffered)
    and 3 must produce byte-identical states and alerts — a slot
    refilled under a dispatch that can still read it would corrupt the
    fold at depth >= 2 only, which is exactly what this pins against
    the depth-1 oracle (native staging wherever available)."""
    from anomod.serve.engine import run_power_law
    prints = []
    for depth in (1, 2, 3):
        eng, rep = run_power_law(pipeline=depth, **_small_serve_kw(seed=7))
        assert rep.pipeline == depth
        prints.append(_engine_fingerprint(eng))
    assert prints[0] == prints[1] == prints[2]


def test_serve_report_carries_wall_decomposition():
    """The staging decomposition the bench block reads: stage/dispatch/
    fold walls accounted per runner, summing to less than the serve
    wall (the rest is admission/detector bookkeeping)."""
    from anomod.serve.engine import run_power_law
    _, rep = run_power_law(**_small_serve_kw())
    assert rep.stage_wall_s > 0
    assert rep.dispatch_wall_s > 0
    assert rep.fold_wall_s > 0
    assert rep.stage_wall_s + rep.dispatch_wall_s + rep.fold_wall_s \
        + rep.score_wall_s <= rep.serve_wall_s + 1e-6
    # decomposition fields are wall measurements: excluded from the
    # shard-determinism comparison by the ONE shared list
    from anomod.serve.engine import SHARD_VARIANT_REPORT_FIELDS
    for f in ("stage_wall_s", "dispatch_wall_s", "fold_wall_s",
              "score_wall_s", "native_staged_dispatches"):
        assert f in SHARD_VARIANT_REPORT_FIELDS


def test_lane_engine_knob_registered_and_validated(monkeypatch):
    """ANOMOD_SERVE_LANE_ENGINE joins the validated Config env contract:
    auto/matmul/scatter/pallas parse, anything else fails loudly.  The
    hands-off default FOLLOWS the step engine (bit-parity backend-stable
    — on this CPU box both resolve to scatter); pallas is an explicit
    opt-in that routes the runner's fused surface to the Mosaic kernel;
    and an explicit ``engine=`` still pins BOTH surfaces to one
    formulation regardless of the knob (the parity tests rely on that).
    """
    from anomod.config import Config, set_config
    from anomod.replay import default_lane_engine, default_step_engine
    assert Config().serve_lane_engine == "auto"
    monkeypatch.setenv("ANOMOD_SERVE_LANE_ENGINE", "pallas")
    assert Config().serve_lane_engine == "pallas"
    monkeypatch.setenv("ANOMOD_SERVE_LANE_ENGINE", "banana")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_LANE_ENGINE"):
        Config()

    cfg = ReplayConfig(n_services=4, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    try:
        monkeypatch.delenv("ANOMOD_SERVE_LANE_ENGINE")
        set_config(Config())
        assert default_lane_engine() == default_step_engine()
        runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2))
        assert runner.lane_engine == runner.engine
        monkeypatch.setenv("ANOMOD_SERVE_LANE_ENGINE", "pallas")
        set_config(Config())
        assert default_lane_engine() == "pallas"
        runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2))
        assert runner.lane_engine == "pallas"
        # an explicit engine= pins both surfaces, knob notwithstanding
        runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2),
                              engine="scatter")
        assert runner.engine == runner.lane_engine == "scatter"
    finally:
        monkeypatch.delenv("ANOMOD_SERVE_LANE_ENGINE", raising=False)
        set_config(Config())


def test_native_knob_registered_and_validated(monkeypatch):
    """ANOMOD_NATIVE joins the validated Config env contract: auto/on/off
    (with 1/0 aliases) parse, anything else fails loudly; off forces the
    interpreter fill even when the .so is fine; on REFUSES to construct
    a runner when the runtime is unusable, quoting the build reason."""
    from anomod.config import Config
    from anomod.io import native as native_io
    assert Config().native == "auto"
    monkeypatch.setenv("ANOMOD_NATIVE", "1")
    assert Config().native == "on"
    monkeypatch.setenv("ANOMOD_NATIVE", "off")
    assert Config().native == "off"
    monkeypatch.setenv("ANOMOD_NATIVE", "banana")
    with pytest.raises(ValueError, match="ANOMOD_NATIVE"):
        Config()

    cfg = ReplayConfig(n_services=4, n_windows=8, window_us=5_000_000,
                       chunk_size=256)
    monkeypatch.setenv("ANOMOD_NATIVE", "off")
    from anomod.config import set_config
    try:
        set_config(Config())
        runner = BucketRunner(cfg, (64, 256), lane_buckets=(1, 2))
        assert runner.native_stage is False
        # =on with an unusable runtime: fail loud with the reason, never
        # silently serve the slow path
        monkeypatch.setenv("ANOMOD_NATIVE", "on")
        set_config(Config())
        monkeypatch.setattr(native_io, "available", lambda: False)
        with pytest.raises(RuntimeError, match="ANOMOD_NATIVE"):
            BucketRunner(cfg, (64, 256), lane_buckets=(1, 2))
    finally:
        monkeypatch.delenv("ANOMOD_NATIVE")
        set_config(Config())
