"""Online detection: incremental state parity + alert quality.

The streaming layer's contract is that it is the SAME replay plane fed
incrementally (anomod.replay.make_chunk_step), so parity with the batch
path is exact for order-independent planes (0/1 counts, histogram, HLL
max-merge) and allclose for the f32 moment sums (different chunk
boundaries reorder the additions).
"""

import numpy as np

from anomod import labels, synth
from anomod.replay import ReplayConfig, replay_numpy, stage_columns
from anomod.schemas import SpanBatch, concat_span_batches, take_spans
from anomod.stream import OnlineDetector, StreamReplay, stream_experiment


def _tt_batch(n_traces=40):
    return concat_span_batches([
        synth.generate_spans(l, n_traces=n_traces)
        for l in labels.labels_for_testbed("TT")[:4]])


def test_take_spans_subsets_rows():
    b = _tt_batch(10)
    idx = np.arange(0, b.n_spans, 3)
    sub = take_spans(b, idx)
    assert sub.n_spans == len(idx)
    np.testing.assert_array_equal(sub.service, b.service[idx])
    np.testing.assert_array_equal(sub.start_us, b.start_us[idx])
    assert sub.services == b.services       # side tables kept whole


def test_stream_state_matches_batch_replay():
    batch = _tt_batch()
    cfg = ReplayConfig(n_services=batch.n_services, chunk_size=2048)
    chunks, n = stage_columns(batch, cfg)
    ref = replay_numpy(chunks, cfg)

    t0 = int(batch.start_us.min())
    sr = StreamReplay(cfg, t0, with_hll=True)
    order = np.argsort(batch.start_us, kind="stable")
    batch = take_spans(batch, order)
    # uneven micro-batches: chunk boundaries differ from the batch staging
    cuts = [0, 1000, 1001, 5000, batch.n_spans]
    for lo, hi in zip(cuts, cuts[1:]):
        sr.push(take_spans(batch, slice(lo, hi)))
    assert sr.n_spans == n
    got = np.asarray(sr.state.agg)
    # 0/1 planes + histogram: small-integer f32 sums, order-independent
    np.testing.assert_array_equal(got[:, :3], ref.agg[:, :3])
    np.testing.assert_array_equal(np.asarray(sr.state.hist), ref.hist)
    # moment planes: f32 accumulation order differs -> allclose
    np.testing.assert_allclose(got[:, 3:], ref.agg[:, 3:], rtol=1e-5,
                               atol=1e-3)
    # HLL registers max-merge, exactly order-independent: compare against
    # a second stream fed as ONE batch
    one = StreamReplay(cfg, t0, with_hll=True)
    one.push(batch)
    np.testing.assert_array_equal(np.asarray(sr.state.hll),
                                  np.asarray(one.state.hll))


def test_streaming_detects_and_localizes_kill_fault():
    label = labels.label_for("Svc_Kill_UserTimeline")
    exp = synth.generate_experiment(label, n_traces=300, seed=0)
    det = stream_experiment(exp.spans)
    ranked = det.ranked_services()
    assert ranked and ranked[0] == label.target_service
    onset = 10                               # fault onset 600 s, 60 s windows
    fw = det.first_alert_window(label.target_service)
    assert fw is not None and onset <= fw <= onset + 6


def test_streaming_detects_latency_fault_tt():
    label = labels.label_for("Lv_P_CPU_preserve")
    exp = synth.generate_experiment(label, n_traces=300, seed=0)
    det = stream_experiment(exp.spans)
    ranked = det.ranked_services()
    assert ranked and ranked[0] == label.target_service
    fw = det.first_alert_window(label.target_service)
    assert fw is not None and 10 <= fw <= 16


def test_streaming_quiet_on_normal_baseline():
    exp = synth.generate_experiment(labels.label_for("Normal_Baseline"),
                                    n_traces=300, seed=0)
    det = stream_experiment(exp.spans)
    assert len(det.alerts) <= 2              # no alert storm without a fault


def test_stream_quality_rows():
    from anomod.stream import stream_quality
    rows = stream_quality("SN", n_traces=300,
                          experiments=["Normal_Baseline",
                                       "Svc_Kill_UserTimeline"])
    assert len(rows) == 2
    normal, kill = rows
    assert "top1_hit" not in normal          # no RCA row for the baseline
    assert kill["top1_hit"] and kill["top3_hit"]
    # signed latency: a marginal pre-onset noise alert on the culprit
    # (window 9, onset 10) legitimately reads as -1
    assert -1 <= kill["detection_latency_windows"] <= 6


def _uniform_batch(n_per_window, n_windows, n_services=2, window_us=60_000_000):
    """Healthy constant-rate, constant-latency synthetic stream."""
    rng = np.random.default_rng(0)
    rows = n_per_window * n_windows * n_services
    start = np.repeat(np.arange(n_windows, dtype=np.int64),
                      n_per_window * n_services) * window_us
    start = start + rng.integers(0, window_us, rows)
    svc = np.tile(np.arange(n_services, dtype=np.int32),
                  rows // n_services)
    return SpanBatch(
        trace=np.arange(rows, dtype=np.int32) % 100,
        parent=np.full(rows, -1, np.int32),
        service=svc, endpoint=np.zeros(rows, np.int32),
        start_us=np.sort(start),
        duration_us=rng.integers(900, 1100, rows).astype(np.int64),
        is_error=np.zeros(rows, np.bool_),
        status=np.full(rows, 200, np.int16),
        kind=np.zeros(rows, np.int8),
        services=tuple(f"svc{i}" for i in range(n_services)),
        endpoints=("ep",), trace_ids=tuple(f"t{i}" for i in range(100)),
    ).validate()


def test_finish_does_not_score_empty_trailing_windows():
    """A stream that ends at window 11 of a 32-window grid must not fire
    the drop signal for windows 12..31 (stream end != fleet outage)."""
    batch = _uniform_batch(n_per_window=20, n_windows=12)
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    det = OnlineDetector(batch.services, cfg, t0_us=0)
    det.push(batch)
    det.finish()
    assert det.alerts == []


def test_ring_rolls_past_grid_and_keeps_detecting():
    """A live stream longer than the window grid keeps scoring: the ring
    evicts old windows, alert indices stay absolute, and a fault at
    window 30 of a 16-window grid is caught."""
    batch = _uniform_batch(n_per_window=20, n_windows=40)
    kill_us = 30 * 60_000_000
    keep = ~((batch.service == 1) & (batch.start_us >= kill_us))
    batch = take_spans(batch, keep)
    cfg = ReplayConfig(n_services=2, n_windows=16, chunk_size=512)
    det = OnlineDetector(batch.services, cfg, t0_us=0)
    # window-sized micro-batches, as a live feed would deliver them
    for w in range(40):
        lo, hi = w * 60_000_000, (w + 1) * 60_000_000
        m = (batch.start_us >= lo) & (batch.start_us < hi)
        det.push(take_spans(batch, m))
    det.finish()
    assert det.replay.window_offset > 0          # the ring really rolled
    dead = [a for a in det.alerts if a.service_name == "svc1"]
    assert dead and dead[0].window in (30, 31)   # absolute indices
    assert not [a for a in det.alerts if a.service_name == "svc0"]


def test_feed_gap_wider_than_grid_no_alert_storm():
    """A collector outage longer than the whole window grid: the anchor
    advances by the FULL gap (spans after the gap bin into their true
    absolute window) and the empty gap windows are skipped as feed
    silence — not scored as a fleet-wide outage."""
    cfg = ReplayConfig(n_services=2, n_windows=16, chunk_size=512)
    healthy = _uniform_batch(n_per_window=20, n_windows=10)
    det = OnlineDetector(healthy.services, cfg, t0_us=0)
    det.push(healthy)
    # 35-window silence, then healthy traffic resumes at window 45
    resumed = _uniform_batch(n_per_window=20, n_windows=2)
    resumed = resumed._replace(start_us=resumed.start_us + 45 * 60_000_000)
    det.push(resumed)
    det.finish()
    assert det.alerts == []                      # no storm from the gap
    # the resumed data landed at its true absolute windows (45, 46)
    assert det.replay.window_offset == 46 - (cfg.n_windows - 1)
    plane = det.replay.agg_plane()
    nonzero_cols = np.nonzero(plane[..., 0].sum(axis=0))[0]
    got_abs = set(int(c) + det.replay.window_offset for c in nonzero_cols)
    assert got_abs == {45, 46}


def test_dependency_aware_ranking_prefers_deepest_anomalous():
    """A gateway whose error spike is explained by its dying callee must
    rank BELOW the callee, even with a louder peak score."""
    label = labels.label_for("Svc_Kill_UserTimeline")
    exp = synth.generate_experiment(label, n_traces=300, seed=0)
    det = stream_experiment(exp.spans)
    ranked = det.ranked_services()
    assert ranked[0] == "user-timeline-service"
    # the gateway still alerted (detection kept its sensitivity)...
    alerted = {a.service_name for a in det.alerts}
    assert "nginx-web-server" in alerted
    # ...but ranks behind the dependency that explains it (structural
    # property of the attribution: anomalous-callee services sort last)
    assert ranked.index("nginx-web-server") > \
        ranked.index("user-timeline-service")
    from anomod.stream import _explained_by_downstream
    anomalous = {a.service for a in det.alerts}
    explained = _explained_by_downstream(det.call_edges, anomalous)
    clean = [det.services.index(n) not in explained for n in ranked]
    assert clean == sorted(clean, reverse=True)   # unexplained first


def test_explained_by_downstream_graph_cases():
    from anomod.stream import _explained_by_downstream as ex
    # direct edge: caller explained by anomalous callee
    assert ex({(0, 1)}, {0, 1}) == {0}
    # chain through a HEALTHY middle hop still explains the caller
    assert ex({(0, 1), (1, 2)}, {0, 2}) == {0}
    # mutual cycle: same SCC -> neither explained (peak order decides)
    assert ex({(0, 1), (1, 0)}, {0, 1}) == set()
    # cycle with a genuinely downstream anomaly: both cycle members explained
    assert ex({(0, 1), (1, 0), (1, 2)}, {0, 1, 2}) == {0, 1}
    # no edges -> nothing explained
    assert ex(set(), {0, 1}) == set()
    # cross-edge DAG: u->v visited via another branch first — u must
    # still see v's transitive anomaly w (memo must be topo-ordered)
    D, u, v, w = 0, 1, 2, 3
    assert ex({(D, u), (D, v), (u, v), (v, w)}, {u, w}) == {u}
    # deep chain (iterative closure, no recursion limit)
    chain = {(i, i + 1) for i in range(3000)}
    assert ex(chain, {0, 3000}) == {0}


def test_multimodal_catches_sparse_kill():
    """The spans-only information floor (a sub-1-span/window service
    killed) is closed by the metric plane: request-rate collapse and
    error-rate series localize media-service directly."""
    from anomod.stream import stream_experiment_multimodal
    label = labels.label_for("Svc_Kill_Media")
    exp = synth.generate_experiment(label, n_traces=300, seed=0)
    span_only = stream_experiment(exp.spans)
    assert span_only.first_alert_window("media-service") is None  # the floor
    det = stream_experiment_multimodal(exp)
    assert det.ranked_services()[0] == "media-service"
    fw = det.first_alert_window("media-service")
    assert fw is not None and 10 <= fw <= 13
    culprit = [a for a in det.alerts if a.service_name == "media-service"]
    assert any(a.evidence in ("metric", "log", "api") for a in culprit)


def test_multimodal_quiet_on_normal():
    from anomod.stream import stream_experiment_multimodal
    exp = synth.generate_experiment(labels.label_for("Normal_Baseline"),
                                    n_traces=300, seed=0)
    det = stream_experiment_multimodal(exp)
    assert len(det.alerts) <= 2


def test_multimodal_state_stays_bounded():
    """The per-window modality planes are pruned as scoring advances —
    a long stream must not accumulate host state without bound."""
    from anomod.schemas import LogBatch
    from anomod.stream import MultimodalDetector
    cfg = ReplayConfig(n_services=2, n_windows=16, chunk_size=512)
    det = MultimodalDetector(("svc0", "svc1"), cfg, t0_us=0, testbed="TT")
    for w in range(40):
        spans = _uniform_batch(n_per_window=20, n_windows=1)
        spans = spans._replace(start_us=spans.start_us + w * 60_000_000)
        t = np.full(10, w * 60.0 + 5.0)
        det.push_logs(LogBatch(service=np.zeros(10, np.int32), t_s=t,
                               level=np.zeros(10, np.int8),
                               services=("svc0", "svc1")))
        det.push(spans)
    det.finish()
    assert len(det._log_tot) <= 4        # pruned, not 40


def test_metric_counter_rateification():
    """A healthy monotone counter (http_requests_total-style) must not
    drift into a false alert: baseline-detected counters are scored on
    window DIFFS."""
    from anomod.stream import MultimodalDetector
    from anomod.schemas import MetricBatch
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    spans = _uniform_batch(n_per_window=20, n_windows=20)
    det = MultimodalDetector(spans.services, cfg, t0_us=0, testbed="TT")
    # counter series for svc0: +240 per window, forever (healthy rate)
    t = np.arange(0, 20 * 60, 15, dtype=np.float64)
    mb = MetricBatch(
        metric=np.zeros(t.shape[0], np.int32),
        series=np.zeros(t.shape[0], np.int32),
        t_s=t, value=np.cumsum(np.full(t.shape[0], 60.0)),
        metric_names=("http_requests_total",), series_keys=('svc="svc0"',),
        series_service=np.array([0], np.int32), services=spans.services)
    det.push_metrics(mb)
    det.push(spans)
    det.finish()
    assert det.alerts == []
    base = det._mm_base["met"]['http_requests_total|svc="svc0"']
    assert base["counter"]          # detected as a counter


def test_consecutive_zero_rejected():
    import pytest
    cfg = ReplayConfig(n_services=2, n_windows=32)
    with pytest.raises(ValueError, match="consecutive"):
        OnlineDetector(("a", "b"), cfg, t0_us=0, consecutive=0)


def test_gap_breaks_hysteresis_streak():
    """With consecutive=2, hot windows on either side of a feed-silence
    gap are NOT a consecutive run."""
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    base = _uniform_batch(n_per_window=20, n_windows=9)
    det = OnlineDetector(base.services, cfg, t0_us=0, consecutive=2)
    det.push(base)
    # window 9 hot for svc1 (all errors), window 10 silent, window 11 hot
    hot = _uniform_batch(n_per_window=20, n_windows=1)

    def at(b, w):
        return b._replace(start_us=b.start_us + w * 60_000_000,
                          is_error=(b.service == 1),
                          status=np.where(b.service == 1, 500,
                                          b.status).astype(np.int16))
    det.push(at(hot, 9))
    det.push(at(hot, 11))
    det.finish()
    assert det.alerts == []          # 9 and 11 are separated by silence


def test_uncalibrated_service_does_not_false_alert():
    """A service with no baseline traffic must not alert on its first busy
    window (its mu/var would be fabricated) — but its drop signal stays
    off too (nothing to drop from)."""
    batch = _uniform_batch(n_per_window=20, n_windows=14)
    late = (batch.service == 1) & (batch.start_us < 10 * 60_000_000)
    batch = take_spans(batch, ~late)             # svc1 exists only from w10
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    det = OnlineDetector(batch.services, cfg, t0_us=0)
    det.push(batch)
    det.finish()
    assert not [a for a in det.alerts if a.service_name == "svc1"]


def test_sharded_stream_replay_matches_single_chip():
    """The mesh-sharded streaming plane (psum-merged per-push deltas over
    the 8-device CPU mesh) is numerically interchangeable with the
    single-chip StreamReplay, and the detector runs on it unchanged."""
    from anomod.parallel import make_mesh
    from anomod.parallel.stream import ShardedStreamReplay

    label = labels.label_for("Svc_Kill_UserTimeline")
    exp = synth.generate_experiment(label, n_traces=200, seed=0)
    batch = exp.spans
    cfg = ReplayConfig(n_services=batch.n_services, chunk_size=1024)
    order = np.argsort(batch.start_us, kind="stable")
    batch = take_spans(batch, order)
    t0 = int(batch.start_us.min())

    single = StreamReplay(cfg, t0)
    mesh = make_mesh()
    sharded = ShardedStreamReplay(cfg, t0, mesh)
    cuts = [0, 3000, 3001, 9000, batch.n_spans]
    for lo, hi in zip(cuts, cuts[1:]):
        mb = take_spans(batch, slice(lo, hi))
        assert single.push(mb) == sharded.push(mb)
    assert sharded.n_spans == single.n_spans
    np.testing.assert_array_equal(np.asarray(sharded.state.hist),
                                  np.asarray(single.state.hist))
    np.testing.assert_allclose(np.asarray(sharded.state.agg),
                               np.asarray(single.state.agg),
                               rtol=1e-5, atol=1e-3)

    # the full detector stack over the mesh: same culprit
    det = OnlineDetector(batch.services, cfg, t0,
                         replay=ShardedStreamReplay(cfg, t0, mesh))
    for lo, hi in zip(cuts, cuts[1:]):
        det.push(take_spans(batch, slice(lo, hi)))
    det.finish()
    assert det.first_alert_window(label.target_service) is not None


def test_ring_random_jumps_match_absolute_accumulator():
    """Property test for the ring math: arbitrary monotone window jumps
    (including gaps wider than the grid) must leave every retained ring
    column equal to a naive absolute-window accumulator."""
    rng = np.random.default_rng(7)
    W, S = 8, 2
    cfg = ReplayConfig(n_services=S, n_windows=W, chunk_size=256)
    sr = StreamReplay(cfg, t0_us=0)
    truth = {}                      # abs window -> [S] span counts
    w_abs = 0
    for _ in range(25):
        w_abs += int(rng.integers(0, 14))      # jumps 0..13 (> grid ok)
        n = int(rng.integers(1, 30))
        svc = rng.integers(0, S, n).astype(np.int32)
        start = (np.full(n, w_abs, np.int64) * cfg.window_us
                 + rng.integers(0, cfg.window_us, n))
        batch = SpanBatch(
            trace=np.zeros(n, np.int32), parent=np.full(n, -1, np.int32),
            service=svc, endpoint=np.zeros(n, np.int32),
            start_us=np.sort(start),
            duration_us=np.full(n, 1000, np.int64),
            is_error=np.zeros(n, np.bool_),
            status=np.full(n, 200, np.int16), kind=np.zeros(n, np.int8),
            services=("a", "b"), endpoints=("e",), trace_ids=("t",),
        )
        got_w = sr.push(batch)
        assert got_w == w_abs       # true absolute window, post-roll
        t = truth.setdefault(w_abs, np.zeros(S))
        np.add.at(t, svc, 1.0)
    plane = sr.agg_plane()          # [S, W, F]
    for col in range(W):
        w = sr.window_offset + col
        expect = truth.get(w, np.zeros(S))
        np.testing.assert_array_equal(plane[:, col, 0], expect)


def test_cusum_resets_on_recovery():
    """No lingering 'still down' alerts once traffic returns: the CUSUM
    run resets at the first window back at the baseline rate."""
    batch = _uniform_batch(n_per_window=20, n_windows=24)
    outage = ((batch.service == 1)
              & (batch.start_us >= 10 * 60_000_000)
              & (batch.start_us < 14 * 60_000_000))
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    det = OnlineDetector(batch.services, cfg, t0_us=0)
    det.push(take_spans(batch, ~outage))
    det.finish()
    dead = [a.window for a in det.alerts if a.service_name == "svc1"]
    assert dead and min(dead) in (10, 11)        # outage caught
    assert max(dead) <= 14                       # nothing after recovery


def test_detector_flags_throughput_drop():
    """A service that stops emitting after window 9 alerts via z_drop."""
    batch = _uniform_batch(n_per_window=20, n_windows=12)
    keep = ~((batch.service == 1) & (batch.start_us >= 10 * 60_000_000))
    cfg = ReplayConfig(n_services=2, n_windows=32, chunk_size=512)
    det = OnlineDetector(batch.services, cfg, t0_us=0)
    det.push(take_spans(batch, keep))
    det.finish()
    dead = [a for a in det.alerts if a.service_name == "svc1"]
    assert dead and dead[0].window in (10, 11)
    assert dead[0].z_drop >= det.z_threshold
    assert not [a for a in det.alerts if a.service_name == "svc0"]


# -- edge-locus attribution (the out-edge plane) ---------------------------


def test_edge_ids_self_vs_cross_vs_missing():
    """Slot mapping: cross spans key to the CALLER's out-edge slot 2S+p;
    roots / own-parented spans (and every span when parent info is
    absent) key to their service's self-edge slot S+c."""
    cfg = ReplayConfig(n_services=3, n_windows=16, chunk_size=256)
    det = OnlineDetector(("a", "b", "c"), cfg, t0_us=0)
    S = 3
    svc = np.array([0, 1, 2, 1], np.int32)
    psvc = np.array([-1, 0, 1, 1], np.int32)   # root, a->b, b->c, self b
    got = det._edge_ids(svc, psvc)
    assert got.tolist() == [S + 0, 2 * S + 0, 2 * S + 1, S + 1]
    assert det._edge_ids(svc, None).tolist() == [S + 0, S + 1, S + 2, S + 1]


def test_edge_mode_node_alerts_match_node_only_detector():
    """The combined id space must not change NODE behavior: the node rows
    see the same spans with the same binning, so the non-edge alert
    stream is identical to an edge_attribution=False detector's."""
    label = labels.label_for("Lv_P_CPU_preserve")
    exp = synth.generate_experiment(label, n_traces=200, seed=3)
    det_on = stream_experiment(exp.spans)
    det_off = stream_experiment(exp.spans, edge_attribution=False)
    node_on = [a for a in det_on.alerts if a.evidence != "edge"]
    assert [(a.window, a.service, a.evidence, round(a.score, 6))
            for a in node_on] == \
           [(a.window, a.service, a.evidence, round(a.score, 6))
            for a in det_off.alerts]


def test_edge_locus_fault_attributed_to_caller():
    """A link fault (callee-side degradation of the culprit's outgoing
    calls, anomod/synth.py fault_locus='edge') leaves every node-scoped
    statistic of the culprit healthy — only the out-edge plane names it.
    The detector must rank the CALLER first with evidence='edge'."""
    label = labels.label_for("Lv_C_travel_detail_failure")
    hard = synth.HardMode(severity=1.0, noise=0.0, fault_locus="edge")
    exp = synth.generate_experiment(label, n_traces=400, seed=0, hard=hard)
    det = stream_experiment(exp.spans)
    ranked = det.ranked_services()
    assert ranked and ranked[0] == label.target_service
    edge_alerts = [a for a in det.alerts if a.evidence == "edge"]
    assert any(a.service_name == label.target_service for a in edge_alerts)
    # propagated errors legitimately heat ancestor out-slots too (failed
    # callee spans error their parents' entry spans, which ride the
    # grandparent's out-edge slot) — the CULPRIT must carry the max
    tgt = list(det.services).index(label.target_service)
    assert det._edge_hot[tgt] == max(det._edge_hot.values())
    # detection latency through the edge plane stays bounded (pooled
    # windows add a few windows over the node path's 0-4)
    fw = det.first_alert_window(label.target_service)
    assert fw is not None and 10 <= fw <= 10 + det.edge_pool


def test_edge_locus_attribution_survives_sparse_density():
    """The sparse-density fix (mass-based two-scale pooling + shrunk
    empirical-Bayes edge baselines + exact-binomial error tail): at the
    offline sweep's knobs (60 traces, severity 0.3, noise 0.5) an
    edge-locus fault whose out-edge baseline holds only a handful of
    spans must still be attributed to the caller — the old fixed-width
    pool with the hard C0 gate scored these rows 0 (docs/QUALITY.md's
    0.17 collapse)."""
    label = labels.label_for("Lv_C_travel_detail_failure")
    hard = synth.HardMode(severity=0.3, noise=0.5, fault_locus="edge")
    exp = synth.generate_spans(label, n_traces=60, seed=0, hard=hard)
    det = stream_experiment(exp)
    edge_alerts = [a for a in det.alerts if a.evidence == "edge"]
    assert any(a.service_name == label.target_service
               for a in edge_alerts), \
        [(a.service_name, a.evidence) for a in det.alerts]
    assert det.ranked_services()[0] == label.target_service


def test_sparse_normal_has_no_edge_alerts():
    """The liberalized sparse-edge path (borrowed baselines, dominance
    tier) must not buy its sensitivity with normal-baseline false
    alerts: a healthy sparse stream produces ZERO edge-evidence
    alerts."""
    label = labels.label_for("Normal_case")
    hard = synth.HardMode(severity=0.3, noise=0.5)
    exp = synth.generate_spans(label, n_traces=60, seed=0, hard=hard)
    det = stream_experiment(exp)
    assert not [a for a in det.alerts if a.evidence == "edge"]


def test_node_fault_not_misattributed_to_caller():
    """Under a NODE fault the culprit's self-edge goes hot, so the
    callee-self-hot guard must suppress out-edge blame on its callers:
    the culprit still ranks first and no caller outranks it via edge
    evidence."""
    label = labels.label_for("Lv_P_CPU_preserve")
    exp = synth.generate_experiment(label, n_traces=300, seed=0)
    det = stream_experiment(exp.spans)
    ranked = det.ranked_services()
    assert ranked and ranked[0] == label.target_service
    tgt = list(det.services).index(label.target_service)
    assert det._self_hot[tgt]                 # locus discriminator fired


def test_sharded_edge_attribution_matches_single_chip():
    """Edge attribution over the mesh: an injected ShardedStreamReplay
    built on the COMBINED id space (edge_combined_cfg) runs the full
    edge-alerting stack, and the alert stream matches the single-chip
    edge detector's on an edge-locus corpus."""
    from anomod.parallel import make_mesh
    from anomod.parallel.stream import ShardedStreamReplay
    from anomod.stream import (edge_combined_cfg, resolve_parent_services,
                               stream_experiment)

    label = labels.label_for("Lv_C_travel_detail_failure")
    hard = synth.HardMode(severity=1.0, noise=0.0, fault_locus="edge")
    exp = synth.generate_spans(label, n_traces=300, seed=0, hard=hard)
    cfg = ReplayConfig(n_services=exp.n_services, chunk_size=1024)
    psvc = resolve_parent_services(exp)
    order = np.argsort(exp.start_us, kind="stable")
    batch, psvc = take_spans(exp, order), psvc[order]
    t0 = int(batch.start_us.min())
    edges = set(zip(batch.service[batch.parent[batch.parent >= 0]].tolist(),
                    batch.service[batch.parent >= 0].tolist()))

    mesh = make_mesh()
    combined = edge_combined_cfg(cfg, batch.n_services)
    det_mesh = OnlineDetector(
        batch.services, cfg, t0, call_edges=edges,
        replay=ShardedStreamReplay(combined, t0, mesh),
        edge_attribution=True)
    det_one = OnlineDetector(batch.services, cfg, t0, call_edges=edges)
    cuts = [0, 4000, 11000, batch.n_spans]
    for lo, hi in zip(cuts, cuts[1:]):
        sl = slice(lo, hi)
        det_mesh.push(take_spans(batch, sl), parent_service=psvc[sl])
        det_one.push(take_spans(batch, sl), parent_service=psvc[sl])
    det_mesh.finish(); det_one.finish()
    key = [(a.window, a.service, a.evidence) for a in det_one.alerts]
    assert [(a.window, a.service, a.evidence)
            for a in det_mesh.alerts] == key
    assert any(a.evidence == "edge" for a in det_mesh.alerts)
    assert det_mesh.ranked_services()[0] == label.target_service
    # a node-keyed injected replay with edge_attribution=True is rejected
    # with the combined-cfg hint
    import pytest
    with pytest.raises(ValueError, match="3\\*S"):
        OnlineDetector(batch.services, cfg, t0,
                       replay=ShardedStreamReplay(cfg, t0, mesh),
                       edge_attribution=True)


def test_rank_tier_demotes_isolated_single_plane_decoy(monkeypatch):
    """Plane-corroboration reorder (round 5): an edge-dominant caller
    bubbles above services whose entire evidence is a single non-span
    plane — UNLESS the per-pair concentration discriminator says the
    caller's heat is blast pointing at one callee, in which case that
    callee keeps its rank (the node-culprit reading)."""
    import numpy as np

    from anomod.replay import ReplayConfig
    from anomod.stream import Alert, MultimodalDetector

    services = ("caller", "decoy", "victim", "other")
    cfg = ReplayConfig(n_services=4, n_windows=16)

    def make_det():
        det = MultimodalDetector(services, cfg, t0_us=0,
                                 call_edges={(0, 2), (0, 3)})
        det.edge_attribution = True
        det._self_hot = np.zeros(4, bool)
        det._edge_hot = {0: 6.0}          # caller is edge-dominant
        det.alerts.extend([
            alert(0, 10, 3.0, "edge"), alert(0, 11, 3.0, "edge"),
            # single-plane log evidence, louder than the edge z
            alert(1, 10, 8.0, "log"),
            alert(2, 10, 9.0, "log"), alert(2, 11, 9.0, "log"),
        ])
        return det

    def alert(svc, w, score, evidence):
        return Alert(window=w, service=svc, service_name=services[svc],
                     score=score, z_latency=0.0, z_error=0.0, z_drop=0.0,
                     evidence=evidence)

    monkeypatch.delenv("ANOMOD_RANK_TIER", raising=False)

    # SPREAD heat across the caller's pairs (the link-fault signature):
    # every single-plane service is demoted below the caller, sustained
    # or not — a sustained decoy is observationally identical
    det = make_det()
    S = 4
    det._pair_base = {0 * S + 2: [20.0, 100.0, 0.0],
                      0 * S + 3: [20.0, 100.0, 0.0]}
    det._pair_anom = {0 * S + 2: [20.0, 140.0, 2.0],
                      0 * S + 3: [20.0, 138.0, 2.0]}
    ranked = det.ranked_services()
    assert ranked[0] == "caller", ranked

    # CONCENTRATED heat on one callee (blast pointing at a node
    # culprit): that callee is exempt and keeps its magnitude rank;
    # the unrelated decoy is still demoted
    det = make_det()
    det._pair_base = {0 * S + 2: [20.0, 100.0, 0.0],
                      0 * S + 3: [20.0, 100.0, 0.0]}
    det._pair_anom = {0 * S + 2: [20.0, 170.0, 4.0],
                      0 * S + 3: [20.0, 101.0, 0.0]}
    ranked = det.ranked_services()
    assert ranked[0] == "victim", ranked
    # the caller yields (explained by the node-borne victim downstream);
    # explained services rank last by the standing convention, so the
    # decoy's relative spot vs the caller is not asserted here

    # tier disabled: raw magnitudes win back their spots
    monkeypatch.setenv("ANOMOD_RANK_TIER", "0")
    ranked0 = det.ranked_services()
    assert ranked0.index("decoy") < ranked0.index("caller")


def test_pair_accumulators_via_push_drive_verdict():
    """End-to-end pair plumbing: spans pushed with parent_service land in
    the right (caller*S+callee) keys with the baseline/anomalous phase
    split on the frozen t0 grid, and _pair_verdict reads concentration
    out of them."""
    import numpy as np

    from anomod.replay import ReplayConfig
    from anomod.schemas import SpanBatch
    from anomod.stream import OnlineDetector

    services = ("caller", "c1", "c2")
    S = 3
    w_us = 1_000_000
    cfg = ReplayConfig(n_services=S, n_windows=16, window_us=w_us)
    det = OnlineDetector(services, cfg, t0_us=0, baseline_windows=4)

    def batch(windows, svc, dur_us):
        n = len(windows)
        start = np.asarray(windows, np.int64) * w_us + 1000
        return SpanBatch(
            trace=np.zeros(n, np.int32), parent=np.zeros(n, np.int32) - 1,
            service=np.full(n, svc, np.int32),
            endpoint=np.zeros(n, np.int32),
            start_us=start,
            duration_us=np.full(n, dur_us, np.int64),
            is_error=np.zeros(n, bool),
            status=np.full(n, 200, np.int16),
            kind=np.zeros(n, np.int8),
            services=services, endpoints=("e",),
            trace_ids=("t",))

    # baseline phase (windows 0-3): both pairs healthy at 10ms
    for c in (1, 2):
        b = batch([0, 0, 0, 1, 1, 2, 2, 3], c, 10_000)
        det.push(b, parent_service=np.zeros(b.n_spans, np.int32))
    # anomalous phase: c1's pair heats 20x, c2 stays flat
    b = batch([8, 8, 8, 9, 9, 10], 1, 200_000)
    det.push(b, parent_service=np.zeros(b.n_spans, np.int32))
    b = batch([8, 8, 9, 9, 10, 10], 2, 10_000)
    det.push(b, parent_service=np.zeros(b.n_spans, np.int32))

    assert set(det._pair_base) == {0 * S + 1, 0 * S + 2}
    assert det._pair_base[1][0] == 8.0          # n spans in baseline
    assert det._pair_anom[1][0] == 6.0
    assert det._pair_verdict(0) == ("concentrated", 1)
    # heat c2's pair too (strongly enough to overcome its earlier
    # healthy anomalous-phase spans) -> spread
    for ws in ([11, 11, 11, 12, 12, 12], [13, 13, 13, 13, 13, 13],
               [14, 14, 14, 14, 14, 14]):
        b = batch(ws, 2, 200_000)
        det.push(b, parent_service=np.zeros(b.n_spans, np.int32))
    assert det._pair_verdict(0) == ("spread", -1)
