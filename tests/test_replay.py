"""Replay engine: jax-vs-numpy parity and staging correctness."""

import numpy as np
import pytest

from anomod import labels, synth
from anomod.replay import (ReplayConfig, make_replay_fn, measure_throughput,
                           percentile_from_hist, replay_numpy, stage_columns,
                           F_COUNT, F_ERR)
from anomod.schemas import concat_span_batches


@pytest.fixture(scope="module")
def tt_batch():
    batches = [synth.generate_spans(l, n_traces=40)
               for l in labels.labels_for_testbed("TT")]
    return concat_span_batches(batches)


def test_stage_columns_shapes(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=1024)
    chunks, n = stage_columns(tt_batch, cfg)
    assert n == tt_batch.n_spans
    for v in chunks.values():
        assert v.shape[1] == 1024
    # padding rows carry the dead segment id
    total_valid = chunks["valid"].sum()
    assert int(total_valid) == n


def test_replay_jax_matches_numpy(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    chunks, _ = stage_columns(tt_batch, cfg)
    ref = replay_numpy(chunks, cfg)
    fn = make_replay_fn(cfg)
    out = fn(chunks)
    agg = np.asarray(out.agg)
    hist = np.asarray(out.hist)
    np.testing.assert_allclose(agg[:, F_COUNT], ref.agg[:, F_COUNT], rtol=1e-6)
    np.testing.assert_allclose(agg[:, F_ERR], ref.agg[:, F_ERR], rtol=1e-6)
    np.testing.assert_allclose(agg, ref.agg, rtol=1e-3)
    np.testing.assert_allclose(hist, ref.hist, rtol=1e-6)
    # total span count conserved
    assert int(agg[:, F_COUNT].sum()) == tt_batch.n_spans


def test_replay_aggregates_match_direct_stats(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    chunks, _ = stage_columns(tt_batch, cfg)
    st = replay_numpy(chunks, cfg)
    # per-service totals (sum over windows) match direct numpy groupby
    agg = st.agg.reshape(cfg.n_services, cfg.n_windows, -1)
    per_svc_count = agg[..., F_COUNT].sum(axis=1)
    direct = np.bincount(tt_batch.service, minlength=cfg.n_services)
    np.testing.assert_array_equal(per_svc_count.astype(int), direct)
    per_svc_err = agg[..., F_ERR].sum(axis=1)
    direct_err = np.bincount(tt_batch.service,
                             weights=tt_batch.is_error.astype(float),
                             minlength=cfg.n_services)
    np.testing.assert_allclose(per_svc_err, direct_err, rtol=1e-6)


def test_percentile_from_hist_monotone(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    chunks, _ = stage_columns(tt_batch, cfg)
    st = replay_numpy(chunks, cfg)
    p50 = percentile_from_hist(st.hist, 0.5)
    p99 = percentile_from_hist(st.hist, 0.99)
    assert (p99 >= p50).all()
    # interpolated values are continuous, not bare bucket indices: occupied
    # rows should mostly land strictly inside buckets
    occupied = st.hist.sum(axis=-1) > 4
    frac = p50[occupied] - np.floor(p50[occupied])
    assert (frac > 0).mean() > 0.5


def test_percentile_interpolation_accuracy():
    """Interpolated histogram percentile approaches the exact log-latency
    percentile much closer than the ±1-bucket quantization of the old
    bucket-index form."""
    rng = np.random.default_rng(0)
    dur_log = np.clip(rng.lognormal(1.6, 0.35, 20_000), 0, 15.999)
    hist = np.bincount(dur_log.astype(np.int64), minlength=16).astype(
        np.float32)[None, :]
    for q in (0.5, 0.9, 0.99):
        exact = np.quantile(dur_log, q)
        interp = float(percentile_from_hist(hist, q)[0])
        assert abs(interp - exact) < 0.35, (q, interp, exact)
    us = percentile_from_hist(hist, 0.99, as_us=True)
    assert np.allclose(us, np.expm1(percentile_from_hist(hist, 0.99)))
    # empty histogram rows report 0, not the max bucket
    empty = np.zeros((3, 16), np.float32)
    assert (percentile_from_hist(empty, 0.99) == 0).all()
    assert (percentile_from_hist(empty, 0.99, as_us=True) == 0).all()


def test_pallas_kernel_block_follows_chunk_size():
    """The throughput harness must pick a block that divides the staged
    span count for any power-of-2-factor chunk_size, and reject chunk
    sizes with no usable factor."""
    from anomod.replay import measure_throughput
    from anomod import labels, synth
    label = labels.labels_for_testbed("TT")[0]
    batch = synth.generate_spans(label, n_traces=10)
    cfg = ReplayConfig(n_services=batch.n_services, chunk_size=1536)  # 3*512
    res = measure_throughput(batch, cfg, repeats=1, kernel="pallas")
    assert res.n_spans == batch.n_spans
    bad = ReplayConfig(n_services=batch.n_services, chunk_size=1000)
    with pytest.raises(ValueError, match="power-of-2"):
        measure_throughput(batch, bad, repeats=1, kernel="pallas")


def test_sorted_staging_reconstructs_segments():
    """stage_sorted_planes invariants: every row of a block belongs to the
    block's window, global segment ids reconstruct from (wid, local), and
    the staged aggregate equals the unsorted one (padding rows are inert)."""
    from anomod.ops.pallas_replay import (pallas_replay_numpy,
                                          stage_sorted_planes)
    rng = np.random.default_rng(3)
    SW, K, BLOCK, H = 600, 128, 256, 16
    n = 5000
    sid = rng.integers(0, SW + 1, n).astype(np.int32)
    planes = np.abs(rng.normal(size=(6, n))).astype(np.float32)
    sid_l, planes_s, wids = stage_sorted_planes(sid, planes, SW,
                                                k=K, block=BLOCK)
    assert sid_l.shape[0] % BLOCK == 0
    assert wids.shape[0] == sid_l.shape[0] // BLOCK
    assert (np.diff(wids) >= 0).all()          # windows in order
    assert sid_l.min() >= 0 and sid_l.max() < K
    gsid = sid_l + np.repeat(wids, BLOCK).astype(np.int32) * K
    got = pallas_replay_numpy(gsid, planes_s, SW, H)
    want = pallas_replay_numpy(sid, planes, SW, H)
    np.testing.assert_allclose(got, want, rtol=1e-6)


#: (blocks a window, inner_repeats, blocks a grid step) of a 5-window
#: corpus (SW 600, k 128), or no list for 5,000 uniform spans.  The kernel
#: folds S staged blocks a grid step, S the largest of 8, 4, 2, 1 that
#: divides the block count.
SORTED_CASES = {
    "uniform-repeats2": (None, 2, None),
    "S8-five-windows-in-one-step": ([3, 2, 1, 1, 1], 1, 8),
    "S8-two-steps-repeats2": ([5, 4, 3, 2, 2], 2, 8),
    "S4": ([3, 3, 2, 2, 2], 1, 4),
    "S2-an-empty-window": ([1, 2, 1, 2, 0], 1, 2),
    "S1-seven-blocks": ([3, 1, 1, 1, 1], 1, 1),
    "S1-eleven-blocks-repeats2": ([5, 3, 1, 1, 1], 2, 1),
}


@pytest.mark.parametrize("case", sorted(SORTED_CASES))
def test_pallas_sorted_kernel_matches_oracle(case):
    """The sorted-window kernel (interpret path) reproduces the unsorted
    oracle: 0/1 planes + histogram exactly, moments within the hi/lo
    bound — at every number of staged blocks a grid step, with window
    boundaries inside a step (each block of a step reads its own window
    id), and with device-side replication via inner_repeats."""
    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          pallas_replay_numpy,
                                          stage_sorted_planes)
    blocks, repeats, per_step = SORTED_CASES[case]
    rng = np.random.default_rng(7)
    SW, H, K, BLOCK = 600, 16, 128, 256
    if blocks is None:
        sid = rng.integers(0, SW + 1, 5000).astype(np.int32)
    else:
        # a window's span count pads to exactly its block count
        sid = rng.permutation(np.concatenate([
            rng.integers(w * K, min((w + 1) * K, SW + 1),
                         (nb - 1) * BLOCK + int(rng.integers(1, BLOCK + 1)))
            for w, nb in enumerate(blocks) if nb])).astype(np.int32)
    n = sid.shape[0]
    valid = (rng.random(n) < 0.9).astype(np.float32)
    dur_us = rng.lognormal(8.0, 1.0, n).astype(np.float32) * valid
    dur = np.log1p(dur_us)
    planes = np.stack([
        valid,
        ((rng.random(n) < 0.2) * valid).astype(np.float32),   # err: 0/1
        ((rng.random(n) < 0.1) * valid).astype(np.float32),   # 5xx: 0/1
        dur_us, dur, dur * dur,
    ])
    sid_l, planes_s, wids = stage_sorted_planes(sid, planes, SW,
                                                k=K, block=BLOCK)
    if blocks is not None:
        assert np.bincount(wids, minlength=len(blocks)).tolist() == blocks
        assert per_step == next(s for s in (8, 4, 2, 1)
                                if wids.shape[0] % s == 0)
        if per_step > 1:                        # a step straddles windows
            assert len(set(wids[:per_step].tolist())) > 1
    fn = make_pallas_replay_sorted_fn(SW, H, k=K, block=BLOCK,
                                      interpret=True, inner_repeats=repeats)
    got = np.asarray(fn(sid_l, planes_s, wids))
    want = pallas_replay_numpy(sid, planes, SW, H) * repeats
    np.testing.assert_array_equal(got[:, :3], want[:, :3])    # exact planes
    np.testing.assert_array_equal(got[:, 6:], want[:, 6:])    # histogram
    np.testing.assert_allclose(got[:, 3:6], want[:, 3:6],     # hi/lo bound
                               rtol=2e-3, atol=1e-2)


def test_pallas_sorted_kernel_sparse_windows():
    """Corpora that leave whole segment windows empty (clustered service
    traffic) must still aggregate correctly: windows with no spans get no
    blocks, and their accumulator columns stay zero.  Also pins the
    zero-span guard."""
    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          pallas_replay_numpy,
                                          stage_sorted_planes)
    rng = np.random.default_rng(11)
    SW, H, K, BLOCK = 600, 16, 128, 256
    n = 1500
    sid = rng.integers(260, 380, n).astype(np.int32)   # one window only
    planes = np.abs(rng.normal(size=(6, n))).astype(np.float32)
    planes[0] = 1.0
    planes[1] = (rng.random(n) < 0.2).astype(np.float32)
    planes[2] = 0.0
    planes[4] = rng.uniform(0, 15, n).astype(np.float32)
    sid_l, planes_s, wids = stage_sorted_planes(sid, planes, SW,
                                                k=K, block=BLOCK)
    assert set(wids.tolist()) == {2}                   # only window 2 staged
    fn = make_pallas_replay_sorted_fn(SW, H, k=K, block=BLOCK,
                                      interpret=True)
    got = np.asarray(fn(sid_l, planes_s, wids))
    want = pallas_replay_numpy(sid, planes, SW, H)
    np.testing.assert_array_equal(got[:, :3], want[:, :3])
    assert (got[:256] == 0).all() and (got[384:] == 0).all()
    # zero-span corpus: defined all-zero output, not uninitialized memory
    # (both kernels share the guard)
    empty = fn(np.zeros(0, np.int32), np.zeros((6, 0), np.float32),
               np.zeros(0, np.int32))
    assert np.asarray(empty).shape == (SW, 6 + H)
    assert (np.asarray(empty) == 0).all()
    from anomod.ops.pallas_replay import make_pallas_replay_fn
    fn_full = make_pallas_replay_fn(SW, H, block=BLOCK, interpret=True)
    empty_full = fn_full(np.zeros(0, np.int32), np.zeros((6, 0), np.float32))
    assert (np.asarray(empty_full) == 0).all()


def test_measure_throughput_pallas_sorted_kernel(tt_batch):
    """End-to-end: the pallas-sorted path stages, runs (interpret on the
    CPU mesh), and passes the span-count audit."""
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    res = measure_throughput(tt_batch, cfg, repeats=1, kernel="pallas-sorted")
    assert res.kernel == "pallas-sorted"
    assert res.n_spans == tt_batch.n_spans


def test_replay_percentiles_tdigest_plane(tt_batch):
    """replay_percentiles (t-digest over the replay segments) tracks exact
    per-segment quantiles within the sketch's error bound."""
    from anomod.replay import replay_percentiles
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    out = replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99))
    assert out.shape == (cfg.sw, 2)
    chunks, _ = stage_columns(tt_batch, cfg)
    sid = chunks["sid"].reshape(-1)
    dur = chunks["dur_raw"].reshape(-1)
    real = sid < cfg.sw
    sid, dur = sid[real], dur[real]
    # exact quantiles on the five most-populated segments; the p99 of a
    # ~70-sample segment rides the top order statistics, so its µs-domain
    # tolerance is wider than the median's
    counts = np.bincount(sid, minlength=cfg.sw)
    for seg in np.argsort(counts)[-5:]:
        vals = dur[sid == seg]
        assert abs(out[seg, 0] - np.quantile(vals, 0.5)) \
            <= 0.08 * max(np.quantile(vals, 0.5), 1.0)
        assert abs(out[seg, 1] - np.quantile(vals, 0.99)) \
            <= 0.20 * max(np.quantile(vals, 0.99), 1.0)
        # and the tail must actually be a tail (the pre-fix empty-centroid
        # bug returned p99 below p50)
        assert out[seg, 1] > out[seg, 0]


def test_replay_percentiles_pallas_engine_matches_host(tt_batch):
    """Engine parity across the digest builds: the TPU auto default
    (engine='xla') and the opt-in Mosaic kernel (engine='pallas',
    interpret path on the CPU mesh) must both reproduce the host digest
    plane, and engine='auto' must resolve to host off-TPU."""
    import pytest
    from anomod.replay import replay_percentiles
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    host = replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99), engine="host")
    auto = replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99), engine="auto")
    np.testing.assert_array_equal(auto, host)
    # the TPU auto default (jitted XLA one-hot build) must reproduce the
    # host plane from the identical staged lanes
    xla = replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99), engine="xla")
    np.testing.assert_allclose(xla, host, rtol=2e-3, atol=1e-2)
    pal = replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99), engine="pallas")
    # identical staging + identical bucket math; only kernel-vs-numpy float
    # ordering differs (lane padding slots carry weight 0)
    np.testing.assert_allclose(pal, host, rtol=2e-3, atol=1e-2)
    with pytest.raises(ValueError, match="engine"):
        replay_percentiles(tt_batch, cfg, engine="exact")
    # env override is normalized: "AUTO" restores auto-selection instead of
    # crashing, "HOST" selects the host build
    import os
    for val in ("AUTO", "HOST"):
        os.environ["ANOMOD_TDIGEST_ENGINE"] = val
        try:
            np.testing.assert_array_equal(
                replay_percentiles(tt_batch, cfg, qs=(0.5, 0.99)), host)
        finally:
            del os.environ["ANOMOD_TDIGEST_ENGINE"]


def test_measure_throughput_smoke(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=4096)
    r = measure_throughput(tt_batch, cfg, repeats=1)
    assert r.n_spans == tt_batch.n_spans
    assert r.spans_per_sec > 0


def test_measure_throughput_numpy_kernel(tt_batch):
    """The cpu-backend engine rides the same harness: replicate scaling,
    count integrity (asserted inside), median-of-N walls."""
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=4096)
    r = measure_throughput(tt_batch, cfg, repeats=3, replicate=2,
                           kernel="numpy")
    assert r.kernel == "numpy"
    assert r.n_spans == 2 * tt_batch.n_spans
    assert r.spans_per_sec > 0
    assert len(r.raw_wall_s) == 3


def test_replay_hll_distinct_traces(tt_batch):
    """HLL plane counts distinct traces per service within sketch error."""
    import numpy as np
    from anomod.ops.hll import hll_estimate
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    chunks, _ = stage_columns(tt_batch, cfg)
    fn = make_replay_fn(cfg, with_hll=True)
    out = fn(chunks)
    regs = np.asarray(out.hll)
    assert regs.shape == (cfg.n_services, cfg.hll_m)
    est = hll_estimate(regs)
    for s in range(cfg.n_services):
        true = len(np.unique(tt_batch.trace[tt_batch.service == s]))
        if true >= 50:
            assert abs(est[s] - true) / true < 0.25, (s, true, est[s])


def test_replay_inner_repeats_scales_state(tt_batch):
    """Device-side replication (bench replicate) = exactly R x one pass."""
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=2048)
    chunks, _ = stage_columns(tt_batch, cfg)
    one = make_replay_fn(cfg)(chunks)
    three = make_replay_fn(cfg, inner_repeats=3)(chunks)
    np.testing.assert_allclose(np.asarray(three.agg),
                               3.0 * np.asarray(one.agg), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(three.hist),
                                  3.0 * np.asarray(one.hist))


def test_measure_throughput_replicate_counts(tt_batch):
    cfg = ReplayConfig(n_services=tt_batch.n_services, chunk_size=4096)
    r = measure_throughput(tt_batch, cfg, repeats=1, replicate=3)
    assert r.n_spans == 3 * tt_batch.n_spans


def test_replay_variance_reconstruction_low_variance():
    """Variance from the bf16 hi/lo moment planes on a LOW-variance latency
    distribution: pins the accepted error bound documented in chunk_step
    (~1.5e-5 * E[x^2] / Var(x) relative after the E[x^2]-E[x]^2 cancellation).
    """
    from anomod import labels, synth
    rng = np.random.default_rng(0)
    base = synth.generate_spans(labels.label_for("Normal_case"), n_traces=400)
    # low-variance log-latency: sigma=0.1 around ~50ms (vs synth's 0.4)
    dur_us = np.exp(rng.normal(np.log(50_000.0), 0.1,
                               base.n_spans)).astype(np.int64)
    batch = base._replace(duration_us=dur_us)
    cfg = ReplayConfig(n_services=batch.n_services, n_windows=1,
                       chunk_size=2048, window_us=10**12)
    chunks, _ = stage_columns(batch, cfg)
    out = make_replay_fn(cfg)(chunks)
    agg = np.asarray(out.agg)
    from anomod.replay import F_LOGLAT, F_LOGLAT2
    x = np.log1p(dur_us.astype(np.float64))
    for s in range(batch.n_services):
        m = batch.service == s
        n = int(m.sum())
        if n < 500:
            continue
        mean = agg[s, F_LOGLAT] / n
        var = agg[s, F_LOGLAT2] / n - mean**2
        true_var = x[m].var()
        # documented bound: rel err ~ 1.5e-5 * E[x^2]/Var ~ 0.2 at sigma=0.1;
        # assert a 30% envelope (and that var stays positive / same scale)
        assert var > 0, (s, var)
        assert abs(var - true_var) / true_var < 0.30, (s, var, true_var)


def test_replay_cli_kernel_flag(capsys):
    """`anomod replay --kernel pallas` runs the fused kernel end to end
    (interpret path on the CPU mesh) and reports which kernel ran."""
    import json

    from anomod.cli import main

    assert main(["replay", "--traces", "10", "--kernel", "pallas"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["kernel"] == "pallas" and out["n_spans"] > 0


def test_replay_cli_sharded(capsys):
    """`anomod replay --devices N` runs the pod-sharded replay (shard_map +
    psum merge) over the virtual mesh from the CLI."""
    import json

    from anomod.cli import main

    assert main(["replay", "--traces", "10", "--devices", "8"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["devices"] == 8 and out["n_spans"] > 0
    assert out["spans_per_sec"] > 0
    # over-asking must fail loudly, not silently shrink the mesh (the
    # reported device count is benchmark provenance)
    from anomod.parallel import make_mesh
    with pytest.raises(ValueError, match="attached"):
        make_mesh(99)
    with pytest.raises(ValueError, match="attached"):
        make_mesh(-1)
    # --replicate is a single-chip knob; combining it with --devices is
    # rejected rather than silently dropped
    with pytest.raises(SystemExit):
        main(["replay", "--traces", "10", "--devices", "8",
              "--replicate", "4"])
    capsys.readouterr()


def test_edge_percentiles_match_numpy_oracle():
    """Per-edge t-digest percentiles: each (caller->callee, window)
    segment's p50/p99 tracks the exact numpy percentile of that edge's
    spans, and a link fault surfaces as the culprit's out-edge p99."""
    from anomod import labels, synth
    from anomod.replay import (ReplayConfig, edge_keyed_batch,
                               replay_edge_percentiles)

    lab = labels.label_for("Lv_D_TRANSACTION_timeout")   # 20x latency fault
    hard = synth.HardMode(severity=1.0, fault_locus="edge")
    batch = synth.generate_spans(lab, n_traces=200, seed=5, hard=hard)
    cfg = ReplayConfig(n_services=batch.n_services, n_windows=8,
                       window_us=300_000_000)
    pct, table = replay_edge_percentiles(batch, cfg)
    eb, table2 = edge_keyed_batch(batch)
    assert table == table2
    pct = pct.reshape(len(table), cfg.n_windows, 3)
    # oracle: exact percentiles of one busy cross edge's spans per window
    t0 = int(batch.start_us.min())
    w = np.minimum((batch.start_us - t0) // cfg.window_us,
                   cfg.n_windows - 1).astype(int)
    counts = np.bincount(eb.service, minlength=len(table))
    cross = [i for i, (a, b) in enumerate(table) if a != b]
    busiest = max(cross, key=lambda i: counts[i])
    for wi in range(cfg.n_windows):
        sel = (eb.service == busiest) & (w == wi)
        if sel.sum() < 30:
            continue
        exact = np.percentile(batch.duration_us[sel], [50, 99])
        got = pct[busiest, wi, [0, 2]]
        np.testing.assert_allclose(got, exact, rtol=0.15)
    # the culprit's out-edges carry the inflated tail in the fault
    # windows vs the SAME edges' healthy windows (same traffic mix —
    # cross-service base-latency differences don't confound the ratio)
    ti = list(batch.services).index(lab.target_service)
    out_edges = [i for i, (a, b) in enumerate(table)
                 if a == ti and b != ti and counts[i] >= 20]
    assert out_edges
    hot = np.nanmax([np.nanmax(pct[i, 2:4, 2]) for i in out_edges])
    cool = np.nanmax([np.nanmax(pct[i, [0, 1, 5, 6], 2])
                      for i in out_edges])
    assert hot > 3 * cool


def test_edge_features_single_pass_matches_single_plane_entries():
    """The combined reporting entry (one re-key + staging pass) returns
    bit-identical planes to the two single-plane entries run separately —
    the CLI's --edge-percentiles view must not drift from them."""
    from anomod import labels, synth
    from anomod.replay import (replay_edge_distinct, replay_edge_features,
                               replay_edge_percentiles)

    batch = synth.generate_spans(labels.label_for("Normal_case"),
                                 n_traces=120, seed=3)
    pct, counts, table = replay_edge_features(batch)
    pct1, table1 = replay_edge_percentiles(batch)
    counts1, table2 = replay_edge_distinct(batch)
    assert table == table1 == table2
    np.testing.assert_array_equal(pct, pct1)
    np.testing.assert_array_equal(counts, counts1)


def test_edge_distinct_traces_match_exact():
    """Per-edge HLL distinct-trace counts track the exact per-edge trace
    cardinality within sketch error (p=8: exact-ish at small counts via
    linear counting, ~7% at thousands)."""
    from anomod import labels, synth
    from anomod.replay import (ReplayConfig, edge_keyed_batch,
                               replay_edge_distinct)

    batch = synth.generate_spans(labels.label_for("Normal_case"),
                                 n_traces=300, seed=1)
    counts, table = replay_edge_distinct(batch)
    eb, _ = edge_keyed_batch(batch)
    for i in range(len(table)):
        sel = eb.service == i
        exact = len(set(batch.trace[sel].tolist()))
        assert abs(counts[i] - exact) <= max(3.0, 0.1 * exact), \
            (table[i], counts[i], exact)


def test_pallas_lane_delta_interpret_matches_scatter_twin():
    """The fused TPU lane kernel's tier-1 twin: make_lane_delta(engine=
    "pallas") runs the single Mosaic kernel in INTERPRET mode on CPU
    (the kernel LOGIC; the compiled pin is tpu_tests/) against the
    XLA:CPU scatter formulation — 0/1 and
    histogram planes exact, latency moments within the bf16 hi/lo
    envelope (the compiled-replay tolerance contract), and a dead pad
    lane's delta exactly zero."""
    import jax

    from anomod.replay import (dead_chunk, default_lane_engine,
                               make_lane_delta, stage_columns)

    assert default_lane_engine() == "scatter"     # CPU backend default
    cfg = ReplayConfig(n_services=5, n_windows=6, window_us=5_000_000,
                       chunk_size=256)
    chunks = []
    for i in range(3):
        batch = synth.generate_spans(labels.label_for("Normal_case"),
                                     n_traces=40, seed=i)
        batch = batch._replace(
            service=(batch.service % cfg.n_services).astype(np.int32),
            services=batch.services[:cfg.n_services])
        staged, _ = stage_columns(batch, cfg, t0_us=0)
        chunks.append({k: v[0] for k, v in staged.items()})
    chunks.append(dead_chunk(cfg, 256, xp=np))    # dead pad lane
    stack = {k: np.stack([c[k] for c in chunks]) for k in chunks[0]}
    sca = jax.jit(make_lane_delta(cfg, engine="scatter"))
    pal = jax.jit(make_lane_delta(cfg, engine="pallas"))
    da, dh = map(np.asarray, sca(stack))
    pa, ph = map(np.asarray, pal(stack))
    np.testing.assert_array_equal(pa[..., :3], da[..., :3])
    np.testing.assert_array_equal(ph, dh)
    np.testing.assert_allclose(pa[..., 3:6], da[..., 3:6], rtol=2e-3,
                               atol=1e-2)
    assert (pa[-1] == 0).all() and (ph[-1] == 0).all()


def test_hi_lo_split_is_not_an_elidable_convert_pair():
    """XLA's TPU pipeline removes an f32->bf16->f32 convert pair, which
    zeroes the lo half of the moment split on the chip (caught in PR 21).
    The split must round through reduce_precision, which the compiler
    keeps — pinned on the lowered step, and bit-equal to the convert
    pair here on the CPU, where the pair survives."""
    import jax
    import jax.numpy as jnp

    from anomod.replay import (ReplayConfig, _split_hi_lo, dead_chunk,
                               make_lane_delta)
    cfg = ReplayConfig(n_services=4, n_windows=8, chunk_size=256)
    for engine in ("matmul", "scatter"):
        stack = {k: jnp.stack([v, v]) for k, v in
                 dead_chunk(cfg, 256).items()}
        text = jax.jit(make_lane_delta(cfg, engine=engine)).lower(
            stack).as_text()
        assert "reduce_precision" in text, engine
    x = jnp.asarray(np.random.default_rng(0).lognormal(8, 2, 4096),
                    jnp.float32)
    hi, lo = _split_hi_lo(x)
    want_hi = x.astype(jnp.bfloat16)
    want_lo = (x - want_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(hi, np.float32),
                                  np.asarray(want_hi, np.float32))
    np.testing.assert_array_equal(np.asarray(lo, np.float32),
                                  np.asarray(want_lo, np.float32))
    assert (np.asarray(lo, np.float32) != 0).mean() > 0.9
